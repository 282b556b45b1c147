"""Profile the TPU into prof_database_tpu.json.

One process on the machine with the chip.  Largest dot is 4096^2 bf16
(32 MB/operand).

Usage:  python scripts/profile_tpu.py
"""
import os
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)
OUT = os.path.join(REPO, "prof_database_tpu.json")


def main():
    import alpa_tpu
    from alpa_tpu.device_mesh import get_global_cluster
    from alpa_tpu.mesh_profiling import profile_all

    alpa_tpu.init("local")
    db = profile_all(get_global_cluster(), OUT)
    for key, res in db.data.items():
        cal = res.fit()
        print(f"{key}: sec/flop@1e12={cal.sec_per_flop(1e12):.3e} "
              f"({1.0 / cal.sec_per_flop(1e12) / 1e12:.1f} TFLOPS)")
    print(f"saved {OUT}")


if __name__ == "__main__":
    main()
