"""Published peaks of the chips the benchmark may run on, keyed by the
``device_kind`` jax reports.  A kind that is not here is an error, never a
default: a utilisation against a guessed peak is worse than none."""

PEAKS = {
    # Google Cloud documentation, "TPU v5e": 197 TFLOP/s bf16, 16 GB of HBM
    # at 819 GB/s, 1,600 Gbit/s of chip-to-chip interconnect, per chip.
    "TPU v5 lite": {
        "bf16_flops_per_s": 197e12,
        "hbm_bytes_per_s": 819e9,
        "hbm_bytes": 16e9,
        "ici_bits_per_s": 1600e9,
    },
}


def peaks_for(device_kind: str) -> dict:
    if device_kind not in PEAKS:
        raise KeyError(
            f"chipbench has no published peaks for device kind "
            f"{device_kind!r}; add it to chipbench/peaks.py with its source")
    return PEAKS[device_kind]
