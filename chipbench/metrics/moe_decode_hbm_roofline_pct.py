"""The grouped matmuls of the expert layers, in the decode program,
against their MEMORY roofline: the bytes of the distinct experts' three
matrices the traced ticks touched (the program's counter
``alpa_moe_experts_touched_total`` over the traced seconds times
``arithmetic_afmoe.expert_bytes``: a true lower bound of what the kernels
read, their rows and results left out) over the HBM peak, over the time
the device events under the program's scope ``grouped_matmul`` took inside
the runs of ``jit_decode`` in those seconds.  At one or two rows an expert
memory bounds it (some 2 operations a byte).  Nothing where the driver
found no such event or the program has no such counter."""
from chipbench import counters


def read(obs):
    found = obs.get("decode_trace") or {}
    touched = counters.delta({"counters": obs.get("traced_counters")},
                             "alpa_moe_experts_touched_total")
    if obs["peaks"] is None or not touched or \
            not found.get("grouped_matmul_events"):
        return None
    least_s = touched * obs["expert_bytes"] / obs["peaks"]["hbm_bytes_per_s"]
    return 100.0 * least_s / found["grouped_matmul_s"]
