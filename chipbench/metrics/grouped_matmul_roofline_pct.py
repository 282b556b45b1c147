"""The grouped matmuls of the expert layers against their roofline: the
least time the chip could take for the work of the traced steps (the
larger of their operations over the bf16 peak and their bytes over the HBM
peak, ``arithmetic_moe.expert_grouped_matmul_work``: forward, the gradient
of the rows, the gradient of the weights) over the time the device events
under the program's scope ``grouped_matmul`` took, the kernels' own
metadata programs included.  At 1,024 rows an expert the operations bound
it (some 700 operations a byte).  Nothing where the driver found no such
event (``obs["expert_trace"]``)."""


def read(obs):
    found = obs.get("expert_trace") or {}
    if obs["peaks"] is None or not found.get("grouped_matmul_events"):
        return None
    flops, bytes_ = obs["grouped_matmul_work"]
    least_s = max(flops / obs["peaks"]["bf16_flops_per_s"],
                  bytes_ / obs["peaks"]["hbm_bytes_per_s"])
    return 100.0 * least_s / found["grouped_matmul_s"]
