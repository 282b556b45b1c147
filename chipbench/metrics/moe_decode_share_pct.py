"""Share of the decode program's device time that falls inside the expert
path: the device events inside the runs of ``jit_decode`` in the traced
seconds whose HLO instruction was traced under the program's
``jax.named_scope("moe")`` (router, top-k, sort, grouped matmuls,
activation, combine, the shared expert), over all device events inside
those runs.  The driver sums the events before the trace is reduced
(``obs["decode_trace"]``, ``drivers/serve_lm.py``); a program that has no
such scope, or a driver that does not look, gives nothing."""


def read(obs):
    found = obs.get("decode_trace") or {}
    if not found.get("moe_events") or not found.get("decode_s"):
        return None
    return 100.0 * found["moe_s"] / found["decode_s"]
