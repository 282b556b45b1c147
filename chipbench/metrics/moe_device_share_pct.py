"""Share of the device's busy time that falls inside the expert path: the
device events of the traced steps whose HLO instruction was traced under
the program's ``jax.named_scope("moe")`` (router, top-k, sort, dispatch,
grouped matmuls, activation, combine, forward and backward), over the busy
time of the same steps.  The driver sums the events before the trace is
reduced (``obs["expert_trace"]``, ``drivers/train_lm.py``); a program that
has no such scope, or a driver that does not look, gives nothing."""


def read(obs):
    found = obs.get("expert_trace") or {}
    trace = obs.get("device_trace")
    if not found.get("expert_path_events") or not trace or \
            not trace["busy_s"]:
        return None
    return 100.0 * found["expert_path_s"] / trace["busy_s"]
