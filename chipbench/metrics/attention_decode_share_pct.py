"""Share of the decode program's device time that falls inside the
attention core: the device events inside the runs of ``jit_decode`` in the
traced seconds whose HLO instruction was traced under the program's
``jax.named_scope("attention")`` (the cache's update, scores, softmax,
values; not the projections), over all device events inside those runs.
It is what reading whole caches costs: the program's attention reads every
cache to its full length, whatever the rows' positions.  Nothing where the
driver found no such event (``obs["decode_trace"]``)."""


def read(obs):
    found = obs.get("decode_trace") or {}
    if not found.get("attention_events") or not found.get("decode_s"):
        return None
    return 100.0 * found["attention_s"] / found["decode_s"]
