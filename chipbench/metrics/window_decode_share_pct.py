"""Share of the decode program's device time that falls inside the window
layers' attention core: the device events inside the runs of ``jit_decode``
in the traced seconds whose HLO instruction was traced under the program's
``jax.named_scope("window_core")`` (the ring's write, the scores over the
ring's slots, the softmax with its sink, the values; not the projections),
over all device events inside those runs.  It lies inside
``attention_decode_share_pct``'s scope.  Nothing where the driver found no
such event (``obs["decode_trace"]``)."""


def read(obs):
    found = obs.get("decode_trace") or {}
    if not found.get("window_core_events") or not found.get("decode_s"):
        return None
    return 100.0 * found["window_core_s"] / found["decode_s"]
