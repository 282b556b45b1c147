"""Share of the chunk program's device time that falls inside the window
layers' attention core: the device events inside the runs of
``jit_chunk_prefill`` in the traced seconds whose HLO instruction was
traced under the program's ``jax.named_scope("window_core")`` (a chunk's
queries scored against the ring and the chunk's own keys, ``window +
chunk`` of them a query where ``window`` are visible; the softmax with its
sink, the values, the ring's write), over all device events inside those
runs.  It lies inside ``attention_chunk_share_pct``'s scope, and is what a
core that scored only the visible band would take away.  Nothing where
the driver found no such event (``obs["chunk_trace"]``)."""


def read(obs):
    found = obs.get("chunk_trace") or {}
    if not found.get("window_core_events") or not found.get("program_s"):
        return None
    return 100.0 * found["window_core_s"] / found["program_s"]
