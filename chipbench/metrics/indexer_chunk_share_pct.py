"""Share of the chunk program's device time that falls inside the indexer
of the layers that select their positions: the device events inside the
runs of ``jit_chunk_prefill`` in the traced seconds whose HLO instruction
was traced under the program's ``jax.named_scope("indexer")`` (the
projections, a chunk's 1,024 x positions scores reduced over the index
heads, the selection's mask), over all device events inside those runs.
It lies inside ``attention_chunk_share_pct``'s scope.  Nothing where the
driver found no such event (``obs["chunk_trace"]``)."""


def read(obs):
    found = obs.get("chunk_trace") or {}
    if not found.get("indexer_events") or not found.get("program_s"):
        return None
    return 100.0 * found["indexer_s"] / found["program_s"]
