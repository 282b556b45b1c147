"""Share of the decode program's device time that falls inside the indexer
of the layers that select their positions: the device events inside the
runs of ``jit_decode`` in the traced seconds whose HLO instruction was
traced under the program's ``jax.named_scope("indexer")`` (the index
queries', keys' and weights' projections, the scores over every position a
row holds, the choice of the ``index_topk`` best), over all device events
inside those runs.  It lies inside ``attention_decode_share_pct``'s scope.
Nothing where the driver found no such event (``obs["decode_trace"]``)."""


def read(obs):
    found = obs.get("decode_trace") or {}
    if not found.get("indexer_events") or not found.get("decode_s"):
        return None
    return 100.0 * found["indexer_s"] / found["decode_s"]
