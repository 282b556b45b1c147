"""Share of the chunk program's device time that falls inside the
attention core: the device events inside the runs of ``jit_chunk_prefill``
in the traced seconds whose HLO instruction was traced under the program's
``jax.named_scope("attention")`` (the cache's write, the expansion of each
key block's latents to per-head keys and values, scores, softmax, values;
not the low-rank projections), over all device events inside those runs.
It is what expanding and reading the cache costs a chunk, and grows with
the chunk's start.  Nothing where the driver found no such event
(``obs["chunk_trace"]``, ``drivers/serve_mla.py``)."""


def read(obs):
    found = obs.get("chunk_trace") or {}
    if not found.get("attention_events") or not found.get("program_s"):
        return None
    return 100.0 * found["attention_s"] / found["program_s"]
