"""Share of the chunk program's device time that falls inside the
shortcut-connected expert branch: the device events inside the runs of
``jit_chunk_prefill`` in the traced seconds whose HLO instruction was
traced under the program's ``jax.named_scope("moe")`` (router, top-k,
sort, grouped matmuls over the rows that landed on a held expert,
activation, combine, the identity experts' part), over all device events
inside those runs.  It is what the branch costs where it does the most
rows (1,024 positions x 12 picks a chunk); a deployment overlaps this
branch with its exchange between the shares, behind the first dense MLP
and the second attention.  Nothing where the driver found no such event
(``obs["chunk_trace"]``, ``drivers/serve_mla.py``)."""


def read(obs):
    found = obs.get("chunk_trace") or {}
    if not found.get("moe_events") or not found.get("program_s"):
        return None
    return 100.0 * found["moe_s"] / found["program_s"]
