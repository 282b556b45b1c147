"""The decode program's full-attention layers against their MEMORY
roofline: the bytes of keys and values the traced ticks' rows held in those
layers (``arithmetic_mimo.full_decode_work``: the program's counter
``alpa_serving_decode_positions_total`` over the traced seconds times what
the full layers hold a position; at one query a row memory bounds the core:
some 64 operations a byte of cache against the chip's 240) over the HBM
peak, over the time the device events under the program's scope
``full_core`` (the cache's write and the kernel over key blocks; not the
projections) took inside the runs of ``jit_decode`` in those seconds.  The
count leaves out the queries, the output, the new position's write and
what the kernel fetches past a row's last position in its last key block
or for the engine's free rows, so it is a true lower bound and the share
cannot pass 100 %; a core that read every position the cache can hold
would read ``served_context / held a row`` times as much in the same
scope.  Nothing where the driver found no such event
(``obs["decode_trace"]``) or the program has no such counter."""
from chipbench import arithmetic_mimo, counters


def read(obs):
    found = obs.get("decode_trace") or {}
    held = counters.delta({"counters": obs.get("traced_counters")},
                          "alpa_serving_decode_positions_total")
    if obs["peaks"] is None or not held or not found.get("full_core_events"):
        return None
    least_s = arithmetic_mimo.full_decode_work(
        obs["config"], held, obs["cache_itemsize"])["bytes"] / \
        obs["peaks"]["hbm_bytes_per_s"]
    return 100.0 * least_s / found["full_core_s"]
