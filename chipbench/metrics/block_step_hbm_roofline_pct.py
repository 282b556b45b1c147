"""The block step against its MEMORY roofline: the least a step of the
traced seconds had to read (``arithmetic_sdar.block_step_least_bytes``:
the attention, router and head weights once, of the routed experts the
distinct ones its positions chose, by the program's counter
``alpa_moe_experts_touched_total`` a step, and of the caches the positions
its active rows HELD, by ``alpa_serving_decode_positions_total`` a step,
not the caches' length) over the HBM peak, over the median time of one run
of ``jit_block_step`` in those seconds.  The count leaves out the
embedding's rows, every activation and every write, and what the free
rows' caches hold, so it is a true lower bound and the share cannot pass
100 %.  At 128 positions a step memory bounds it (some 30 operations a
byte of the experts, against 240 at the ridge).  Nothing where the trace
holds no such run or the program has no such counters."""
from chipbench import arithmetic_sdar, counters, stats

BLOCK_PROGRAM = "jit_block_step"


def read(obs):
    trace = obs.get("device_trace")
    traced = {"counters": obs.get("traced_counters")}
    steps = counters.delta(traced, "alpa_serving_decode_steps_total")
    touched = counters.delta(traced, "alpa_moe_experts_touched_total")
    positions = counters.delta(traced,
                               "alpa_serving_decode_positions_total")
    if trace is None or obs["peaks"] is None or not steps or \
            not touched or not positions:
        return None
    runs = trace["program_runs"].get(BLOCK_PROGRAM)
    if not runs:
        return None
    least = arithmetic_sdar.block_step_least_bytes(
        obs["config"], touched / steps, positions / steps,
        obs["cache_itemsize"])
    least_s = sum(least.values()) / obs["peaks"]["hbm_bytes_per_s"]
    return 100.0 * least_s / stats.median(runs)
