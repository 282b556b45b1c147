"""The whole decode tick of a shortcut-connected decoder against its MEMORY
roofline: the least a tick of the traced seconds had to read
(``arithmetic_longcat.decode_tick_bytes``: both attentions', both dense
MLPs' and the router's weights of every layer once; of the routed experts
held the distinct ones its rows chose, by the program's counter
``alpa_moe_experts_touched_total`` a tick; of the eight latent caches the
positions its active rows HELD, by ``alpa_serving_decode_positions_total``
a tick, not the caches' length; the head's slice) over the HBM peak, over
the median time of one run of ``jit_decode`` on the device in those
seconds.  The count leaves out the embedding's rows, every activation and
every write, what the free rows' caches hold and the key blocks' rounding,
so it is a true lower bound and the share cannot pass 100 %.  At 32 rows
memory bounds the tick (some 30 operations a byte of the dense weights,
against 240 at the ridge).  It is the cell's share of the whole step: what
bounds a later claim here.  Nothing where the trace holds no such run or
the program has no such counters."""
from chipbench import arithmetic_longcat, counters, stats

DECODE_PROGRAM = "jit_decode"


def read(obs):
    trace = obs.get("device_trace")
    traced = {"counters": obs.get("traced_counters")}
    steps = counters.delta(traced, "alpa_serving_decode_steps_total")
    touched = counters.delta(traced, "alpa_moe_experts_touched_total")
    positions = counters.delta(traced,
                               "alpa_serving_decode_positions_total")
    if trace is None or obs["peaks"] is None or not steps or \
            touched is None or not positions:
        return None
    runs = trace["program_runs"].get(DECODE_PROGRAM)
    if not runs:
        return None
    least = arithmetic_longcat.decode_tick_bytes(
        obs["config"], touched / steps / obs["expert_layers"],
        positions / steps, obs["cache_itemsize"])
    least_s = sum(least.values()) / obs["peaks"]["hbm_bytes_per_s"]
    return 100.0 * least_s / stats.median(runs)
