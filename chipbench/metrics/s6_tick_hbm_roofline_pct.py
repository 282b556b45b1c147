"""The WHOLE decode tick of a decoder of Mamba-1 mixers against its MEMORY
roofline, as ``ssm_tick_hbm_roofline_pct`` holds Nemotron's: the least a
tick of the traced seconds had to move (``arithmetic_jamba.tick_bytes``:
every layer's weights once; the head's table once; both states of the rows
that held a request (``alpa_serving_tokens_total`` a tick: an active row
is given one token), once in and once out; of the attention caches the
positions those rows HELD, by ``alpa_serving_decode_positions_total`` a
tick) over the HBM peak, over the median time of one run of ``jit_decode``
on the device in those seconds.  The whole tick and not the mixers' part:
a part's own time leaves out the weights the compiler streams ahead under
the part before.  The count leaves out the embedding's rows, every
activation, the free rows' states and the caches' writes, so it is a true
lower bound and the share cannot pass 100.  Nothing where the trace holds
no such run or the program has no such counters."""
from chipbench import arithmetic_jamba, counters, stats

DECODE_PROGRAM = "jit_decode"


def read(obs):
    trace = obs.get("device_trace")
    config = obs.get("config") or {}
    traced = {"counters": obs.get("traced_counters")}
    steps = counters.delta(traced, "alpa_serving_decode_steps_total")
    tokens = counters.delta(traced, "alpa_serving_tokens_total")
    positions = counters.delta(traced,
                               "alpa_serving_decode_positions_total")
    if trace is None or obs.get("peaks") is None or not steps or \
            not tokens or not positions or "mamba_dt_rank" not in config:
        return None
    runs = trace["program_runs"].get(DECODE_PROGRAM)
    if not runs:
        return None
    least = arithmetic_jamba.tick_bytes(
        config, min(tokens / steps, obs["engine_rows"]), positions / steps,
        obs["cache_itemsize"])
    least_s = sum(least.values()) / obs["peaks"]["hbm_bytes_per_s"]
    return 100.0 * least_s / stats.median(runs)
