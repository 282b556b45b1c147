"""The decode program against its memory roofline: the bytes one tick has
to read (every served weight once, and the keys and values of every position
its rows attend to: ``arithmetic.decode_tick_bytes``) over the HBM peak,
divided by the median time one run of the decode program takes on the
device (the profiler's ``XLA Modules`` line, traced seconds of the window).
Memory bounds it: a tick of 4 rows does about 2 FLOPs per weight byte.  The
host's share of a tick is not in it: that is ``tick_ms``."""
from chipbench import arithmetic, stats

# the engine's decode step is ``jax.jit(decode)`` (serve/generation.py)
DECODE_PROGRAM = "jit_decode"


def read(obs):
    trace = obs["device_trace"]
    ticks = stats.program_spans(obs, "engine.decode-tick")
    if obs["peaks"] is None or trace is None or not ticks:
        return None
    runs = trace["program_runs"].get(DECODE_PROGRAM)
    if not runs:
        return None
    # a token at index k of a request with p prompt tokens was made by a
    # tick that attended to p + k positions of that row
    positions = sum(len(rec["prompt_ids"]) + k
                    for rec, k, _ in stats.window_tokens(obs))
    config = obs["config"]
    per_tick = arithmetic.decode_tick_bytes(
        obs["weight_bytes"], positions / len(ticks), config["hidden_size"],
        config["num_hidden_layers"], obs["cache_itemsize"])
    least_s = per_tick / obs["peaks"]["hbm_bytes_per_s"]
    return 100.0 * least_s / stats.median(runs)
