"""Median duration of the engine's ``engine.decode-tick`` spans in the
window.  A tick ends in a read-back of the sampled tokens, so it is a true
tick time."""
from chipbench import stats


def read(obs):
    ticks = stats.program_spans(obs, "engine.decode-tick")
    if not ticks:
        return None
    return stats.median([s["dur_us"] for s in ticks]) / 1e3
