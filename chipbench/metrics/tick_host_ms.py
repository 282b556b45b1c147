"""The host's own share of an engine tick: an ``engine.decode-tick`` span
less its ``engine.wait`` child (where the host waits for the device), median
over the traced ticks.  What is left is sampling, per-row resampling,
enqueueing the decode, delivering tokens and admitting requests."""
from chipbench import stats


def read(obs):
    ticks = stats.program_spans(obs, "engine.decode-tick")
    waits = sorted((s["ts_us"], s["dur_us"])
                   for s in stats.program_spans(obs, "engine.wait"))
    if not ticks or not waits:
        return None
    own = []
    for tick in ticks:
        lo, hi = tick["ts_us"], tick["ts_us"] + tick["dur_us"]
        own.append(tick["dur_us"] -
                   sum(d for ts, d in waits if lo <= ts <= hi))
    return stats.median(own) / 1e3
