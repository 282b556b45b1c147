"""What a dense admission keeps the engine's thread for: the median
duration of the program's ``engine.prefill`` spans of the traced interval
whose ``path`` is ``dense``.  The span runs from taking the row to the
return of the scatter's enqueue, so it is the host's work around two
asynchronous calls (the inputs, the call of the prefill, the call of the
scatter) and holds no device time unless the host is made to wait.  While
it lasts nothing else is enqueued: beyond the one decode queued ahead, the
chip idles for it.  Nothing where the interval holds no dense admission (an
engine that admits in chunks, an interval without an arrival)."""
from chipbench import stats


def read(obs):
    spans = [s for s in stats.program_spans(obs, "engine.prefill")
             if (s["args"] or {}).get("path") == "dense"]
    if not spans:
        return None
    return stats.median([s["dur_us"] for s in spans]) / 1e3
