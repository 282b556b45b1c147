"""Mean share of the engine's rows that were decoding, over the window's
ticks (the ``active`` argument of ``engine.decode-tick``)."""
from chipbench import stats


def read(obs):
    ticks = stats.program_spans(obs, "engine.decode-tick")
    if not ticks:
        return None
    active = sum(s["args"]["active"] for s in ticks) / len(ticks)
    return 100.0 * active / obs["engine_rows"]
