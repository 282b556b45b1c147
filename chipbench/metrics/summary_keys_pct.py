"""Of the keys the traced ticks' queries saw, the share that were
summaries: the rise of the program's counter
``alpa_serving_eva_keys_total{kind="summary"}`` over the rise of both
kinds (the engine feeds them a tick from the rows' positions: a query at
``t`` sees ``t % window + 1`` exact keys and ``window / chunk x (t //
window)`` summaries).  How far the traffic reaches the mechanism: 0 for a
cell whose rows never leave their first window, where the model is a dense
multi-head decoder.  Nothing where the program has no such counter."""
from chipbench import counters

SERIES = 'alpa_serving_eva_keys_total{kind="%s"}'


def read(obs):
    traced = {"counters": obs.get("traced_counters")}
    exact, summary = (counters.delta(traced, SERIES % kind)
                      for kind in ("exact", "summary"))
    if exact is None or summary is None or not exact + summary:
        return None
    return 100.0 * summary / (exact + summary)
