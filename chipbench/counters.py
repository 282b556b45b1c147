"""What the readers of ``program_counter`` metrics share: the change of the
program's metrics registry over the measured window.  ``obs["counters"]``
is a pair of ``MetricsRegistry.snapshot()`` dicts, taken by the driver at
the window's start and end: a counter is a number there, a histogram a dict
with its ``count`` and ``sum``.  A series the program does not have, or
that did not move in the window, reads as None."""


def delta(obs: dict, name: str):
    """How much the counter ``name`` rose over the window."""
    pair = obs.get("counters")
    if not pair or name not in pair[1]:
        return None
    return pair[1][name] - pair[0].get(name, 0.0)


def mean_observed(obs: dict, name: str):
    """Mean of what the histogram ``name`` observed in the window (the rise
    of its sum over the rise of its count)."""
    pair = obs.get("counters")
    if not pair or name not in pair[1]:
        return None
    before = pair[0].get(name, {"count": 0, "sum": 0.0})
    n = pair[1][name]["count"] - before["count"]
    if n <= 0:
        return None
    return (pair[1][name]["sum"] - before["sum"]) / n


def moved(obs: dict) -> dict:
    """For the record: the rise of every counter (and gauge) that moved
    over the window, and [count, sum] of what each histogram observed."""
    before, after = obs["counters"]
    out = {}
    for name, value in after.items():
        if isinstance(value, dict):
            was = before.get(name, {"count": 0, "sum": 0.0})
            if value["count"] != was["count"]:
                out[name] = [value["count"] - was["count"],
                             value["sum"] - was["sum"]]
        elif value != before.get(name, 0.0):
            out[name] = value - before.get(name, 0.0)
    return out


def per_step(obs: dict, name: str, steps: str):
    """The rise of the counter ``name`` over the rise of ``steps``."""
    total, n = delta(obs, name), delta(obs, steps)
    if total is None or not n:
        return None
    return total / n
