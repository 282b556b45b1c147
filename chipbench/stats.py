"""The arithmetic the metric readers share."""
import math


def percentile(values, q: float) -> float:
    """Nearest-rank percentile: the smallest value with at least ``q`` per
    cent of the sample at or below it."""
    ordered = sorted(values)
    if not ordered:
        raise ValueError("percentile of nothing")
    rank = max(1, math.ceil(q / 100.0 * len(ordered)))
    return ordered[rank - 1]


def median(values) -> float:
    ordered = sorted(values)
    if not ordered:
        raise ValueError("median of nothing")
    mid = len(ordered) // 2
    return ordered[mid] if len(ordered) % 2 else \
        (ordered[mid - 1] + ordered[mid]) / 2


def train_tokens_per_s(obs: dict) -> float:
    """Tokens of the whole steps completed, over the time to the end of the
    last of them."""
    steps = obs["steps"]
    return len(steps) * obs["tokens_per_step"] / steps[-1][2]


def peak_memory_gb(obs: dict):
    """Largest allocator peak over the run's chips, in GB; None where the
    backend reports none."""
    peaks = [m["peak_bytes_in_use"] for m in obs["memory"]
             if m["peak_bytes_in_use"]]
    return max(peaks) / 1e9 if peaks else None


def program_spans(obs: dict, name_prefix: str, in_window: bool = True) -> list:
    """The program's own ``TraceRecorder`` spans whose name starts with
    ``name_prefix``; by default only those that began inside the measured
    window."""
    lo, hi = obs["program_window_us"]
    return [s for s in obs["program_spans"]
            if s["name"].startswith(name_prefix) and
            (not in_window or lo <= s["ts_us"] <= hi)]


def ttft_waits(requests: list, drain_end: float) -> list:
    """Seconds from when each request was DUE to its first streamed token.
    A request that never got a token waits until the end of the drain.
    (One cut off at the end of a closed-loop window is left out: it was not
    given the time to answer.)"""
    return [(rec["token_times"][0] if rec["token_times"] else drain_end)
            - rec["due"] for rec in requests if not rec["cut"]]


def window_tokens(obs: dict) -> list:
    """(request, index of the token, time) of every token that reached a
    client inside the measured window, whether or not its request was
    finished by the end of it."""
    t0, t1 = obs["window"]
    return [(rec, k, t) for rec in obs["requests"]
            for k, t in enumerate(rec["token_times"]) if t0 <= t <= t1]
