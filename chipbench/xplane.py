"""Reduction of a jax profiler trace (``*.xplane.pb``) to what the
benchmark reports: busy and idle seconds of each chip, the device
operations that took most time (summed by kind and result type), and the
idle gaps by what the host was doing.  Read with ``jax.profiler.ProfileData``, nothing else.

An idle gap is named by spans: the benchmark's own, and the program's
``TraceRecorder`` spans of a capture, which the caller has shifted onto the
profiler's clock (``trace.Capture.offset_us``).  Each instant of a gap goes
to the SHORTEST span open at that instant, on whatever thread, so a span
keeps only the time none of its children (and no shorter span elsewhere)
covers, and a gap that straddles two phases is split between them.

A TPU's plane is named ``/device:TPU:<n>``.  Its line ``XLA Ops`` holds one
event per executed HLO operation; ``XLA Modules`` holds one event per run of
a compiled program, named ``jit_<function>(<fingerprint>)``, from its first
operation to its last: they contain the operations and are not counted into
the busy time again, but give each program's time on the device.  Host threads are lines of the
plane ``/host:CPU``; the benchmark's own spans appear there as events named
``chipbench.<span>`` (see ``observe.Spans``), on the same clock.
"""
import bisect
import glob
import heapq
import os
import re

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
HOST_PLANE = "/host:CPU"
SPAN_PREFIX = "chipbench."
WINDOW_SPAN = SPAN_PREFIX + "traced_window"
UNATTRIBUTED = "unattributed"


def find_xplane(trace_dir: str) -> str:
    found = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return found[-1]


def union(intervals: list) -> list:
    """Sorted, non-overlapping union of (start, end) intervals."""
    merged = []
    for start, end in sorted(intervals):
        if merged and start <= merged[-1][1]:
            if end > merged[-1][1]:
                merged[-1][1] = end
        else:
            merged.append([start, end])
    return merged


def _clip(intervals, lo, hi):
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if min(e, hi) > max(s, lo)]


def _overlap(a0, a1, b0, b1):
    return max(0.0, min(a1, b1) - max(a0, b0))


def shortest_cover(spans: list) -> list:
    """Sorted, non-overlapping [(start, end, name)]: over each stretch the
    name of the shortest of the spans (name, start, end) open there.  Where
    no span is open there is no entry.  Two spans of one length: the name
    first in the alphabet, so that the answer does not depend on order."""
    spans = sorted((s, e, n) for n, s, e in spans if e > s)
    points = sorted({t for s, e, _ in spans for t in (s, e)})
    cover, open_, nxt = [], [], 0       # open_: heap of (length, name, end)
    for lo, hi in zip(points, points[1:]):
        while nxt < len(spans) and spans[nxt][0] <= lo:
            s, e, n = spans[nxt]
            heapq.heappush(open_, (e - s, n, e))
            nxt += 1
        while open_ and open_[0][2] <= lo:
            heapq.heappop(open_)
        if not open_:
            continue
        name = open_[0][1]
        if cover and cover[-1][2] == name and cover[-1][1] == lo:
            cover[-1][1] = hi
        else:
            cover.append([lo, hi, name])
    return [tuple(c) for c in cover]


def gap_shares(cover: list, starts: list, g0, g1) -> dict:
    """{name: nanoseconds} of the gap [g0, g1] by ``cover`` (as
    ``shortest_cover`` gives it; ``starts`` its start times); what no span
    covers goes to ``unattributed``."""
    shares, named = {}, 0.0
    i = max(0, bisect.bisect_right(starts, g0) - 1)
    while i < len(cover) and cover[i][0] < g1:
        d = _overlap(g0, g1, cover[i][0], cover[i][1])
        if d > 0:
            shares[cover[i][2]] = shares.get(cover[i][2], 0.0) + d
            named += d
        i += 1
    if g1 - g0 > named:
        shares[UNATTRIBUTED] = (g1 - g0) - named
    return shares


def op_label(hlo: str) -> str:
    """A device event's name is its whole HLO instruction.  The label keeps
    what tells operations apart and survives a recompile: the instruction's
    name without its number, and the type of its (first) result, as in
    ``copy bf16[4,2048,32,64]``."""
    head, _, rest = hlo.partition(" = ")
    base = re.sub(r"(\.\d+|\.remat\d*|\.clone)+$", "", head.lstrip("%"))
    shape = re.search(r"[a-z]+\d*\[[\d,]*\]", rest)
    return f"{base} {shape.group(0)}" if shape else base


def module_label(name: str) -> str:
    """``jit_decode(6784743675013484822)`` -> ``jit_decode``: the
    fingerprint changes with every recompile."""
    return re.sub(r"\(\d+\)$", "", name)


def read_trace(path: str):
    """(device events by chip, host spans, program runs by chip): device
    events are {chip: [(name, start_ns, end_ns)]} from each TPU plane's
    ``XLA Ops`` line, program runs the same from its ``XLA Modules`` line;
    host spans are [(name, start_ns, end_ns)] of the benchmark's own
    annotations."""
    import jax.profiler
    data = jax.profiler.ProfileData.from_file(path)
    device, host, modules = {}, [], {}
    for plane in data.planes:
        m = DEVICE_PLANE.match(plane.name)
        if m:
            by_line = {OPS_LINE: [], MODULES_LINE: []}
            for line in plane.lines:
                if line.name in by_line:
                    by_line[line.name] += [
                        (e.name, e.start_ns, e.start_ns + e.duration_ns)
                        for e in line.events]
            device[int(m.group(1))] = by_line[OPS_LINE]
            modules[int(m.group(1))] = by_line[MODULES_LINE]
        elif plane.name == HOST_PLANE:
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith(SPAN_PREFIX):
                        host.append((e.name, e.start_ns,
                                     e.start_ns + e.duration_ns))
    return device, host, modules


def program_runs(modules: dict, lo: float, hi: float) -> dict:
    """{program: sorted seconds of each of its runs that lie wholly inside
    [lo, hi]}, over all chips."""
    runs = {}
    for events in modules.values():
        for name, s, e in events:
            if lo <= s and e <= hi:
                runs.setdefault(module_label(name), []).append((e - s) / 1e9)
    return {name: sorted(secs) for name, secs in runs.items()}


def reduce_events(device: dict, host: list, modules: dict = None,
                  top: int = 10, program: list = ()) -> dict:
    """The summary of one traced window.  The window is the benchmark's
    span ``chipbench.traced_window`` where the trace holds it, else from
    the first to the last device event.  Chips with no event at all in the
    window count as idle all through it.  All seconds are means over the
    chips in ``device``; ``program_runs`` holds the seconds of every single
    run.  ``program`` holds the program's own spans (name, start_ns,
    end_ns), already on the trace's clock: they name idle gaps beside the
    benchmark's spans in ``host``."""
    if not device or not any(device.values()):
        raise ValueError("the trace holds no device operation")
    window = [(s, e) for n, s, e in host if n == WINDOW_SPAN]
    if window:
        lo, hi = min(s for s, _ in window), max(e for _, e in window)
    else:
        lo = min(s for ev in device.values() for _, s, _e in ev)
        hi = max(e for ev in device.values() for _, _s, e in ev)
    cover = shortest_cover(
        [(n[len(SPAN_PREFIX):], s, e) for n, s, e in host
         if n != WINDOW_SPAN] + list(program))
    cover_starts = [c[0] for c in cover]
    # the longest stretches of the window that no span covers, [seconds,
    # at which second of the window]: where "unattributed" comes from
    covered = union(_clip([(s, e) for s, e, _ in cover], lo, hi))
    edges = [lo] + [t for iv in covered for t in iv] + [hi]
    bare = sorted(([(g1 - g0) / 1e9, (g0 - lo) / 1e9]
                   for g0, g1 in zip(edges[0::2], edges[1::2]) if g1 > g0),
                  reverse=True)[:5]
    n_chips = len(device)
    busy_by_chip, op_ns, op_count, gap_ns = {}, {}, {}, {}
    for chip, events in device.items():
        busy = union(_clip([(s, e) for _, s, e in events], lo, hi))
        busy_by_chip[chip] = sum(e - s for s, e in busy) / 1e9
        for name, s, e in events:
            d = _overlap(s, e, lo, hi)
            if d > 0:
                label = op_label(name)
                op_ns[label] = op_ns.get(label, 0.0) + d
                op_count[label] = op_count.get(label, 0) + 1
        edges = [lo] + [t for iv in busy for t in iv] + [hi]
        for g0, g1 in zip(edges[0::2], edges[1::2]):
            if g1 <= g0:
                continue
            # each instant of the gap goes to the shortest span open then
            for name, ns in gap_shares(cover, cover_starts, g0, g1).items():
                gap_ns[name] = gap_ns.get(name, 0.0) + ns

    def ranked(table):
        rows = sorted(table.items(), key=lambda kv: -kv[1])[:top]
        return [[name, ns / 1e9 / n_chips] for name, ns in rows]

    return {
        "window_s": (hi - lo) / 1e9,
        "busy_s": sum(busy_by_chip.values()) / n_chips,
        "busy_s_by_chip": {str(c): busy_by_chip[c]
                           for c in sorted(busy_by_chip)},
        # operations of one label are summed; "x<n>" is how many ran, a chip
        "device_ops": [[f"{label} x{round(op_count[label] / n_chips)}", sec]
                       for label, sec in ranked(op_ns)],
        "idle_gaps": ranked(gap_ns),
        "uncovered": bare,
        "program_runs": program_runs(modules or {}, lo, hi),
    }


def reduce_trace(trace_dir: str, program: list = ()) -> dict:
    return reduce_events(*read_trace(find_xplane(trace_dir)),
                         program=program)
