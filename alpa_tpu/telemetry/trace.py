"""Span tracing: thread-safe recorder exporting Chrome-trace JSON.

One :class:`TraceRecorder` per process collects *spans* (named,
categorized, nested intervals), *instants* (point events — the legacy
``timer.Tracer`` bridge lands here) and *counter* samples (e.g. the
overlap transfer pool's in-flight window).  Every event carries a
*track*: a stable ``tid`` in the exported trace.  By default the track
is the recording thread (``"driver"`` for the main thread, the thread
name otherwise — pool workers get their ``alpa-overlap-N`` names), but
call sites that know better pass one explicitly (``"mesh 3"`` for
per-instruction spans).

``to_chrome_trace()`` emits the Chrome trace event format
(``{"traceEvents": [...]}``) with ``B``/``E`` duration pairs, ``M``
thread-name metadata, ``i`` instants and ``C`` counters — loadable
directly in Perfetto / chrome://tracing.  ``merge_chrome_traces``
combines per-mesh / per-process files onto distinct pids.

``start_capture`` / ``stop_capture`` switch the spans on together with
``jax.profiler`` in a running process, any number of times, and give the
offset between this recorder's clock and the profiler's (read from one
marker span written to both), so that every span, also one written by
``complete()`` from a pool thread, can be laid over the device events.

Zero-cost-when-off: the module-level ``_ENABLED`` flag (seeded from
``ALPA_TPU_TRACE`` via ``global_config.telemetry_enabled``) is checked
before *any* allocation — ``span()`` returns a shared no-op singleton
when tracing is off, and the register-file replay checks the flag once
per step, not per instruction (guarded by a <2% overhead test).
"""
import dataclasses
import glob
import json
import os
import threading
import time
from typing import Any, Dict, List, Optional

from alpa_tpu.global_env import global_config
from alpa_tpu.telemetry import device_time

__all__ = [
    "TraceRecorder", "get_recorder", "set_recorder", "enabled",
    "set_enabled", "span", "instant", "counter", "begin", "end",
    "now_us", "merge_chrome_traces", "CATEGORIES", "NULL_SPAN",
    "Capture", "start_capture", "stop_capture", "last_capture",
    "CAPTURE_MARKER",
]

# category taxonomy (docs/observability.md) — free-form strings are
# accepted; these are the ones the built-in instrumentation uses.
CATEGORIES = ("compile", "instruction", "transfer", "resharding",
              "checkpoint", "serving", "runtime", "legacy")

# perf_counter epoch shared by every event in this process so that
# timestamps from different threads land on one comparable axis.
_EPOCH = time.perf_counter()


def _now_us() -> float:
    return (time.perf_counter() - _EPOCH) * 1e6


def now_us() -> float:
    """Current time on the recorder's shared epoch — pair with
    :meth:`TraceRecorder.complete` for externally-timed spans."""
    return _now_us()


class _NullSpan:
    """Shared do-nothing context manager returned while tracing is off.

    A singleton (``__slots__``, no state) so the disabled path allocates
    nothing — tests assert ``span("a") is span("b")``."""
    __slots__ = ()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False


_NULL_SPAN = _NullSpan()
# for call sites that look at ``enabled()`` once for many spans (the
# serving engine: once a tick) and hand out the no-op themselves
NULL_SPAN = _NULL_SPAN


class _Span:
    """Live span token: context manager AND explicit begin/end handle."""
    __slots__ = ("_rec", "name", "category", "args", "track", "_t0")

    def __init__(self, rec: "TraceRecorder", name: str, category: str,
                 args: Optional[Dict[str, Any]], track: Optional[str]):
        self._rec = rec
        self.name = name
        self.category = category
        self.args = args
        self.track = track
        self._t0 = _now_us()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self._rec._finish(self)
        return False


class TraceRecorder:
    """Thread-safe in-memory event store (bounded by ``max_events``)."""

    def __init__(self, max_events: Optional[int] = None):
        if max_events is None:
            max_events = int(getattr(global_config,
                                     "telemetry_max_events", 200000))
        self.max_events = max_events
        self._lock = threading.Lock()
        # completed spans: (name, category, ts_us, dur_us, tid, args)
        self._spans: List[tuple] = []
        # instants: (name, category, ts_us, tid, args)
        self._instants: List[tuple] = []
        # counters: (name, ts_us, value, tid)
        self._counters: List[tuple] = []
        self._tids: Dict[str, int] = {}
        self._dropped = 0

    # ---- track / tid bookkeeping ------------------------------------

    def _tid(self, track: Optional[str]) -> int:
        if track is None:
            t = threading.current_thread()
            track = ("driver" if t is threading.main_thread()
                     else t.name)
        tid = self._tids.get(track)
        if tid is None:
            with self._lock:
                tid = self._tids.setdefault(track, len(self._tids) + 1)
        return tid

    def _room(self, store: List[tuple]) -> bool:
        if len(store) >= self.max_events:
            self._dropped += 1
            return False
        return True

    # ---- recording --------------------------------------------------

    def span(self, name: str, category: str = "runtime",
             args: Optional[Dict[str, Any]] = None,
             track: Optional[str] = None) -> _Span:
        return _Span(self, name, category, args, track)

    def _finish(self, s: _Span):
        t1 = _now_us()
        tid = self._tid(s.track)
        with self._lock:
            if self._room(self._spans):
                self._spans.append((s.name, s.category, s._t0,
                                    t1 - s._t0, tid, s.args))

    def begin(self, name: str, category: str = "runtime",
              args: Optional[Dict[str, Any]] = None,
              track: Optional[str] = None) -> _Span:
        """Explicit open for async work; close with :meth:`end`.  Pass
        ``track`` when begin and end run on different threads."""
        return self.span(name, category, args, track)

    def end(self, token: Optional[_Span]):
        if token is not None and token is not _NULL_SPAN:
            self._finish(token)

    def complete(self, name: str, category: str, ts_us: float,
                 dur_us: float, args: Optional[Dict[str, Any]] = None,
                 track: Optional[str] = None):
        """Record an already-timed span — async work whose start was
        stamped on another thread (e.g. the overlap pool's queue-wait
        child, whose begin is the driver-side submit).  ``ts_us`` must
        come from :func:`now_us` so it shares the process epoch."""
        tid = self._tid(track)
        with self._lock:
            if self._room(self._spans):
                self._spans.append((name, category, ts_us, dur_us, tid,
                                    args))

    def instant(self, name: str, category: str = "runtime",
                args: Optional[Dict[str, Any]] = None,
                track: Optional[str] = None):
        tid = self._tid(track)
        with self._lock:
            if self._room(self._instants):
                self._instants.append((name, category, _now_us(), tid,
                                       args))

    def counter(self, name: str, value: float,
                track: Optional[str] = None):
        tid = self._tid(track if track is not None else name)
        with self._lock:
            if self._room(self._counters):
                self._counters.append((name, _now_us(), value, tid))

    def clear(self):
        with self._lock:
            self._spans.clear()
            self._instants.clear()
            self._counters.clear()
            self._tids.clear()
            self._dropped = 0

    # ---- introspection / export -------------------------------------

    @property
    def n_events(self) -> int:
        with self._lock:
            return (len(self._spans) + len(self._instants) +
                    len(self._counters))

    def spans(self) -> List[Dict[str, Any]]:
        """Completed spans as dicts (test/tooling convenience)."""
        with self._lock:
            items = list(self._spans)
            tids = dict(self._tids)
        names = {v: k for k, v in tids.items()}
        return [{"name": n, "category": c, "ts_us": ts, "dur_us": dur,
                 "tid": tid, "track": names.get(tid), "args": args}
                for n, c, ts, dur, tid, args in items]

    def to_chrome_trace(self, pid: int = 0,
                        process_name: str = "alpa_tpu") -> Dict[str, Any]:
        with self._lock:
            spans = list(self._spans)
            instants = list(self._instants)
            counters = list(self._counters)
            tids = dict(self._tids)
            dropped = self._dropped
        events: List[Dict[str, Any]] = [{
            "name": "process_name", "ph": "M", "pid": pid, "tid": 0,
            "args": {"name": process_name},
        }]
        for track, tid in sorted(tids.items(), key=lambda kv: kv[1]):
            events.append({"name": "thread_name", "ph": "M",
                           "pid": pid, "tid": tid,
                           "args": {"name": track}})
        timed: List[Dict[str, Any]] = []
        for name, cat, ts, dur, tid, args in spans:
            b = {"name": name, "cat": cat, "ph": "B", "ts": ts,
                 "pid": pid, "tid": tid}
            if args:
                b["args"] = args
            timed.append(b)
            timed.append({"name": name, "cat": cat, "ph": "E",
                          "ts": ts + dur, "pid": pid, "tid": tid})
        for name, cat, ts, tid, args in instants:
            ev = {"name": name, "cat": cat, "ph": "i", "s": "t",
                  "ts": ts, "pid": pid, "tid": tid}
            if args:
                ev["args"] = args
            timed.append(ev)
        for name, ts, value, tid in counters:
            timed.append({"name": name, "ph": "C", "ts": ts,
                          "pid": pid, "tid": tid,
                          "args": {"value": value}})
        # E before B on timestamp ties so a span ending exactly where a
        # sibling starts still nests; real perf_counter stamps are
        # strictly increasing per thread.
        timed.sort(key=lambda e: (e["ts"], 0 if e["ph"] == "E" else 1))
        events.extend(timed)
        trace = {"traceEvents": events,
                 "displayTimeUnit": "ms"}
        if dropped:
            trace["alpa_dropped_events"] = dropped
        return trace

    def save(self, path: str, pid: int = 0,
             process_name: str = "alpa_tpu"):
        with open(path, "w", encoding="utf-8") as f:
            json.dump(self.to_chrome_trace(pid, process_name), f)


def merge_chrome_traces(traces: List[Dict[str, Any]]) -> Dict[str, Any]:
    """Merge chrome traces (e.g. one per mesh/process) onto distinct
    pids so every input keeps its own track group in Perfetto."""
    events: List[Dict[str, Any]] = []
    for pid, trace in enumerate(traces):
        for ev in trace.get("traceEvents", []):
            ev = dict(ev)
            ev["pid"] = pid
            events.append(ev)
    return {"traceEvents": events, "displayTimeUnit": "ms"}


# ---- module-level recorder + zero-cost-when-off front door -----------

_ENABLED = bool(getattr(global_config, "telemetry_enabled", False))
_RECORDER = TraceRecorder()


def get_recorder() -> TraceRecorder:
    return _RECORDER


def set_recorder(rec: TraceRecorder) -> TraceRecorder:
    """Swap the process recorder (tests install a fresh one); returns
    the previous recorder."""
    global _RECORDER
    prev = _RECORDER
    _RECORDER = rec
    return prev


def enabled() -> bool:
    return _ENABLED


def set_enabled(flag: bool) -> bool:
    """Flip tracing on/off; keeps ``global_config.telemetry_enabled`` in
    sync.  Returns the previous value."""
    global _ENABLED
    prev = _ENABLED
    _ENABLED = bool(flag)
    global_config.telemetry_enabled = bool(flag)
    return prev


def span(name: str, category: str = "runtime",
         args: Optional[Dict[str, Any]] = None,
         track: Optional[str] = None):
    """Context manager recording a span — or the shared no-op singleton
    when tracing is off (no allocation on the disabled path)."""
    if not _ENABLED:
        return _NULL_SPAN
    return _RECORDER.span(name, category, args, track)


def begin(name: str, category: str = "runtime",
          args: Optional[Dict[str, Any]] = None,
          track: Optional[str] = None) -> Optional[_Span]:
    """Open an async span; returns None when tracing is off (safe to
    pass straight back to :func:`end`)."""
    if not _ENABLED:
        return None
    return _RECORDER.begin(name, category, args, track)


def end(token: Optional[_Span]):
    if token is not None:
        _RECORDER.end(token)


def instant(name: str, category: str = "runtime",
            args: Optional[Dict[str, Any]] = None,
            track: Optional[str] = None):
    if _ENABLED:
        _RECORDER.instant(name, category, args, track)


def counter(name: str, value: float, track: Optional[str] = None):
    if _ENABLED:
        _RECORDER.counter(name, value, track)


# ---- capture: spans and the device profiler, on and off together ------

# the one span written both to the recorder and to the profiler's trace
CAPTURE_MARKER = "alpa.capture"


@dataclasses.dataclass
class Capture:
    """What :func:`stop_capture` returns: the recorder's spans of the
    capture (dicts as :meth:`TraceRecorder.spans` gives them, the marker
    among them), where the profiler wrote its trace, and the marker's
    start on the recorder's clock.  :func:`stop_capture` reads the
    profiler's trace once, while the file is there, and the capture keeps
    what :meth:`offset_us` and :meth:`device_time` answer from."""
    log_dir: str
    spans: List[Dict[str, Any]]
    marker_ts_us: float
    _offset_us: Optional[float] = None
    _device_time: Optional[Dict[str, Any]] = None
    # what the pipeshard executables alive when the capture stopped said of
    # their programs (``device_time.registered_pipelines``)
    _pipelines: Any = ()
    _pipeline_time: Optional[Dict[str, Any]] = None

    def xplane_path(self) -> str:
        found = sorted(glob.glob(os.path.join(
            self.log_dir, "plugins", "profile", "*", "*.xplane.pb")))
        if not found:
            raise FileNotFoundError(f"no .xplane.pb under {self.log_dir}")
        return found[-1]

    def _read(self) -> None:
        """The one read of the profiler's trace: the marker (the offset
        between the clocks, the window) and the device events, reduced."""
        marker, chips = device_time.read_profile(self.xplane_path(),
                                                 CAPTURE_MARKER)
        if marker is not None:
            self._offset_us = marker[0] / 1e3 - self.marker_ts_us
        self._device_time = device_time.reduce_events(chips, marker)

    def offset_us(self) -> float:
        """Microseconds to ADD to a recorder timestamp (``ts_us``) to get
        the same instant on the clock of the profiler's events
        (``start_ns / 1e3`` of an xplane event, host or device): the
        marker's start as the profiler saw it minus its start as the
        recorder saw it."""
        if self._device_time is None:
            self._read()
        if self._offset_us is None:
            raise ValueError(
                f"the profiler's trace holds no {CAPTURE_MARKER!r} event: "
                "the offset between the clocks is unknown")
        return self._offset_us

    def device_time(self) -> Dict[str, Any]:
        """The capture's device time by chip, program and part of the
        model (``telemetry/device_time.py`` ``reduce_events`` says what the
        table holds).  Without a TPU's plane in the trace, as on the CPU,
        it holds no chip."""
        if self._device_time is None:
            self._read()
        return self._device_time

    def pipeline_time(self) -> Dict[str, Any]:
        """The traced pipeshard steps' account on the device's clock, by
        mesh (``"mesh 0"``), in seconds summed over the steps::

            {mesh: {"chips": n, "envelope_s", "busy_s", "boundary_s",
                    "upstream_s", "dispatch_s", "edge_s",
                    "collective_exposed_s", "collective_hidden_s",
                    "dispatch_by_span": {span: seconds}}}

        ``busy_s`` and the four causes of idleness add up to
        ``envelope_s`` (``telemetry/perf.py`` ``_device_bubbles`` says what
        each cause is); the two collective times are means over the mesh's
        chips.  ``{}`` where no pipeshard step was traced or none joined
        (the CPU: no device events).  Made when first asked for, from what
        :func:`stop_capture` read and kept; reads nothing again."""
        if self._pipeline_time is None:
            self._pipeline_time = {}
            if self._pipelines and self._device_time is not None:
                from alpa_tpu.telemetry import perf
                self._pipeline_time = perf.pipeline_time(self,
                                                         self._pipelines)
        return self._pipeline_time


# (log_dir, enabled() before, the marker's annotation, its start)
_CAPTURE: Optional[tuple] = None
_LAST_CAPTURE: Optional[Capture] = None


def last_capture() -> Optional[Capture]:
    """The newest :class:`Capture` that :func:`stop_capture` returned in
    this process (None before the first): for a reader that is handed no
    capture."""
    return _LAST_CAPTURE


def start_capture(log_dir: str) -> None:
    """Start tracing in this process: clear the recorder, switch the
    spans on, start ``jax.profiler`` writing to ``log_dir`` (no python
    call stacks; host annotations on), and open the marker span.  End
    with :func:`stop_capture`; the pair can be used again and again."""
    global _CAPTURE
    if _CAPTURE is not None:
        raise RuntimeError("a capture is already running "
                           f"(into {_CAPTURE[0]})")
    import jax.profiler
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    options.host_tracer_level = 2
    _RECORDER.clear()
    jax.profiler.start_trace(log_dir, profiler_options=options)
    was = set_enabled(True)
    note = jax.profiler.TraceAnnotation(CAPTURE_MARKER)
    note.__enter__()
    _CAPTURE = (log_dir, was, note, _now_us())


def stop_capture() -> Capture:
    """Close the marker, stop the profiler, put ``enabled()`` back to
    what :func:`start_capture` found, read the profiler's trace once (the
    offset between the clocks; the device time by program and by part of
    the model, for which the programs that ran are asked for their HLO
    text: ``telemetry/device_time.py``) and return the :class:`Capture`,
    which :func:`last_capture` gives again.  The recorder keeps its spans
    until it is cleared."""
    global _CAPTURE, _LAST_CAPTURE
    if _CAPTURE is None:
        raise RuntimeError("no capture is running")
    import jax.profiler
    log_dir, was, note, ts = _CAPTURE
    _CAPTURE = None
    end = _now_us()
    note.__exit__(None, None, None)
    _RECORDER.complete(CAPTURE_MARKER, "runtime", ts, end - ts,
                       track="capture")
    try:
        jax.profiler.stop_trace()
    finally:
        set_enabled(was)
    capture = Capture(log_dir, _RECORDER.spans(), ts,
                      _pipelines=device_time.registered_pipelines())
    try:
        capture._read()     # pylint: disable=protected-access
    except FileNotFoundError:   # the profiler wrote nothing: asked again,
        pass                    # offset_us() and device_time() say so
    _LAST_CAPTURE = capture
    return capture
