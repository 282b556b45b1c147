"""What a run observes about itself: the benchmark's own spans, jax's
compile events, device memory, and the clock that set-up is measured on."""
import contextlib
import os
import threading
import time


def seconds_since_process_start() -> float:
    """Seconds since the kernel started this process: the interpreter's own
    start-up and every import count as set-up.  (Field 22 of
    /proc/self/stat is the start time in clock ticks since boot.)"""
    with open("/proc/self/stat") as f:
        fields = f.read().rsplit(")", 1)[1].split()
    started = int(fields[19]) / os.sysconf("SC_CLK_TCK")
    return time.clock_gettime(time.CLOCK_BOOTTIME) - started


class Spans:
    """The benchmark's own spans around its calls into the program:
    (name, start, end) on ``time.perf_counter``.  While the profiler runs
    each span is also written into the profiler's trace
    (``jax.profiler.TraceAnnotation``), which puts it on the clock of the
    device events, so that an idle gap can be given the name of what the
    host was doing."""

    def __init__(self):
        self.items = []
        self.annotate = False
        self._lock = threading.Lock()

    @contextlib.contextmanager
    def span(self, name: str):
        note = None
        if self.annotate:
            import jax.profiler
            note = jax.profiler.TraceAnnotation("chipbench." + name)
            note.__enter__()
        tic = time.perf_counter()
        try:
            yield
        finally:
            toc = time.perf_counter()
            if note is not None:
                note.__exit__(None, None, None)
            with self._lock:
                self.items.append((name, tic, toc))


class CompileEvents:
    """Seconds jax spent tracing, lowering and compiling, and the hits and
    misses of its persistent compilation cache, from ``jax.monitoring``
    (the pattern of ``chip_smoke.py``'s ``_Setup``)."""

    TRACE = "/jax/core/compile/jaxpr_trace_duration"
    LOWER = "/jax/core/compile/jaxpr_to_mlir_module_duration"
    COMPILE = "/jax/core/compile/backend_compile_duration"
    HITS = "/jax/compilation_cache/cache_hits"
    MISSES = "/jax/compilation_cache/cache_misses"

    def __init__(self):
        import jax.monitoring
        self.seconds = {}
        self.counts = {}
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, secs, **_):
        self.seconds[event] = self.seconds.get(event, 0.0) + secs
        self.counts[event] = self.counts.get(event, 0) + 1

    def _event(self, event, **_):
        self.counts[event] = self.counts.get(event, 0) + 1

    def snapshot(self) -> dict:
        return {"seconds": dict(self.seconds), "counts": dict(self.counts)}


def device_memory(devices) -> list:
    out = []
    for d in devices:
        stats = d.memory_stats() or {}
        out.append({"id": d.id,
                    "bytes_in_use": stats.get("bytes_in_use"),
                    "peak_bytes_in_use": stats.get("peak_bytes_in_use")})
    return out
