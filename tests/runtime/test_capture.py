"""The capture control (``telemetry.trace.start_capture`` /
``stop_capture``) and the serving engine's spans and counters it switches
on: one clock for the recorder's spans and the profiler's events, spans
nested where the work happens, counters that count once a request."""
import json
import time
import urllib.request

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from alpa_tpu.model.gpt_model import GPTConfig, init_gpt_real
from alpa_tpu.serve import Generator, run_controller
from alpa_tpu.serve import engine as engine_mod
from alpa_tpu.serve.engine import ContinuousBatchingEngine
from alpa_tpu.serve.generation import GenerationConfig
from alpa_tpu.telemetry import metrics as tmetrics
from alpa_tpu.telemetry import trace as ttrace
from alpa_tpu.telemetry.trace import TraceRecorder

CFG = GPTConfig(hidden_size=32, num_layers=2, num_heads=4, seq_len=64,
                vocab_size=64)
BUCKET = 16
TICK_CHILDREN = ("engine.sample", "engine.dispatch", "engine.wait",
                 "engine.deliver")


@pytest.fixture
def recorder():
    """A fresh recorder with tracing OFF; both restored afterwards."""
    rec = TraceRecorder()
    old = ttrace.set_recorder(rec)
    prev = ttrace.set_enabled(False)
    yield rec
    ttrace.set_enabled(prev)
    ttrace.set_recorder(old)


def _profiler_events(capture, name):
    data = jax.profiler.ProfileData.from_file(capture.xplane_path())
    return [e for plane in data.planes for line in plane.lines
            for e in line.events if e.name == name]


@pytest.mark.parametrize("was_on", [False, True])
def test_capture_twice_leaves_enabled_as_found(recorder, tmp_path, was_on):
    ttrace.set_enabled(was_on)
    for k in range(2):
        with ttrace.span("stale"):      # cleared by the next start
            pass
        ttrace.start_capture(str(tmp_path / f"t{k}"))
        assert ttrace.enabled()
        with ttrace.span(f"inside-{k}"):
            jnp.ones((8, 8)).sum().block_until_ready()
        capture = ttrace.stop_capture()
        assert ttrace.enabled() is was_on
        names = [s["name"] for s in capture.spans]
        assert names == [f"inside-{k}", ttrace.CAPTURE_MARKER]
        assert len(_profiler_events(capture, ttrace.CAPTURE_MARKER)) == 1


def test_capture_misuse_is_an_error(recorder, tmp_path):
    with pytest.raises(RuntimeError, match="no capture"):
        ttrace.stop_capture()
    ttrace.start_capture(str(tmp_path / "a"))
    try:
        with pytest.raises(RuntimeError, match="already running"):
            ttrace.start_capture(str(tmp_path / "b"))
    finally:
        ttrace.stop_capture()
    assert not ttrace.enabled()


@pytest.mark.parametrize("from_pool_thread", [False, True])
def test_offset_puts_recorder_spans_on_the_profilers_clock(
        recorder, tmp_path, from_pool_thread):
    """A span written to the recorder and, as an annotation, to the
    profiler lands within 1 ms of itself once shifted by the marker's
    offset — also one written by ``complete()`` from stamps."""
    ttrace.start_capture(str(tmp_path / "t"))
    time.sleep(0.01)
    if from_pool_thread:
        ts = ttrace.now_us()
        with jax.profiler.TraceAnnotation("probe"):
            time.sleep(0.005)
        ttrace.get_recorder().complete("probe", "transfer", ts,
                                       ttrace.now_us() - ts, track="pool")
    else:
        with ttrace.span("probe"):
            with jax.profiler.TraceAnnotation("probe"):
                time.sleep(0.005)
    capture = ttrace.stop_capture()
    (seen,) = _profiler_events(capture, "probe")
    (span,) = [s for s in capture.spans if s["name"] == "probe"]
    shifted_us = span["ts_us"] + capture.offset_us()
    assert abs(shifted_us - seen.start_ns / 1e3) < 1000.0
    assert abs(span["dur_us"] - seen.duration_ns / 1e3) < 1000.0


def test_engine_phases_are_the_shared_noop_while_tracing_is_off(recorder):
    assert engine_mod._phase(None, "a") is engine_mod._phase(None, "b")
    assert engine_mod._phase(None, "a") is ttrace.span("c")
    assert ttrace.span("a") is ttrace.span("b") is ttrace.NULL_SPAN


def _engine(max_batch=2):
    model, params = init_gpt_real(CFG, 1)
    gen = Generator(model, params, CFG, batch_size=1,
                    prompt_buckets=[BUCKET])
    return ContinuousBatchingEngine(gen, max_batch=max_batch,
                                    prompt_bucket=BUCKET)


def _wait_for_ticks(rec, n, timeout=30.0):
    """A request is done when its last token is delivered; the tick that
    delivered it closes its span a moment later, on the engine's thread."""
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        if sum(s["name"] == "engine.decode-tick" for s in rec.spans()) >= n:
            return
        time.sleep(0.01)
    raise AssertionError(f"fewer than {n} ticks were recorded")


def _inside(child, parent):
    return parent["ts_us"] <= child["ts_us"] and \
        child["ts_us"] + child["dur_us"] <= \
        parent["ts_us"] + parent["dur_us"]


def test_engine_counters_count_once_a_request(recorder):
    """Tracing off: the always-on counters still tick, once a request."""
    reg = tmetrics.get_registry()
    prompts = [np.arange(1, 1 + n, dtype=np.int32) for n in (3, 7, 12)]
    eng = _engine()
    try:
        before = reg.snapshot()
        for p in prompts:
            eng.submit(p, GenerationConfig(max_new_tokens=3))
        after = reg.snapshot()
    finally:
        eng.shutdown()

    def rise(name):
        return after[name] - before.get(name, 0)

    assert rise("alpa_serving_admissions_total") == len(prompts)
    assert rise("alpa_serving_prefill_prompt_tokens_total") == \
        sum(len(p) for p in prompts)
    assert rise("alpa_serving_prefill_padded_tokens_total") == \
        BUCKET * len(prompts)
    waits = after["alpa_serving_queue_wait_seconds"]
    was = before.get("alpa_serving_queue_wait_seconds", {"count": 0})
    assert waits["count"] - was["count"] == len(prompts)
    assert recorder.n_events == 0       # and nothing was traced


def test_engine_spans_nest_and_share_the_request_id(recorder, tmp_path):
    """A streamed completion over HTTP inside a capture: the queue wait
    ends where the admission takes the row, the prefill is the admission's
    child, every tick has its four phases, and ``rid`` is the same from
    ``serve.request`` down."""
    model, params = init_gpt_real(CFG, 1)
    gen = Generator(model, params, CFG, batch_size=1,
                    prompt_buckets=[BUCKET])
    server = run_controller(port=0)
    try:
        server.controller.register_model("tiny", gen)
        ttrace.start_capture(str(tmp_path / "t"))
        req = urllib.request.Request(
            f"http://127.0.0.1:{server.port}/completions",
            data=json.dumps({"model": "tiny", "stream": True,
                             "prompt_ids": [1, 2, 3, 4, 5],
                             "max_new_tokens": 4}).encode(),
            headers={"Content-Type": "application/json"})
        with urllib.request.urlopen(req) as r:
            events = [json.loads(line[6:]) for line in r
                      if line.startswith(b"data: ")]
        _wait_for_ticks(recorder, 4)
        capture = ttrace.stop_capture()
    finally:
        replica = server.controller._pick_replica("tiny")
        if replica._engine is not None:
            replica.engine.shutdown()
        server.shutdown()
    assert [e for e in events if "token" in e] and events[-1] == \
        {"done": True}
    by_name = {}
    for s in capture.spans:
        by_name.setdefault(s["name"], []).append(s)

    (request,) = by_name["serve.request"]
    (wait,) = by_name["engine.queue-wait"]
    (admit,) = by_name["engine.admit"]
    (prefill,) = by_name["engine.prefill"]
    rid = request["args"]["rid"]
    assert wait["args"] == {"rid": rid, "prompt_len": 5}
    assert prefill["args"] == {"rid": rid, "prompt_len": 5,
                               "padded_len": BUCKET, "path": "dense",
                               "chunks": 1, "head_rows": BUCKET}
    assert admit["args"] == {"n": 1}
    assert all(s["category"] == "serving" for s in
               (request, wait, admit, prefill))
    # queue-wait -> admit -> prefill: the wait begins inside the request
    # and ends inside the admission that took the row
    assert _inside(wait, request) and _inside(prefill, admit)
    wait_end = wait["ts_us"] + wait["dur_us"]
    assert admit["ts_us"] <= wait_end <= prefill["ts_us"]
    assert admit["track"] == prefill["track"] == "serve-engine"

    ticks = by_name["engine.decode-tick"]
    assert len(ticks) == 4      # one a token
    # the loop's waits for a request are named too (the engine is built by
    # the first streamed request, so its first wait can be in the capture):
    # before the first tick or after the last, never over one
    idles = by_name.get("engine.idle", [])
    assert all(i["ts_us"] + i["dur_us"] <= ticks[0]["ts_us"] or
               i["ts_us"] >= ticks[-1]["ts_us"] + ticks[-1]["dur_us"]
               for i in idles)
    for name in TICK_CHILDREN:
        children = by_name[name]
        assert len(children) == len(ticks)
        assert all(any(_inside(c, t) for t in ticks) for c in children)
        assert {c["track"] for c in children} == {"serve-engine"}
    assert sum(s["args"]["tokens"] for s in by_name["engine.deliver"]) == 4
    # a greedy request: no active row pays for the sort and the draw
    assert all(s["args"] == {"rows": 0} for s in by_name["engine.sample"])
    # a tick's phases follow each other and leave the tick some self time
    for tick in ticks:
        inside = sorted((c for name in TICK_CHILDREN for c in by_name[name]
                         if _inside(c, tick)), key=lambda c: c["ts_us"])
        assert [c["name"] for c in inside] == list(TICK_CHILDREN)
        assert sum(c["dur_us"] for c in inside) <= tick["dur_us"]


def test_engine_idle_between_requests_is_named(recorder):
    """With no row active the loop waits for a request under
    ``engine.idle``, so a device idle for want of traffic has a name."""
    eng = _engine()
    try:
        cfg = GenerationConfig(max_new_tokens=2)
        eng.submit(np.array([1, 2, 3], np.int32), cfg)     # warm, untraced
        ttrace.set_enabled(True)
        eng.submit(np.array([4, 5], np.int32), cfg)
        time.sleep(0.05)
        eng.submit(np.array([6, 7], np.int32), cfg)
        _wait_for_ticks(recorder, 4)
        ttrace.set_enabled(False)
    finally:
        eng.shutdown()
    spans = recorder.spans()
    ticks = [s for s in spans if s["name"] == "engine.decode-tick"]
    idles = [s for s in spans if s["name"] == "engine.idle"]
    assert len(ticks) == 4
    # the wait between the two requests (one after the last may follow)
    (idle,) = [s for s in idles if s["ts_us"] < ticks[2]["ts_us"]]
    assert idle["dur_us"] >= 40_000 and idle["track"] == "serve-engine"
    assert ticks[1]["ts_us"] + ticks[1]["dur_us"] <= idle["ts_us"]
    assert idle["ts_us"] + idle["dur_us"] <= ticks[2]["ts_us"]
