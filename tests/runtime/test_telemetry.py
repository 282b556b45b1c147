"""Unified telemetry layer (ISSUE 5 tentpole).

Oracle 1: the Chrome trace a traced multi-mesh pipeshard train step
exports is schema-valid — every ``E`` closes a matching ``B`` on its
track, instruction/transfer/checkpoint spans land on distinct named
tracks, and the multi-trace merge keeps per-process track groups.
Oracle 2: the metrics registry — exact counts under concurrent
increments, correct percentiles on a known distribution, valid
Prometheus text exposition.  Oracle 3: zero-cost-when-off — the
disabled path allocates nothing (shared null-span singleton) and the
register-dispatch replay pays <2% overhead vs the raw op loop.
"""
import collections
import json
import os
import subprocess
import sys
import threading
import time

import numpy as np
import pytest

import alpa_tpu
from alpa_tpu.global_env import global_config
from alpa_tpu.telemetry import metrics as tmetrics
from alpa_tpu.telemetry import trace as ttrace
from alpa_tpu.telemetry.trace import TraceRecorder, merge_chrome_traces

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


@pytest.fixture
def fresh_trace():
    """Fresh recorder + tracing on; restores both afterwards."""
    rec = TraceRecorder()
    old_rec = ttrace.set_recorder(rec)
    prev = ttrace.set_enabled(True)
    yield rec
    ttrace.set_enabled(prev)
    ttrace.set_recorder(old_rec)


def _check_chrome_schema(trace):
    """Every E closes a matching B on its (pid, tid); returns the
    per-track completed span names."""
    assert "traceEvents" in trace
    spans_by_track = collections.defaultdict(list)
    stacks = collections.defaultdict(list)
    events = sorted(
        (e for e in trace["traceEvents"] if e.get("ph") in ("B", "E")),
        key=lambda e: (e["ts"], 0 if e["ph"] == "E" else 1))
    for e in events:
        key = (e.get("pid", 0), e["tid"])
        if e["ph"] == "B":
            assert isinstance(e["name"], str) and e["ts"] >= 0
            stacks[key].append(e)
        else:
            assert stacks[key], f"E without open B on track {key}: {e}"
            b = stacks[key].pop()
            assert e["ts"] >= b["ts"]
            spans_by_track[key].append(b["name"])
    dangling = {k: [e["name"] for e in v] for k, v in stacks.items() if v}
    assert not dangling, f"unclosed B events: {dangling}"
    return spans_by_track


def _track_names(trace):
    """tid -> thread_name from the metadata events."""
    return {e["tid"]: e["args"]["name"]
            for e in trace["traceEvents"]
            if e.get("ph") == "M" and e.get("name") == "thread_name"}


# ---------------------------------------------------------------------
# span recorder basics
# ---------------------------------------------------------------------

class TestTraceRecorder:

    def test_nested_spans_and_instants(self, fresh_trace):
        with ttrace.span("outer", "runtime", {"k": 1}):
            with ttrace.span("inner", "compile"):
                pass
        ttrace.instant("tick", "legacy", {"info": "x"})
        ttrace.counter("inflight", 2)
        trace = fresh_trace.to_chrome_trace()
        by_track = _check_chrome_schema(trace)
        all_names = [n for names in by_track.values() for n in names]
        assert set(all_names) == {"outer", "inner"}
        phs = collections.Counter(e["ph"] for e in trace["traceEvents"])
        assert phs["i"] == 1 and phs["C"] == 1 and phs["M"] >= 2

    def test_begin_end_cross_thread(self, fresh_trace):
        tok = ttrace.begin("async-op", "transfer", None, "pool")

        def closer():
            ttrace.end(tok)

        t = threading.Thread(target=closer)
        t.start()
        t.join()
        spans = fresh_trace.spans()
        assert [s["name"] for s in spans] == ["async-op"]
        assert spans[0]["track"] == "pool"

    def test_tids_stable_per_track(self, fresh_trace):
        for _ in range(3):
            with ttrace.span("a", "runtime", None, "mesh 0"):
                pass
            with ttrace.span("b", "runtime", None, "mesh 1"):
                pass
        spans = fresh_trace.spans()
        tids = {s["track"]: {x["tid"] for x in spans
                             if x["track"] == s["track"]}
                for s in spans}
        assert all(len(v) == 1 for v in tids.values())
        assert tids["mesh 0"] != tids["mesh 1"]

    def test_max_events_drops_and_reports(self, fresh_trace):
        fresh_trace.max_events = 10
        for i in range(50):
            with ttrace.span(f"s{i}", "runtime"):
                pass
        trace = fresh_trace.to_chrome_trace()
        _check_chrome_schema(trace)
        assert trace["alpa_dropped_events"] == 40

    def test_merge_assigns_distinct_pids(self, fresh_trace):
        with ttrace.span("one", "runtime"):
            pass
        t1 = fresh_trace.to_chrome_trace()
        merged = merge_chrome_traces([t1, t1])
        pids = {e["pid"] for e in merged["traceEvents"]}
        assert pids == {0, 1}
        _check_chrome_schema(merged)

    def test_save_is_valid_json(self, fresh_trace, tmp_path):
        with ttrace.span("one", "runtime"):
            pass
        path = tmp_path / "trace.json"
        fresh_trace.save(str(path))
        with open(path, encoding="utf-8") as f:
            trace = json.load(f)
        _check_chrome_schema(trace)


# ---------------------------------------------------------------------
# zero-cost-when-off
# ---------------------------------------------------------------------

class TestDisabledMode:

    def test_null_span_is_shared_singleton(self):
        assert not ttrace.enabled()
        assert ttrace.span("a") is ttrace.span("b")
        assert ttrace.begin("a") is None
        ttrace.end(None)  # no-op
        ttrace.instant("x")
        ttrace.counter("x", 1.0)

    def test_disabled_records_nothing(self, fresh_trace):
        ttrace.set_enabled(False)
        with ttrace.span("invisible", "runtime"):
            pass
        ttrace.instant("invisible")
        assert fresh_trace.n_events == 0

    def test_register_replay_overhead_under_guard(self):
        """The disabled fast path checks the enabled flag ONCE per step:
        replaying a big synthetic register program through execute()
        must stay within 2% of the raw op loop."""
        from alpa_tpu.pipeline_parallel.runtime_emitter import (
            RegisterFileProgram)
        assert not ttrace.enabled()
        n_ops = 20000
        sink = [0]

        def op(regs, _sink=sink):
            _sink[0] += 1

        ops = [op] * n_ops
        prog = RegisterFileProgram(
            num_slots=1, ops=ops, n_instructions=n_ops,
            by_opcode={"RUN": n_ops}, slot_of={}, n_coalesced_groups=0,
            n_fixups=0, text="synthetic",
            op_meta=[("RUN synth", "instruction", "mesh 0")] * n_ops)
        regs = [None]

        def timed(fn):
            t0 = time.perf_counter()
            fn()
            return time.perf_counter() - t0

        def raw():
            for o in prog.ops:
                o(regs)

        # warm both paths
        raw()
        prog.execute(regs)
        # interleave the two measurements and take the best per-round
        # ratio: a genuine per-instruction cost in execute() would show
        # up in EVERY round, while one-sided scheduler jitter (the flaky
        # failure mode of timing two independent best-ofs) does not.
        ratio = min(
            timed(lambda: prog.execute(regs)) / timed(raw)
            for _ in range(15))
        assert ratio < 1.02, (
            f"disabled-telemetry replay overhead {ratio - 1:.2%} "
            f"exceeds the 2% guard over {n_ops} ops")

    def test_traced_replay_emits_op_spans(self, fresh_trace):
        from alpa_tpu.pipeline_parallel.runtime_emitter import (
            RegisterFileProgram)
        ops = [lambda regs: None] * 3
        prog = RegisterFileProgram(
            num_slots=1, ops=ops, n_instructions=3,
            by_opcode={"RUN": 3}, slot_of={}, n_coalesced_groups=0,
            n_fixups=0, text="synthetic",
            op_meta=[("RUN a", "instruction", "mesh 0"),
                     ("RESHARD 0->1", "instruction", "mesh 1"),
                     ("FREE", "instruction", "mesh 1")])
        prog.execute([None])
        names = [s["name"] for s in fresh_trace.spans()]
        assert names == ["RUN a", "RESHARD 0->1", "FREE"]


# ---------------------------------------------------------------------
# metrics registry
# ---------------------------------------------------------------------

class TestMetricsRegistry:

    def test_counter_concurrent_increments_exact(self):
        reg = tmetrics.MetricsRegistry()
        c = reg.counter("t_total", "test")
        h = reg.histogram("t_seconds", "test")
        n_threads, n_incs = 8, 2000

        def worker():
            for _ in range(n_incs):
                c.inc()
                h.observe(0.01)

        threads = [threading.Thread(target=worker)
                   for _ in range(n_threads)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        assert c.value == n_threads * n_incs
        assert h.summary()["count"] == n_threads * n_incs

    def test_histogram_percentiles_known_distribution(self):
        reg = tmetrics.MetricsRegistry()
        h = reg.histogram("lat", "test")
        for v in range(1, 101):        # 0.001 .. 0.100
            h.observe(v / 1000.0)
        assert abs(h.percentile(50) - 0.050) <= 0.001
        assert abs(h.percentile(95) - 0.095) <= 0.001
        assert abs(h.percentile(99) - 0.099) <= 0.001
        s = h.summary()
        assert s["count"] == 100
        assert abs(s["sum"] - sum(v / 1000.0 for v in range(1, 101))) \
            < 1e-9
        # cumulative buckets: everything <= 0.1 bucket, nothing <= 1ms
        # except the single 0.001 observation
        buckets = dict(h.bucket_counts())
        assert buckets[0.1] == 100
        assert buckets[0.001] == 1

    def test_labels_and_kind_mismatch(self):
        reg = tmetrics.MetricsRegistry()
        fam = reg.counter("hits", "test", labelnames=("ns",))
        fam.labels("ilp").inc(2)
        fam.labels("stage_dp").inc()
        vals = {k: c.value for k, c in fam.children()}
        assert vals == {("ilp",): 2, ("stage_dp",): 1}
        with pytest.raises(Exception):
            reg.gauge("hits")          # same name, different kind
        with pytest.raises(Exception):
            fam.labels("ilp").inc(-1)  # counters only go up

    def test_gauge_set_max(self):
        reg = tmetrics.MetricsRegistry()
        g = reg.gauge("hi", "test")
        g.set_max(5)
        g.set_max(3)
        assert g.value == 5

    def test_prometheus_text_exposition(self):
        reg = tmetrics.MetricsRegistry()
        reg.counter("req_total", "requests", ("code",)).labels("200").inc()
        reg.gauge("depth", "queue depth").set(7)
        reg.histogram("lat_seconds", "latency").observe(0.003)
        text = reg.to_prometheus_text()
        assert "# HELP req_total requests" in text
        assert "# TYPE req_total counter" in text
        assert 'req_total{code="200"} 1' in text
        assert "depth 7" in text
        assert 'lat_seconds_bucket{le="+Inf"} 1' in text
        assert "lat_seconds_count 1" in text
        assert "lat_seconds_sum 0.003" in text

    def test_collectors_fill_compile_cache_gauges(self):
        """The live global registry exposes compile-cache state through
        its collector even though the cache instance is swapped per
        test."""
        from alpa_tpu.compile_cache import get_compile_cache
        get_compile_cache()            # ensure a live instance
        text = tmetrics.get_registry().to_prometheus_text()
        assert "alpa_compile_cache_memory_entries" in text

    def test_thin_stat_views_keep_legacy_shapes(self):
        from alpa_tpu.checkpoint import metrics as ckpt_metrics
        from alpa_tpu.pipeline_parallel.cross_mesh_resharding import (
            get_planner_stats, reset_planner_stats)
        from alpa_tpu.pipeline_parallel.runtime_emitter import (
            get_overlap_runtime_stats, reset_overlap_runtime_stats)
        reset_overlap_runtime_stats()
        rt = get_overlap_runtime_stats()
        assert set(rt) == {"steps", "transfer_busy_s", "wait_blocked_s",
                           "n_hoisted", "n_launches",
                           "last_overlap_fraction", "last_window"}
        assert isinstance(rt["steps"], int)
        reset_planner_stats()
        pl = get_planner_stats()
        assert set(pl) == {"plans", "total_bytes", "broadcast_bytes",
                           "max_link_bytes", "max_link_bytes_naive"}
        ckpt_metrics.incr("saves")
        assert ckpt_metrics.snapshot()["saves"] == 1
        ckpt_metrics.reset()
        assert ckpt_metrics.snapshot() == {}


# ---------------------------------------------------------------------
# legacy Tracer bridge
# ---------------------------------------------------------------------

class TestTracerBridge:

    def test_log_mirrors_into_unified_trace(self, fresh_trace):
        from alpa_tpu.timer import Tracer
        tr = Tracer()
        tr.log("old-site", "info=1")
        # old API unchanged
        assert tr.events[-1].name == "old-site"
        assert tr.to_chrome_trace()[-1]["name"] == "old-site"
        # and mirrored as a legacy-category instant
        trace = fresh_trace.to_chrome_trace()
        inst = [e for e in trace["traceEvents"] if e.get("ph") == "i"]
        assert inst and inst[0]["name"] == "old-site"
        assert inst[0]["cat"] == "legacy"

    def test_log_without_tracing_stays_local(self, fresh_trace):
        from alpa_tpu.timer import Tracer
        ttrace.set_enabled(False)
        tr = Tracer()
        tr.log("quiet")
        assert tr.events[-1].name == "quiet"
        assert fresh_trace.n_events == 0


# ---------------------------------------------------------------------
# end-to-end: traced multi-mesh pipeshard train step
# ---------------------------------------------------------------------

class TestTracedPipeshard:

    def test_overlap_step_exports_valid_multi_track_trace(
            self, fresh_trace, tmp_path):
        """THE acceptance scenario: a traced overlap train step on
        multiple meshes + a checkpoint save exports ONE merged Chrome
        trace with instruction spans per mesh track, transfer-pool
        spans, and checkpoint spans — schema-valid everywhere."""
        from alpa_tpu import PipeshardParallel
        from alpa_tpu.checkpoint.manager import CheckpointManager
        from alpa_tpu.pipeline_parallel.layer_construction import (
            AutoLayerOption)
        from alpa_tpu.pipeline_parallel.stage_construction import (
            UniformStageOption)
        from alpa_tpu.testing import (create_mlp_train_state_and_batch,
                                      get_mlp_train_step)
        alpa_tpu.init("local")
        prev_mode = global_config.pipeline_dispatch_mode
        global_config.pipeline_dispatch_mode = "overlap"
        try:
            method = PipeshardParallel(
                num_micro_batches=2,
                layer_option=AutoLayerOption(layer_num=4),
                stage_option=UniformStageOption(num_stages=4))
            step = get_mlp_train_step(method, use_value_and_grad=False)
            state, batch = create_mlp_train_state_and_batch(
                batch_size=8, input_dim=8, hidden_dim=8, output_dim=8,
                num_layers=4, manual_pipeline_layer=False)
            for _ in range(2):
                state, val = step(state, batch)
            float(val)
            assert step.get_last_executable() \
                .last_dispatch_stats["mode"] == "overlap"
            mgr = CheckpointManager(str(tmp_path / "ckpt"))
            mgr.save(0, {"w": np.ones((4,), np.float32)}, sync=True)
        finally:
            global_config.pipeline_dispatch_mode = prev_mode

        trace = fresh_trace.to_chrome_trace()
        by_track = _check_chrome_schema(trace)
        names = _track_names(trace)
        tid_of = {v: k for k, v in names.items()}

        # instruction spans on >= 2 distinct mesh tracks
        mesh_tracks = [t for t in tid_of if t.startswith("mesh ")]
        assert len(mesh_tracks) >= 2, f"tracks: {sorted(tid_of)}"
        run_tracks = [t for t in mesh_tracks
                      if any(n.startswith("RUN")
                             for n in by_track[(0, tid_of[t])])]
        assert len(run_tracks) >= 2

        all_names = [n for v in by_track.values() for n in v]
        # transfer-pool spans (driver-side LAUNCH/WAIT + pool-side work)
        assert any(n.startswith(("LAUNCH", "WAIT")) for n in all_names)
        assert any(n.startswith("reshard.") for n in all_names)
        # checkpoint + step + compile spans in the SAME merged trace
        assert "checkpoint.save" in all_names
        assert "pipeshard.step" in all_names
        assert any(n in ("ilp-solve", "ilp-cache-replay")
                   for n in all_names)
        # each step carries its running number, and its two phases (placing
        # the inputs, the replay) lie inside it, one after the other
        spans = fresh_trace.spans()
        steps = [s for s in spans if s["name"] == "pipeshard.step"]
        assert [s["args"]["step"] for s in steps] == [0, 1]
        for name in ("pipeshard.place-inputs", "pipeshard.replay"):
            inner = [s for s in spans if s["name"] == name]
            assert len(inner) == len(steps)
            for step_span, s in zip(steps, inner):
                assert step_span["ts_us"] <= s["ts_us"] and \
                    s["ts_us"] + s["dur_us"] <= \
                    step_span["ts_us"] + step_span["dur_us"]
        # the transfer in-flight window rides a counter track
        assert any(e.get("ph") == "C" and
                   e["name"] == "transfers_in_flight"
                   for e in trace["traceEvents"])
        # overlap registry metrics flowed
        text = tmetrics.get_registry().to_prometheus_text()
        assert "alpa_overlap_steps_total" in text
        assert "alpa_checkpoint_stat_total" in text

    def test_tracing_does_not_force_interpreter_fallback(self,
                                                         fresh_trace):
        """Unlike legacy collect_trace, span telemetry keeps the lowered
        fast paths."""
        from alpa_tpu import PipeshardParallel
        from alpa_tpu.pipeline_parallel.layer_construction import (
            AutoLayerOption)
        from alpa_tpu.pipeline_parallel.stage_construction import (
            UniformStageOption)
        from alpa_tpu.testing import (create_mlp_train_state_and_batch,
                                      get_mlp_train_step)
        alpa_tpu.init("local")
        prev_mode = global_config.pipeline_dispatch_mode
        global_config.pipeline_dispatch_mode = "registers"
        try:
            method = PipeshardParallel(
                num_micro_batches=2,
                layer_option=AutoLayerOption(layer_num=4),
                stage_option=UniformStageOption(num_stages=4))
            step = get_mlp_train_step(method, use_value_and_grad=False)
            state, batch = create_mlp_train_state_and_batch(
                batch_size=8, input_dim=8, hidden_dim=8, output_dim=8,
                num_layers=4, manual_pipeline_layer=False)
            state, val = step(state, batch)
            float(val)
            assert step.get_last_executable() \
                .last_dispatch_stats["mode"] == "registers"
        finally:
            global_config.pipeline_dispatch_mode = prev_mode
        assert any(s["name"].startswith("RUN")
                   for s in fresh_trace.spans())


# ---------------------------------------------------------------------
# trace_tool CLI
# ---------------------------------------------------------------------

class TestTraceTool:

    def _make_trace_file(self, path):
        rec = TraceRecorder()
        old_rec = ttrace.set_recorder(rec)
        prev = ttrace.set_enabled(True)
        try:
            for i in range(3):
                with ttrace.span(f"RUN stage{i}", "instruction", None,
                                 f"mesh {i}"):
                    time.sleep(0.001)
            with ttrace.span("plan", "compile"):
                pass
        finally:
            ttrace.set_enabled(prev)
            ttrace.set_recorder(old_rec)
        rec.save(str(path))

    def test_merge_summarize_top(self, tmp_path):
        a, b = tmp_path / "a.json", tmp_path / "b.json"
        self._make_trace_file(a)
        self._make_trace_file(b)
        merged = tmp_path / "merged.json"
        tool = os.path.join(REPO, "scripts", "trace_tool.py")
        env = dict(os.environ, JAX_PLATFORMS="cpu")
        r = subprocess.run(
            [sys.executable, tool, "merge", str(merged), str(a), str(b)],
            capture_output=True, text=True, env=env, check=True)
        assert "merged 2 trace file(s)" in r.stdout
        with open(merged, encoding="utf-8") as f:
            _check_chrome_schema(json.load(f))
        r = subprocess.run(
            [sys.executable, tool, "summarize", str(merged)],
            capture_output=True, text=True, env=env, check=True)
        assert "instruction" in r.stdout and "compile" in r.stdout
        r = subprocess.run(
            [sys.executable, tool, "top", str(merged), "--top", "3"],
            capture_output=True, text=True, env=env, check=True)
        assert "RUN stage0" in r.stdout
