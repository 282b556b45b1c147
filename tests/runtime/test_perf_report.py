"""Step perf analysis engine + regression gate (ISSUE 9 tentpole).

Oracle 1: on the committed synthetic 2-mesh 4-microbatch fixture trace
(known durations), the measured critical path, per-mesh bubble
fractions, and the queue-wait/wire transfer split are pinned exactly,
and per-mesh fractions sum to 1.  Oracle 2: the what-if re-simulator is
monotone — zeroing ops never increases the makespan, and zeroing an
off-critical-path op never beats zeroing an on-path op.  Oracle 3: the
MFU formula against hand-computed FLOPs.  Oracle 4: the perf gate
passes on the committed baseline and fails loudly on an injected 2×
regression.  Oracle 5 (end-to-end): a live traced overlap step yields a
graph-joined report, the three gauges, and ``perf_report.txt``.
"""
import copy
import json
import os

import pytest

import alpa_tpu
from alpa_tpu.analysis.critical_path import (TimedOp, longest_path,
                                             measured_critical_path,
                                             simulate_dag, whatif)
from alpa_tpu.global_env import global_config
from alpa_tpu.telemetry import metrics as tmetrics
from alpa_tpu.telemetry import perf
from alpa_tpu.telemetry import trace as ttrace
from alpa_tpu.telemetry.trace import TraceRecorder

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
FIXTURE = os.path.join(REPO, "benchmark", "results",
                       "perf_gate_fixture_trace.json")
BASELINE = os.path.join(REPO, "benchmark", "results",
                        "perf_gate_baseline.json")


@pytest.fixture
def fresh_trace():
    """Fresh recorder + tracing on; restores both afterwards."""
    rec = TraceRecorder()
    old_rec = ttrace.set_recorder(rec)
    prev = ttrace.set_enabled(True)
    yield rec
    ttrace.set_enabled(prev)
    ttrace.set_recorder(old_rec)


def _load_fixture():
    with open(FIXTURE, encoding="utf-8") as f:
        return json.load(f)


# ---------------------------------------------------------------------
# critical-path walk + DAG re-simulation (pure data layer)
# ---------------------------------------------------------------------

class TestCriticalPath:

    OPS = [
        TimedOp(0, "RUN s0", "exec", "mesh 0", 0.0, 100.0),
        TimedOp(1, "LAUNCH r", "launch", "mesh 0", 100.0, 105.0),
        TimedOp(2, "WAIT r", "wait", "mesh 1", 105.0, 150.0),
        TimedOp(3, "RUN s1", "exec", "mesh 1", 150.0, 260.0),
    ]
    PREDS = {1: [0], 2: [1], 3: [2]}

    def test_walk_spans_envelope_with_causal_edges(self):
        cp = measured_critical_path(self.OPS, self.PREDS)
        assert [s.op.idx for s in cp.steps] == [0, 1, 2, 3]
        assert cp.total_us == 260.0
        assert cp.gap_us == 0.0
        assert cp.coverage == pytest.approx(1.0)
        # vias: first op is the walk start, the rest causal
        assert cp.steps[0].via == "start"
        assert all(s.via == "dep" for s in cp.steps[1:])
        assert sum(s.share for s in cp.steps) == pytest.approx(1.0)

    def test_gap_attribution(self):
        ops = [
            TimedOp(0, "RUN a", "exec", "mesh 0", 0.0, 100.0),
            TimedOp(1, "RUN b", "exec", "mesh 0", 130.0, 200.0),
        ]
        cp = measured_critical_path(ops, {1: [0]})
        assert cp.steps[1].gap_us == pytest.approx(30.0)
        assert cp.total_us + cp.gap_us == pytest.approx(cp.envelope_us)

    def test_issue_order_fallback_binds_without_graph(self):
        # concurrent tracks, no causal edges: the walk still spans the
        # envelope via the latest-earlier-finisher fallback
        cp = measured_critical_path(self.OPS, {})
        assert cp.steps[-1].op.idx == 3
        assert cp.total_us + cp.gap_us >= 0.95 * cp.envelope_us

    def test_simulate_matches_hand_makespan(self):
        durs = [o.dur_us for o in self.OPS]
        makespan, finish = simulate_dag(durs, [[], [0], [1], [2]])
        assert makespan == 260.0
        assert finish == [100.0, 105.0, 150.0, 260.0]
        length, path = longest_path(durs, [[], [0], [1], [2]])
        assert length == 260.0 and path == [0, 1, 2, 3]

    def test_whatif_monotone_and_onpath_beats_offpath(self):
        # chain A(100)->B(100)->C(100); D(10) dangles off-path
        durs = [100.0, 100.0, 100.0, 10.0]
        preds = [[], [0], [1], [0]]
        baseline, _ = simulate_dag(durs, preds)
        assert baseline == 300.0
        zero_onpath = whatif(durs, preds, {1})
        zero_offpath = whatif(durs, preds, {3})
        assert zero_onpath <= baseline and zero_offpath <= baseline
        # zeroing the off-path op never beats zeroing the on-path op
        assert zero_offpath >= zero_onpath
        assert zero_onpath == 200.0 and zero_offpath == 300.0
        # zeroing everything floors at 0
        assert whatif(durs, preds, {0, 1, 2, 3}) == 0.0


# ---------------------------------------------------------------------
# committed fixture trace: pinned report numbers
# ---------------------------------------------------------------------

class TestFixtureReport:

    def test_pinned_critical_path_and_envelope(self):
        report = perf.report_from_trace(_load_fixture())
        assert report is not None
        assert report.n_ops == 16
        assert report.envelope_us == pytest.approx(600.0)
        # acceptance: path total within 5% of the measured envelope
        assert report.critical_path.total_us == pytest.approx(596.0)
        assert report.critical_path.coverage >= 0.95
        # the path is the mesh-1 RUN chain seeded by mesh 0's first RUN
        top = report.critical_path.top(4)
        assert all(s.op.name.startswith("RUN stage_1") for s in top)

    def test_pinned_bubble_fractions_sum_to_one(self):
        report = perf.report_from_trace(_load_fixture())
        assert set(report.bubbles) == {"mesh 0", "mesh 1"}
        m0, m1 = report.bubbles["mesh 0"], report.bubbles["mesh 1"]
        assert m0.bubble_fraction == pytest.approx(0.30, abs=1e-6)
        assert m0.busy_us == pytest.approx(420.0)
        assert m1.warmup_us == pytest.approx(105.0)
        assert m1.drain_us == pytest.approx(4.0)
        assert m1.stream_wait_us == pytest.approx(11.0)
        for b in report.bubbles.values():
            fr = b.fractions()
            assert sum(fr.values()) == pytest.approx(1.0, abs=1e-6)
            assert 1.0 - fr["busy"] == pytest.approx(b.bubble_fraction)

    def test_pinned_transfer_split(self):
        t = perf.report_from_trace(_load_fixture()).transfers
        # 4 transfers x 7us wire + 1us queue-wait; 5+2+2+2 exposed WAITs
        assert t.wire_us == pytest.approx(28.0)
        assert t.queue_wait_us == pytest.approx(4.0)
        assert t.pool_busy_us == pytest.approx(28.0)
        assert t.exposed_wait_us == pytest.approx(11.0)
        assert t.hidden_us == pytest.approx(17.0)
        assert t.overlap_fraction == pytest.approx(1.0 - 11.0 / 28.0)

    def test_whatif_reshard_on_report(self):
        report = perf.report_from_trace(_load_fixture())
        verdict = report.whatif("reshard")
        assert verdict["n_zeroed"] == 8          # 4 LAUNCH + 4 WAIT
        assert 0.0 <= verdict["saving_fraction"] < 1.0
        assert verdict["whatif_us"] <= verdict["baseline_us"]
        # zeroing the RUNs saves more than zeroing the transfers
        assert report.whatif("run")["saving_us"] >= verdict["saving_us"]

    def test_format_text_and_dict_roundtrip(self):
        report = perf.report_from_trace(_load_fixture())
        text = report.format_text()
        assert "critical path" in text and "per-mesh bubbles" in text
        d = report.to_dict()
        json.dumps(d)  # serializable
        assert d["critical_path_us"] == pytest.approx(596.0)


# ---------------------------------------------------------------------
# MFU formula
# ---------------------------------------------------------------------

class TestMfu:

    def test_stage_flops_matches_hand_computed_matmul(self):
        import jax
        import jax.numpy as jnp
        w = jnp.ones((16, 4), jnp.float32)
        closed = jax.make_jaxpr(lambda x: x @ w)(
            jnp.ones((8, 16), jnp.float32))
        # dot_general: 2 * prod(out.shape) * contracted = 2*8*4*16
        assert perf.stage_flops(closed) == pytest.approx(1024.0)

    def test_stage_flops_tiny_mlp_dominated_by_matmuls(self):
        import jax
        import jax.numpy as jnp
        w1 = jnp.ones((16, 32), jnp.float32)
        w2 = jnp.ones((32, 4), jnp.float32)

        def mlp(x):
            return jnp.maximum(x @ w1, 0.0) @ w2

        closed = jax.make_jaxpr(mlp)(jnp.ones((8, 16), jnp.float32))
        matmuls = 2 * 8 * 32 * 16 + 2 * 8 * 4 * 32   # 8192 + 2048
        got = perf.stage_flops(closed)
        assert matmuls <= got <= matmuls * 1.2       # + relu elementwise

    def test_stage_flops_counts_a_rematerialised_blocks_backward(self):
        import jax
        import jax.numpy as jnp
        w = jnp.ones((16, 16), jnp.float32)

        def block(x):
            return jnp.sum(jnp.tanh(x @ w) @ w)

        x = jnp.ones((8, 16), jnp.float32)
        plain = perf.stage_flops(jax.make_jaxpr(jax.grad(block))(x))
        closed = jax.make_jaxpr(jax.grad(jax.checkpoint(block)))(x)
        assert any(e.primitive.name == "remat2" for e in closed.jaxpr.eqns)
        # the two products forward, their input gradients, and the
        # recomputation of the first: not the one elementwise operation a
        # ``remat2`` equation counts as elsewhere
        matmul = 2 * 8 * 16 * 16
        assert plain >= 3 * matmul
        assert perf.stage_flops(closed) >= plain

    def test_knob_overrides_generation_peak(self):
        prev = global_config.device_peak_tflops
        try:
            global_config.device_peak_tflops = 123.0
            assert perf.device_peak_tflops() == 123.0
            assert perf.peak_flops_info()["peak_bf16_tflops"] == 123.0
            assert perf.compute_mfu(61.5) == pytest.approx(0.5)
        finally:
            global_config.device_peak_tflops = prev

    def test_default_peak_comes_from_generation_specs(self):
        from alpa_tpu.mesh_profiling import TPU_GENERATION_SPECS
        prev = global_config.device_peak_tflops
        try:
            global_config.device_peak_tflops = 0.0
            info = perf.peak_flops_info("v5e")
            assert info["generation"] == "v5e"
            assert info["peak_bf16_tflops"] == \
                TPU_GENERATION_SPECS["v5e"]["peak_bf16_tflops"]
            # no knob, no TPU: there is no peak to assume on the CPU mesh
            with pytest.raises(ValueError):
                perf.peak_flops_info()
        finally:
            global_config.device_peak_tflops = prev

    def test_mfu_from_time(self):
        # 1e12 FLOPs in 1 s on 1 chip = 1 TFLOPS; peak 2 -> MFU 0.5
        assert perf.mfu_from_time(1e12, 1.0, 1, 2.0) == \
            pytest.approx(0.5)
        assert perf.mfu_from_time(1e12, 0.0, 1, 2.0) == 0.0


# ---------------------------------------------------------------------
# perf regression gate
# ---------------------------------------------------------------------

class TestPerfGate:

    def test_gate_passes_on_committed_baseline(self):
        from benchmark.perf_gate import flatten_metrics, gate
        report = perf.report_from_trace(_load_fixture())
        verdict = gate(flatten_metrics(report.to_dict()),
                       baseline_path=BASELINE)
        assert verdict["pass"], verdict
        assert verdict["n_checked"] >= 8
        assert verdict["n_failed"] == 0

    def test_gate_fails_loudly_on_2x_regression(self):
        from benchmark.perf_gate import check, flatten_metrics
        trace = copy.deepcopy(_load_fixture())
        for e in trace["traceEvents"]:
            if e.get("ph") in ("B", "E"):
                e["ts"] = e["ts"] * 2.0      # inject 2x latency
        report = perf.report_from_trace(trace)
        with open(BASELINE, encoding="utf-8") as f:
            baseline = json.load(f)
        verdict = check(flatten_metrics(report.to_dict()), baseline)
        assert not verdict["pass"]
        failed = {c["metric"]: c for c in verdict["checks"]
                  if not c["ok"]}
        assert "critical_path_us" in failed
        assert failed["critical_path_us"]["ratio"] == pytest.approx(
            2.0, rel=1e-3)
        assert "max_ratio" in failed["critical_path_us"]["reason"]

    def test_gate_cli_exit_codes(self, tmp_path):
        from benchmark import perf_gate
        assert perf_gate.main(["--trace", FIXTURE,
                               "--baseline", BASELINE]) == 0
        trace = copy.deepcopy(_load_fixture())
        for e in trace["traceEvents"]:
            if e.get("ph") in ("B", "E"):
                e["ts"] = e["ts"] * 2.0
        bad = tmp_path / "regressed.json"
        bad.write_text(json.dumps(trace))
        assert perf_gate.main(["--trace", str(bad),
                               "--baseline", BASELINE]) == 1

    def test_gate_verdicts_hit_metrics_registry(self):
        from benchmark.perf_gate import gate, flatten_metrics
        report = perf.report_from_trace(_load_fixture())
        gate(flatten_metrics(report.to_dict()), baseline_path=BASELINE)
        text = tmetrics.get_registry().to_prometheus_text()
        assert 'alpa_perf_gate_total{result="pass"}' in text

    def test_only_shared_metrics_checked(self):
        from benchmark.perf_gate import check
        verdict = check({"unknown_metric": 1.0},
                        {"metrics": {"other": {"value": 1.0,
                                               "max_ratio": 1.1}}})
        assert not verdict["pass"]          # nothing checked != pass
        assert verdict["n_checked"] == 0
        assert verdict["n_skipped"] == 1


# ---------------------------------------------------------------------
# end-to-end: live traced overlap step -> graph-joined report
# ---------------------------------------------------------------------

class TestLivePipeshard:

    def test_overlap_step_perf_report_and_debug_dump(
            self, fresh_trace, tmp_path):
        from alpa_tpu import PipeshardParallel
        from alpa_tpu.pipeline_parallel.layer_construction import (
            AutoLayerOption)
        from alpa_tpu.pipeline_parallel.stage_construction import (
            UniformStageOption)
        from alpa_tpu.testing import (create_mlp_train_state_and_batch,
                                      get_mlp_train_step)
        alpa_tpu.init("local")
        prev_mode = global_config.pipeline_dispatch_mode
        prev_peak = global_config.device_peak_tflops
        global_config.pipeline_dispatch_mode = "overlap"
        global_config.device_peak_tflops = 1.0   # CPU run: pin the peak
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
            ex = step.get_last_executable()
            assert ex.last_dispatch_stats["mode"] == "overlap"

            report = ex.get_perf_report()
            assert report is not None
            assert report.source == "trace"
            # spans joined 1:1 against the lowered program's op_meta,
            # so the walk rides real dataflow edges
            assert report.aligned, report.notes
            prog = ex._register_programs["overlap"]
            assert report.n_ops == len(prog.ops)
            assert report.envelope_us > 0
            # the walk spans the op window inside the step envelope
            # (the envelope also holds driver arg-placement / output
            # work, so coverage is < 1 on a live run; the exact
            # within-5% bound is pinned on the fixture above)
            cp = report.critical_path
            assert 0.0 < cp.total_us + cp.gap_us <= cp.envelope_us
            assert cp.coverage > 0.5
            # >= 2 mesh tracks, each with fractions summing to 1
            assert len(report.bubbles) >= 2
            for b in report.bubbles.values():
                assert sum(b.fractions().values()) == pytest.approx(
                    1.0, abs=1e-6)
                assert b.sched_num_clock is not None
            # S2: the pool recorded queue-wait/wire child spans
            pool_names = {s["name"] for s in fresh_trace.spans()
                          if (s["track"] or "").startswith(
                              "alpa-overlap")}
            assert "reshard.wait" in pool_names
            assert "reshard.wire" in pool_names
            assert report.transfers.pool_busy_us > 0
            # MFU attribution found the stage RUN spans
            assert report.stages, "no stage MFU rows"
            for s in report.stages.values():
                assert s.n_runs >= 1 and s.flops_per_run > 0
                assert s.mfu >= 0

            # what-if on the real DAG is monotone
            w = report.whatif("reshard")
            assert w["whatif_us"] <= w["baseline_us"]

            # gauges flowed into the central registry
            text = tmetrics.get_registry().to_prometheus_text()
            assert "alpa_critical_path_us" in text
            assert 'alpa_step_bubble_fraction{mesh="0"}' in text
            assert "alpa_stage_mfu{stage=" in text

            # perf_report.txt lands in the debug dump
            from alpa_tpu import monitoring
            dump = tmp_path / "dump"
            monitoring.dump_debug_info(ex, str(dump))
            txt = (dump / "perf_report.txt").read_text()
            assert "critical path" in txt
            assert "per-mesh bubbles" in txt
        finally:
            global_config.pipeline_dispatch_mode = prev_mode
            global_config.device_peak_tflops = prev_peak

    def test_flight_fallback_when_tracing_off(self):
        """Tracing off, flight ring on: get_perf_report still joins a
        step from the ring."""
        from alpa_tpu import PipeshardParallel
        from alpa_tpu.pipeline_parallel.layer_construction import (
            AutoLayerOption)
        from alpa_tpu.pipeline_parallel.stage_construction import (
            UniformStageOption)
        from alpa_tpu.telemetry import flight as tflight
        from alpa_tpu.testing import (create_mlp_train_state_and_batch,
                                      get_mlp_train_step)
        if not tflight.enabled():
            pytest.skip("flight recorder disabled")
        alpa_tpu.init("local")
        prev_mode = global_config.pipeline_dispatch_mode
        global_config.pipeline_dispatch_mode = "registers"
        # fresh empty recorder (tracing stays OFF): earlier tests may
        # have left a stale step span that would shadow the fallback
        old_rec = ttrace.set_recorder(TraceRecorder())
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
            ex = step.get_last_executable()
            assert not ttrace.enabled()
            report = ex.get_perf_report()
            assert report is not None
            assert report.source == "flight"
            assert report.n_ops > 0
            assert report.envelope_us > 0
        finally:
            global_config.pipeline_dispatch_mode = prev_mode
            ttrace.set_recorder(old_rec)
