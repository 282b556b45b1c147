"""Profile-guided replanning (ISSUE 12): measured-cost calibration
store, model-drift observability, and hot-swapped replans.

Oracle 1 (store): robust stats and drift math, disk persistence with
the content-addressed per-entry layout, and a fingerprint that is
invariant to sample counts but sensitive to measured values.
Oracle 2 (parity, satellite 3): with tracing off, the flight-ring
fallback calibrates the *same signatures with the same sample counts*
as the traced path on the committed fixture trace.
Oracle 3 (off-mode): ``replan_mode=off`` consults nothing — strategy
choices, costs, and compile-cache keys are byte-identical to a build
with no store, even with a populated (mispriced) store on disk.
Oracle 4 (replan): a deliberately mispriced edge flips the strategy
choice, and an unchanged store replays the flipped decision from the
compile cache (``TestOffMode``).
Oracle 5 (observability): the drift gauges flow to ``/metrics``,
``calibration.txt`` lands in the debug dump, the ``drift`` / ``--edges``
CLIs render the fixture, and the profiling DB stamps its schema and
warns on out-of-range lookups.
Oracle 6 (live): ``consider_replan`` on a real 2-mesh pipeshard
executable — None when off, a suggest verdict that applies nothing,
and an auto hot-swap that re-lowers (verifier re-run) while the step
output stays bit-exact.
"""
import json
import logging
import os

import numpy as np
import pytest

import alpa_tpu
from alpa_tpu.global_env import global_config
from alpa_tpu.telemetry import calibration as cal
from alpa_tpu.telemetry import metrics as tmetrics
from alpa_tpu.telemetry import perf

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
FIXTURE = os.path.join(REPO, "benchmark", "results",
                       "perf_gate_fixture_trace.json")


def _load_fixture():
    with open(FIXTURE, encoding="utf-8") as f:
        return json.load(f)


@pytest.fixture(autouse=True)
def _calibration_env():
    """Fresh global store + restored replan knobs per test."""
    prev = (global_config.replan_mode,
            global_config.calibration_min_samples,
            global_config.calibration_dir,
            global_config.reshard_strategy,
            global_config.pipeline_dispatch_mode)
    cal.reset_calibration_store(None)
    yield
    (global_config.replan_mode,
     global_config.calibration_min_samples,
     global_config.calibration_dir,
     global_config.reshard_strategy,
     global_config.pipeline_dispatch_mode) = prev
    cal.reset_calibration_store(None)


# ---------------------------------------------------------------------
# Oracle 1: the store itself
# ---------------------------------------------------------------------

class TestStore:

    def test_robust_stats_and_drift(self):
        store = cal.CalibrationStore(None)
        for v in (10.0, 2.0, 7.0, 7.0, 100.0):
            store.observe("reshard_wire", "edge:a->b", v, modeled_us=2.0)
        e = store.get("reshard_wire", "edge:a->b")
        assert e.count == 5
        assert e.median_us == pytest.approx(7.0)
        assert e.p90_us <= 100.0
        assert e.drift_ratio == pytest.approx(3.5)
        assert e.ewma_us > 0

    def test_disk_persistence_and_reload(self, tmp_path):
        d = str(tmp_path / "cal")
        store = cal.CalibrationStore(d)
        store.observe("reshard_wire", "edge:a->b", 7.0, modeled_us=2.0)
        store.observe("stage_run", "stage:s0", 100.0)
        files = sorted(os.listdir(d))
        assert len(files) == 2
        assert any(f.startswith("reshard_wire-") for f in files)
        assert any(f.startswith("stage_run-") for f in files)
        # every entry file is valid stamped JSON
        for f in files:
            with open(os.path.join(d, f), encoding="utf-8") as fh:
                data = json.load(fh)
            assert data["format"] == cal.CALIBRATION_FORMAT_VERSION
        reloaded = cal.CalibrationStore(d)
        assert len(reloaded) == 2
        assert reloaded.get("reshard_wire",
                            "edge:a->b").median_us == pytest.approx(7.0)
        assert reloaded.fingerprint() == store.fingerprint()

    def test_wrong_format_entry_skipped(self, tmp_path):
        d = str(tmp_path / "cal")
        store = cal.CalibrationStore(d)
        store.observe("stage_run", "stage:s0", 100.0)
        bogus = os.path.join(d, "stage_run-deadbeefdeadbeef.json")
        with open(bogus, "w", encoding="utf-8") as f:
            json.dump({"format": 999, "samples": "nope"}, f)
        reloaded = cal.CalibrationStore(d)       # must not raise
        assert len(reloaded) == 1

    def test_fingerprint_count_invariant_value_sensitive(self):
        store = cal.CalibrationStore(None)
        store.observe("stage_run", "stage:s0", 100.0)
        fp0 = store.fingerprint()
        store.observe("stage_run", "stage:s0", 100.0)   # same value
        assert store.fingerprint() == fp0
        store.observe("stage_run", "stage:s0", 999.0)   # moves the stats
        assert store.fingerprint() != fp0

    def test_min_samples_gates_consult(self):
        global_config.calibration_min_samples = 3
        store = cal.CalibrationStore(None)
        store.observe("stage_run", "stage:s0", 100.0)
        store.observe("stage_run", "stage:s0", 100.0)
        assert store.measured_us("stage_run", "stage:s0") is None
        store.observe("stage_run", "stage:s0", 100.0)
        assert store.measured_us("stage_run",
                                 "stage:s0") == pytest.approx(100.0)

    def test_cache_token_off_vs_active(self):
        global_config.replan_mode = "off"
        assert cal.calibration_cache_token() is None
        global_config.replan_mode = "suggest"
        tok = cal.calibration_cache_token()
        assert tok is not None and tok.startswith("cal:")
        # stage-DP / ILP key parts ride the same token
        from alpa_tpu.pipeline_parallel.stage_construction import (
            _cal_key_parts)
        assert _cal_key_parts() == [tok]
        global_config.replan_mode = "off"
        assert _cal_key_parts() == []


# ---------------------------------------------------------------------
# Oracle 2: traced vs flight-ring ingest parity on the fixture
# ---------------------------------------------------------------------

class TestIngestParity:

    PINNED_COUNTS = {"stage:stage_0": 4, "stage:stage_1": 4,
                     "edge:stage_0->stage_1": 4}

    def test_traced_ingest_pinned(self):
        store = cal.CalibrationStore(None)
        ingested = cal.ingest_chrome_trace(_load_fixture(), store=store)
        assert ingested == self.PINNED_COUNTS
        assert store.get("stage_run",
                         "stage:stage_0").median_us == pytest.approx(100.0)
        assert store.get("stage_run",
                         "stage:stage_1").median_us == pytest.approx(120.0)
        # pool reshard.wire children: the true wire time, 7 us
        assert store.get(
            "reshard_wire",
            "edge:stage_0->stage_1").median_us == pytest.approx(7.0)

    def test_flight_fallback_same_keys_and_counts(self):
        """Satellite 3: no tracing (no pool spans) still produces store
        entries — same signatures, same sample counts; the wire value is
        the coarser LAUNCH->WAIT envelope."""
        traced = cal.CalibrationStore(None)
        cal.ingest_chrome_trace(_load_fixture(), store=traced)

        report = perf.report_from_trace(_load_fixture())
        flight = cal.CalibrationStore(None)
        ingested = cal.ingest_report(report, store=flight)

        assert ingested == self.PINNED_COUNTS
        assert ({(e.kind, e.signature, e.count) for e in flight.entries()}
                == {(e.kind, e.signature, e.count)
                    for e in traced.entries()})
        # stage medians identical; wire differs (envelope vs wire leg)
        for sig in ("stage:stage_0", "stage:stage_1"):
            assert flight.get("stage_run", sig).median_us == \
                traced.get("stage_run", sig).median_us
        assert flight.get(
            "reshard_wire",
            "edge:stage_0->stage_1").median_us == pytest.approx(35.5)


# ---------------------------------------------------------------------
# Oracle 3: off-mode is byte-identical
# ---------------------------------------------------------------------

def _two_mesh_edge():
    import jax
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
    devs = jax.devices()
    src_mesh = Mesh(np.array(devs[:4]), ("x",))
    dst_mesh = Mesh(np.array(devs[4:8]), ("x",))
    return (NamedSharding(src_mesh, P("x", None)),
            NamedSharding(dst_mesh, P()))


class TestOffMode:

    def _misprice_winner(self, store, src, dst):
        from alpa_tpu.pipeline_parallel import cross_mesh_resharding as cmr
        global_config.replan_mode = "suggest"
        chosen, costs, _ = cmr.choose_strategy((8, 8), 4, src, dst)
        sig = cal.wire_signature((8, 8), 4, cmr._sharding_key(src),
                                 cmr._sharding_key(dst), chosen)
        for _ in range(4):
            store.observe("reshard_wire", sig, 500.0,
                          modeled_us=costs[chosen] * 1e6)
        global_config.replan_mode = "off"
        return chosen, costs

    def test_off_mode_choice_identical_with_populated_store(self):
        from alpa_tpu.pipeline_parallel import cross_mesh_resharding as cmr
        src, dst = _two_mesh_edge()
        global_config.replan_mode = "off"
        base_chosen, base_costs, _ = cmr.choose_strategy((8, 8), 4,
                                                         src, dst)
        store = cal.CalibrationStore(None)
        cal.reset_calibration_store(store)
        analytic_chosen, _ = self._misprice_winner(store, src, dst)
        assert analytic_chosen == base_chosen

        chosen, costs, _ = cmr.choose_strategy((8, 8), 4, src, dst)
        assert chosen == base_chosen
        assert costs == base_costs                 # byte-identical
        global_config.replan_mode = "suggest"
        flipped, _, _ = cmr.choose_strategy((8, 8), 4, src, dst)
        assert flipped != base_chosen              # the store now binds

    def test_off_mode_cache_key_unchanged(self):
        """A decision cached before the store existed replays under
        off-mode with a populated store: the key has no calibration
        part."""
        from alpa_tpu.pipeline_parallel import cross_mesh_resharding as cmr
        src, dst = _two_mesh_edge()
        global_config.replan_mode = "off"
        chosen0, _, from_cache0 = cmr.resolve_strategy((8, 8), 4,
                                                       src, dst)
        assert not from_cache0
        store = cal.CalibrationStore(None)
        cal.reset_calibration_store(store)
        self._misprice_winner(store, src, dst)
        chosen1, _, from_cache1 = cmr.resolve_strategy((8, 8), 4,
                                                       src, dst)
        assert from_cache1 and chosen1 == chosen0
        # under suggest the key gains the fingerprint -> fresh solve,
        # flipped decision; resolving again replays it from cache
        global_config.replan_mode = "suggest"
        chosen2, _, from_cache2 = cmr.resolve_strategy((8, 8), 4,
                                                       src, dst)
        assert not from_cache2 and chosen2 != chosen0
        chosen3, _, from_cache3 = cmr.resolve_strategy((8, 8), 4,
                                                       src, dst)
        assert from_cache3 and chosen3 == chosen2

    def test_estimate_stage_cost_consults_only_when_active(self):
        from alpa_tpu import mesh_profiling as mp
        from alpa_tpu.device_mesh import LogicalDeviceMesh
        store = cal.CalibrationStore(None)
        cal.reset_calibration_store(store)
        global_config.replan_mode = "off"

        class _Comp:                               # zero-FLOP stage
            eqns = ()

        mesh = LogicalDeviceMesh(None, np.arange(2).reshape(1, 2))
        analytic = mp.estimate_stage_cost([_Comp()], mesh, None,
                                          use_ilp=False)
        assert len(store) == 0                     # off: not consulted
        global_config.replan_mode = "suggest"
        same = mp.estimate_stage_cost([_Comp()], mesh, None,
                                      use_ilp=False)
        assert same == pytest.approx(analytic)     # no samples yet
        sig = cal.stage_cost_signature(0.0, 2)
        for _ in range(3):
            store.observe("stage_run", sig, 12345.0)
        assert mp.estimate_stage_cost(
            [_Comp()], mesh, None,
            use_ilp=False) == pytest.approx(12345e-6)
        # the consult attached the analytic prediction it superseded
        e = store.get("stage_run", sig)
        assert e.modeled_us == pytest.approx(analytic * 1e6)


# ---------------------------------------------------------------------
# Oracle 5: drift observability + prof-DB validation
# ---------------------------------------------------------------------

class TestObservability:

    def test_drift_gauges_and_report_text(self):
        store = cal.get_calibration_store()
        cal.ingest_chrome_trace(_load_fixture(), store=store)
        store.set_modeled("reshard_wire", "edge:stage_0->stage_1", 2.0)
        text = tmetrics.get_registry().to_prometheus_text()
        assert 'alpa_cost_model_drift_ratio{kind="reshard_wire"} 3.5' \
            in text
        assert 'alpa_calibration_samples_total{kind="stage_run"} 8' \
            in text
        report = cal.format_calibration_report(store)
        assert "calibration store: 3 entries" in report
        assert "edge:stage_0->stage_1" in report
        assert "3.50" in report                    # the drift column

    def test_drift_cli_and_edges_cli(self, capsys):
        from scripts import perf_tool, trace_tool
        perf_tool.main(["drift", FIXTURE, "--json"])
        rows = json.loads(capsys.readouterr().out)
        assert {r["signature"] for r in rows} == {
            "stage:stage_0", "stage:stage_1", "edge:stage_0->stage_1"}
        trace_tool.main(["summarize", FIXTURE, "--edges"])
        out = capsys.readouterr().out
        assert "reshard edges" in out
        assert "stage_0->stage_1" in out
        assert "direct_p2p" in out                 # untagged label
        assert "7.0" in out                        # wire median us

    def test_edge_wire_table_values(self):
        joined = perf._join_spans(
            perf.spans_from_chrome(_load_fixture()), None)
        rows = cal.edge_wire_table(joined)
        assert len(rows) == 1
        r = rows[0]
        assert (r["src"], r["dst"]) == ("stage_0", "stage_1")
        assert r["strategy"] == "direct_p2p"
        assert r["n"] == 4
        assert r["median_us"] == pytest.approx(7.0)
        assert r["bytes"] is None and r["gbps"] is None

    def test_prof_db_schema_stamp_roundtrip(self, tmp_path):
        from alpa_tpu import mesh_profiling as mp
        r = mp.MeshProfilingResult()
        r.record("all_reduce", ("1x2", 2), 1024.0, 1e-4)
        db = mp.ProfilingResultDatabase({"1x2-test": r})
        path = str(tmp_path / "db.json")
        db.save(path)
        with open(path, encoding="utf-8") as f:
            raw = json.load(f)
        assert raw["schema_version"] == mp.PROF_DB_SCHEMA_VERSION
        assert "1x2-test" in raw["meshes"]
        loaded = mp.ProfilingResultDatabase.load(path)
        assert loaded.query("1x2-test").estimate(
            "all_reduce", ("1x2", 2), 1024.0) == pytest.approx(1e-4)

    def test_prof_db_legacy_load_warns(self, tmp_path, caplog):
        from alpa_tpu import mesh_profiling as mp
        path = str(tmp_path / "legacy.json")
        with open(path, "w", encoding="utf-8") as f:
            json.dump({"1x2-legacy": mp.MeshProfilingResult().to_json()},
                      f)
        with caplog.at_level(logging.WARNING,
                             logger="alpa_tpu.mesh_profiling"):
            db = mp.ProfilingResultDatabase.load(path)
        assert db.query("1x2-legacy") is not None
        assert any("no schema_version stamp" in r.message
                   for r in caplog.records)

    def test_committed_dbs_are_stamped(self):
        for name in ("prof_database_cpu8.json", "prof_database_tpu.json"):
            with open(os.path.join(REPO, name), encoding="utf-8") as f:
                raw = json.load(f)
            assert raw.get("schema_version") == 1, name

    def test_out_of_range_estimate_warns_once(self, caplog):
        from alpa_tpu import mesh_profiling as mp
        r = mp.MeshProfilingResult()
        key = ((0, 4), 4, "oob-test")
        r.record("all_gather", key, 100.0, 1e-5)
        r.record("all_gather", key, 1000.0, 1e-4)
        with caplog.at_level(logging.WARNING,
                             logger="alpa_tpu.mesh_profiling"):
            v = r.estimate("all_gather", key, 1e6)
            r.estimate("all_gather", key, 1e6)     # second: silent
        assert v == pytest.approx(1e-4)            # clamped, not wild
        warned = [rec for rec in caplog.records
                  if "out of measured range" in rec.message]
        assert len(warned) == 1
        assert "oob-test" in warned[0].message     # key (mesh shape) shown
        with caplog.at_level(logging.WARNING,
                             logger="alpa_tpu.mesh_profiling"):
            assert r.estimate("all_gather", key,
                              500.0) is not None   # in-range: silent
        assert len([rec for rec in caplog.records
                    if "out of measured range" in rec.message]) == 1


# ---------------------------------------------------------------------
# Oracle 6: consider_replan on a live 2-mesh pipeshard executable
# ---------------------------------------------------------------------

def _build_pipeshard_step():
    from alpa_tpu import PipeshardParallel
    from alpa_tpu.pipeline_parallel.layer_construction import (
        ManualLayerOption)
    from alpa_tpu.pipeline_parallel.stage_construction import (
        UniformStageOption)
    from alpa_tpu.testing import (create_mlp_train_state_and_batch,
                                  get_mlp_train_step)
    alpa_tpu.init("local")
    method = PipeshardParallel(
        num_micro_batches=2,
        layer_option=ManualLayerOption(),
        stage_option=UniformStageOption(num_stages=2))
    state, batch = create_mlp_train_state_and_batch(
        batch_size=64, num_layers=4, manual_pipeline_layer=True)
    step = get_mlp_train_step(method, use_value_and_grad=True)
    return step, state, batch


class TestConsiderReplan:

    def test_live_off_suggest_auto(self, tmp_path):
        step, state, batch = _build_pipeshard_step()
        global_config.replan_mode = "off"
        state, loss0 = step(state, batch)
        loss0 = float(loss0)
        ex = step.get_last_executable()

        # off: no verdict, nothing consulted
        assert ex.consider_replan() is None

        # suggest: verdict without application; perf ingest fed the
        # store (per-stage RUN samples at minimum)
        global_config.replan_mode = "suggest"
        v = ex.consider_replan()
        assert v is not None
        assert v["mode"] == "suggest" and v["applied"] is False
        assert v["baseline_critical_path_us"] > 0
        assert v["predicted_critical_path_us"] > 0
        assert isinstance(v["strategy_flips"], list)
        assert v["calibration_fingerprint"]
        store = cal.get_calibration_store()
        assert any(e.kind == "stage_run" for e in store.entries())

        # calibration.txt lands in the debug dump
        from alpa_tpu import monitoring
        dump = tmp_path / "dump"
        monitoring.dump_debug_info(ex, str(dump))
        txt = (dump / "calibration.txt").read_text()
        assert "calibration store" in txt

        # auto: hot-swap path — the verdict reports both fingerprints
        # and a step replayed after the (possible) re-lowering is
        # bit-exact against the pre-replan program.  The train step
        # donates its state, so each run gets an identical fresh state.
        from alpa_tpu.testing import create_mlp_train_state_and_batch
        state_a, batch_a = create_mlp_train_state_and_batch(
            batch_size=64, num_layers=4, manual_pipeline_layer=True)
        _, loss_a = step(state_a, batch_a)
        loss_a = float(loss_a)
        global_config.replan_mode = "auto"
        v2 = ex.consider_replan()
        assert v2 is not None and v2["mode"] == "auto"
        assert "plan_fingerprint_before" in v2
        assert "plan_fingerprint_after" in v2
        assert v2["applied"] == bool(v2["strategy_flips"])
        if not v2["strategy_flips"]:
            assert v2["plan_fingerprint_before"] == \
                v2["plan_fingerprint_after"]
        state_b, batch_b = create_mlp_train_state_and_batch(
            batch_size=64, num_layers=4, manual_pipeline_layer=True)
        _, loss_b = step(state_b, batch_b)
        assert float(loss_b) == loss_a
