"""Elastic training (ISSUE 16): detect -> quiesce -> snapshot ->
re-solve -> resume, end to end on the committed CPU fixtures.

The load-bearing checks mirror the acceptance criteria:

* an injected worker-kill on the 2-stage pipeshard fixture recovers —
  quiesce, snapshot, re-solve for the surviving half of the mesh,
  resume — within the step budget, with every post-resume loss
  **bitwise equal** to an uninterrupted run restored from the same
  step on the same surviving plan;
* a candidate plan whose verdict carries any NEW (analysis, code)
  finding is rejected and the supervisor rolls back to the old plan +
  last verified checkpoint (pinned negative test);
* retry exhaustion at an elastic fault site escalates to the recovery
  manager instead of propagating the raw error (pinned).

The dp=4->dp=2 live rescale and the >=20-seed kill-schedule fuzz live
in test_elastic_fuzz.py.  See docs/fault_tolerance.md#elastic-training.
"""
import os
import subprocess
import sys
import tempfile
import time

import jax
import numpy as np
import pytest

import alpa_tpu
from alpa_tpu import elastic, fault
from alpa_tpu.checkpoint.manager import CheckpointManager
from alpa_tpu.device_mesh import VirtualPhysicalMesh
from alpa_tpu.elastic import (ElasticSupervisor, PreemptionNotice,
                              WedgeDetector, WorkerLost)
from alpa_tpu.pipeline_parallel.layer_construction import ManualLayerOption
from alpa_tpu.pipeline_parallel.stage_construction import UniformStageOption
from alpa_tpu.testing import create_mlp_train_state_and_batch, \
    get_mlp_train_step

pytestmark = pytest.mark.fault

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


@pytest.fixture(autouse=True)
def _clean_elastic_state():
    yield
    fault.set_escalation_manager(None)
    elastic._ACTIVE = None


@pytest.fixture(autouse=True)
def _reset_ckpt_metrics():
    """Supervisor snapshots bump the process-global checkpoint counters
    test_telemetry pins; reset after each test."""
    from alpa_tpu.checkpoint import metrics
    yield
    metrics.reset()


def make_solve(num_stages=2):
    """Per-device-set memoized pipeshard solve — the supervisor's
    re-solve hook.  Memoization matters twice over: an episode whose
    survivors match the current set reuses the identical compiled
    executable (bitwise continuity for free), and the comparator run
    below gets the exact executable the supervisor hot-swapped to."""
    cache = {}

    def solve(devices):
        key = tuple(id(d) for d in devices)
        if key not in cache:
            n = len(devices)
            vm = VirtualPhysicalMesh(
                1, n, np.array(list(devices), dtype=object).reshape(1, n))
            method = alpa_tpu.PipeshardParallel(
                devices=vm, num_micro_batches=2,
                layer_option=ManualLayerOption(),
                stage_option=UniformStageOption(num_stages=num_stages))
            cache[key] = get_mlp_train_step(method,
                                            use_value_and_grad=True)
        return cache[key]

    return solve


def fresh_state_and_batch():
    # PRNGKey(0)-deterministic: every call returns bitwise-identical
    # initial state, so "recreate" == "copy" for comparator runs
    return create_mlp_train_state_and_batch(
        batch_size=64, num_layers=4, manual_pipeline_layer=True)


def run_supervised(sup, batch, until, max_calls=50):
    """Drive sup.step until ``until`` steps commit; returns
    {step_index: loss} over every committed step."""
    losses = {}
    for _ in range(max_calls):
        if sup.step_index >= until:
            return losses
        loss = sup.step(batch)
        losses[sup.step_index] = np.asarray(loss)
    raise AssertionError(f"no progress: stuck at step {sup.step_index}")


class TestWedgeDetector:
    """The probe-timeout taxonomy, as unit checks (no mesh
    needed: the probe is injectable)."""

    def test_ok_wedged_dead(self):
        det = WedgeDetector(probe_timeout_s=0.2)
        det._probe = lambda mesh: True
        assert det.probe_one(object()) == "ok"
        det._probe = lambda mesh: time.sleep(5.0)
        assert det.probe_one(object()) == "wedged"

        def dead(mesh):
            raise RuntimeError("runtime gone")

        det._probe = dead
        assert det.probe_one(object()) == "dead"
        det._probe = lambda mesh: False
        assert det.probe_one(object()) == "dead"

    def test_sweep_short_circuits_after_first_wedge(self):
        probed = []

        def probe(mesh):
            probed.append(mesh)
            if mesh == "m1":
                time.sleep(5.0)
            return True

        det = WedgeDetector(mesh_group=["m0", "m1", "m2", "m3"],
                            probe=probe, probe_timeout_s=0.2)
        statuses = det.check()
        assert statuses == {0: "ok", 1: "wedged", 2: "skipped",
                            3: "skipped"}
        # never probe past a wedge
        assert probed == ["m0", "m1"]
        assert not det.healthy()

    def test_check_is_an_injection_point(self):
        det = WedgeDetector(mesh_group=[], probe_timeout_s=0.2)
        with fault.FaultPlan(fault.FaultSpec("wedge_detected")):
            with pytest.raises(fault.InjectedFault):
                det.check()


class TestEscalation:
    """Satellite 1 pinned behavior: retry exhaustion at an elastic site
    escalates to the recovery manager instead of propagating raw."""

    def test_exhaustion_escalates_to_recovery_manager(self):
        rm = fault.RecoveryManager()
        fault.set_escalation_manager(rm)

        def boom():
            raise RuntimeError("host gone")

        with pytest.raises(fault.ServiceDegradedError) as exc:
            fault.call_with_retry(
                boom, site="worker_lost",
                policy=fault.RetryPolicy(max_attempts=2, base_delay=0.001,
                                         max_delay=0.005, jitter=0.0))
        # chained, not swallowed: the root cause stays reachable
        assert isinstance(exc.value.__cause__, RuntimeError)
        # the manager entered (and possibly completed) recovery —
        # whatever it did, it is no longer idling in SUSPECT
        assert rm.state in (fault.MeshHealth.RECOVERING,
                            fault.MeshHealth.HEALTHY,
                            fault.MeshHealth.DEGRADED)

    def test_non_elastic_site_still_raises_raw(self):
        fault.set_escalation_manager(fault.RecoveryManager())

        def boom():
            raise RuntimeError("probe down")

        with pytest.raises(RuntimeError, match="probe down"):
            fault.call_with_retry(
                boom, site="probe",
                policy=fault.RetryPolicy(max_attempts=2, base_delay=0.001,
                                         max_delay=0.005, jitter=0.0))

    def test_no_manager_installed_raises_raw(self):
        def boom():
            raise RuntimeError("host gone")

        with pytest.raises(RuntimeError, match="host gone"):
            fault.call_with_retry(
                boom, site="worker_lost",
                policy=fault.RetryPolicy(max_attempts=2, base_delay=0.001,
                                         max_delay=0.005, jitter=0.0))

    def test_supervisor_escalation_queues_an_episode(self, tmp_path):
        """The supervisor registers itself as the escalation manager;
        an exhausted elastic-site retry becomes a queued episode the
        next step boundary drains."""
        alpa_tpu.init(cluster="local")
        state, batch = fresh_state_and_batch()
        step = get_mlp_train_step()  # plain jit: no pipeshard compile
        sup = ElasticSupervisor(lambda devices: step, state,
                                checkpoint_root=str(tmp_path))
        assert fault.get_escalation_manager() is sup

        def boom():
            raise RuntimeError("worker died")

        with pytest.raises(fault.ServiceDegradedError):
            fault.call_with_retry(
                boom, site="worker_lost",
                policy=fault.RetryPolicy(max_attempts=2, base_delay=0.001,
                                         max_delay=0.005, jitter=0.0))
        sup.step(batch)
        assert [e["reason"] for e in sup.episodes] == ["worker_lost"]
        assert sup.episodes[0]["replan"] == "reused"


class TestSupervisorPipeshard:

    def test_worker_kill_resolves_for_survivors_bitwise(self, tmp_path):
        """Acceptance: kill half the mesh at a step boundary; the
        supervisor re-solves a 2-stage plan over the surviving 4
        devices and every post-resume loss is bitwise-equal to an
        uninterrupted run restored from the same step on the same
        surviving plan."""
        alpa_tpu.init(cluster="local")
        solve = make_solve()
        state, batch = fresh_state_and_batch()
        sup = ElasticSupervisor(solve, state,
                                checkpoint_root=str(tmp_path))
        survivors = list(jax.devices())[:4]
        with fault.FaultPlan(fault.FaultSpec(
                "worker_lost", after=2,
                exc=lambda: WorkerLost(survivors=survivors))):
            losses = run_supervised(sup, batch, until=5)

        assert [e["reason"] for e in sup.episodes] == ["worker_lost"]
        ep = sup.episodes[0]
        assert ep["quiesced"] is True
        assert ep["snapshot"] == "boundary"
        assert ep["replan"] == "accepted"
        assert ep["devices_before"] == 8 and ep["devices_after"] == 4
        assert ep["within_step_budget"] and ep["within_time_budget"]
        assert sup.devices == survivors

        # /healthz surface
        report = elastic.status_report()
        assert report["devices"] == 4
        assert report["episodes"] == 1
        assert report["last_episode"]["reason"] == "worker_lost"
        assert report["recovering"] is False

        # comparator: restore the SAME step into the SAME surviving
        # plan (memoized solve returns the hot-swapped executable) and
        # run forward uninterrupted
        r = ep["restored_step"]
        mgr = CheckpointManager(str(tmp_path), async_save=False)
        c_state, _ = fresh_state_and_batch()
        c_state = mgr.restore(c_state, step=r)
        c_step = solve(survivors)
        for i in range(r + 1, 6):
            c_state, c_loss = c_step(c_state, batch)
            assert np.array_equal(losses[i], np.asarray(c_loss)), (
                f"post-resume loss diverged at step {i}: "
                f"{losses[i]!r} != {np.asarray(c_loss)!r}")

    def test_preemption_grace_then_wedge(self, tmp_path):
        """One supervisor, two episodes: a preemption notice whose
        snapshot lands inside the grace window, then a mid-step wedge
        (hung probe) that resets and resumes from the last verified
        checkpoint.  Bitwise continuity must survive both."""
        alpa_tpu.init(cluster="local")
        solve = make_solve()
        state, batch = fresh_state_and_batch()
        det = WedgeDetector(mesh_group=[object()],
                            probe=lambda m: time.sleep(5.0),
                            probe_timeout_s=0.1)
        sup = ElasticSupervisor(solve, state,
                                checkpoint_root=str(tmp_path),
                                wedge_detector=det)
        with fault.FaultPlan(
                fault.FaultSpec("preemption_notice", after=1,
                                exc=lambda: PreemptionNotice(
                                    grace_s=30.0)),
                fault.FaultSpec("stage_launch", times=1, after=20)):
            losses = run_supervised(sup, batch, until=4)

        reasons = [e["reason"] for e in sup.episodes]
        assert reasons == ["preemption_notice", "wedge_detected"], reasons
        preempt, wedge = sup.episodes
        assert preempt["snapshot"] == "grace"
        assert preempt["snapshot_before_kill"] is True
        assert wedge["mid_step"] is True
        assert wedge["snapshot"] == "skipped"  # torn state: never saved
        assert wedge["restored_step"] is not None

        # continuity: same plan throughout, so the loss curve must
        # bitwise-match an uninterrupted run of the same executable
        base_state, _ = fresh_state_and_batch()
        base_step = solve(list(jax.devices()))
        for i in range(1, 5):
            base_state, bl = base_step(base_state, batch)
            assert np.array_equal(losses[i], np.asarray(bl)), i

    def test_new_finding_rejects_candidate_and_rolls_back(self, tmp_path):
        """Pinned negative test: a re-lowered plan whose verdict shows
        ANY new (analysis, code) finding is rejected; the supervisor
        keeps the old plan + devices and training continues bitwise."""
        from alpa_tpu.analysis.plan_verifier import Finding
        from alpa_tpu.pipeline_parallel.pipeshard_executable import \
            PipeshardDriverExecutable

        alpa_tpu.init(cluster="local")
        solve = make_solve()
        state, batch = fresh_state_and_batch()
        sup = ElasticSupervisor(solve, state,
                                checkpoint_root=str(tmp_path))
        run_supervised(sup, batch, until=2)  # captures the baseline
        assert sup._baseline_findings is not None

        orig = PipeshardDriverExecutable.get_plan_verdict

        def tainted(self, mode="registers"):
            v = orig(self, mode)
            if v is not None and not any(
                    f.code == "injected.synthetic" for f in v.warnings):
                v.warnings.append(Finding(
                    "injected", "injected.synthetic",
                    "pretend regression on the candidate plan"))
            return v

        PipeshardDriverExecutable.get_plan_verdict = tainted
        try:
            survivors = list(jax.devices())[4:]
            with fault.FaultPlan(fault.FaultSpec(
                    "worker_lost", times=1,
                    exc=lambda: WorkerLost(survivors=survivors))):
                losses = run_supervised(sup, batch, until=4)
        finally:
            PipeshardDriverExecutable.get_plan_verdict = orig

        ep = sup.episodes[0]
        assert ep["replan"] == "rejected"
        # rollback: old plan, old devices
        assert len(sup.devices) == 8
        assert sup._step_fn is solve(list(jax.devices()))

        base_state, _ = fresh_state_and_batch()
        base_step = solve(list(jax.devices()))
        for i in range(1, 5):
            base_state, bl = base_step(base_state, batch)
            if i in losses:
                assert np.array_equal(losses[i], np.asarray(bl)), i


class TestCkptToolLastGood:
    """Satellite 2: the supervisor and the shell runbook share one
    source of truth for the restore target."""

    def test_prints_last_verified_step(self, tmp_path):
        state, _ = create_mlp_train_state_and_batch(8, hidden_dim=8)
        mgr = CheckpointManager(str(tmp_path), async_save=False)
        mgr.save(3, state, sync=True)
        mgr.save(7, state, sync=True)
        out = subprocess.run(
            [sys.executable, os.path.join(REPO_ROOT, "scripts",
                                          "ckpt_tool.py"),
             "last-good", str(tmp_path)],
            capture_output=True, text=True, timeout=120)
        assert out.returncode == 0, out.stderr
        assert out.stdout.strip() == "7"
        assert int(out.stdout) == mgr.last_verified_step()

    def test_skips_corrupt_newest_step(self, tmp_path):
        state, _ = create_mlp_train_state_and_batch(8, hidden_dim=8)
        mgr = CheckpointManager(str(tmp_path), async_save=False)
        mgr.save(3, state, sync=True)
        state7 = state.replace(params=jax.tree_util.tree_map(
            lambda x: x + 1, state.params))
        mgr.save(7, state7, sync=True)
        # bit-rot a chunk only step 7 references (the store is
        # content-addressed: identical leaves dedupe across steps)
        step3_hashes = {e["hash"]
                        for l in mgr.store.read_manifest(3)["leaves"]
                        .values() for e in l["chunks"]}
        manifest = mgr.store.read_manifest(7)
        only7 = [e["hash"] for l in manifest["leaves"].values()
                 for e in l["chunks"] if e["hash"] not in step3_hashes]
        assert only7, "step 7 shares every chunk with step 3?"
        path = mgr.store.chunk_path(only7[0])
        with open(path, "r+b") as f:
            f.write(b"\xff" * 8)
        out = subprocess.run(
            [sys.executable, os.path.join(REPO_ROOT, "scripts",
                                          "ckpt_tool.py"),
             "last-good", str(tmp_path)],
            capture_output=True, text=True, timeout=120)
        assert out.returncode == 0, out.stderr
        assert out.stdout.strip() == "3"

    def test_exits_nonzero_when_nothing_verifies(self, tmp_path):
        (tmp_path / "manifests").mkdir()
        out = subprocess.run(
            [sys.executable, os.path.join(REPO_ROOT, "scripts",
                                          "ckpt_tool.py"),
             "last-good", str(tmp_path)],
            capture_output=True, text=True, timeout=120)
        assert out.returncode != 0
