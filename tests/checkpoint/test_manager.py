"""CheckpointManager: async overlap, failure surfacing, resume safety.

Holds two ISSUE 3 acceptance tests: the async save must return before
the writer thread has committed the step (its recorded blocking time
covers the staging only), and a checkpoint saved on one mesh shape must
restore bit-exactly onto another.
"""
import threading

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from alpa_tpu import fault
from alpa_tpu.checkpoint.manager import (CheckpointManager,
                                         CheckpointSaveError,
                                         PlanFingerprintMismatch,
                                         RecoveryCheckpointer)
from alpa_tpu.checkpoint.policy import RetentionPolicy


def _state(seed=0, n=4, shape=(32, 16)):
    rng = np.random.default_rng(seed)
    return {"params": {f"layer{i}": {
        "kernel": rng.standard_normal(shape).astype(np.float32),
        "bias": rng.standard_normal(shape[1:]).astype(np.float32),
    } for i in range(n)}, "step": np.int64(seed)}


def _assert_trees_equal(a, b):
    la = jax.tree_util.tree_leaves(a)
    lb = jax.tree_util.tree_leaves(b)
    assert len(la) == len(lb)
    for x, y in zip(la, lb):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


class TestRoundtrip:

    def test_nested_pytree_bit_exact(self, tmp_path):
        ma = CheckpointManager(str(tmp_path), async_save=False)
        state = _state(0)
        ma.save(3, state, plan_fingerprint="fp0")
        ma.wait()
        assert ma.latest_step() == 3
        restored = ma.restore(_state(99), expected_plan_fingerprint="fp0")
        _assert_trees_equal(restored, state)

    def test_restore_missing_leaf_is_loud(self, tmp_path):
        ma = CheckpointManager(str(tmp_path), async_save=False)
        ma.save(1, {"a": np.ones(4, np.float32)})
        with pytest.raises(KeyError, match="no leaf"):
            ma.restore({"a": np.zeros(4, np.float32),
                        "b": np.zeros(4, np.float32)})

    def test_retention_applied_after_each_save(self, tmp_path):
        ma = CheckpointManager(str(tmp_path), async_save=False,
                               policy=RetentionPolicy(keep_last_k=2))
        for step in (1, 2, 3, 4):
            ma.save(step, {"w": np.full(8, float(step), np.float32)})
        ma.wait()
        assert ma.all_steps() == [3, 4]
        restored = ma.restore({"w": np.zeros(8, np.float32)})
        np.testing.assert_array_equal(restored["w"], np.full(8, 4.0))


class TestAsyncOverlap:
    """Acceptance: an async save blocks its caller for the staging
    only; the write and the manifest commit happen behind it."""

    def test_async_save_returns_before_the_write_commits(self, tmp_path):
        """Ordering, not a ratio of wall clocks: the store's write is
        held on an event, so ``save`` can only return if it does not
        wait for it."""
        ma = CheckpointManager(str(tmp_path / "async"))
        entered, release = threading.Event(), threading.Event()
        real_write = ma.store.write_step

        def held_write(*args, **kwargs):
            entered.set()
            assert release.wait(60), "the test never released the write"
            return real_write(*args, **kwargs)

        ma.store.write_step = held_write
        state = {"w": jnp.arange(4096, dtype=jnp.float32)}
        ma.save(1, state)
        # save returned while the writer thread is still held: nothing
        # is committed, and what the caller was blocked for is final
        assert entered.wait(60)
        assert ma.latest_step() is None
        blocking = ma.last_blocking_seconds
        assert 0.0 <= ma.last_staging_seconds <= blocking
        release.set()
        ma.wait()
        assert ma.latest_step() == 1
        assert ma.store.verify_step(1)["ok"]
        # the write's time went to last_write_seconds, not to the
        # caller's blocking time
        assert ma.last_blocking_seconds == blocking
        assert ma.last_write_seconds > 0.0

        # the synchronous save is the contrast: committed on return
        sync_ma = CheckpointManager(str(tmp_path / "sync"))
        sync_ma.save(1, state, sync=True)
        assert sync_ma.latest_step() == 1
        assert sync_ma.last_blocking_seconds >= sync_ma.last_write_seconds

    def test_double_buffer_serializes_writes(self, tmp_path):
        """save(N+1) joins save(N)'s write: never two writes in
        flight, and every step lands committed."""
        ma = CheckpointManager(str(tmp_path))
        in_flight = []
        max_in_flight = []
        real_write = ma.store.write_step

        def tracking_write(*args, **kwargs):
            in_flight.append(1)
            max_in_flight.append(len(in_flight))
            try:
                return real_write(*args, **kwargs)
            finally:
                in_flight.pop()

        ma.store.write_step = tracking_write
        for step in range(1, 6):
            ma.save(step, {"w": np.full(64, float(step), np.float32)})
        ma.wait()
        assert max(max_in_flight) == 1
        assert ma.all_steps() == [1, 2, 3, 4, 5]


class TestFailureSurfacing:

    def test_background_failure_raises_from_wait(self, tmp_path):
        ma = CheckpointManager(str(tmp_path))

        def boom(*args, **kwargs):
            raise OSError("disk full")

        ma.store.write_step = boom
        ma.save(7, {"w": np.ones(8, np.float32)})
        with pytest.raises(CheckpointSaveError, match="disk full") as ei:
            ma.wait()
        assert ei.value.step == 7
        assert ma.latest_step() is None        # atomic: no manifest

    def test_background_failure_raises_from_next_save(self, tmp_path):
        ma = CheckpointManager(str(tmp_path))
        real_write = ma.store.write_step
        calls = []

        def boom_once(*args, **kwargs):
            calls.append(1)
            if len(calls) == 1:
                raise OSError("disk full")
            return real_write(*args, **kwargs)

        ma.store.write_step = boom_once
        ma.save(1, {"w": np.ones(8, np.float32)})
        ma._pending.join()                     # write thread has failed
        with pytest.raises(CheckpointSaveError):
            ma.save(2, {"w": np.zeros(8, np.float32)})
        # the error was consumed; the manager keeps working
        ma.save(2, {"w": np.zeros(8, np.float32)})
        ma.wait()
        assert ma.latest_step() == 2


class TestPlanFingerprint:

    def test_mismatch_refuses_restore(self, tmp_path):
        ma = CheckpointManager(str(tmp_path), async_save=False)
        ma.save(1, {"w": np.ones(8, np.float32)},
                plan_fingerprint="a" * 64)
        with pytest.raises(PlanFingerprintMismatch, match="saved under"):
            ma.restore({"w": np.zeros(8, np.float32)},
                       expected_plan_fingerprint="b" * 64)
        # matching fingerprint restores fine
        ma.restore({"w": np.zeros(8, np.float32)},
                   expected_plan_fingerprint="a" * 64)

    def test_fingerprint_taken_from_executable(self, tmp_path):

        class FakeExecutable:

            def __init__(self, fp):
                self._fp = fp

            def get_plan_fingerprint(self):
                return self._fp

        ma = CheckpointManager(str(tmp_path), async_save=False)
        ma.save(1, {"w": np.ones(8, np.float32)},
                executable=FakeExecutable("plan-x"))
        assert ma.store.read_manifest(1)["plan_fingerprint"] == "plan-x"
        with pytest.raises(PlanFingerprintMismatch):
            ma.restore({"w": np.zeros(8, np.float32)},
                       executable=FakeExecutable("plan-y"))

    def test_unstamped_checkpoint_restores_with_warning(self, tmp_path):
        ma = CheckpointManager(str(tmp_path), async_save=False)
        ma.save(1, {"w": np.ones(8, np.float32)})
        # no saved fingerprint: cannot validate, must not hard-fail
        ma.restore({"w": np.zeros(8, np.float32)},
                   expected_plan_fingerprint="c" * 64)


class TestCrossMeshRestore:
    """Acceptance: save on mesh shape A, restore onto mesh shape B,
    bit-exact (resharding-on-read)."""

    def test_8x1_to_2x4_bit_exact(self, tmp_path):
        devices = jax.devices()
        assert len(devices) >= 8, "conftest pins 8 virtual CPU devices"
        arr = np.arange(16 * 8, dtype=np.float32).reshape(16, 8)

        mesh_a = Mesh(np.array(devices[:8]).reshape(8), ("x",))
        sharded_a = jax.device_put(
            arr, NamedSharding(mesh_a, P("x", None)))
        ma = CheckpointManager(str(tmp_path), async_save=False)
        ma.save(1, {"w": sharded_a})

        mesh_b = Mesh(np.array(devices[:8]).reshape(2, 4), ("x", "y"))
        shard_b = NamedSharding(mesh_b, P("x", "y"))
        restored = ma.restore({"w": arr}, shardings={"w": shard_b})
        out = restored["w"]
        assert isinstance(out, jax.Array)
        assert out.sharding.is_equivalent_to(shard_b, out.ndim)
        np.testing.assert_array_equal(np.asarray(out), arr)
        # each device holds only its (8, 2) slice
        assert out.addressable_shards[0].data.shape == (8, 2)

    def test_sharded_to_host_bit_exact(self, tmp_path):
        devices = jax.devices()
        arr = np.random.default_rng(3).standard_normal(
            (24, 4)).astype(np.float32)
        mesh = Mesh(np.array(devices[:4]).reshape(4), ("x",))
        sharded = jax.device_put(arr, NamedSharding(mesh, P("x", None)))
        ma = CheckpointManager(str(tmp_path), async_save=False)
        ma.save(1, {"w": sharded})
        restored = ma.restore({"w": np.zeros_like(arr)})
        np.testing.assert_array_equal(restored["w"], arr)


class TestRecoveryCheckpointer:
    """fault.RecoveryManager wiring: snapshot on real degradation only,
    automatic restore of the last verified step on recovery."""

    def _make(self, tmp_path, probe_ok):
        live = {"state": _state(1, n=1, shape=(8,))}
        recovery = fault.RecoveryManager(
            mesh_group=["m0"],
            probe=lambda mesh: probe_ok[0],
            retry_policy=fault.RetryPolicy(max_attempts=1,
                                           base_delay=0.0, max_delay=0.0,
                                           jitter=0.0))
        ma = CheckpointManager(str(tmp_path), async_save=False)
        ckpt = RecoveryCheckpointer(
            ma, recovery,
            state_provider=lambda: live["state"],
            state_setter=lambda s: live.__setitem__("state", s),
            plan_fingerprint="fp")
        return live, recovery, ckpt

    def test_transient_blip_no_snapshot_no_restore(self, tmp_path):
        probe_ok = [True]                      # re-probe passes at once
        live, recovery, ckpt = self._make(tmp_path, probe_ok)
        recovery.observe([0])
        assert recovery.state is fault.MeshHealth.HEALTHY
        assert ckpt.snapshots_saved == 0
        assert ckpt.restores_done == 0

    def test_degrade_snapshots_then_recover_restores(self, tmp_path):
        probe_ok = [False]
        live, recovery, ckpt = self._make(tmp_path, probe_ok)
        original = jax.tree_util.tree_map(np.copy, live["state"])

        recovery.observe([0])                  # -> RECOVERING -> DEGRADED
        assert recovery.state is fault.MeshHealth.DEGRADED
        assert ckpt.snapshots_saved == 1
        assert ckpt.manager.latest_step() == 1
        assert ckpt.manager.store.verify_step(1)["ok"]

        # the in-flight state is lost/corrupted during the outage
        live["state"]["params"]["layer0"]["kernel"][:] = -1.0

        probe_ok[0] = True
        recovery.observe([])                   # clean round -> HEALTHY
        assert recovery.state is fault.MeshHealth.HEALTHY
        assert ckpt.restores_done == 1
        _assert_trees_equal(live["state"], original)
