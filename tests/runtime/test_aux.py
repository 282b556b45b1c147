"""Aux subsystems: checkpoint save/restore, data loader, parallel plan,
create-state / follow methods (ref tests/runtime/, SURVEY.md §4.6)."""
import os
import tempfile

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

import alpa_tpu
from alpa_tpu import DataParallel, ShardParallel, Zero3Parallel
from alpa_tpu.create_state_parallel import CreateStateParallel
from alpa_tpu.data_loader import DataLoader, get_batch_shardings
from alpa_tpu.follow_parallel import FollowParallel
from alpa_tpu.parallel_plan import (ParallelPlan, executable_to_plan,
                                    plan_to_method)
from alpa_tpu.serialization import (checkpoint_wait, restore_checkpoint,
                                    save_checkpoint)
from alpa_tpu.testing import (assert_allclose, create_mlp_train_state_and_batch,
                              get_mlp_train_step, init_params)


class TestCheckpoint:

    def test_save_restore_roundtrip(self, tmp_path):
        state, batch = create_mlp_train_state_and_batch()
        step = get_mlp_train_step(Zero3Parallel(), use_value_and_grad=True)
        state, _ = step(state, batch)  # state now sharded
        ckpt = str(tmp_path / "ckpt")
        save_checkpoint(ckpt, state.params, step=1)
        checkpoint_wait()
        target = jax.tree_util.tree_map(jnp.zeros_like,
                                        jax.device_get(state.params))
        restored = restore_checkpoint(ckpt, target)
        assert_allclose(jax.device_get(state.params), restored)

    def test_cross_topology_restore(self, tmp_path):
        """Save sharded one way, restore with a different sharding."""
        mesh8 = Mesh(np.array(jax.devices()).reshape(8), ("x",))
        x = jax.device_put(jnp.arange(64.0).reshape(8, 8),
                           NamedSharding(mesh8, P("x")))
        ckpt = str(tmp_path / "ckpt2")
        save_checkpoint(ckpt, {"w": x}, step=0)
        checkpoint_wait()
        mesh4 = Mesh(np.array(jax.devices()[:4]).reshape(2, 2), ("a", "b"))
        new_sharding = NamedSharding(mesh4, P(None, "b"))
        restored = restore_checkpoint(
            ckpt, {"w": jnp.zeros((8, 8))}, {"w": new_sharding})
        assert_allclose(np.asarray(restored["w"]), np.asarray(x))
        assert restored["w"].sharding.is_equivalent_to(new_sharding, 2)

    def test_local_cache_drain(self, tmp_path):
        state, _ = create_mlp_train_state_and_batch()
        ckpt = str(tmp_path / "final")
        cache = str(tmp_path / "cache")
        save_checkpoint(ckpt, {"p": jnp.ones((4, 4))}, step=0,
                        local_cache_dir=cache)
        checkpoint_wait()
        restored = restore_checkpoint(ckpt, {"p": jnp.zeros((4, 4))})
        assert_allclose(np.asarray(restored["p"]), np.ones((4, 4)))


class TestDataLoader:

    def test_prefetching_loader_places_batches(self):
        state, batch = create_mlp_train_state_and_batch(batch_size=16)
        step = get_mlp_train_step(DataParallel(), use_value_and_grad=True)
        state, _ = step(state, batch)
        ex = step.get_last_executable()
        # shardings of the two batch leaves (x, y) in flat order
        batch_shardings = [
            s for s, a in zip(ex.in_shardings, ex.in_avals)
            if a.shape[:1] == (16,)
        ]

        def it():
            for i in range(4):
                yield {
                    "x": np.full((16, 32), i, np.float32),
                    "y": np.full((16, 32), i, np.float32),
                }

        loader = DataLoader(it, {"x": batch_shardings[0],
                                 "y": batch_shardings[1]},
                            prefetch_size=2)
        count = 0
        for placed in loader:
            assert isinstance(placed["x"], jax.Array)
            assert placed["x"].sharding.is_equivalent_to(
                batch_shardings[0], 2)
            state, _ = step(state, placed)
            count += 1
        assert count == 4


class TestDistributedDataLoader:

    def test_loads_only_addressable_rows(self):
        """Per-shard callback loading (ref MeshWorkerDataLoader:229): each
        shard's rows are requested exactly once; the assembled global
        array matches the logical batch."""
        from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

        from alpa_tpu.data_loader import DistributedDataLoader

        mesh = Mesh(np.array(jax.devices()).reshape(8), ("dp",))
        sharding = NamedSharding(mesh, P("dp", None))
        requested = []

        def next_batch_fn(step):
            def row_loader(start, stop):
                requested.append((step, start, stop))
                rows = np.arange(start, stop, dtype=np.float32)
                return (np.full((stop - start, 4), step, np.float32) +
                        rows[:, None])
            return row_loader

        loader = DistributedDataLoader((16, 4), sharding, next_batch_fn,
                                       num_batches=3)
        batches = list(loader)
        assert len(batches) == 3
        for step, b in enumerate(batches):
            assert isinstance(b, jax.Array)
            want = step + np.arange(16, dtype=np.float32)[:, None] + \
                np.zeros((16, 4), np.float32)
            assert_allclose(np.asarray(b), want)
        # 8 shards x 2 rows each, per batch — never the full batch at once
        per_step = [(s, a, b) for (s, a, b) in requested if s == 0]
        assert len(per_step) == 8
        assert all(b - a == 2 for (_, a, b) in per_step)

    def test_loader_errors_propagate(self):
        """A failing row loader must raise in the consumer, not silently
        truncate the epoch."""
        from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

        from alpa_tpu.data_loader import DistributedDataLoader

        mesh = Mesh(np.array(jax.devices()).reshape(8), ("dp",))
        sharding = NamedSharding(mesh, P("dp", None))

        def next_batch_fn(step):
            def row_loader(start, stop):
                if step == 1:
                    raise IOError("shard file missing")
                return np.zeros((stop - start, 4), np.float32)
            return row_loader

        loader = DistributedDataLoader((16, 4), sharding, next_batch_fn,
                                       num_batches=3)
        got = []
        with pytest.raises(IOError, match="shard file missing"):
            for b in loader:
                got.append(b)
        assert len(got) == 1


class TestParallelPlan:

    def test_plan_roundtrip(self, tmp_path):
        state, batch = create_mlp_train_state_and_batch()
        step = get_mlp_train_step(ShardParallel(), use_value_and_grad=True)
        state, _ = step(state, batch)
        plan = executable_to_plan(step.get_last_executable())
        fn = str(tmp_path / "plan.pkl")
        plan.save(fn)
        loaded = ParallelPlan.load(fn)
        method = plan_to_method(loaded)
        # replay: compiles without search and matches numerics
        state2, _ = create_mlp_train_state_and_batch()
        step2 = get_mlp_train_step(method, use_value_and_grad=True)
        s_a, _ = step2(state2, batch)
        assert s_a is not None


class TestCreateStateAndFollow:

    def test_create_state_sharded_init(self):
        state, batch = create_mlp_train_state_and_batch()
        train_step = get_mlp_train_step(Zero3Parallel(),
                                        use_value_and_grad=True)
        # prime the executable
        s1, _ = train_step(state, batch)

        import optax
        from flax.training import train_state as ts

        from alpa_tpu.testing import MLPModel

        model = MLPModel(hidden_dim=32, output_dim=32, num_layers=2)

        def create_state():
            rng = jax.random.PRNGKey(0)
            params = init_params(model, rng, jnp.ones((64, 32)))
            return ts.TrainState.create(apply_fn=model.apply, params=params,
                                        tx=optax.sgd(1e-2, momentum=0.9))

        method = CreateStateParallel(train_step, (state, batch))
        init_fn = alpa_tpu.parallelize(create_state, method=method,
                                       batch_argnums=())
        new_state = init_fn()
        # leaves must come back sharded like the train step inputs
        ex = train_step.get_last_executable()
        flat_new = jax.tree_util.tree_leaves(new_state)
        n_state = len(flat_new)
        for x, s in zip(flat_new, ex.in_shardings[:n_state]):
            if hasattr(x, "sharding"):
                assert x.sharding.is_equivalent_to(s, np.ndim(x))

    def test_follow_parallel_eval_step(self):
        state, batch = create_mlp_train_state_and_batch()
        train_step = get_mlp_train_step(ShardParallel(),
                                        use_value_and_grad=True)
        state, _ = train_step(state, batch)

        def eval_step(state, batch):
            out = state.apply_fn(state.params, batch["x"])
            return ((out - batch["y"])**2).mean(axis=-1)

        method = FollowParallel(train_step, (state, batch))
        efn = alpa_tpu.parallelize(eval_step, method=method)
        losses = efn(state, batch)
        ref = eval_step(state, batch)
        assert_allclose(np.asarray(losses), np.asarray(ref), 1e-4, 1e-4)


if __name__ == "__main__":
    pytest.main([__file__, "-x", "-q"])


class TestMonitoring:

    def test_check_alive_and_dump(self, tmp_path):
        from alpa_tpu.device_mesh import LocalPhysicalDeviceMesh
        from alpa_tpu.monitoring import check_alive, dump_debug_info

        mesh = LocalPhysicalDeviceMesh()
        assert check_alive(mesh)

        state, batch = create_mlp_train_state_and_batch()
        step = get_mlp_train_step(DataParallel(), use_value_and_grad=True)
        step(state, batch)
        d = str(tmp_path / "dump")
        dump_debug_info(step.get_last_executable(), d)
        assert (tmp_path / "dump" / "compiled_hlo.txt").exists()
