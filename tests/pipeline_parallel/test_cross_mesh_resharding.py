"""Cross-mesh resharding planner tests
(ref tests/pipeline_parallel/test_cross_mesh_resharding.py:30-120)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from alpa_tpu.pipeline_parallel.cross_mesh_resharding import (
    ReshardingTask, Tile, VirtualDistributedArray, plan_resharding)


def _mesh(n, names=("x",), shape=None):
    devs = np.array(jax.devices()[:n])
    if shape:
        devs = devs.reshape(shape)
    return Mesh(devs, names)


class TestTileMath:

    def test_intersect(self):
        a = Tile(((0, 4), (0, 8)))
        b = Tile(((2, 6), (4, 12)))
        c = a.intersect(b)
        assert c.slices == ((2, 4), (4, 8))
        assert c.size == 8
        assert a.intersect(Tile(((4, 8), (0, 8)))) is None

    def test_vda_from_sharding(self):
        mesh = _mesh(4)
        s = NamedSharding(mesh, P("x"))
        vda = VirtualDistributedArray.from_sharding((8, 4), s)
        assert len(vda.device_tiles) == 4
        # tiles partition the rows
        rows = sorted(t.slices[0] for t in vda.device_tiles)
        assert rows == [(0, 2), (2, 4), (4, 6), (6, 8)]

    def test_vda_replicated(self):
        mesh = _mesh(4)
        s = NamedSharding(mesh, P())
        vda = VirtualDistributedArray.from_sharding((8,), s)
        uniq = vda.unique_tiles
        assert len(uniq) == 1
        assert len(next(iter(uniq.values()))) == 4


class TestPlanning:

    def test_plan_covers_destination(self):
        src_mesh = _mesh(4)
        dst_mesh = Mesh(np.array(jax.devices()[4:8]), ("y",))
        src = NamedSharding(src_mesh, P("x"))        # row sharded 4-way
        dst = NamedSharding(dst_mesh, P(None, "y"))  # col sharded 4-way
        spec = plan_resharding((8, 8), 4, src, dst,
                               allow_allgather_rewrite=False)
        # every dst tile fully covered
        for req in spec.requests:
            covered = sum(s.tile.size for s in req.srcs)
            assert covered == req.dst_tile.size
        # row x col intersection: 4 pieces per destination tile
        assert spec.total_tiles() == 16

    def test_load_balanced_sources(self):
        """Replicated source: transfers spread across source shards."""
        src_mesh = _mesh(4)
        dst_mesh = Mesh(np.array(jax.devices()[4:8]), ("y",))
        src = NamedSharding(src_mesh, P())       # replicated on 4
        dst = NamedSharding(dst_mesh, P("y"))
        spec = plan_resharding((8, 8), 4, src, dst,
                               allow_allgather_rewrite=False)
        used_srcs = {s.src_shard_index for r in spec.requests
                     for s in r.srcs}
        assert len(used_srcs) >= 2, "all transfers pinned to one source"

    def test_allgather_rewrite_reduces_bytes(self):
        """dst replicated -> rewrite sends 1/k slices + intra-mesh gather
        (MLSys'23 local-allgather optimization)."""
        src_mesh = _mesh(4)
        dst_mesh = Mesh(np.array(jax.devices()[4:8]), ("y",))
        src = NamedSharding(src_mesh, P("x"))
        dst = NamedSharding(dst_mesh, P())       # fully replicated dst
        naive = plan_resharding((8, 8), 4, src, dst,
                               allow_allgather_rewrite=False)
        smart = plan_resharding((8, 8), 4, src, dst,
                               allow_allgather_rewrite=True)
        assert smart.allgather_rewrite
        assert smart.transfer_bytes < naive.transfer_bytes
        # ideal: k-fold reduction (k = 4 replicas)
        assert smart.transfer_bytes * 4 <= naive.transfer_bytes + 1e-6

    def test_execution_matches_device_put(self):
        src_mesh = _mesh(4)
        dst_mesh = Mesh(np.array(jax.devices()[4:8]).reshape(2, 2),
                        ("a", "b"))
        src = NamedSharding(src_mesh, P("x"))
        dst = NamedSharding(dst_mesh, P("b", "a"))
        x = jax.device_put(jnp.arange(64.0).reshape(8, 8), src)
        spec = plan_resharding((8, 8), 4, src, dst)
        task = ReshardingTask(spec, dst)
        y = task.run(x)
        np.testing.assert_array_equal(np.asarray(y), np.asarray(x))
        assert y.sharding.is_equivalent_to(dst, 2)


class TestPlannedExecution:
    """The executor drives the plan literally: executed cross-mesh bytes
    must equal the spec's accounting (VERDICT r1 next#5; ref
    SymbolicReshardingTask :418 send/recv + :935 broadcast)."""

    def _src_dst(self):
        src_mesh = _mesh(4)
        dst_mesh = Mesh(np.array(jax.devices()[4:8]), ("y",))
        return src_mesh, dst_mesh

    def test_tiled_bytes_match_plan(self):
        src_mesh, dst_mesh = self._src_dst()
        src = NamedSharding(src_mesh, P("x"))        # rows 4-way
        dst = NamedSharding(dst_mesh, P(None, "y"))  # cols 4-way
        x = jax.device_put(jnp.arange(64.0, dtype=jnp.float32)
                           .reshape(8, 8), src)
        spec = plan_resharding((8, 8), x.dtype.itemsize, src, dst,
                               allow_allgather_rewrite=False)
        task = ReshardingTask(spec, dst)
        y = task.run(x, mode="tiled")
        np.testing.assert_array_equal(np.asarray(y), np.asarray(x))
        assert y.sharding.is_equivalent_to(dst, 2)
        assert task.last_report.cross_mesh_bytes == spec.transfer_bytes
        assert task.last_report.intra_mesh_bytes == 0

    def test_multiprocess_wire_bytes_accounting(self):
        """run_multiprocess packs tiles in a widened psum work dtype;
        wire_bytes must reflect that (2x planned for bf16), while
        cross_mesh_bytes stays planned-payload bytes (ADVICE r3)."""
        src_mesh, dst_mesh = self._src_dst()
        src = NamedSharding(src_mesh, P("x"))
        dst = NamedSharding(dst_mesh, P(None, "y"))
        x = jax.device_put(jnp.arange(64.0, dtype=jnp.bfloat16)
                           .reshape(8, 8), src)
        spec = plan_resharding((8, 8), x.dtype.itemsize, src, dst,
                               allow_allgather_rewrite=False)
        task = ReshardingTask(spec, dst)
        y = task.run_multiprocess(x)
        np.testing.assert_array_equal(np.asarray(y, np.float32),
                                      np.arange(64.0).reshape(8, 8))
        rep = task.last_report
        assert rep.cross_mesh_bytes == spec.transfer_bytes
        assert rep.wire_bytes == 2 * rep.cross_mesh_bytes

    def test_allgather_rewrite_executes_fewer_cross_bytes(self):
        src_mesh, dst_mesh = self._src_dst()
        src = NamedSharding(src_mesh, P("x"))
        dst = NamedSharding(dst_mesh, P())   # fully replicated dst
        x = jax.device_put(jnp.arange(64.0, dtype=jnp.float32)
                           .reshape(8, 8), src)
        naive = plan_resharding((8, 8), 4, src, dst,
                                allow_allgather_rewrite=False)
        smart = plan_resharding((8, 8), 4, src, dst,
                                allow_allgather_rewrite=True)
        t_naive = ReshardingTask(naive, dst)
        y1 = t_naive.run(x, mode="tiled")
        t_smart = ReshardingTask(smart, dst)
        y2 = t_smart.run(x, mode="tiled")
        np.testing.assert_array_equal(np.asarray(y1), np.asarray(x))
        np.testing.assert_array_equal(np.asarray(y2), np.asarray(x))
        # executed bytes == planned bytes in both modes; the rewrite's
        # cross-mesh leg is k=4x smaller, paid for by intra-mesh gather
        assert t_naive.last_report.cross_mesh_bytes == naive.transfer_bytes
        assert t_smart.last_report.cross_mesh_bytes == smart.transfer_bytes
        assert (t_smart.last_report.cross_mesh_bytes * 4
                <= t_naive.last_report.cross_mesh_bytes + 1e-6)
        assert t_smart.last_report.intra_mesh_bytes > 0

    def test_broadcast_mode_unique_tiles_cross_once(self):
        src_mesh, dst_mesh = self._src_dst()
        src = NamedSharding(src_mesh, P("x"))
        dst = NamedSharding(dst_mesh, P())   # every dst device = full array
        x = jax.device_put(jnp.arange(64.0, dtype=jnp.float32)
                           .reshape(8, 8), src)
        spec = plan_resharding((8, 8), 4, src, dst,
                               allow_allgather_rewrite=False)
        task = ReshardingTask(spec, dst)
        y = task.run(x, mode="broadcast")
        np.testing.assert_array_equal(np.asarray(y), np.asarray(x))
        # the full array crosses exactly once (256 B), not once per replica
        assert task.last_report.cross_mesh_bytes == 8 * 8 * 4
        assert task.last_report.intra_mesh_bytes > 0

    def test_scalar_transfer_accounted(self):
        """0-d arrays (e.g. the loss) go through the planned path too —
        executed bytes must match the plan, not silently report zero."""
        src_mesh, dst_mesh = self._src_dst()
        src = NamedSharding(src_mesh, P())
        dst = NamedSharding(dst_mesh, P())
        x = jax.device_put(jnp.float32(3.25), src)
        spec = plan_resharding((), 4, src, dst)
        task = ReshardingTask(spec, dst)
        y = task.run(x, mode="tiled")
        assert float(y) == 3.25
        assert task.last_report.cross_mesh_bytes == spec.transfer_bytes
        assert task.last_report.mode == "tiled"

    def test_permuted_2d_dst_tiled(self):
        src_mesh = _mesh(4, shape=(2, 2), names=("a", "b"))
        dst_mesh = Mesh(np.array(jax.devices()[4:8]).reshape(2, 2),
                        ("c", "d"))
        src = NamedSharding(src_mesh, P("a", "b"))
        dst = NamedSharding(dst_mesh, P("d", None))
        x = jax.device_put(jnp.arange(96.0, dtype=jnp.float32)
                           .reshape(8, 12), src)
        spec = plan_resharding((8, 12), 4, src, dst)
        task = ReshardingTask(spec, dst)
        y = task.run(x, mode="tiled")
        np.testing.assert_array_equal(np.asarray(y), np.asarray(x))
        assert task.last_report.cross_mesh_bytes == spec.transfer_bytes


class TestPipeshardPlannedExecution:
    """End-to-end: a pipelined step under resharding_execution='planned'
    matches the device_put fast path bit-for-bit and reports executed
    bytes (SURVEY §4 strategy 5)."""

    @pytest.mark.parametrize("mode", ["send_recv", "broadcast"])
    def test_pipeshard_numerics_and_accounting(self, mode):
        import alpa_tpu
        from alpa_tpu import PipeshardParallel
        from alpa_tpu.global_env import global_config
        from alpa_tpu.pipeline_parallel.layer_construction import (
            ManualLayerOption)
        from alpa_tpu.pipeline_parallel.stage_construction import (
            UniformStageOption)
        from alpa_tpu.testing import (assert_allclose,
                                      create_mlp_train_state_and_batch,
                                      get_mlp_train_step)

        alpa_tpu.init(cluster="local")
        method = PipeshardParallel(
            num_micro_batches=2, layer_option=ManualLayerOption(),
            stage_option=UniformStageOption(num_stages=2))
        state_p, batch = create_mlp_train_state_and_batch(
            batch_size=64, num_layers=4, manual_pipeline_layer=True)
        state_s, _ = create_mlp_train_state_and_batch(
            batch_size=64, num_layers=4, manual_pipeline_layer=True)

        old_exec = global_config.resharding_execution
        old_mode = global_config.resharding_mode
        global_config.resharding_execution = "planned"
        global_config.resharding_mode = mode
        try:
            pstep = get_mlp_train_step(method, use_value_and_grad=True)
            serial = get_mlp_train_step(None)
            state_p, loss_p = pstep(state_p, batch)
            state_s, loss_s = serial(state_s, batch)
            ex = pstep.get_last_executable()
            report = ex.get_resharding_report()
        finally:
            global_config.resharding_execution = old_exec
            global_config.resharding_mode = old_mode
        assert_allclose(float(loss_s), float(loss_p), 2e-3, 2e-3)
        assert_allclose(jax.device_get(state_s.params),
                        jax.device_get(state_p.params), 2e-3, 2e-3)
        if ex._resharding_bytes:
            assert ex._executed_resharding_bytes > 0
            assert "executed" in report


class TestLinkAccounting:
    """Byte-accounting audit + broadcast load balancing (ISSUE 4).

    One fully pinned scenario — rows sharded 4-way (devices 0-3) to
    fully replicated on a second 4-device mesh (devices 4-7), shape
    (8, 8) f32, allgather rewrite off so S = 8*8*4 = 256 B:

    * send_recv accounting counts once PER REPLICA: 4S = 1024 B;
    * broadcast accounting counts each unique tile ONCE: S = 256 B
      (the pre-audit report multiplied broadcast bytes by the
      replication factor);
    * naive broadcast routing lands all 4 unique 64 B tiles on the
      replica group's first holder (ingress 256 B); balanced routing
      spreads them, 64 B per member — a 4x max-link reduction.
    """

    S = 8 * 8 * 4          # full-array payload bytes

    def _spec(self):
        src_mesh = _mesh(4)
        dst_mesh = Mesh(np.array(jax.devices()[4:8]), ("y",))
        src = NamedSharding(src_mesh, P("x"))   # rows 4-way
        dst = NamedSharding(dst_mesh, P())      # replicated x4
        spec = plan_resharding((8, 8), 4, src, dst,
                               allow_allgather_rewrite=False)
        return spec, src, dst

    def test_pinned_send_recv_vs_broadcast_totals(self):
        from alpa_tpu.pipeline_parallel.cross_mesh_resharding import (
            naive_transfer_bytes)
        spec, _, dst = self._spec()
        # send_recv: every replica fetches the full array
        assert spec.transfer_bytes == 4 * self.S == 1024
        assert naive_transfer_bytes((8, 8), 4, dst,
                                    mode="send_recv") == 4 * self.S
        # broadcast: the unique destination tile crosses exactly once
        assert spec.broadcast_bytes == self.S == 256
        assert naive_transfer_bytes((8, 8), 4, dst,
                                    mode="broadcast") == self.S

    def test_pinned_broadcast_max_link_balanced_vs_naive(self):
        from alpa_tpu.pipeline_parallel.cross_mesh_resharding import (
            compute_link_loads)
        spec, _, _ = self._spec()
        # naive: all 4 unique 64 B tiles converge on the first holder
        assert spec.max_link_bytes_broadcast_naive == self.S == 256
        # balanced: one tile per member; every link carries 64 B
        assert spec.max_link_bytes_broadcast == self.S / 4 == 64
        loads = compute_link_loads(spec, broadcast=True, loadbalance=True)
        assert set(loads["ingress"].values()) == {64.0}
        assert set(loads["egress"].values()) == {64.0}
        nloads = compute_link_loads(spec, broadcast=True,
                                    loadbalance=False)
        assert max(nloads["ingress"].values()) == 256.0

    def test_pinned_send_recv_max_link(self):
        spec, _, _ = self._spec()
        # each src row shard feeds all 4 replicas (4 * 64 B egress);
        # each dst replica ingests the full array (256 B) — balancing
        # cannot help: every piece has exactly one holder and one taker
        assert spec.max_link_bytes == self.S == 256
        assert spec.max_link_bytes_naive == self.S

    def test_send_order_interleaves_sources(self):
        spec, _, _ = self._spec()
        order = spec.send_order
        all_moves = {(ri, si) for ri, req in enumerate(spec.requests)
                     for si in range(len(req.srcs))}
        assert set(order) == all_moves and len(order) == len(all_moves)
        # greedy least-issued-egress: the first 4 moves come from 4
        # DISTINCT source devices (plan order would drain one request —
        # all 4 of its pieces — before touching the next)
        first_devs = [
            spec.src_device_ids[
                spec.requests[ri].srcs[si].src_shard_index]
            for ri, si in order[:4]]
        assert len(set(first_devs)) == 4

    def test_executed_report_matches_planned_max_link(self):
        from alpa_tpu.pipeline_parallel.cross_mesh_resharding import (
            compute_link_loads)
        spec, src, dst = self._spec()
        x = jax.device_put(jnp.arange(64.0, dtype=jnp.float32)
                           .reshape(8, 8), src)
        task = ReshardingTask(spec, dst)
        y = task.run(x, mode="tiled")
        np.testing.assert_array_equal(np.asarray(y), np.asarray(x))
        loads = compute_link_loads(spec, broadcast=False)
        assert task.last_report.max_link_bytes == loads["max_link_bytes"]

    def test_pinned_slice_all_gather_strategy_stats(self):
        """Collective lowering (ISSUE 7): for the same pinned 4+4 plan
        the ``slice_all_gather`` wire leg must move the whole array
        exactly once — at most the pinned 256 B broadcast figure — with
        one 64 B message per link; ``direct_p2p`` pays 4 messages and
        256 B on the busiest link.  A selection or link-stats regression
        fails here."""
        spec, _, _ = self._spec()
        stats = spec.strategy_stats
        assert {"direct_p2p", "slice_all_gather"} <= set(stats)
        sag = stats["slice_all_gather"]
        assert sag["total_bytes"] == self.S == 256     # ≤ broadcast 256
        assert sag["max_link_bytes"] == self.S / 4 == 64
        assert sag["max_link_messages"] == 1
        direct = stats["direct_p2p"]
        assert direct["total_bytes"] == 4 * self.S == 1024
        assert direct["max_link_bytes"] == self.S == 256
        assert direct["max_link_messages"] == 4
        # default knobs: direct would go through the host on this edge
        # (sharded -> replicated).  The meshes' axes have other names
        # ("x", "y"), so no aligned_relayout is offered; the scattered
        # landing is the one candidate whose wire leg is 1:1
        assert "aligned_relayout" not in stats
        assert spec.strategy == "slice_all_gather"
        assert set(spec.strategy_costs) == set(stats)

    def test_planner_counters_accumulate(self):
        from alpa_tpu.pipeline_parallel.cross_mesh_resharding import (
            get_planner_stats, reset_planner_stats)
        reset_planner_stats()
        try:
            self._spec()
            st = get_planner_stats()
            assert st["plans"] == 1
            assert st["total_bytes"] == 4 * self.S
            assert st["broadcast_bytes"] == self.S
            assert st["max_link_bytes"] == self.S        # send_recv link
            assert st["max_link_bytes_naive"] == self.S
        finally:
            reset_planner_stats()


if __name__ == "__main__":
    pytest.main([__file__, "-x", "-q"])
