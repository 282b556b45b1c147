"""ZeRO weight-update sharding (ISSUE 10): path classification,
cost-modeled optimizer-state partitioning, and numeric equivalence.

Oracle: ZeRO-2/ZeRO-3 are pure *layout* changes — the updated
parameters must match the replicated data-parallel baseline (bitwise
where the partitioner reduces in the same order, else to a few units in
the last place of a leaf's largest entry); the memory-budgeted ILP must
pick sharded optimizer state on its own (chosen by cost, not forced).
"""
import numpy as np
import pytest

import alpa_tpu
from alpa_tpu.parallel_method import (DataParallel, ShardParallel,
                                      Zero2Parallel, Zero3Parallel)
from alpa_tpu.shard_parallel.auto_sharding import (
    AutoShardingOption, is_opt_state_path, is_param_path, path_components,
    resolved_zero_stage)
from alpa_tpu.testing import (create_mlp_train_state_and_batch,
                              get_mlp_train_step)


class TestPathClassification:
    """plan_rule_based used to match optimizer-state leaves by raw
    substring (``"nu" in path`` also hit ``num_*``); classification now
    matches path *components*."""

    def test_opt_state_paths(self):
        assert is_opt_state_path("[0].opt_state[0].mu['Dense_0']['kernel']")
        assert is_opt_state_path("[0].opt_state[0].nu['head']['bias']")
        assert is_opt_state_path(".opt_state.trace['Dense_0']['kernel']")
        assert is_opt_state_path(".mu['Dense_0']['kernel']")

    def test_adversarial_param_names_are_not_opt_state(self):
        # "nu" inside "num_embeddings"/"nu_head" and "trace" inside
        # "trace_proj" must NOT classify as optimizer state
        for path in (".params['num_embeddings']['kernel']",
                     ".params['nu_head']['kernel']",
                     ".params['trace_proj']['bias']",
                     ".params['momentum_encoder']['kernel']"):
            assert not is_opt_state_path(path), path
            assert is_param_path(path), path

    def test_mirror_tree_precedence(self):
        # optax moment trees mirror the params tree: a "params" component
        # under opt_state is still optimizer state
        p = "[0].opt_state[0].mu['params']['Dense_0']['kernel']"
        assert is_opt_state_path(p)
        assert not is_param_path(p)

    def test_path_components(self):
        assert path_components(".opt_state[0].mu['nu_head']") == \
            ("opt_state", "0", "mu", "nu_head")

    def test_resolved_zero_stage(self):
        assert resolved_zero_stage(AutoShardingOption(zero_stage="0")) == 0
        assert resolved_zero_stage(AutoShardingOption(zero_stage="2")) == 2
        assert resolved_zero_stage(AutoShardingOption(zero_stage="3")) == 3
        assert resolved_zero_stage(AutoShardingOption()) == -1
        # legacy flags force a stage under "auto"
        assert resolved_zero_stage(AutoShardingOption(
            prefer_reduce_scatter=True)) == 2
        assert resolved_zero_stage(AutoShardingOption(
            force_zero_stage_3=True)) == 3
        with pytest.raises(ValueError, match="zero_stage"):
            resolved_zero_stage(AutoShardingOption(zero_stage="1"))


def _train(method, n_steps=2, batch_size=16, hidden_dim=64):
    state, batch = create_mlp_train_state_and_batch(
        batch_size, hidden_dim=hidden_dim)
    step = get_mlp_train_step(method, use_value_and_grad=True)
    for _ in range(n_steps):
        state, loss = step(state, batch)
    return state, loss, step.get_last_executable()


def _sharded_input_count(ex):
    n = 0
    for sh, av in zip(ex.in_shardings, ex.in_avals):
        if av.shape and np.prod(sh.shard_shape(av.shape)) < \
                np.prod(av.shape):
            n += 1
    return n


def _is_partitioned(leaf):
    return np.prod(leaf.sharding.shard_shape(leaf.shape)) < \
        np.prod(leaf.shape)


def _assert_same_training(state_a, loss_a, state_b, loss_b, ulps=8):
    """Two layouts of one computation after ``_train``'s two steps.

    Bit equality cannot hold in general: once parameters or gradients
    are sharded, XLA's SPMD partitioner splits the reductions (the batch
    mean of the loss, the gradients' sums over the batch) over the
    devices in another order, and float32 addition is not associative.
    On this XLA:CPU the loss moves by up to 3 units in the last place
    and a parameter by up to 2.5 of its leaf's largest entry; what the
    design promises is the same update, so both are held to ``ulps``."""
    import jax
    la, lb = np.float32(loss_a), np.float32(loss_b)
    assert abs(la - lb) <= ulps * np.spacing(max(la, lb)), (la, lb)
    flat_a = jax.tree_util.tree_leaves_with_path(state_a.params)
    flat_b = jax.tree_util.tree_leaves(state_b.params)
    assert len(flat_a) == len(flat_b)
    for (path, a), b in zip(flat_a, flat_b):
        a, b = np.asarray(a), np.asarray(b)
        tol = ulps * np.spacing(np.abs(a).max())
        assert np.abs(a - b).max() <= tol, \
            (jax.tree_util.keystr(path), np.abs(a - b).max(), tol)


class TestZeroNumerics:
    """ZeRO stages vs replicated DP: the same update, sharded state."""

    def test_zero2_bit_exact_vs_dp(self):
        alpa_tpu.init("local")
        _, loss_dp, _ = _train(DataParallel())
        state2, loss_z2, ex2 = _train(Zero2Parallel())
        np.testing.assert_array_equal(np.asarray(loss_dp),
                                      np.asarray(loss_z2))
        # the optimizer-state leaves really are partitioned
        opt_leaf = state2.opt_state[0].trace["params"]["Dense_0"]["kernel"]
        assert _is_partitioned(opt_leaf)

    def test_zero3_same_update_as_dp(self):
        alpa_tpu.init("local")
        state_dp, loss_dp, _ = _train(DataParallel())
        state3, loss_z3, _ = _train(Zero3Parallel())
        _assert_same_training(state_dp, loss_dp, state3, loss_z3)
        # ZeRO-3 also shards the parameters; DP keeps them whole
        assert _is_partitioned(state3.params["params"]["Dense_0"]["kernel"])
        assert not _is_partitioned(
            state_dp.params["params"]["Dense_0"]["kernel"])

    def test_zero_stage_knob_forces_sharding(self):
        alpa_tpu.init("local")
        state0, loss0, ex0 = _train(ShardParallel(
            auto_sharding_option=AutoShardingOption(zero_stage="0")))
        state2, loss2, ex2 = _train(ShardParallel(
            auto_sharding_option=AutoShardingOption(zero_stage="2")))
        _assert_same_training(state0, loss0, state2, loss2)
        # the knob's point: stage 2 partitions the optimizer state,
        # stage 0 keeps it whole
        opt0 = state0.opt_state[0].trace["params"]["Dense_0"]["kernel"]
        opt2 = state2.opt_state[0].trace["params"]["Dense_0"]["kernel"]
        assert _is_partitioned(opt2) and not _is_partitioned(opt0)
        assert _sharded_input_count(ex2) > _sharded_input_count(ex0)
        # zero_stage is part of the parallel plan: resume validation
        # (checkpoint manager) must distinguish the two layouts
        assert ex0.get_plan_fingerprint() != ex2.get_plan_fingerprint()


class TestCostModeledChoice:
    """The tentpole claim: ZeRO-2 chosen BY COST under ``zero_stage=
    "auto"`` — a per-device memory budget that replicated optimizer
    state cannot satisfy flips the ILP to reduce-scatter-aware sharded
    strategies; a generous budget keeps replication (all-gather latency
    is charged, memory is not needed)."""

    def _state_bytes(self):
        import jax
        state, _ = create_mlp_train_state_and_batch(16, hidden_dim=64)
        return sum(
            np.prod(a.shape) * a.dtype.itemsize
            for a in jax.tree_util.tree_leaves(state)
            if hasattr(a, "shape") and a.shape)

    def test_budget_flips_ilp_to_sharded_opt_state(self):
        alpa_tpu.init("local")
        _, loss_g, ex_g = _train(ShardParallel(
            auto_sharding_option=AutoShardingOption()))
        tight = int(self._state_bytes() * 0.66)
        _, loss_t, ex_t = _train(ShardParallel(
            auto_sharding_option=AutoShardingOption(
                memory_budget_per_device=tight)))
        # same math, different layout
        np.testing.assert_array_equal(np.asarray(loss_g),
                                      np.asarray(loss_t))
        assert _sharded_input_count(ex_t) > _sharded_input_count(ex_g)
