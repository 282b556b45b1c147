"""Utility unit tests (ref tests/util/: OrderedSet, cost model, flops)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from alpa_tpu.device_mesh import LogicalDeviceMesh
from alpa_tpu.util import (OrderedSet, compute_gpt_parameter_count,
                           compute_gpt_tflops, count_communication_primitives,
                           divide_evenly, jaxpr_eqn_flops, split_list)


class TestOrderedSet:

    def test_order_preserved(self):
        s = OrderedSet([3, 1, 2])
        s.add(1)
        s.add(5)
        assert list(s) == [3, 1, 2, 5]

    def test_set_ops(self):
        a = OrderedSet([1, 2, 3])
        b = OrderedSet([2, 3, 4])
        assert list(a | b) == [1, 2, 3, 4]
        assert list(a & b) == [2, 3]
        assert list(a - b) == [1]
        assert a == {1, 2, 3}
        a.discard(99)  # no error
        a.remove(1)
        assert 1 not in a

    def test_pop_fifo(self):
        s = OrderedSet([7, 8, 9])
        assert s.pop() == 7
        assert len(s) == 2


class TestCostModel:

    def test_collective_costs_scale_with_axis(self):
        lm = LogicalDeviceMesh(None, np.arange(8).reshape(4, 2),
                               mesh_beta=(0.1, 0.01))
        # bigger axis, bigger beta -> bigger cost
        assert lm.all_reduce_cost(1 << 20, 0) > lm.all_reduce_cost(
            1 << 20, 1)
        # single-element axis is free
        lm2 = LogicalDeviceMesh(None, np.arange(4).reshape(4, 1))
        assert lm2.all_gather_cost(1 << 20, 1) == 0.0
        # all-reduce ~ 2x all-gather bytes on a ring
        ar = lm.all_reduce_cost(1 << 24, 0)
        ag = lm.all_gather_cost(1 << 24, 0)
        assert 1.5 < ar / ag < 2.5

    def test_gpt_flops_accounting(self):
        n = compute_gpt_parameter_count(12, 768, 51200)
        assert 1.2e8 < n < 1.7e8  # ~GPT-125M
        tf = compute_gpt_tflops(8, 1024, 12, 768, 51200, 1, latency=0.1)
        assert tf > 0

    def test_eqn_flops_dot(self):
        cj = jax.make_jaxpr(lambda a, b: a @ b)(
            jnp.ones((64, 128)), jnp.ones((128, 32)))
        dot = [e for e in cj.jaxpr.eqns
               if e.primitive.name == "dot_general"][0]
        assert jaxpr_eqn_flops(dot) == 2 * 64 * 128 * 32


class TestHloCounting:

    def test_opcode_position_only(self):
        hlo = """
%ar = f32[8]{0} all-reduce(f32[8]{0} %p0), replica_groups={}
%use = f32[8]{0} add(f32[8]{0} %ar, f32[8]{0} %p0)
%ag.1 = (f32[4]{0}, f32[4]{0}) all-gather-start(f32[2]{0} %x)
%d = f32[4]{0} all-gather-done((f32[4]{0}, f32[4]{0}) %ag.1)
"""
        total, ar, ag, rs, a2a = count_communication_primitives(hlo)
        assert (total, ar, ag, rs, a2a) == (2, 1, 1, 0, 0)


class TestListHelpers:

    def test_split_and_divide(self):
        assert split_list([1, 2, 3, 4, 5], [2, 3]) == [[1, 2], [3, 4, 5]]
        assert divide_evenly(10, 3) == [4, 3, 3]


def test_init_params_builds_the_eager_parameters_bit_for_bit():
    """Why ``alpa_tpu.testing.init_params`` may stand wherever a test said
    ``model.init``: the OLMoE toy of ``tests/model/test_olmoe.py`` (norms,
    attention with q/k norms, a router and its experts, an untied head)
    built both ways is the same tree, leaf by leaf, dtypes too."""
    from alpa_tpu.model.gpt_model import GPTModel, config_from_hf
    from alpa_tpu.testing import init_params
    from chipbench import run
    toy = run.load_json(run.HERE, "configs", "toy-olmoe.json")
    model = GPTModel(config_from_hf(toy, dtype=jnp.float32))
    key = jax.random.PRNGKey(2)
    ids = jax.random.randint(jax.random.PRNGKey(0), (2, 32), 0,
                             toy["vocab_size"])
    eager = jax.tree_util.tree_leaves_with_path(model.init(key, ids))
    compiled = jax.tree_util.tree_leaves_with_path(
        init_params(model, key, ids))
    assert [path for path, _ in eager] == [path for path, _ in compiled]
    assert len(eager) == 23
    for (path, a), (_, b) in zip(eager, compiled):
        assert a.dtype == b.dtype and a.shape == b.shape, path
        np.testing.assert_array_equal(a, b, err_msg=str(path))


if __name__ == "__main__":
    pytest.main([__file__, "-x", "-q"])
