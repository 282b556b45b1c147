"""A platform choice whose TPU branch is a kernel, on a mesh of more than
one device: the planner plans and binds its default branch
(``shard_parallel/kernel_choice.py``)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from alpa_tpu.device_mesh import LocalPhysicalDeviceMesh
from alpa_tpu.model.gpt_model import GPTConfig, TransformerBlock
from alpa_tpu.ops import flash_attention as fa
from alpa_tpu.shard_parallel import kernel_choice
from alpa_tpu.shard_parallel.auto_sharding import AutoShardingOption
from alpa_tpu.shard_parallel.ilp import solution_cost
from alpa_tpu.shard_parallel.solver import plan_auto_sharding
from alpa_tpu.telemetry import metrics as tmetrics
from alpa_tpu.testing import init_params


BATCH, SEQ, HEADS, DIM = 4, 512, 4, 64


def _block_grad(remat=True):
    """The flat ``value_and_grad`` of one block whose attention fits the
    kernels, and its arguments' avals."""
    cfg = GPTConfig(hidden_size=HEADS * DIM, num_heads=HEADS, num_layers=1,
                    seq_len=SEQ, vocab_size=128, dtype=jnp.bfloat16)
    block = TransformerBlock(cfg)
    x = jax.ShapeDtypeStruct((BATCH, SEQ, HEADS * DIM), jnp.bfloat16)
    params = jax.eval_shape(lambda: block.init(
        jax.random.PRNGKey(0), jnp.zeros(x.shape, x.dtype)))
    flat, tree = jax.tree_util.tree_flatten((params, x))

    def flat_fn(*leaves):
        p, xx = jax.tree_util.tree_unflatten(tree, leaves)
        apply = lambda p, xx: block.apply(p, xx)[0]   # noqa: E731
        if remat:
            apply = jax.checkpoint(apply)
        loss, grads = jax.value_and_grad(
            lambda p: apply(p, xx).astype(jnp.float32).sum())(p)
        return [loss] + jax.tree_util.tree_leaves(grads)

    return flat_fn, [jax.ShapeDtypeStruct(a.shape, a.dtype) for a in flat]


def _primitives(jaxpr, found=None):
    found = set() if found is None else found
    for eqn in jaxpr.eqns:
        found.add(eqn.primitive.name)
        for value in eqn.params.values():
            for sub in (value if isinstance(value, (tuple, list))
                        else [value]):
                inner = getattr(sub, "jaxpr", sub)
                if hasattr(inner, "eqns"):
                    _primitives(inner, found)
    return found


def _plan(fun, avals, devices):
    mesh = LocalPhysicalDeviceMesh(jax.devices()[:devices])
    batch_idx = [i for i, a in enumerate(avals)
                 if a.shape == (BATCH, SEQ, HEADS * DIM)]
    _, in_shardings, _, shape, (graph, choice) = plan_auto_sharding(
        kernel_choice.for_mesh_of(devices, fun), avals, [""] * len(avals),
        batch_idx, mesh, AutoShardingOption(), return_graph=True)
    return dict(
        shape=shape, cost=solution_cost(graph, choice),
        labels=[n.label for n in graph.nodes],
        strategies=[n.strategies[s].name
                    for n, s in zip(graph.nodes, choice)],
        specs=[str(s.spec) for s in in_shardings],
        primitives={e.primitive.name for e in graph.flat_eqns})


def test_a_traced_block_holds_the_choice():
    fun, avals = _block_grad()
    found = _primitives(jax.make_jaxpr(fun)(*avals).jaxpr)
    assert {"platform_index", "cond", "pallas_call"} <= found


@pytest.mark.parametrize("remat", [True, False],
                         ids=["checkpoint", "plain"])
def test_on_two_devices_the_plan_is_the_reference_cores(monkeypatch, remat):
    """On a two-device logical mesh a block whose shapes fit the kernels
    is planned to the strategies and the cost it has with
    ``reference_attention`` traced in their place (the parent's program):
    no barrier for a ``cond``, no kernel in what is planned, the einsums
    of the attention among the planned equations."""
    from jax.interpreters import partial_eval as pe
    fun, avals = _block_grad(remat)
    chosen = _plan(fun, avals, 2)
    monkeypatch.setattr(fa, "fits", lambda q, k: False)
    closed = jax.make_jaxpr(_block_grad(remat)[0])(*avals)
    # less its dead equations, as the bound program is (each way of
    # tracing leaves dead equations of its own behind)
    live, _ = pe.dce_jaxpr(closed.jaxpr, [True] * len(closed.out_avals),
                           instantiate=True)
    reference = _plan(lambda *args: jax.core.eval_jaxpr(
        live, closed.consts, *args), avals, 2)
    assert "pallas_call" not in reference["primitives"]
    assert "barrier:cond" not in chosen["labels"]
    assert not {"cond", "pallas_call"} & chosen["primitives"]
    # the platform's index and what the kernels' branch would have saved
    # are dead once the default is bound, and go
    assert chosen["primitives"] == reference["primitives"]
    assert chosen["labels"] == reference["labels"]
    assert chosen["strategies"] == reference["strategies"]
    assert chosen["shape"] == reference["shape"]
    assert chosen["specs"] == reference["specs"]
    assert chosen["cost"] == pytest.approx(reference["cost"], rel=1e-9)


def test_on_one_device_the_choice_stays():
    fun, avals = _block_grad()
    assert kernel_choice.for_mesh_of(1, fun) is fun
    planned = _plan(fun, avals, 1)
    assert "cond" in planned["primitives"]
    assert "barrier:cond" in planned["labels"]


def test_a_program_without_a_choice_is_traced_as_it_was():
    calls = []

    def fun(x):
        calls.append(1)
        return [jnp.sin(x) * 2]

    x = jax.ShapeDtypeStruct((8,), jnp.float32)
    closed = jax.make_jaxpr(fun)(x)
    assert kernel_choice.bind_defaults(closed) is closed
    planned = kernel_choice.for_mesh_of(2, fun)
    assert str(jax.make_jaxpr(planned)(x)) == str(closed)


def test_the_bound_default_computes_the_choices_result():
    """Values and gradients of a choice bound to its default are the
    default's: what the forward default saves reaches the backward
    default."""
    def chosen(x, w):
        return jax.lax.platform_dependent(
            x, w, tpu=lambda x, w: fa.flash_attention(x, x, x) * w,
            default=lambda x, w: jnp.tanh(x) * w)

    def flat(x, w):
        loss, grads = jax.value_and_grad(
            lambda x, w: jax.jit(chosen)(x, w).sum(), argnums=(0, 1))(x, w)
        return [loss, *grads]

    x = jnp.linspace(-1.0, 1.0, 2 * 512 * 2 * 64).reshape(2, 512, 2, 64)
    w = jnp.float32(3.0)
    closed = jax.make_jaxpr(flat)(x, w)
    assert "pallas_call" in _primitives(closed.jaxpr)
    bound = kernel_choice.bind_defaults(closed)
    assert not {"cond", "pallas_call"} & _primitives(bound.jaxpr)
    got = jax.core.eval_jaxpr(bound.jaxpr, bound.consts, x, w)
    want = jax.value_and_grad(lambda x, w: (jnp.tanh(x) * w).sum(),
                              argnums=(0, 1))(x, w)
    for a, b in zip(got, jax.tree_util.tree_leaves(want)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-6)


def test_the_gauge_says_what_the_planner_bound():
    gauge = tmetrics.get_registry().gauge(
        "alpa_attention_core", "", ("core", "heads", "head_dim", "seq"))
    fused = gauge.labels("fused", HEADS, DIM, SEQ)
    reference = gauge.labels("reference", HEADS, DIM, SEQ)
    fused.set(0)
    before = reference.value
    fun, avals = _block_grad()
    closed = jax.make_jaxpr(fun)(*avals)
    traced = fused.value
    assert traced >= 1 and reference.value == before
    kernel_choice.bind_defaults(closed)
    assert fused.value == 0 and reference.value == before + traced


def test_pipeshard_stages_on_two_devices_run_the_reference_core():
    """A pipeshard step whose layers fit the kernels, two stages of two
    devices each: every stage's program is planned with
    ``reference_attention`` bound (the gauge says so), and the step's loss
    is the serial program's."""
    import optax
    from flax.training import train_state

    import alpa_tpu
    from alpa_tpu.model.gpt_model import GPTModel
    from alpa_tpu.model.model_util import gpt_lm_loss
    from alpa_tpu.pipeline_parallel.layer_construction import \
        ManualLayerOption
    from alpa_tpu.pipeline_parallel.stage_construction import \
        UniformStageOption
    cfg = GPTConfig(vocab_size=128, hidden_size=HEADS * DIM, num_layers=2,
                    num_heads=HEADS, seq_len=SEQ, remat_blocks=True,
                    pipeline_boundary_every=1)
    model = GPTModel(cfg)
    ids = jax.random.randint(jax.random.PRNGKey(1), (BATCH, SEQ), 0, 128)
    batch = {"input_ids": ids, "labels": jnp.roll(ids, -1, axis=1)}
    params = init_params(model, jax.random.PRNGKey(0), ids)
    state = train_state.TrainState.create(apply_fn=model.apply,
                                          params=params, tx=optax.sgd(0.1))
    gauge = tmetrics.get_registry().gauge(
        "alpa_attention_core", "", ("core", "heads", "head_dim", "seq"))
    fused = gauge.labels("fused", HEADS, DIM, SEQ)
    reference = gauge.labels("reference", HEADS, DIM, SEQ)
    serial = float(gpt_lm_loss(model.apply, params, batch))
    fused.set(0)
    before = reference.value

    alpa_tpu.init(cluster="local", devices=jax.devices()[:4])
    try:
        @alpa_tpu.parallelize(
            method=alpa_tpu.PipeshardParallel(
                num_micro_batches=2, pipeline_schedule="1f1b",
                layer_option=ManualLayerOption(),
                stage_option=UniformStageOption(num_stages=2)),
            donate_argnums=())
        def train_step(state, batch):
            loss, grads = alpa_tpu.value_and_grad(
                lambda p: gpt_lm_loss(state.apply_fn, p, batch))(
                    state.params)
            return state.apply_gradients(grads=grads), loss

        _, loss = train_step(state, batch)
        executable = train_step.get_last_executable()
        assert {s.jax_mesh.devices.size
                for s in executable.stage_execs} == {2}
        assert float(loss) == pytest.approx(serial, rel=1e-5)
    finally:
        alpa_tpu.shutdown()
    assert fused.value == 0 and reference.value > before
