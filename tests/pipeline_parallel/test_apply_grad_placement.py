"""Where the apply-grad equations run, and the train state with them
(ISSUE 25).

``partition_apply_grad`` places every equation of the optimizer update on
the mesh of the parameter it serves: forward from the placed gradients and
parameters, then backward from the readers for what has no placed input
(``decay * mu``).  So a moment is placed at launch on the mesh that also
writes its new value, and no leaf of the state but a tied table crosses
between meshes when a step starts.

Oracles: (a) after two steps of a two-stage Adam step with a tied
embedding every leaf sits on the device set its ``input_place`` names, the
launch-moved counter rises by one array a step, and each moment's
``input_place`` is its parameter's; (b) three steps equal, bit for bit,
the same step planned with the old forward-only rule; (c) the rule itself
on hand-built equations.
"""
import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest
from flax.training import train_state
from jax.extend.core import Var

import alpa_tpu
from alpa_tpu import PipeshardParallel
from alpa_tpu.pipeline_parallel import apply_grad
from alpa_tpu.pipeline_parallel.apply_grad import (
    apply_partition_is_acyclic, assign_apply_grad_meshes,
    partition_apply_grad)
from alpa_tpu.pipeline_parallel.layer_construction import ManualLayerOption
from alpa_tpu.pipeline_parallel.primitive_def import mark_pipeline_boundary
from alpa_tpu.pipeline_parallel.stage_construction import UniformStageOption
from alpa_tpu.telemetry.metrics import get_registry
from alpa_tpu.testing import init_params

MOVED_ARRAYS = "alpa_pipeshard_launch_moved_arrays_total"
MOVED_BYTES = "alpa_pipeshard_launch_moved_bytes_total"
RELAID_ARRAYS = "alpa_pipeshard_launch_relayout_arrays_total"


class TiedLM(nn.Module):
    """Two pipeline layers around one table: looked up in the first, the
    output projection of the second (GPT's tied ``wte`` at toy size)."""
    vocab: int = 64
    hidden: int = 16

    @nn.compact
    def __call__(self, ids):
        wte = self.param("wte", nn.initializers.normal(0.02),
                         (self.vocab, self.hidden))
        x = nn.relu(nn.Dense(self.hidden)(wte[ids]))
        mark_pipeline_boundary()
        x = nn.relu(nn.Dense(self.hidden)(x))
        return x @ wte.T


def tied_lm_state_and_batch(batch_size=8, seq_len=8, vocab=64, hidden=16,
                            tx=None):
    model = TiedLM(vocab=vocab, hidden=hidden)
    rng = jax.random.PRNGKey(0)
    ids = jax.random.randint(rng, (batch_size, seq_len), 0, vocab)
    labels = jax.random.randint(jax.random.PRNGKey(1),
                                (batch_size, seq_len), 0, vocab)
    state = train_state.TrainState.create(
        apply_fn=model.apply, params=init_params(model, rng, ids),
        tx=tx or optax.adam(1e-2))
    return state, {"ids": np.asarray(ids), "labels": np.asarray(labels)}


def tied_lm_train_step(num_micro_batches=2):
    """The Adam step of :class:`TiedLM` as two stages on two meshes."""

    def train_step(state, batch):

        def loss_fn(params):
            logits = state.apply_fn(params, batch["ids"])
            return optax.softmax_cross_entropy_with_integer_labels(
                logits, batch["labels"]).mean()

        loss, grads = alpa_tpu.value_and_grad(loss_fn)(state.params)
        return state.apply_gradients(grads=grads), loss

    method = PipeshardParallel(
        num_micro_batches=num_micro_batches,
        layer_option=ManualLayerOption(),
        stage_option=UniformStageOption(num_stages=2))
    return alpa_tpu.parallelize(train_step, method=method)


def _forward_only_assignment(apply_eqns, var_mesh):
    """The rule this PR replaced, kept here as the reference: an eqn goes
    to the mesh of its largest placed input, and to mesh 0 without one."""
    placed = dict(var_mesh)
    eqn_mesh = []
    for e in apply_eqns:
        sized = [(float(np.prod(v.aval.shape)), -i, placed[v])
                 for i, v in enumerate(e.invars)
                 if isinstance(v, Var) and v in placed]
        m = max(sized)[2] if sized else 0
        eqn_mesh.append(m)
        placed.update((v, m) for v in e.outvars)
    return eqn_mesh


def _device_set(x):
    return frozenset(d.id for d in x.sharding.device_set)


@pytest.fixture
def four_devices():
    alpa_tpu.init("local", devices=jax.devices()[:4])


# ---------------------------------------------------------------------
# (a) the state stays where the plan wants it
# ---------------------------------------------------------------------

def test_state_comes_back_on_the_mesh_that_reads_it(four_devices):
    step = tied_lm_train_step()
    state, batch = tied_lm_state_and_batch()
    reg = get_registry()
    relaid_before = reg.snapshot().get(RELAID_ARRAYS, 0)
    moved = []
    for _ in range(3):
        before = reg.snapshot()
        state, _ = step(state, batch)
        after = reg.snapshot()
        moved.append((after.get(MOVED_ARRAYS, 0) - before.get(MOVED_ARRAYS, 0),
                      after.get(MOVED_BYTES, 0) - before.get(MOVED_BYTES, 0)))
    assert reg.snapshot().get(RELAID_ARRAYS, 0) == relaid_before
    ex = step.get_last_executable()
    wte_bytes = 64 * 16 * 4
    # the first launch places what the caller hands over and counts
    # nothing; every later one carries the tied table to its second mesh
    assert moved == [(0, 0), (1, wte_bytes), (1, wte_bytes)]

    mesh_devices = [frozenset(d.id for d in m.flat_devices)
                    for m in ex.mesh_group.meshes]
    assert mesh_devices[0].isdisjoint(mesh_devices[1])
    leaves = dict(zip(ex.global_invars, jax.tree_util.tree_leaves(state)))
    mesh_of_path = {}
    n_checked = 0
    for v, places in ex.input_place.items():
        path = ex.invar_paths[v]
        mesh_of_path[path] = [m for m, _ in places]
        if not path.startswith("[0]"):      # the batch
            continue
        if path.endswith("['wte']") and ".params" in path:
            assert mesh_of_path[path] == [0, 1]
            continue
        assert len(places) == 1, path
        assert _device_set(leaves[v]) == mesh_devices[places[0][0]], path
        n_checked += 1
    assert n_checked == 2 + 3 * 4 + 2    # step, count; mu, nu, param

    # every moment is placed where its parameter is (the tied table's
    # where its gradient is summed: the first mesh of the two)
    n_moments = 0
    for path, meshes in mesh_of_path.items():
        for moment in (".mu", ".nu"):
            tag = f".opt_state[0]{moment}"
            if tag in path:
                param = path.replace(tag, ".params")
                assert meshes == mesh_of_path[param][:1], path
                n_moments += 1
    assert n_moments == 10
    assert mesh_of_path["[0].opt_state[0].mu['params']['Dense_1']"
                        "['kernel']"] == [1]


def test_new_state_leaves_are_written_as_the_old_ones_are_read(
        four_devices):
    """The program that writes a state leaf's new value pins it to the
    sharding the next launch wants the leaf with, so the launch has
    nothing to re-lay out (on the chip, 16 moments that came back sharded
    where they are read replicated cost 0.9 s a step through the host)."""
    step = tied_lm_train_step()
    state, batch = tied_lm_state_and_batch()
    step(state, batch)
    ex = step.get_last_executable()
    n_state = len(jax.tree_util.tree_leaves(state))
    n_pinned = 0
    for old, new in zip(ex.global_invars[:n_state],
                        ex.global_outvars[:n_state]):
        writer = next(e for e in ex.apply_execs if new in e.outvars)
        wanted = dict(ex.input_place[old])[writer.mesh_id]
        assert writer.pinned_out[new] is wanted
        made = writer.out_shardings[writer.outvars.index(new)]
        assert made.is_equivalent_to(wanted, len(old.aval.shape))
        n_pinned += 1
    assert n_pinned == n_state == 17


def test_unification_pins_an_aliased_output_to_its_readers_sharding():
    """``_unify_same_mesh_shardings`` on stand-ins: an output named as an
    alias of an input adopts the first same-mesh reader's sharding; one on
    another mesh, or with no alias, is left to the compiler."""
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from alpa_tpu.pipeline_parallel.pipeshard_executable import (
        _unify_same_mesh_shardings)

    class Stub:

        def __init__(self, mesh_id, invars, in_shardings, outvars):
            self.mesh_id, self.invars, self.outvars = mesh_id, invars, outvars
            self.in_shardings = list(in_shardings)
            self.planned_in = list(in_shardings)
            self.planned_out = [None] * len(outvars)
            self.pinned_out = {}

        def donated_out_shardings(self):
            return {}

    mesh = Mesh(np.array(jax.devices()[:2]), ("x",))
    replicated, split = NamedSharding(mesh, P()), NamedSharding(mesh, P("x"))
    jaxpr = jax.make_jaxpr(lambda a, b: (a * 2.0, b * 2.0, a + b))(
        F8, F8).jaxpr
    (mu, nu), (new_mu, new_nu, other) = jaxpr.invars, jaxpr.outvars
    reader = Stub(1, [mu, nu], [replicated, split], [])
    writer = Stub(1, [mu, nu], [split, replicated], [new_mu, new_nu, other])
    elsewhere = Stub(0, [], [], [new_mu])
    _unify_same_mesh_shardings([reader, writer, elsewhere],
                               {new_mu: mu, new_nu: nu})
    assert writer.in_shardings == [replicated, split]   # first reader wins
    assert writer.pinned_out == {new_mu: replicated, new_nu: split}
    assert elsewhere.pinned_out == {}


# ---------------------------------------------------------------------
# (b) the same numbers as under the forward-only rule
# ---------------------------------------------------------------------

def _three_steps(tx):
    step = tied_lm_train_step()
    state, batch = tied_lm_state_and_batch(tx=tx)
    values = []
    for _ in range(3):
        state, loss = step(state, batch)
        values.append([np.asarray(x) for x in
                       jax.tree_util.tree_leaves((loss, state))])
    ex = step.get_last_executable()
    on_mesh_1 = sorted(ex.invar_paths[v] for v, places in
                       ex.input_place.items() if places[0][0] == 1)
    return values, on_mesh_1


@pytest.mark.parametrize("tx, n_leaves, bitwise_steps", [
    (optax.sgd(1e-2, momentum=0.5), 12, 3),
    (optax.adam(1e-2), 18, 1),
], ids=["momentum", "adam"])
def test_three_steps_equal_to_forward_only_rule(four_devices, monkeypatch,
                                                tx, n_leaves, bitwise_steps):
    """Same eqns, same order inside each mesh's program, same dtypes: only
    the mesh of ``decay * moment`` differs.  So loss, parameters and
    moments are equal bit for bit wherever the compiler has no choice:
    with a momentum of 0.5, whose product is exact, in all three steps;
    with Adam in the first, whose moments are still zero.  From Adam's
    second step on XLA:CPU rounds ``g * g * (1 - b2) + b2 * nu`` fewer
    times where both halves sit in one program than where ``b2 * nu``
    arrived rounded from the other mesh, and the last bits of some
    entries differ: a few parts in 1e7 of the leaf's largest entry."""
    values, on_mesh_1 = _three_steps(tx)
    monkeypatch.setattr(apply_grad, "assign_apply_grad_meshes",
                        _forward_only_assignment)
    old_values, old_on_mesh_1 = _three_steps(tx)
    # the reference did plan the other placement: no moment on mesh 1
    assert any(".opt_state" in p for p in on_mesh_1)
    assert not any(".opt_state" in p for p in old_on_mesh_1)
    for i, (new, old) in enumerate(zip(values, old_values)):
        assert len(new) == len(old) == n_leaves
        for a, b in zip(new, old):
            assert a.dtype == b.dtype and a.shape == b.shape
            if i < bitwise_steps:
                assert (a == b).all()
            else:
                assert np.abs(a - b).max() <= 1e-6 * np.abs(b).max()


# ---------------------------------------------------------------------
# (c) the rule, on hand-built equations
# ---------------------------------------------------------------------

F8 = jax.ShapeDtypeStruct((8,), jnp.float32)
F4 = jax.ShapeDtypeStruct((4,), jnp.float32)
I0 = jax.ShapeDtypeStruct((), jnp.int32)


def _partition(fun, avals, placed):
    """Trace ``fun`` and partition its eqns over two meshes; ``placed``
    maps an argument's position to its mesh.  Returns the mesh of every
    output of ``fun``, the mesh of every eqn, and the computations."""
    jaxpr = jax.make_jaxpr(fun)(*avals).jaxpr
    var_mesh = {jaxpr.invars[i]: m for i, m in placed.items()}
    comps, out_mesh = partition_apply_grad(
        jaxpr.eqns, var_mesh, 2, jaxpr.outvars, {})
    eqn_mesh = assign_apply_grad_meshes(jaxpr.eqns, var_mesh)
    assert [len(c.eqns) for c in comps] == \
        [eqn_mesh.count(0), eqn_mesh.count(1)]
    return [out_mesh[v] for v in jaxpr.outvars], eqn_mesh, comps


@pytest.mark.parametrize("n_readers_on_1, want", [(2, 1), (1, 0)])
def test_open_eqn_goes_where_most_bytes_are_read(n_readers_on_1, want):
    """``t`` has no placed input.  Read once on mesh 0 and twice on mesh
    1 it goes to mesh 1; read once on each, to the lower."""

    def fun(mu, g0, g1):
        t = mu * 0.9
        outs = [g0 + t] + [g1 * float(k + 2) + t
                           for k in range(n_readers_on_1)]
        return [t] + outs

    out_mesh, eqn_mesh, _ = _partition(fun, [F8, F8, F8], {1: 0, 2: 1})
    assert out_mesh[0] == eqn_mesh[0] == want
    assert out_mesh[1:] == [0] + [1] * n_readers_on_1


def test_bytes_read_decide_not_the_number_of_readers():
    """One eqn, two results: mesh 0 reads the 4-element one twice (32
    bytes), mesh 1 the 16-element one once (64 bytes)."""

    def fun(mu, g0, g1):
        small, big = jax.jit(lambda x: (x[:4] * 0.5, x * 0.5))(mu)
        return g0 + small, g0 * small, g1 + big

    f16 = jax.ShapeDtypeStruct((16,), jnp.float32)
    out_mesh, eqn_mesh, _ = _partition(fun, [f16, F4, f16], {1: 0, 2: 1})
    assert out_mesh == [0, 0, 1]
    assert eqn_mesh == [1, 0, 0, 1]


def test_eqn_read_by_nothing_goes_to_mesh_0():

    def fun(count, g1):
        return count + 1, g1 * 2.0

    out_mesh, eqn_mesh, comps = _partition(fun, [I0, F8], {1: 1})
    assert out_mesh == [0, 1] and eqn_mesh == [0, 1]
    assert apply_partition_is_acyclic(comps)


def test_backward_and_forward_sweeps_alternate():
    """``a`` and ``b`` have no placed input and reach mesh 1 backward
    from ``c``; ``d`` reads only ``a``, is read by nothing, and follows
    ``a`` forward once that is placed, not the leftovers to mesh 0."""

    def fun(mu, g1):
        a = mu * 0.9
        b = a * a
        c = g1 + b
        d = a + 1.0
        return c, d

    out_mesh, eqn_mesh, comps = _partition(fun, [F8, F8], {1: 1})
    assert out_mesh == [1, 1] and eqn_mesh == [1, 1, 1, 1]
    assert comps[0].eqns == [] and comps[0].invars == []


def test_adam_moments_land_beside_their_gradients():
    """optax's own Adam over one parameter a mesh: every eqn that reads a
    moment runs on the mesh of that moment's gradient."""
    tx = optax.adam(1e-3)
    params = {"p0": jnp.ones((8,)), "p1": jnp.ones((8,))}

    def fun(g0, g1, opt_state):
        updates, new_state = tx.update({"p0": g0, "p1": g1}, opt_state)
        return updates, new_state

    opt_state = tx.init(params)
    jaxpr = jax.make_jaxpr(fun)(F8, F8, opt_state).jaxpr
    g0, g1 = jaxpr.invars[:2]
    eqn_mesh = assign_apply_grad_meshes(jaxpr.eqns, {g0: 0, g1: 1})
    # the flattened state: count, mu.p0, mu.p1, nu.p0, nu.p1
    _count, mu0, mu1, nu0, nu1 = jaxpr.invars[2:]
    for moment, want in ((mu0, 0), (mu1, 1), (nu0, 0), (nu1, 1)):
        readers = [m for e, m in zip(jaxpr.eqns, eqn_mesh)
                   if moment in e.invars]
        assert readers == [want]
    assert eqn_mesh != _forward_only_assignment(jaxpr.eqns,
                                                {g0: 0, g1: 1})


def test_cyclic_partition_is_found_and_forced_to_mesh_0():
    """A global norm reads both gradients and scales both: whichever mesh
    sums it, the two computations feed each other."""

    def fun(g0, g1):
        norm = jnp.sum(g0 * g0) + jnp.sum(g1 * g1)
        return g0 / norm, g1 / norm

    jaxpr = jax.make_jaxpr(fun)(F8, F8).jaxpr
    var_mesh = dict(zip(jaxpr.invars, (0, 1)))
    comps, _ = partition_apply_grad(jaxpr.eqns, var_mesh, 2,
                                    jaxpr.outvars, {})
    assert all(c.eqns for c in comps)
    assert not apply_partition_is_acyclic(comps)
    comps, out_mesh = partition_apply_grad(jaxpr.eqns, var_mesh, 2,
                                           jaxpr.outvars, {}, force_mesh=0)
    assert apply_partition_is_acyclic(comps)
    assert len(comps[0].eqns) == len(jaxpr.eqns) and comps[1].eqns == []
    assert [out_mesh[v] for v in jaxpr.outvars] == [0, 0]
