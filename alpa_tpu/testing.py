"""Test utilities: numeric comparison + model fixtures.

Analog of ref ``alpa/testing.py`` (SURVEY.md §4): the core oracle is
serial-vs-parallel numeric equivalence, plus structural assertions on
compiled HLO.
"""
import collections
import re
import zlib
from functools import partial
from typing import Any, Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np
import optax
from flax import linen as nn
from flax.training import train_state

import alpa_tpu
from alpa_tpu.pipeline_parallel.primitive_def import mark_pipeline_boundary


def assert_allclose(x: Any, y: Any, rtol=1e-4, atol=1e-4):
    """Recursive pytree comparison (ref testing.py:28)."""
    if isinstance(x, dict):
        assert isinstance(y, dict) and set(x) == set(y)
        for k in x:
            assert_allclose(x[k], y[k], rtol, atol)
    elif isinstance(x, (tuple, list)):
        assert isinstance(y, (tuple, list)) and len(x) == len(y)
        for a, b in zip(x, y):
            assert_allclose(a, b, rtol, atol)
    elif hasattr(x, "__array__") or np.isscalar(x):
        assert hasattr(y, "__array__") or np.isscalar(y), f"{x} vs {y}"
        np.testing.assert_allclose(np.asarray(x), np.asarray(y), rtol, atol)
    elif x is None:
        assert y is None
    else:
        assert isinstance(y, type(x)) or isinstance(x, type(y))
        if hasattr(x, "__dict__"):
            assert_allclose(x.__dict__, y.__dict__, rtol, atol)


class MLPModel(nn.Module):
    """Simple MLP fixture (ref testing.py:54)."""
    hidden_dim: int
    output_dim: int
    num_layers: int = 2
    manual_pipeline_layer: bool = False

    @nn.compact
    def __call__(self, x):
        for i in range(self.num_layers):
            if self.manual_pipeline_layer and i == self.num_layers // 2:
                mark_pipeline_boundary()
            dim = (self.output_dim
                   if i == self.num_layers - 1 else self.hidden_dim)
            x = nn.Dense(features=dim)(x)
            if i != self.num_layers - 1:
                x = nn.relu(x)
        return x


def highest(fn, *args, **kwargs):
    """``fn(*args, **kwargs)`` at full matmul precision: what a comparison
    with a float32 reference runs under, so that it reads the mathematics
    and not the backend's default rounding."""
    with jax.default_matmul_precision("highest"):
        return fn(*args, **kwargs)


def jitted(fn):
    """``fn`` as one compiled program in place of one dispatch (and at
    first use one compilation) an operation: for a whole model's ``apply``
    or ``jax.grad`` whose result is only compared.  A function of its own
    every time, so that a trace made before, under another monkeypatch,
    is never found again."""
    return jax.jit(lambda *args, **kwargs: fn(*args, **kwargs))


def init_params(model, rngkey, *args, **kwargs):
    """``model.init(rngkey, *args, **kwargs)`` as one compiled program,
    under the caller's matmul precision.  Outside ``jit`` flax dispatches
    (and at first use compiles) every initializer and every operation of
    the forward pass one by one.  The leaves of flax's initializers are
    the same bit for bit (``tests/util/test_util.py`` holds one toy to
    that); an initializer that adds a constant to a draw (``gpt_model``'s
    sinks) may differ in the last bit, the sum being one multiply-add
    here."""
    return jitted(partial(model.init, **kwargs))(rngkey, *args)


def shake(params, names, seed=0):
    """``params`` with every leaf whose last key is in ``names`` (norm
    scales, biases, routers' biases) moved by a draw of spread 0.3, away
    from the 1 or 0 it is initialised to, so that a weight left out or
    applied in the wrong place shows."""
    def moved(path, x):
        if path[-1].key not in names:
            return x
        # (a checksum of the leaf's path and not ``hash``, which Python
        # salts anew in every process: a test near its tolerance then
        # passed or failed by the process it ran in)
        key = jax.random.fold_in(
            jax.random.PRNGKey(seed),
            zlib.crc32(jax.tree_util.keystr(path).encode()) % 997)
        return x + 0.3 * jax.random.normal(key, x.shape, x.dtype)
    return jax.tree_util.tree_map_with_path(moved, params)


def create_train_state(rngkey, model, inputs, learning_rate=1e-2):
    # eager on purpose: the models that come here are a few Dense layers
    # and many tests make several states (a kill-schedule fuzz 21); the
    # process compiles their handful of operations once, where a program
    # a call would be compiled every time (CHANGES.md, PR 55)
    params = model.init(rngkey, *inputs)
    tx = optax.sgd(learning_rate=learning_rate, momentum=0.9)
    return train_state.TrainState.create(apply_fn=model.apply,
                                         params=params,
                                         tx=tx)


def create_mlp_train_state_and_batch(batch_size=64,
                                     input_dim=32,
                                     hidden_dim=32,
                                     output_dim=32,
                                     num_layers=2,
                                     manual_pipeline_layer=False):
    rngkey = jax.random.PRNGKey(0)
    x = jax.random.normal(rngkey, (batch_size, input_dim), jnp.float32)
    y = jax.random.normal(rngkey, (batch_size, output_dim), jnp.float32)
    model = MLPModel(hidden_dim=hidden_dim,
                     output_dim=output_dim,
                     num_layers=num_layers,
                     manual_pipeline_layer=manual_pipeline_layer)
    state = create_train_state(rngkey, model, [x])
    return state, {"x": x, "y": y}


def get_mlp_train_step(parallel_method=None, use_value_and_grad=False):
    """Build a train step; with a method -> parallelized, else plain jit."""

    def train_step(state, batch):

        def loss_func(params):
            out = state.apply_fn(params, batch["x"])
            return jnp.mean((out - batch["y"])**2)

        if parallel_method is not None:
            if use_value_and_grad:
                val, grads = alpa_tpu.value_and_grad(loss_func)(state.params)
            else:
                grads = alpa_tpu.grad(loss_func)(state.params)
                val = jnp.zeros((), jnp.float32)
        else:
            val, grads = jax.value_and_grad(loss_func)(state.params)
        new_state = state.apply_gradients(grads=grads)
        return new_state, val

    if parallel_method is not None:
        return alpa_tpu.parallelize(train_step, method=parallel_method)
    return jax.jit(train_step)


def get_gpt_train_step(config, batch_size, parallel_method=None,
                       learning_rate=1e-4):
    """A language-model train step of ``GPTModel(config)`` under Adam, as
    the benchmark's training driver builds it: ``(train_step,
    create_state, batch)``.  With a method the step is parallelized and
    donates its state (compile it from shapes with
    ``train_step.get_executable(jax.eval_shape(create_state), batch)``);
    without one it is a plain ``jax.jit``."""
    from alpa_tpu.model.gpt_model import GPTModel
    from alpa_tpu.model.model_util import gpt_lm_loss
    model = GPTModel(config)
    shape = (batch_size, config.seq_len)
    tx = optax.adam(learning_rate)
    k_ids, k_labels = jax.random.split(jax.random.PRNGKey(1))
    batch = {
        "input_ids": jax.random.randint(k_ids, shape, 0, config.vocab_size),
        "labels": jax.random.randint(k_labels, shape, 0, config.vocab_size),
    }

    def create_state():
        params = init_params(model, jax.random.PRNGKey(0),
                             jnp.ones(shape, jnp.int32))
        return train_state.TrainState.create(apply_fn=model.apply,
                                             params=params, tx=tx)

    def train_step(state, batch):

        def loss_fn(params):
            return gpt_lm_loss(state.apply_fn, params, batch)

        grad_fn = (alpa_tpu.value_and_grad if parallel_method is not None
                   else jax.value_and_grad)
        loss, grads = grad_fn(loss_fn)(state.params)
        return state.apply_gradients(grads=grads), loss

    if parallel_method is not None:
        train_step = alpa_tpu.parallelize(train_step, method=parallel_method,
                                          static_argnums=(),
                                          donate_argnums=(0,))
    else:
        train_step = jax.jit(train_step)
    return train_step, create_state, batch


_GATHER = re.compile(r"= (\w+\[[\d,]*\])\S* all-gather(?:-start)?\(")


def gathers_by_shape(text):
    """How many all-gathers of a compiled program's text give each shape."""
    return collections.Counter(_GATHER.findall(text))


def _shape_of(aval):
    return f"{np.dtype(aval.dtype).name.replace('float', 'f')}" \
           f"[{','.join(map(str, aval.shape))}]"


def donated_accumulator_faults(executable):
    """What is wrong with a compiled pipeshard executable's donated
    gradient accumulators, as ``(program, what)``; ``[]`` when every
    backward stage keeps each accumulator in the sharding of its sum,
    shards the rank-2 ones (the kernels: a product is sharded over one of
    its output dimensions) and gathers none of them, and every update
    program gathers a kernel at most once.  (A rank-1 sum may still be
    gathered: the planner holds the ``split`` and ``concatenate`` around
    an attention core replicated, GSPMD does not, and a bias's gradient
    is then produced sharded where the plan has it whole.)"""
    wrong = []
    for stage in executable.stage_execs:
        pairs = stage.donated_pairs()
        if not stage.name.endswith("_bwd"):
            continue
        assert pairs, stage.name
        gathered = gathers_by_shape(stage.compiled.as_text())
        for i, k in pairs:
            aval = stage.invars[i].aval
            ndim = len(aval.shape)
            if not stage.in_shardings[i].is_equivalent_to(
                    stage.out_shardings[k], ndim):
                wrong.append((stage.name, f"{aval} in != out"))
            if ndim == 2:
                if stage.in_shardings[i].is_fully_replicated:
                    wrong.append((stage.name, f"{aval} replicated"))
                if gathered[_shape_of(aval)]:
                    wrong.append((stage.name, f"{aval} gathered"))
    for apply in executable.apply_execs:
        leaves = collections.Counter(
            _shape_of(apply.invars[i].aval) for i in apply.donate_idx)
        for shape, n in gathers_by_shape(apply.compiled.as_text()).items():
            # a kernel, its two moments and its summed gradient are four
            # arrays of one shape: one gather a kernel, not one an array
            if n > max(leaves[shape] // 3, 1):
                wrong.append((apply.name, f"{shape} gathered {n} times "
                              f"for {leaves[shape]} leaves"))
    return wrong


def handed_over_faults(executable):
    """What is wrong with the values a compiled pipeshard executable's
    forward stage hands its backward stage on one mesh, as ``(program,
    what)``; ``[]`` when every value the forward stage writes and the
    backward stage reads leaves the one as it enters the other, every
    value both read enters both alike, each of those is compiled as the
    program's own plan had it (``planned_in``, ``planned_out``), and the
    forward program's text all-gathers no array of the shape of a
    handed-over output that leaves whole (a plan that GSPMD had to close
    the gap to)."""
    wrong = []
    n = executable.num_fwd_stages
    for fwd, bwd in zip(executable.stage_execs[:n],
                        executable.stage_execs[n:]):
        assert fwd.mesh_id == bwd.mesh_id, (fwd.name, bwd.name)
        gathered = gathers_by_shape(fwd.compiled.as_text())
        for i, v in enumerate(bwd.invars):
            ndim = len(v.aval.shape)
            enters = bwd.in_shardings[i]
            if not enters.is_equivalent_to(bwd.planned_in[i], ndim):
                wrong.append((bwd.name, f"{v.aval} read not as planned"))
            if v in fwd.outvars:
                k = fwd.outvars.index(v)
                if not fwd.out_shardings[k].is_equivalent_to(enters, ndim):
                    wrong.append((fwd.name, f"{v.aval} out != in"))
                planned = fwd.planned_out[k]
                if planned is not None and not planned.is_equivalent_to(
                        fwd.out_shardings[k], ndim):
                    wrong.append((fwd.name,
                                  f"{v.aval} written not as planned"))
                # (one that leaves sharded is not what a gather's result,
                # which is whole on a mesh of two, was made for)
                if fwd.out_shardings[k].is_fully_replicated and \
                        gathered[_shape_of(v.aval)]:
                    wrong.append((fwd.name, f"{v.aval} gathered"))
            elif v in fwd.invars:
                j = fwd.invars.index(v)
                if not fwd.in_shardings[j].is_equivalent_to(enters, ndim):
                    wrong.append((bwd.name, f"{v.aval} in != in"))
                if not fwd.in_shardings[j].is_equivalent_to(
                        fwd.planned_in[j], ndim):
                    wrong.append((fwd.name,
                                  f"{v.aval} read not as planned"))
    return wrong


def data_loader_input_iter_func(start, end, batch_size):
    """Deterministic fake-data iterator used by data loader tests."""
    num = (end - start) // batch_size
    for i in range(num):
        yield (np.full((batch_size, 32), i, np.float32),
               np.full((batch_size,), i, np.int32))
