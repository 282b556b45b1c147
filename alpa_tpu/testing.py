"""Test utilities: numeric comparison + model fixtures.

Analog of ref ``alpa/testing.py`` (SURVEY.md §4): the core oracle is
serial-vs-parallel numeric equivalence, plus structural assertions on
compiled HLO.
"""
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


def create_train_state(rngkey, model, inputs, learning_rate=1e-2):
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


def data_loader_input_iter_func(start, end, batch_size):
    """Deterministic fake-data iterator used by data loader tests."""
    num = (end - start) // batch_size
    for i in range(num):
        yield (np.full((batch_size, 32), i, np.float32),
               np.full((batch_size,), i, np.int32))
