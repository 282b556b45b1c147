"""MLP trained via @alpa_tpu.parallelize.

Runs on any device set; use the virtual CPU mesh for a pod stand-in:
  XLA_FLAGS=--xla_force_host_platform_device_count=8 \
      python examples/mnist_mlp.py --platform cpu
"""
import argparse

import jax
import jax.numpy as jnp
import numpy as np
import optax
from flax import linen as nn
from flax.training import train_state

import alpa_tpu


class MLP(nn.Module):

    @nn.compact
    def __call__(self, x):
        x = nn.Dense(512)(x)
        x = nn.relu(x)
        return nn.Dense(10)(x)


def synthetic_mnist(batch_size, rng):
    x = rng.randn(batch_size, 784).astype(np.float32)
    y = rng.randint(0, 10, (batch_size,))
    return {"x": x, "y": y}


def main():
    parser = argparse.ArgumentParser()
    parser.add_argument("--platform", default=None)
    parser.add_argument("--steps", type=int, default=50)
    parser.add_argument("--batch-size", type=int, default=1024)
    args = parser.parse_args()
    if args.platform:
        jax.config.update("jax_platforms", args.platform)

    alpa_tpu.init(cluster="local")
    print(f"devices: {jax.devices()}")

    model = MLP()
    rng = jax.random.PRNGKey(0)
    batch = synthetic_mnist(args.batch_size, np.random.RandomState(0))
    params = model.init(rng, jnp.asarray(batch["x"]))
    state = train_state.TrainState.create(apply_fn=model.apply,
                                          params=params,
                                          tx=optax.adam(1e-3))

    @alpa_tpu.parallelize(method=alpa_tpu.DataParallel())
    def train_step(state, batch):

        def loss_fn(p):
            logits = state.apply_fn(p, batch["x"])
            return optax.softmax_cross_entropy_with_integer_labels(
                logits, batch["y"]).mean()

        loss, grads = alpa_tpu.value_and_grad(loss_fn)(state.params)
        return state.apply_gradients(grads=grads), loss

    for i in range(args.steps):
        state, loss = train_step(state, batch)
        if i % 10 == 0:
            print(f"step {i:4d}  loss {float(loss):.4f}")
    print(f"final loss {float(loss):.4f}")


if __name__ == "__main__":
    from alpa_tpu.platform import enable_compilation_cache
    enable_compilation_cache()
    main()
