"""GPT-2-class intra-op auto-sharding on one host.

  python examples/gpt2_training.py                 # real chip(s)
  XLA_FLAGS=--xla_force_host_platform_device_count=8 \
      python examples/gpt2_training.py --platform cpu --model tiny
"""
import argparse
import time

import jax
import jax.numpy as jnp
import optax
from flax.training import train_state

import alpa_tpu
from alpa_tpu.model.gpt_model import GPTConfig, GPTModel
from alpa_tpu.model.model_util import cross_entropy_loss
from alpa_tpu.util import compute_gpt_tflops

MODELS = {
    "tiny": GPTConfig(hidden_size=128, num_layers=4, num_heads=8,
                      seq_len=128, vocab_size=1024),
    # 1,024 positions of 12 heads of 64: on one TPU chip the attention of
    # every layer is the fused kernels of ops/flash_attention.py, chosen
    # from these shapes (``gpt_model.attention``); on a mesh of several
    # chips, and on the CPU, ``reference_attention``
    "125M": GPTConfig(hidden_size=768, num_layers=12, num_heads=12,
                      seq_len=1024, vocab_size=51200,
                      dtype=jnp.bfloat16),
}


def main():
    parser = argparse.ArgumentParser()
    parser.add_argument("--platform", default=None)
    parser.add_argument("--model", default="125M", choices=MODELS)
    parser.add_argument("--batch-size", type=int, default=8)
    parser.add_argument("--num-micro-batches", type=int, default=1)
    parser.add_argument("--steps", type=int, default=20)
    args = parser.parse_args()
    if args.platform:
        jax.config.update("jax_platforms", args.platform)

    alpa_tpu.init(cluster="local")
    config = MODELS[args.model]
    model = GPTModel(config)
    rng = jax.random.PRNGKey(0)
    ids = jax.random.randint(rng, (args.batch_size, config.seq_len), 0,
                             config.vocab_size)
    labels = jax.random.randint(jax.random.PRNGKey(1),
                                (args.batch_size, config.seq_len), 0,
                                config.vocab_size)
    params = model.init(rng, ids)
    state = train_state.TrainState.create(apply_fn=model.apply,
                                          params=params,
                                          tx=optax.adamw(1e-4))

    method = alpa_tpu.ShardParallel(
        num_micro_batches=(args.num_micro_batches
                           if args.num_micro_batches > 1 else None))

    @alpa_tpu.parallelize(method=method, donate_argnums=(0,))
    def train_step(state, batch):

        def loss_fn(p):
            logits = state.apply_fn(p, batch["ids"])
            return cross_entropy_loss(logits.astype(jnp.float32),
                                      batch["labels"])

        loss, grads = alpa_tpu.value_and_grad(loss_fn)(state.params)
        return state.apply_gradients(grads=grads), loss

    batch = {"ids": ids, "labels": labels}
    for _ in range(3):
        state, loss = train_step(state, batch)
        float(loss)
    tic = time.perf_counter()
    for i in range(args.steps):
        state, loss = train_step(state, batch)
    final = float(loss)
    dt = (time.perf_counter() - tic) / args.steps
    tflops = compute_gpt_tflops(args.batch_size, config.seq_len,
                                config.num_layers, config.hidden_size,
                                config.vocab_size, len(jax.devices()), dt)
    print(f"loss {final:.4f}  {dt*1e3:.1f} ms/step  "
          f"{tflops:.1f} TFLOPS/device")


if __name__ == "__main__":
    from alpa_tpu.platform import enable_compilation_cache
    enable_compilation_cache()
    main()
