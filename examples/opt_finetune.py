"""Decoder-LM finetuning with inter+intra-op
(pipeshard) parallelism.

  XLA_FLAGS=--xla_force_host_platform_device_count=8 \
      python examples/opt_finetune.py --platform cpu
"""
import argparse

import jax
import jax.numpy as jnp
import optax
from flax.training import train_state

import alpa_tpu
from alpa_tpu.model.gpt_model import GPTConfig, GPTModel
from alpa_tpu.model.model_util import cross_entropy_loss


def main():
    parser = argparse.ArgumentParser()
    parser.add_argument("--platform", default=None)
    parser.add_argument("--num-stages", type=int, default=2)
    parser.add_argument("--num-micro-batches", type=int, default=4)
    parser.add_argument("--steps", type=int, default=5)
    parser.add_argument("--auto-stages", action="store_true",
                        help="use the OSDI'22-style auto stage search")
    args = parser.parse_args()
    if args.platform:
        jax.config.update("jax_platforms", args.platform)

    alpa_tpu.init(cluster="local")

    config = GPTConfig(hidden_size=128, num_layers=8, num_heads=8,
                       seq_len=128, vocab_size=2048,
                       pipeline_boundary_every=2)
    model = GPTModel(config)
    rng = jax.random.PRNGKey(0)
    ids = jax.random.randint(rng, (16, config.seq_len), 0,
                             config.vocab_size)
    labels = jax.random.randint(jax.random.PRNGKey(1),
                                (16, config.seq_len), 0, config.vocab_size)
    params = model.init(rng, ids)
    state = train_state.TrainState.create(apply_fn=model.apply,
                                          params=params,
                                          tx=optax.adamw(1e-4))

    stage_option = (alpa_tpu.AutoStageOption() if args.auto_stages else
                    alpa_tpu.UniformStageOption(args.num_stages))
    method = alpa_tpu.PipeshardParallel(
        num_micro_batches=args.num_micro_batches,
        layer_option=alpa_tpu.ManualLayerOption(),
        stage_option=stage_option,
        pipeline_schedule="1f1b")

    @alpa_tpu.parallelize(method=method)
    def train_step(state, batch):

        def loss_fn(p):
            logits = state.apply_fn(p, batch["ids"])
            return cross_entropy_loss(logits.astype(jnp.float32),
                                      batch["labels"])

        loss, grads = alpa_tpu.value_and_grad(loss_fn)(state.params)
        return state.apply_gradients(grads=grads), loss

    batch = {"ids": ids, "labels": labels}
    for i in range(args.steps):
        state, loss = train_step(state, batch)
        print(f"step {i}  loss {float(loss):.4f}")
    ex = train_step.get_last_executable()
    print(ex.get_resharding_report())
    print("schedule:")
    print(ex.get_schedule_text())


if __name__ == "__main__":
    from alpa_tpu.platform import enable_compilation_cache
    enable_compilation_cache()
    main()
