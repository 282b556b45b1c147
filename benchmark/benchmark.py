"""Benchmark driver.

Analog of ref ``benchmark/alpa/benchmark.py``: run a named suite of cases,
time the train step, report latency / TFLOPS / tokens-per-sec, append a
TSV record (ref util.write_tsv).

Usage:
  python benchmark/benchmark.py --suite gpt.tiny [--dump results.tsv]
"""
import argparse
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).parent.parent))

import jax
import jax.numpy as jnp
import numpy as np


def build_case(case):
    import optax
    from flax.training import train_state

    import alpa_tpu
    from alpa_tpu.model.model_util import cross_entropy_loss

    dtype = jnp.bfloat16 if case.dtype == "bfloat16" else jnp.float32
    rng = jax.random.PRNGKey(0)

    if case.family == "gpt":
        from alpa_tpu.model.gpt_model import GPTConfig, GPTModel
        cfg = GPTConfig(dtype=dtype, **case.model)
        model = GPTModel(cfg)
        ids = jax.random.randint(rng, (case.batch_size, cfg.seq_len), 0,
                                 cfg.vocab_size)
        labels = jax.random.randint(jax.random.PRNGKey(1),
                                    (case.batch_size, cfg.seq_len), 0,
                                    cfg.vocab_size)
        batch = {"ids": ids, "labels": labels}
        params = model.init(rng, ids)

        def loss_of(state, p, b):
            logits = state.apply_fn(p, b["ids"])
            return cross_entropy_loss(logits.astype(jnp.float32),
                                      b["labels"])

        def flops(latency):
            from alpa_tpu.util import compute_gpt_tflops
            return compute_gpt_tflops(case.batch_size, cfg.seq_len,
                                      cfg.num_layers, cfg.hidden_size,
                                      cfg.vocab_size, len(jax.devices()),
                                      latency)

        tokens = case.batch_size * cfg.seq_len
    elif case.family == "moe":
        from alpa_tpu.model.moe import MoEConfig, MoELMModel
        cfg = MoEConfig(dtype=dtype, **case.model)
        model = MoELMModel(cfg)
        ids = jax.random.randint(rng, (case.batch_size, cfg.seq_len), 0,
                                 cfg.vocab_size)
        labels = jax.random.randint(jax.random.PRNGKey(1),
                                    (case.batch_size, cfg.seq_len), 0,
                                    cfg.vocab_size)
        batch = {"ids": ids, "labels": labels}
        params = model.init(rng, ids)

        def loss_of(state, p, b):
            logits, aux = state.apply_fn(p, b["ids"])
            return cross_entropy_loss(logits.astype(jnp.float32),
                                      b["labels"]) + 0.01 * aux

        def flops(latency):
            from alpa_tpu.util import compute_moe_tflops
            return compute_moe_tflops(case.batch_size, cfg.seq_len,
                                      cfg.num_layers, cfg.hidden_size,
                                      cfg.expert_group_size, cfg.vocab_size,
                                      cfg.num_experts, len(jax.devices()),
                                      latency)

        tokens = case.batch_size * cfg.seq_len
    elif case.family == "unet":
        from alpa_tpu.model.unet_2d import UNet2D, UNetConfig
        cfg = UNetConfig(dtype=dtype, **case.model)
        model = UNet2D(cfg)
        res = case.method_kwargs.get("resolution", 32)
        x = jax.random.normal(rng, (case.batch_size, res, res,
                                    cfg.in_channels), dtype)
        t = jax.random.randint(jax.random.PRNGKey(2), (case.batch_size,),
                               0, 1000)
        noise = jax.random.normal(jax.random.PRNGKey(3), x.shape, dtype)
        batch = {"x": x, "t": t, "noise": noise}
        params = model.init(rng, x, t)

        def loss_of(state, p, b):
            pred = state.apply_fn(p, b["x"], b["t"])
            return ((pred.astype(jnp.float32) -
                     b["noise"].astype(jnp.float32))**2).mean()

        from alpa_tpu.util import jaxpr_eqn_flops
        fwd_jaxpr = jax.make_jaxpr(lambda p: model.apply(p, x, t))(params)
        fwd_flops = sum(jaxpr_eqn_flops(e) for e in fwd_jaxpr.jaxpr.eqns)

        def flops(latency):
            return 3.0 * fwd_flops / latency / len(jax.devices()) / 1e12

        tokens = case.batch_size
    elif case.family == "wresnet":
        import optax as _optax
        from alpa_tpu.model.wide_resnet import WResNetConfig, WideResNet
        cfg = WResNetConfig(dtype=dtype, **case.model)
        model = WideResNet(cfg)
        x = jax.random.normal(rng, (case.batch_size, 224, 224, 3), dtype)
        y = jax.random.randint(jax.random.PRNGKey(1), (case.batch_size,),
                               0, cfg.num_classes)
        batch = {"x": x, "y": y}
        params = model.init(rng, x)

        def loss_of(state, p, b):
            import optax
            logits = state.apply_fn(p, b["x"])
            return optax.softmax_cross_entropy_with_integer_labels(
                logits.astype(jnp.float32), b["y"]).mean()

        # analytic formulas don't cover convs well: count fwd flops off
        # the traced jaxpr, x3 for fwd+bwd (standard accounting)
        from alpa_tpu.util import jaxpr_eqn_flops
        fwd_jaxpr = jax.make_jaxpr(lambda p: model.apply(p, x))(params)
        fwd_flops = sum(jaxpr_eqn_flops(e) for e in fwd_jaxpr.jaxpr.eqns)

        def flops(latency):
            return 3.0 * fwd_flops / latency / len(jax.devices()) / 1e12

        tokens = case.batch_size
    else:
        raise ValueError(case.family)

    state = train_state.TrainState.create(apply_fn=model.apply,
                                          params=params,
                                          tx=optax.adam(1e-4))

    if case.method == "pipeshard":
        method = alpa_tpu.PipeshardParallel(
            num_micro_batches=case.num_micro_batches,
            layer_option=alpa_tpu.AutoLayerOption(
                layer_num=case.method_kwargs.get("layer_num", 2)),
            stage_option=alpa_tpu.UniformStageOption(
                case.method_kwargs.get("num_stages")))
    elif case.method == "auto_pipeshard":
        # full auto inter+intra search (ref suite_auto_*.py): OSDI'22
        # stage DP over submesh choices, per-stage ILP inside
        from alpa_tpu.pipeline_parallel.stage_construction import (
            AutoStageOption)
        method = alpa_tpu.PipeshardParallel(
            num_micro_batches=case.num_micro_batches,
            layer_option=alpa_tpu.AutoLayerOption(
                layer_num=case.method_kwargs.get("layer_num", 2)),
            stage_option=AutoStageOption(
                profiling_database_filename=case.method_kwargs.get(
                    "prof_db")))
    elif case.method == "dp":
        method = alpa_tpu.DataParallel(
            num_micro_batches=case.num_micro_batches)
    elif case.method == "zero3":
        method = alpa_tpu.Zero3Parallel(
            num_micro_batches=case.num_micro_batches)
    else:
        method = alpa_tpu.ShardParallel(
            num_micro_batches=case.num_micro_batches)

    @alpa_tpu.parallelize(method=method, donate_argnums=(0,))
    def train_step(state, batch):
        loss, grads = alpa_tpu.value_and_grad(
            lambda p: loss_of(state, p, batch))(state.params)
        return state.apply_gradients(grads=grads), loss

    return train_step, state, batch, flops, tokens


def run_case(case, warmup=3, n_iter=8):
    import alpa_tpu
    alpa_tpu.init(cluster="local")
    train_step, state, batch, flops, tokens = build_case(case)
    tic = time.time()
    for _ in range(warmup):
        state, loss = train_step(state, batch)
        float(loss)
    compile_and_warm = time.time() - tic
    tic = time.perf_counter()
    for _ in range(n_iter):
        state, loss = train_step(state, batch)
    float(loss)
    latency = (time.perf_counter() - tic) / n_iter
    return {
        "case": case.name,
        "latency_s": round(latency, 5),
        "tflops_per_device": round(flops(latency), 2),
        "tokens_per_sec": round(tokens / latency, 1),
        "warmup_s": round(compile_and_warm, 1),
    }


def main():
    parser = argparse.ArgumentParser()
    parser.add_argument("--suite", required=True)
    parser.add_argument("--dump", default="benchmark_results.tsv")
    parser.add_argument("--niter", type=int, default=8)
    parser.add_argument("--platform", default=None, choices=["cpu"],
                        help="'cpu' pins a virtual CPU mesh of "
                        "--cpu-devices devices; omit to use whatever "
                        "backend jax selects")
    parser.add_argument("--cpu-devices", type=int, default=8)
    args = parser.parse_args()

    if args.platform == "cpu":
        from alpa_tpu.platform import pin_cpu_platform
        pin_cpu_platform(args.cpu_devices)
    from alpa_tpu.platform import enable_compilation_cache
    enable_compilation_cache()

    from benchmark.suites import suites
    from alpa_tpu.util import write_tsv

    cases = suites[args.suite]
    for case in cases:
        result = run_case(case, n_iter=args.niter)
        heads = list(result.keys())
        write_tsv(heads, [result[h] for h in heads], args.dump)


if __name__ == "__main__":
    main()
