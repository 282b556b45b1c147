"""Benchmark driver: GPT train-step throughput on one TPU chip.

Prints ONE JSON line:
  {"metric": ..., "value": N, "unit": ..., "vs_baseline": N}

Baseline anchor (BASELINE.md): the reference's published manual-3D GPT-2.6B
result of 37.01 TFLOPS/GPU on 8x V100 (ref benchmark/alpa/README.md:89-101).
vs_baseline = achieved TFLOPS-per-chip / 37.01.

One in-process run.  It measures on a TPU or not at all: without one, with
an unknown variant, or with a config whose estimated HBM exceeds
``HBM_GATE_GB``, it writes the reason to stderr, prints no metric line and
exits non-zero.
"""
import json
import os
import sys
import time

BASELINE_TFLOPS_PER_DEVICE = 37.01

# Ceiling in *estimator* units.  estimate_hbm_gb is deliberately
# conservative (it counts fp32 logits + their grad without assuming XLA
# fuses or frees them): h2048-l16-bs8 estimates 15.6 GB;
# remat_policy="dots", batch 16 and h2048-l24 with fp32 adam estimate
# >= 20.2 GB.
HBM_GATE_GB = 16.0


def gpt_param_count(hidden_size, num_layers, vocab_size, seq_len,
                    mlp_ratio=4, tie_embeddings=True):
    per_layer = (4 + 2 * mlp_ratio) * hidden_size ** 2 \
        + (9 + 2 * mlp_ratio) * hidden_size  # biases + 2 LN
    emb = vocab_size * hidden_size + seq_len * hidden_size
    head = 0 if tie_embeddings else vocab_size * hidden_size
    return per_layer * num_layers + emb + head + 2 * hidden_size


def estimate_hbm_gb(config, batch_size, optimizer_bytes_per_param=8.0,
                    chunked_ce=False):
    """Estimated peak HBM for one train step of ``config`` at ``batch_size``.

    params are fp32 (flax param_dtype default) = 4 B/p; optimizer state
    defaults to fp32 adam (2 moments) = 8 B/p.  Activations assume
    per-block remat: L boundary activations + one live block's
    intermediates, in the compute dtype, plus fp32 logits (+ their grad)
    unless the loss is chunked.
    """
    import numpy as np
    p = gpt_param_count(config.hidden_size, config.num_layers,
                        config.vocab_size, config.seq_len, config.mlp_ratio,
                        config.tie_embeddings)
    act_bytes = np.dtype(config.dtype).itemsize
    tokens = batch_size * config.seq_len
    h = config.hidden_size
    # live block intermediates: qkv(3h) + attn scores/probs + proj(h) +
    # mlp(4h + 4h) + residuals — call it ~20h per token (bs8/s1024
    # attention scores are 32 MB/head-batch slice, negligible after fusing)
    per_block = tokens * 20 * h * act_bytes
    if getattr(config, "remat_blocks", False):
        # per-block remat: keep only block boundaries + one live block
        boundary = tokens * h * act_bytes * config.num_layers
        block_peak = per_block
        if getattr(config, "remat_policy", None) == "dots":
            # saved dot outputs per layer: qkv 3h + proj h + mlp 5h ≈ 9h
            boundary += tokens * 9 * h * act_bytes * config.num_layers
    else:
        # no remat: every layer's intermediates live until backward
        boundary = per_block * config.num_layers
        block_peak = 0
    logits = 0 if chunked_ce else 2 * tokens * config.vocab_size * 4
    total = p * (4.0 + optimizer_bytes_per_param) + boundary + block_peak \
        + logits + tokens * h * 4  # grads materialize alongside fp32 master
    return total / 1e9


BENCH_SHAPES = {"": (2048, 16), "h2048l24": (2048, 24),
                "h2560l16": (2560, 16)}


def read_bench_variants():
    """(opt, ce, shape, errors): the env-selected experiment variants."""
    opt = os.environ.get("ALPA_TPU_BENCH_OPT", "adam")
    ce = os.environ.get("ALPA_TPU_BENCH_CE", "dense")
    shape = os.environ.get("ALPA_TPU_BENCH_SHAPE", "")
    errors = [f"{k}={v!r}" for k, v, ok in (
        ("ALPA_TPU_BENCH_OPT", opt, ("adam", "bf16adam")),
        ("ALPA_TPU_BENCH_CE", ce, ("dense", "chunked")),
        ("ALPA_TPU_BENCH_SHAPE", shape, tuple(BENCH_SHAPES)),
    ) if v not in ok]
    return opt, ce, shape, errors


def _refuse(message: str) -> int:
    """A refusal is an error on stderr and a non-zero exit: no metric
    line is printed for a run that measured nothing."""
    sys.stderr.write(f"bench.py: {message}\n")
    return 1


def main() -> int:
    import jax
    import jax.numpy as jnp
    import optax

    import alpa_tpu
    from alpa_tpu.model.gpt_model import GPTConfig, GPTModel
    from alpa_tpu.model.model_util import gpt_lm_loss
    from alpa_tpu.platform import enable_compilation_cache
    from alpa_tpu.util import compute_gpt_tflops

    devices = jax.devices()
    if devices[0].platform != "tpu":
        return _refuse(f"needs a TPU, jax found {devices[0].platform!r} "
                       f"({devices[0].device_kind}); nothing measured")
    n_dev = len(devices)
    enable_compilation_cache()

    # Experiment variants, opt-in via env:
    #   ALPA_TPU_BENCH_OPT=bf16adam   adam with bf16 first moment (6 B/p
    #                                 optimizer state instead of 8)
    #   ALPA_TPU_BENCH_CE=chunked     chunked lm-head+CE (no fp32 logits)
    #   ALPA_TPU_BENCH_SHAPE=h2048l24 bigger model rung (gated by HBM est)
    # a typo is refused: a silently-defaulted variant would claim an
    # experiment that did not run
    opt_variant, ce_variant, shape_variant, bad = read_bench_variants()
    if bad:
        return _refuse(f"unknown bench variant(s): {bad}")

    # GPT-1.3B-class config in bf16 (h2048 l16), batch 8 x seq 1024;
    # per-block remat is required to fit l16.  head_dim 64 throughout,
    # so numbers are comparable across shapes.
    hidden, layers = BENCH_SHAPES[shape_variant]
    config = GPTConfig(hidden_size=hidden, num_layers=layers,
                       num_heads=hidden // 64, seq_len=1024,
                       vocab_size=51200, dtype=jnp.bfloat16,
                       attention_impl="reference", remat_blocks=True)
    batch_size = 8

    opt_bytes = 6.0 if opt_variant == "bf16adam" else 8.0
    est = estimate_hbm_gb(config, batch_size,
                          optimizer_bytes_per_param=opt_bytes,
                          chunked_ce=ce_variant == "chunked")
    if est > HBM_GATE_GB:
        return _refuse(f"refused: estimated {est:.1f} GB HBM > gate "
                       f"{HBM_GATE_GB} GB")

    alpa_tpu.init(cluster="local")
    model = GPTModel(config)
    rng = jax.random.PRNGKey(0)
    input_ids = jax.random.randint(rng, (batch_size, config.seq_len), 0,
                                   config.vocab_size)
    labels = jax.random.randint(rng, (batch_size, config.seq_len), 0,
                                config.vocab_size)
    params = model.init(rng, input_ids)
    if opt_variant == "bf16adam":
        # bf16 first moment: 2 B/p saved; the variance stays fp32
        tx = optax.adam(1e-4, mu_dtype=jnp.bfloat16)
    else:
        tx = optax.adam(1e-4)
    from flax.training import train_state
    state = train_state.TrainState.create(apply_fn=model.apply, params=params,
                                          tx=tx)

    @alpa_tpu.parallelize(method=alpa_tpu.ShardParallel(),
                          donate_argnums=(0,))
    def train_step(state, batch):

        def loss_fn(p):
            # chunked is the variant that frees the fp32 logits for
            # bigger shape rungs
            return gpt_lm_loss(state.apply_fn, p, batch,
                               chunked=ce_variant == "chunked")

        loss, grads = alpa_tpu.value_and_grad(loss_fn)(state.params)
        return state.apply_gradients(grads=grads), loss

    batch = {"input_ids": input_ids, "labels": labels}

    # Warmup: first call compiles; the next two absorb one-time runtime
    # warmup (executable load, transfer setup).
    for _ in range(3):
        state, loss = train_step(state, batch)
        float(loss)  # force full completion

    n_iter = 10
    tic = time.perf_counter()
    for _ in range(n_iter):
        state, loss = train_step(state, batch)
    float(loss)  # drains the on-device queue
    latency = (time.perf_counter() - tic) / n_iter

    tokens_per_sec = batch_size * config.seq_len / latency
    tflops = compute_gpt_tflops(batch_size, config.seq_len, config.num_layers,
                                config.hidden_size, config.vocab_size, n_dev,
                                latency)
    # MFU against the detected generation's bf16 peak — the honest
    # number (vs_baseline divides by a V100's 37.01 for cross-framework
    # comparability with the reference recipe, which flatters a TPU).
    # the one MFU formula (ISSUE 9): telemetry.perf resolves the peak
    # from the device_peak_tflops knob or the detected generation's
    # TPU_GENERATION_SPECS entry
    from alpa_tpu.telemetry.perf import compute_mfu, peak_flops_info
    info = peak_flops_info()
    mfu = {"generation": info["generation"],
           "peak_bf16_tflops": info["peak_bf16_tflops"],
           "mfu": round(compute_mfu(tflops, info["peak_bf16_tflops"]), 4)}
    result = {
        "metric": "gpt_train_tflops_per_chip",
        "value": round(tflops, 3),
        "unit": "TFLOPS/chip",
        "vs_baseline": round(tflops / BASELINE_TFLOPS_PER_DEVICE, 4),
        "detail": {
            "model": f"h{config.hidden_size}-l{config.num_layers}",
            "opt": opt_variant,
            "ce": ce_variant,
            "batch": batch_size,
            "seq": config.seq_len,
            "latency_s": round(latency, 5),
            "tokens_per_sec": round(tokens_per_sec, 1),
            "n_devices": n_dev,
            "platform": devices[0].platform,
            "device_kind": devices[0].device_kind,
            **mfu,
        },
    }
    print(json.dumps(result))
    return 0


if __name__ == "__main__":
    sys.exit(main())
