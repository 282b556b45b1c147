"""Flash-vs-XLA attention at long sequence lengths (fwd+bwd), on-chip.

The regime where blocked attention should win: XLA's reference path
materializes the (B, H, S, S) score tensor in HBM (fp32), so its HBM
traffic grows as S^2 while flash stays O(S * D).  Each case is memory-
estimated first and skipped above ``SAFE_HBM_GB``.  One process:

    python scripts/flash_longseq_bench.py

Prints one JSON line per (impl, seq, blocks) case.
"""
import json
import time

import jax
import jax.numpy as jnp
import numpy as np

from alpa_tpu.model.gpt_model import reference_attention
from alpa_tpu.ops.flash_attention import flash_attention

SAFE_HBM_GB = 10.0


def est_hbm_gb(impl, b, s, h, d, dtype_bytes=2):
    qkv = 3 * b * s * h * d * dtype_bytes
    grads = qkv + b * s * h * d * dtype_bytes
    if impl == "reference":
        # fp32 S^2 temporaries across fwd+bwd: scores, probs (saved for
        # the backward), dprobs, dscores — ~4 live buffers at peak
        scores = 4 * b * h * s * s * 4
    else:
        scores = b * h * s * 2 * 4  # lse + delta rows
    return (qkv + grads + scores) / 1e9


def run_case(impl, s, b=1, h=8, d=64, block_q=256, block_k=256, n_iter=10):
    est = est_hbm_gb(impl, b, s, h, d)
    if est > SAFE_HBM_GB:
        print(json.dumps({"impl": impl, "seq": s,
                          "skipped": f"est {est:.1f} GB > {SAFE_HBM_GB}"}),
              flush=True)
        return
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    q, k, v = (jax.random.normal(kk, (b, s, h, d), jnp.bfloat16) * 0.5
               for kk in ks)

    if impl == "reference":
        attn = lambda q, k, v: reference_attention(q, k, v, causal=True)
    else:
        attn = lambda q, k, v: flash_attention(
            q, k, v, causal=True, block_q=block_q, block_k=block_k)

    def loss(q, k, v):
        return (attn(q, k, v).astype(jnp.float32) ** 2).sum()

    step = jax.jit(jax.grad(loss, argnums=(0, 1, 2)))
    g = step(q, k, v)
    float(g[0][0, 0, 0, 0])  # compile + settle
    tic = time.perf_counter()
    for _ in range(n_iter):
        g = step(q, k, v)
    float(g[0][0, 0, 0, 0])
    lat = (time.perf_counter() - tic) / n_iter
    # causal fwd: qk + pv = 2 * 2*b*h*s^2*d * 0.5; bwd ~2.5x fwd
    flops = 3.5 * 2 * b * h * s * s * d
    print(json.dumps({
        "impl": impl, "seq": s, "batch": b, "heads": h,
        "blocks": [block_q, block_k] if impl == "flash" else None,
        "latency_s": round(lat, 5),
        "tflops": round(flops / lat / 1e12, 2),
        "est_hbm_gb": round(est, 2),
    }), flush=True)


def main():
    import sys
    which = sys.argv[1] if len(sys.argv) > 1 else "compare"
    if which == "compare":
        for s in (2048, 4096):
            run_case("reference", s)
            run_case("flash", s)
    elif which == "blocks":
        for bq, bk in ((128, 128), (256, 256), (512, 512), (256, 512),
                       (512, 1024)):
            run_case("flash", 4096, block_q=bq, block_k=bk)
    elif which == "long":
        # flash-only: XLA's S^2 scores no longer fit here
        for s in (8192, 16384):
            run_case("flash", s)
            run_case("reference", s)  # will skip via the gate at 16k


if __name__ == "__main__":
    main()
