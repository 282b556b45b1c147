"""Time the attention cores on the chip, forward plus backward, by shape.

    chiprun -- python3 scripts/time_attention_cores.py

One line of JSON a (shape, core): milliseconds a call of
``value_and_grad`` (the mean of ``--calls`` back-to-back calls behind a
warm-up, the last one waited for), and the largest absolute difference of
the output and the three gradients from ``reference_attention`` on the
same inputs in float32.  The cores: ``reference_attention``; the kernels
of ``alpa_tpu/ops/flash_attention.py`` at several blocks; jax's own
``pallas.ops.tpu.flash_attention`` at several blocks.  The shapes: 8,192
tokens a call at 512 to 4,096 positions a row, heads of 64 (32 of them)
and of 128 (16); with ``--checkpoint`` the core under ``jax.checkpoint``
(the forward pass runs twice, as in a rematerialised block).

PERF.md section 6 (PR 45) holds what it printed on the v5e.
"""
import argparse
import json
import os
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental.pallas.ops.tpu import flash_attention as jax_flash

from alpa_tpu.model.gpt_model import reference_attention
from alpa_tpu.ops import flash_attention as ours


TOKENS = 8192


def jax_core(block):
    def core(q, k, v):
        seq, dim = q.shape[1], q.shape[3]
        size = min(block, seq)
        sizes = jax_flash.BlockSizes(
            block_q=size, block_k_major=size, block_k=size, block_b=1,
            block_q_major_dkv=size, block_k_major_dkv=size,
            block_k_dkv=size, block_q_dkv=size, block_k_major_dq=size,
            block_k_dq=size, block_q_dq=size)
        out = jax_flash.flash_attention(
            *(x.transpose(0, 2, 1, 3) for x in (q, k, v)), causal=True,
            sm_scale=float(1 / np.sqrt(dim)), block_sizes=sizes)
        return out.transpose(0, 2, 1, 3)
    return core


def our_core(block):
    return lambda q, k, v: ours.flash_attention(
        q, k, v, causal=True, block_q=block, block_k=block)


def reference_core(q, k, v):
    return reference_attention(q, k, v, causal=True)


def main():
    parser = argparse.ArgumentParser()
    parser.add_argument("--calls", type=int, default=20)
    parser.add_argument("--seqs", type=int, nargs="+",
                        default=[512, 1024, 2048, 4096])
    parser.add_argument("--dims", type=int, nargs="+", default=[64, 128])
    parser.add_argument("--blocks", type=int, nargs="+",
                        default=[256, 512, 1024])
    parser.add_argument("--checkpoint", action="store_true")
    parser.add_argument("--cores", nargs="+",
                        default=["reference", "ours", "jax"])
    args = parser.parse_args()
    device = jax.devices()[0]
    cores = [("reference", reference_core)]
    cores += [(f"ours-{b}", our_core(b)) for b in args.blocks]
    cores += [(f"jax-{b}", jax_core(b)) for b in args.blocks]
    cores = [c for c in cores if c[0].split("-")[0] in args.cores]
    for dim in args.dims:
        for seq in args.seqs:
            shape = (TOKENS // seq, seq, 2048 // dim, dim)
            keys = jax.random.split(jax.random.PRNGKey(seq + dim), 4)
            q, k, v, w = (jax.random.normal(key, shape, jnp.float32)
                          for key in keys)

            def graded(core):
                if args.checkpoint:
                    core = jax.checkpoint(core)

                def loss(q, k, v):
                    return jnp.sum(core(q, k, v).astype(jnp.float32) * w)
                return jax.jit(jax.value_and_grad(loss, argnums=(0, 1, 2)))

            exact = jax.tree_util.tree_leaves(
                graded(reference_core)(q, k, v))
            half = tuple(x.astype(jnp.bfloat16) for x in (q, k, v))
            for name, core in cores:
                line = {"shape": shape, "core": name,
                        "checkpoint": args.checkpoint,
                        "device": device.device_kind}
                try:
                    step = graded(core)
                    got = jax.block_until_ready(step(*half))
                    line["max_abs_diff"] = [
                        float(jnp.max(jnp.abs(a.astype(jnp.float32) - b)))
                        for a, b in zip(jax.tree_util.tree_leaves(got),
                                        exact)]
                    jax.block_until_ready(step(*half))
                    tic = time.perf_counter()
                    for _ in range(args.calls):
                        got = step(*half)
                    jax.block_until_ready(got)
                    line["ms"] = 1e3 * (time.perf_counter() - tic) / \
                        args.calls
                except Exception as e:  # pylint: disable=broad-except
                    line["error"] = str(e)[:300]
                print(json.dumps(line), flush=True)


if __name__ == "__main__":
    main()
