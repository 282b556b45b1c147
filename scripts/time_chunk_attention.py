"""Time, on the chip, the attention core of a prefill chunk's full layers
over the written cache (``model/gpt_model.py`` ``cached_attention`` /
``folded_cached_attention`` at more than a few new queries a row), at
MiMo-V2-Flash's and Trinity-Mini's published widths, and hold each core to
the float32 reference there:

    chiprun -- python3 scripts/time_chunk_attention.py
    chiprun -- python3 scripts/time_chunk_attention.py --blocks 2048x512 \
        2048x1024 4096x512 4096x2048 --padded-pairs

One JSON line a shape, a chunk's start and a core: ``ms`` a call (the
median of ``--repeat`` runs of ``--inner`` calls that end in one
``block_until_ready``), ``peak_pct``, the share of 197 TFLOP/s that is,
counting the heads' own products over the keys the chunk's queries see (2 x
(Dk + Dv) a query head a visible key), and ``max_diff``, the largest
absolute difference from ``reference_attention`` computed in float32 from
the same bfloat16 operands.  The cores: ``query_key_blocks``, the kernel
``ops/cached_attention.py`` ``chunk_attention`` (what a program lowered for
a TPU runs since PR 56), and ``before``, the core the same call took until
then (MiMo: the walk over key blocks in ``jax.numpy``,
``_attention_over_folded_blocks``; Trinity: ``reference_attention`` over
every position the cache can hold).

``--blocks RxK``: the kernel again with ``QUERY_ROWS`` R and
``CHUNK_BLOCK_K`` K in place of the module's.  ``--padded-pairs``: at
MiMo's shape, the kernel with the queries zero-padded into a PAIR of
key/value heads' 384 channels (three whole lane tiles, no slice inside the
kernel) and the pair's 256 value channels a product, of which a head keeps
its half: twice the heads' own products, the alternative to cutting a
head's 192 channels out of the block.
"""
import argparse
from functools import partial
import json
import os
import statistics
import sys
import time

import jax
import jax.numpy as jnp

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
from alpa_tpu.model import gpt_model as gm  # noqa: E402
from alpa_tpu.ops import cached_attention as ca  # noqa: E402

CHUNK = 1024
PEAK_FLOPS = 197e12
# name: (query heads, key/value heads, Dk, Dv, served context, the caches'
# heads folded into the channels, chunk starts)
SHAPES = {
    "mimo": (64, 4, 192, 128, 32768, True, (0, 7168, 27648)),
    "trinity": (32, 4, 128, 128, 16384, False, (0, 3072, 15360)),
}


def rnd(i, *shape):
    return jax.random.normal(jax.random.PRNGKey(i), shape,
                             jnp.float32).astype(jnp.bfloat16)


def own_flops(heads, dk, dv, start):
    """The heads' own products over the keys a chunk at ``start`` sees."""
    seen = CHUNK * start + CHUNK * (CHUNK + 1) // 2
    return 2 * heads * (dk + dv) * seen


def timed(fn, *args, repeat, inner):
    # (a function of its own: whatever ``fn`` reads of its module when it
    # is traced is read again)
    traced = jax.jit(lambda *args: fn(*args))
    out = jax.block_until_ready(traced(*args))
    times = []
    for _ in range(repeat):
        tic = time.perf_counter()
        for _ in range(inner):
            last = traced(*args)
        jax.block_until_ready(last)
        times.append((time.perf_counter() - tic) / inner)
    return out, statistics.median(times)


def reference(q, k, v, offset):
    return gm.reference_attention(q, k, v, causal=True, offset=offset)


def padded_pairs(q, k_cache, v_cache, offset, interpret=False):
    """``chunk_attention`` with each query zero-padded into its PAIR of
    key/value heads' channels: the folded caches seen as ``Hkv / 2`` heads
    of twice the channels, of whose values a head keeps its half."""
    b, s, nh, dim = q.shape
    nkv = k_cache.shape[2] // dim
    dv = v_cache.shape[2] // nkv
    half = jax.nn.one_hot(jnp.arange(nh) // (nh // nkv) % 2, 2,
                          dtype=q.dtype)                       # (H, 2)
    # the kernel scales by the padded width's root: undo it here (exact
    # in float32, once rounded to bfloat16)
    wide = (q.astype(jnp.float32) * 2 ** 0.5).astype(q.dtype)
    wide = (wide[:, :, :, None, :] * half[:, :, None]).reshape(
        b, s, nh, 2 * dim)
    out = ca.chunk_attention(wide, k_cache, v_cache, offset,
                             interpret=interpret)
    return jnp.einsum("bshpd,hp->bshd", out.reshape(b, s, nh, 2, dv), half)


def main():
    parser = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    parser.add_argument("--shapes", nargs="+", default=list(SHAPES),
                        choices=list(SHAPES))
    parser.add_argument("--repeat", type=int, default=10)
    parser.add_argument("--inner", type=int, default=4)
    parser.add_argument("--blocks", nargs="*", default=[],
                        help="QUERY_ROWSxCHUNK_BLOCK_K to time besides "
                        "the module's")
    parser.add_argument("--padded-pairs", action="store_true")
    parser.add_argument("--tiny", action="store_true",
                        help="a rehearsal of the control flow off the "
                        "chip: contexts of 2,048, the kernel interpreted")
    args = parser.parse_args()
    device = jax.devices()[0]
    print(json.dumps({"device": device.platform,
                      "kind": device.device_kind}), flush=True)
    run = partial(timed, repeat=args.repeat, inner=args.inner)
    for name in args.shapes:
        heads, nkv, dk, dv, context, folded, starts = SHAPES[name]
        if args.tiny:
            context, starts = 2048, (0, 1024)
        q = rnd(0, 1, CHUNK, heads, dk)
        k, v = rnd(1, 1, context, nkv, dk), rnd(2, 1, context, nkv, dv)
        if folded:
            k, v = (x.reshape(1, context, -1) for x in (k, v))
            before = gm._attention_over_folded_blocks
        else:
            before = reference
        assert ca.chunk_fits(q, k, v)
        kernel = partial(ca.chunk_attention, interpret=args.tiny)
        cores = [("query_key_blocks", kernel, {}), ("before", before, {})]
        for spec in args.blocks:
            rows, block_k = (int(x) for x in spec.split("x"))
            cores.append((f"query_key_blocks[{spec}]", kernel,
                          {"QUERY_ROWS": rows, "CHUNK_BLOCK_K": block_k}))
        if args.padded_pairs and folded:
            cores.append(("padded_pairs",
                          partial(padded_pairs, interpret=args.tiny), {}))
        for start in starts:
            offset = jnp.int32(start)
            # the float32 reference over the positions the chunk sees
            f32 = [x.astype(jnp.float32) for x in (q, k, v)]
            want = jax.jit(before)(f32[0], f32[1][:, :start + CHUNK],
                                   f32[2][:, :start + CHUNK], offset)
            for core, fn, constants in cores:
                saved = {c: getattr(ca, c) for c in constants}
                for c, value in constants.items():
                    setattr(ca, c, value)
                try:
                    out, seconds = run(fn, q, k, v, offset)
                finally:
                    for c, value in saved.items():
                        setattr(ca, c, value)
                print(json.dumps({
                    "shape": name, "start": start, "core": core,
                    "ms": round(1e3 * seconds, 3),
                    "peak_pct": round(100 * own_flops(
                        heads, dk, dv, start) / seconds / PEAK_FLOPS, 1),
                    "max_diff": float(jnp.abs(
                        out.astype(jnp.float32) - want).max())}),
                    flush=True)


if __name__ == "__main__":
    main()
