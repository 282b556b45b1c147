"""Time, on the chip, the parts of a latent layer that selects its positions
(``model/gpt_model.py`` ``LatentAttention`` with ``index_topk``), at
dots3-note's published widths, and hold each kernel to its ``jax.numpy``
twin there:

    chiprun -- python3 scripts/time_dsa_parts.py [--held 8192 32768]

One JSON line a part: ``ms`` (the median of ``--repeat`` runs that end in
``block_until_ready``) and, where the part has a twin, ``max_diff`` against
it.  A decode's parts at 16 rows holding ``held`` positions each: the index
scores, the top-2,048, the gather of the selected rows, the absorbed core
over them.  A chunk's at 1,024 queries that end at ``held``: the index
scores, the selection's mask, the expanded core under it.
"""
import argparse
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
from alpa_tpu.ops import latent_attention as la  # noqa: E402

ROWS, CONTEXT, TOPK = 16, 32768, 2048
HEADS, RANK, DN, DR, DV = 128, 512, 128, 64, 128
J, DI = 64, 128
SCALE = (DN + DR) ** -0.5


def rnd(i, *shape, dtype=jnp.bfloat16):
    return jax.random.normal(jax.random.PRNGKey(i), shape,
                             jnp.float32).astype(dtype)


def timed(name, fn, *args, repeat, twin=None, **more):
    fn = jax.jit(fn)
    out = jax.block_until_ready(fn(*args))
    times = []
    for _ in range(repeat):
        tic = time.perf_counter()
        jax.block_until_ready(fn(*args))
        times.append(time.perf_counter() - tic)
    line = {"part": name, "ms": round(1e3 * statistics.median(times), 3),
            **more}
    if twin is not None:
        want = jax.jit(twin)(*args)
        seen = jnp.isfinite(want)
        line["max_diff"] = float(jnp.abs(jnp.where(
            seen, out.astype(jnp.float32) - want.astype(jnp.float32),
            0.0)).max())
        line["same_unseen"] = bool((jnp.isfinite(out) == seen).all())
    print(json.dumps(line), flush=True)
    return out


def main():
    parser = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    parser.add_argument("--held", type=int, nargs="+",
                        default=[8192, 32768])
    parser.add_argument("--repeat", type=int, default=10)
    args = parser.parse_args()
    print(json.dumps({"device": jax.devices()[0].device_kind}), flush=True)
    keys = rnd(0, ROWS, CONTEXT, DI)
    rows = rnd(1, ROWS, CONTEXT, 640)
    w_kv_b = rnd(2, RANK, HEADS, DN + DV) * RANK ** -0.5
    for held in args.held:
        # --- a decode tick's selecting layer
        q = rnd(3, ROWS, 1, J, DI)
        w = rnd(4, ROWS, 1, J, dtype=jnp.float32)
        q_pos = jnp.full((ROWS, 1), held - 1, jnp.int32)
        scores = timed("decode index_scores", la.index_scores, q, w, keys,
                       q_pos, repeat=args.repeat, held=held,
                       twin=gm._index_scores_blocks)
        chosen, real = timed(
            "decode top_k", lambda s: gm.selected_positions(s[:, 0], TOPK),
            scores, repeat=args.repeat, held=held)
        taken = timed(
            "decode gather", lambda r, c: jnp.take_along_axis(
                r, c[:, :, None], axis=1), rows, chosen, repeat=args.repeat,
            held=held)
        q_lat, q_pe = rnd(5, ROWS, 1, HEADS, RANK), rnd(6, ROWS, 1, HEADS, DR)

        def core(kernel):
            def run(q_lat, q_pe, taken, real):
                return kernel(q_lat, q_pe, taken[..., :RANK],
                              taken[..., RANK:RANK + DR].swapaxes(1, 2),
                              real - 1, scale=SCALE)
            return run

        timed("decode absorbed over the selection", core(la.absorbed),
              q_lat, q_pe, taken, real, repeat=args.repeat, held=held,
              twin=core(gm._absorbed_core))
        # --- a chunk's, its 1,024 queries ending at ``held``
        start = held - 1024
        q = rnd(7, 1, 1024, J, DI)
        w = rnd(8, 1, 1024, J, dtype=jnp.float32)
        q_pos = start + jnp.arange(1024, dtype=jnp.int32)[None]
        scores = timed("chunk index_scores", la.index_scores, q, w,
                       keys[:1], q_pos, repeat=args.repeat, held=held,
                       twin=gm._index_scores_blocks)
        mask = timed("chunk selected_mask_upto",
                     lambda s: gm.selected_mask_upto(s, TOPK,
                                                     jnp.int32(held)),
                     scores, repeat=args.repeat, held=held)
        whole = timed("chunk selected_mask, the whole cache",
                      lambda s: gm.selected_mask(s, TOPK), scores,
                      repeat=args.repeat, held=held)
        print(json.dumps({"part": "chunk masks agree", "held": held,
                          "same": bool((mask == whole).all()),
                          "selected_min_max": [int(mask.sum(-1).min()),
                                               int(mask.sum(-1).max())]}),
              flush=True)
        q_nope, q_pe = rnd(9, 1, 1024, HEADS, DN), rnd(10, 1, 1024, HEADS, DR)
        k_pe = rows[:1, :, RANK:RANK + DR].swapaxes(1, 2)
        timed("chunk expanded under the mask",
              lambda qn, qp, r, kp, wk, m: la.expanded(
                  qn, qp, r, kp, wk, jnp.asarray([start], jnp.int32),
                  scale=SCALE, selected=m.astype(jnp.int8)),
              q_nope, q_pe, rows[:1], k_pe, w_kv_b, mask,
              repeat=args.repeat, held=held)
        if held <= 8192:
            # the twin holds every head's scores: short caches only
            short = held
            timed("chunk expanded under the mask, short cache",
                  lambda qn, qp, r, kp, wk, m: la.expanded(
                      qn, qp, r, kp, wk, jnp.asarray([start], jnp.int32),
                      scale=SCALE, selected=m.astype(jnp.int8)),
                  q_nope[:, :256], q_pe[:, :256], rows[:1, :short],
                  k_pe[:, :, :short], w_kv_b, mask[:, :256, :short],
                  repeat=3, held=held,
                  twin=lambda qn, qp, r, kp, wk, m:
                  gm._latent_attention_masked(
                      qn, qp, r[..., :RANK], kp, wk,
                      m & (jnp.arange(short)[None, None] <=
                           start + jnp.arange(256)[None, :, None]),
                      scale=SCALE))


if __name__ == "__main__":
    main()
