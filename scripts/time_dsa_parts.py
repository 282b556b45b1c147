"""Time, on the chip, the parts of a latent layer that selects its positions
(``model/gpt_model.py`` ``LatentAttention`` with ``index_topk``), at
dots3-note's published widths, and hold each kernel to its ``jax.numpy``
twin there:

    chiprun -- python3 scripts/time_dsa_parts.py [--held 8192 32768]
    chiprun -- python3 scripts/time_dsa_parts.py --selection
    chiprun -- python3 scripts/time_dsa_parts.py --cores [--held 2048 8192]
    chiprun -- python3 scripts/time_dsa_parts.py --chunk-core [--sweep]

One JSON line a part: ``ms`` (the median of ``--repeat`` runs that end in
``block_until_ready``) and, where the part has a twin, ``max_diff`` against
it.  A decode's parts at 16 rows holding ``held`` positions each: the index
scores, the top-2,048, the gather of the selected rows, the absorbed core
over them.  A chunk's at 1,024 queries that end at ``held``: the index
scores, the selection's mask, the expanded core under it.

``--selection``: a decode's selection alone, at dots3-note's shape (16 rows,
one query each, 32,768 positions) and GLM-5's (16 rows, two queries each,
24,576), the longest row holding a third of the cache and all of it:
``jax.lax.top_k`` as PR 53 ran it, the same over the queries folded into the
rows and the static leading part that holds them alone, ``selected_positions``
(counts and a compaction, no sort) over the whole and over that leading part,
and its parts (the counts; the compaction as
shipped, its block's counts fetched by a one-hot product; the same with a row
gather in the product's place), each compared with ``top_k`` as sets.

``--cores``: the two cores of a selecting layer's DECODE
(``gpt_model.latent_attention_over_selection``) at both selecting cells'
shapes (dots3-note: 16 rows, one query of 128 heads over 32,768 positions;
GLM-5: 16 rows, two queries of 64 heads over 24,576), every row holding
``held`` positions, ``--held`` from 2,048 to the whole cache: the gather of
each query's selected rows with the absorbed core over the copy, whose price
does not depend on what the rows hold, beside the table turned back into its
mask (``mask_of``) and the kernel that reads the cache as it lies under it
(``absorbed_under_mask``), whose price is the key blocks the rows hold; each
against the other (``max_diff``).  The last line of a shape is the fit that
``gpt_model.GATHER_WORTH_KEY_BLOCKS`` quotes: a (row, query)'s gather in key
blocks under the mask.  ``--tiny`` rehearses the same on a CPU at toy widths,
the kernel interpreted.

``--chunk-core``: the core of a selecting layer's CHUNK alone
(``la.expanded``) at both selecting cells' shapes (dots3-note: 1,024 queries
of 128 heads, keys and values of 128 channels, a cache of 32,768; GLM-5: 64
heads, keys of 192 channels handed in as 256 and values of 256, a cache of
24,576), the chunk ending at ``held`` (``--held``, and the whole cache):
under the selection's mask beside the same call without one, which walks the
same keys in blocks of 512 (ROADMAP A14(a)(1)'s comparison at one shape);
with a short cache, each against ``gm._latent_attention_masked``
(``max_diff``).  ``--sweep`` adds the masked core at key blocks of 512 and
1,024, and at 1,024 a step's queries in one, two and four parts: the readings
that ``la.SELECTED_BLOCK_K`` and ``la.SELECTED_PARTS`` rest on.  ``--tiny``
rehearses it on a CPU.
"""
import argparse
from functools import partial
import json
import os
import statistics
import sys
import time
from unittest import mock

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


def timed(name, fn, *args, repeat, twin=None, inner=1, lines=None, **more):
    """``inner``: calls dispatched back to back before the one wait, so
    that a part of well under a millisecond is not the host's dispatch.
    ``lines``: a list the printed line is appended to."""
    fn = jax.jit(fn)
    out = jax.block_until_ready(fn(*args))
    times = []
    for _ in range(repeat):
        tic = time.perf_counter()
        for _ in range(inner):
            last = fn(*args)
        jax.block_until_ready(last)
        times.append((time.perf_counter() - tic) / inner)
    line = {"part": name, "ms": round(1e3 * statistics.median(times), 3),
            **more}
    if twin is not None:
        want = jax.jit(twin)(*args)
        seen = jnp.isfinite(want)
        line["max_diff"] = float(jnp.abs(jnp.where(
            seen, out.astype(jnp.float32) - want.astype(jnp.float32),
            0.0)).max())
        line["same_unseen"] = bool((jnp.isfinite(out) == seen).all())
    if lines is not None:
        lines.append(line)
    print(json.dumps(line), flush=True)
    return out


def positions_of_by_gather(mask, k):
    """``gm.positions_of`` with the slot's block fetched by a gather of
    rows of ``LANES`` counts where the shipped one multiplies by a one-hot:
    the candidate that lost (PERF.md, PR 54)."""
    lanes = gm.LANES
    r, n = mask.shape
    blocks = -(-n // lanes)
    held = jnp.pad(mask, ((0, 0), (0, blocks * lanes - n))).reshape(
        r, blocks, lanes)
    inside = jnp.cumsum(held, axis=-1, dtype=jnp.int32)
    total = inside[..., -1]
    through = jnp.cumsum(total, axis=-1)
    slot = jax.lax.broadcasted_iota(jnp.int32, (1, k, 1), 1)
    ended = through[:, None, :] <= slot
    block = ended.sum(-1, dtype=jnp.int32)
    skipped = jnp.where(ended, total[:, None, :], 0).sum(-1)
    counts = jnp.take_along_axis(
        inside, jnp.minimum(block, blocks - 1)[..., None], axis=1)
    place = (counts <= slot - skipped[..., None]).sum(-1, dtype=jnp.int32)
    return jnp.minimum(block * lanes + place, n - 1)


def selection(repeat):
    """A decode's selection alone (module docstring)."""
    # twenty calls a wait: these parts take a few tenths of a millisecond
    time_part = partial(timed, repeat=repeat, inner=20)
    for rows, queries, context in ((16, 1, 32768), (16, 2, 24576)):
        for held in (context // 3, context):
            # the rows hold from half of ``held`` up to all of it
            lengths = held // 2 + (held - held // 2) * \
                jnp.arange(1, rows + 1) // rows
            q_pos = lengths[:, None] - queries + jnp.arange(queries)[None]
            scores = jnp.where(
                jnp.arange(context)[None, None] <= q_pos[..., None],
                rnd(11, rows, queries, context, dtype=jnp.float32), -jnp.inf)
            shape = {"shape": [rows, queries, context], "held": held}
            # the static leading part that holds them all
            # (``selected_mask_upto``'s lengths)
            part = next(n for n in (2 * TOPK, 4 * TOPK, 8 * TOPK, context)
                        if n >= min(held, context))
            # one query a row went in as (rows, Sk), two as (rows, 2, Sk)
            _, want = time_part(
                "decode top_k, as PR 53 ran it",
                lambda s: jax.lax.top_k(s, TOPK),
                scores[:, 0] if queries == 1 else scores, **shape)
            want = want.reshape(scores.shape[:-1] + (TOPK,))

            def same(name, got, real):
                hit = jnp.zeros(scores.shape, bool)
                put = jax.vmap(jax.vmap(lambda h, p: h.at[p].set(True)))
                live = jnp.arange(TOPK) < real[..., None]
                print(json.dumps({
                    "part": name + ": the set top_k names", **shape,
                    "same": bool(((put(hit, got) & (scores > -jnp.inf)) ==
                                  (put(hit, want) & (scores > -jnp.inf))
                                  ).all()),
                    "ascending": bool(((jnp.diff(got, axis=-1) > 0) |
                                       ~live[..., 1:]).all()),
                    "real_min_max": [int(real.min()), int(real.max())]}),
                      flush=True)

            time_part("decode top_k, queries folded, the leading part",
                      lambda s: jax.lax.top_k(
                          s.reshape(-1, context)[:, :part], TOPK), scores,
                      leading=part, **shape)
            got, real = time_part(
                "decode selected_positions (no sort)",
                lambda s: gm.selected_positions(s, TOPK), scores, **shape)
            same("selected_positions", got, real)
            time_part("decode selected_positions, the leading part alone",
                      lambda s: gm.selected_positions(s[..., :part], TOPK),
                      scores, leading=part, **shape)
            folded = scores.reshape(-1, context)[:, :part]
            mask = time_part(
                "  its counts: selected_mask, the leading part",
                lambda s: gm.selected_mask(s, TOPK), folded,
                leading=part, **shape)
            for name, compact in (
                    ("  its compaction: a one-hot product (shipped)",
                     gm.positions_of),
                    ("  its compaction: a row gather",
                     positions_of_by_gather)):
                table = time_part(name, lambda m: compact(m, TOPK), mask,
                                  leading=part, **shape)
                same(name.strip(), table.reshape(got.shape), real)


def cores(repeat, helds, tiny):
    """A decode's two cores over one selection (module docstring)."""
    time_part = partial(timed, repeat=repeat, inner=1 if tiny else 10)
    block_k = la.DECODE_BLOCK_K
    shapes = [(16, 1, 128, 32768), (16, 2, 64, 24576)]
    rank, dn, dv, topk = RANK, DN, DV, TOPK
    if tiny:
        shapes, rank, dn, dv, topk = [(2, 2, 16, 4096)], 128, 128, 128, 256
        helds = [1024, 4096]
    width = gm.latent_row_width(rank, DR)
    for rows, queries, heads, context in shapes:
        cache = rnd(1, rows, context, width)
        w_kv_b = rnd(2, rank, heads, dn + dv) * rank ** -0.5
        q_nope = rnd(5, rows, queries, heads, dn)
        q_pe = rnd(6, rows, queries, heads, DR)
        shape = {"shape": [rows, queries, heads, context]}
        points = []
        for held in [h for h in helds if topk <= h < context] + [context]:
            index = jnp.full((rows,), held - queries, jnp.int32)
            q_pos = index[:, None] + jnp.arange(queries)[None]
            scores = jnp.where(
                jnp.arange(context)[None, None] <= q_pos[..., None],
                rnd(11, rows, queries, context, dtype=jnp.float32), -jnp.inf)
            positions, real = jax.jit(
                lambda s: gm.selected_positions(s, topk))(scores)
            more = dict(shape, held=held,
                        key_blocks=rows * -(-held // block_k))
            chosen = time_part(
                "decode mask_of (the table back to its mask)",
                lambda p, r: gm.mask_of(p.reshape(rows * queries, topk),
                                        r.reshape(rows * queries), context),
                positions, real, **more)
            chosen = chosen.reshape(rows, queries, context)
            q_lat = jnp.einsum("bqhd,rhd->bqhr", q_nope, w_kv_b[..., :dn])
            time_part("decode absorbed_under_mask, the kernel alone",
                      partial(la.absorbed_under_mask, scale=SCALE,
                              interpret=tiny),
                      q_lat, q_pe, cache, chosen, index, **more)
            args = (q_nope, q_pe, cache, w_kv_b, index, positions, real)

            def under_mask(qn, qp, c, w, i, p, r):
                # the core a cache of few blocks takes whatever it holds
                with mock.patch.object(gm, "GATHER_WORTH_KEY_BLOCKS",
                                       context):
                    return gm.latent_attention_over_selection(
                        qn, qp, c, w, SCALE, i, p, r, interpret=tiny)

            def gathered(qn, qp, c, w, i, p, r):
                return gm.latent_attention_gathered(qn, qp, c, w, SCALE, p, r)

            whole = []
            time_part("decode under the mask, whole", under_mask, *args,
                      twin=gathered, lines=whole, **more)
            time_part("decode gather + absorbed, whole", gathered, *args,
                      lines=whole, **more)
            points.append((more["key_blocks"], whole[0]["ms"],
                           whole[1]["ms"]))
        # under the mask: ms = fixed + a_block * key blocks (least squares)
        n = len(points)
        mean_x = sum(p[0] for p in points) / n
        mean_y = sum(p[1] for p in points) / n
        a_block = sum((p[0] - mean_x) * (p[1] - mean_y) for p in points) / \
            max(sum((p[0] - mean_x) ** 2 for p in points), 1e-9)
        fixed = mean_y - a_block * mean_x
        gather = statistics.median(p[2] for p in points)
        print(json.dumps(dict(
            shape, part="a (row, query)'s gather in key blocks under the mask",
            under_mask_fixed_ms=round(fixed, 4),
            under_mask_us_a_key_block=round(1e3 * a_block, 3),
            gathered_ms=gather,
            worth_key_blocks=round(
                (gather - fixed) / max(a_block, 1e-9) / (rows * queries),
                1))), flush=True)


def chunk_core(repeat, helds, tiny, sweep):
    """A chunk's expanded core with and without its mask (module
    docstring)."""
    sq, rank, topk = 1024, RANK, TOPK
    # heads, a key's channels as the kernel takes them, a value's, the cache
    shapes = {"dots3-note": (128, 128, 128, 32768),
              "glm-5": (64, 256, 256, 24576)}
    if tiny:
        sq, rank, topk = 64, 128, 128
        shapes, helds = {"toy": (4, 128, 128, 2048)}, [1024]
    width = gm.latent_row_width(rank, DR)

    def core(start, masked):
        def run(qn, qp, r, kp, wk, m):
            return la.expanded(
                qn, qp, r, kp, wk, jnp.asarray([start], jnp.int32),
                scale=SCALE, interpret=tiny,
                selected=m.astype(jnp.int8) if masked else None)
        return run

    def twin(start, masked):
        def run(qn, qp, r, kp, wk, m):
            seen = jnp.arange(r.shape[1])[None, None] <= \
                start + jnp.arange(qn.shape[1])[None, :, None]
            return gm._latent_attention_masked(
                qn, qp, r[..., :rank], kp, wk, seen & m if masked else seen,
                scale=SCALE)
        return run

    for name, (heads, dn, dv, context) in shapes.items():
        rows = rnd(1, 1, context, width)
        k_pe = rows[:, :, rank:rank + DR].swapaxes(1, 2)
        w_kv_b = rnd(2, rank, heads, dn + dv) * rank ** -0.5
        q_nope, q_pe = rnd(9, 1, sq, heads, dn), rnd(10, 1, sq, heads, DR)
        for held in [h for h in helds if sq <= h < context] + [context]:
            start = held - sq
            q_pos = start + jnp.arange(sq, dtype=jnp.int32)[None]
            scores = jnp.where(
                jnp.arange(context)[None, None] <= q_pos[..., None],
                rnd(11, 1, sq, context, dtype=jnp.float32), -jnp.inf)
            mask = jax.jit(lambda s: gm.selected_mask_upto(
                s, topk, jnp.int32(held)))(scores)
            more = {"shape": name, "heads": heads, "held": held}
            args = (q_nope, q_pe, rows, k_pe, w_kv_b, mask)
            for masked, part in ((True, "chunk expanded under the mask"),
                                 (False, "chunk expanded, no mask")):
                timed(part, core(start, masked), *args, repeat=repeat,
                      **more)
                if held <= 8192:
                    # the twin holds every head's scores: a quarter of the
                    # queries over a cache that ends with the chunk
                    few = sq // 4
                    timed(part + ", short cache", core(start, masked),
                          q_nope[:, :few], q_pe[:, :few], rows[:, :held],
                          k_pe[:, :, :held], w_kv_b, mask[:, :few, :held],
                          repeat=3, twin=twin(start, masked), **more)
            if not sweep:
                continue
            shipped = la.SELECTED_BLOCK_K, la.SELECTED_PARTS
            for block_k, parts in ((512, 1), (1024, 1), (1024, 2), (1024, 4)):
                la.SELECTED_BLOCK_K, la.SELECTED_PARTS = block_k, parts
                timed("chunk expanded under the mask, swept",
                      core(start, True), *args, repeat=repeat,
                      block_k=block_k, parts=parts, **more)
            la.SELECTED_BLOCK_K, la.SELECTED_PARTS = shipped


def main():
    parser = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    parser.add_argument("--held", type=int, nargs="+",
                        default=[8192, 32768])
    parser.add_argument("--repeat", type=int, default=10)
    parser.add_argument("--selection", action="store_true",
                        help="a decode's selection alone, both shapes")
    parser.add_argument("--cores", action="store_true",
                        help="a decode's two cores over one selection, both "
                        "shapes")
    parser.add_argument("--chunk-core", action="store_true",
                        help="a chunk's expanded core with and without its "
                        "mask, both shapes")
    parser.add_argument("--sweep", action="store_true",
                        help="with --chunk-core: the masked core by key "
                        "block and by the parts of a step's queries")
    parser.add_argument("--tiny", action="store_true",
                        help="with --cores or --chunk-core: toy widths, the "
                        "kernel interpreted (a CPU rehearsal)")
    args = parser.parse_args()
    print(json.dumps({"device": jax.devices()[0].device_kind}), flush=True)
    if args.selection:
        return selection(args.repeat)
    if args.cores:
        return cores(args.repeat, args.held, args.tiny)
    if args.chunk_core:
        return chunk_core(args.repeat, args.held, args.tiny, args.sweep)
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
