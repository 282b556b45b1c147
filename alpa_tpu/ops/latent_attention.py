"""Latent attention (MLA) over a latent cache: the expanded core of a
prefill's chunk and the absorbed core of a decode, one Pallas kernel each,
and the index scores of a layer that selects its positions.

**Expanded** (``expanded``).

What a prefill's chunk needs of ``model/gpt_model.py`` ``LatentAttention``:
many new queries against every cached position so far, in the published
(expanded) form.  The cache holds a position's normed latent ``c`` and its
shared rotated key ``k_pe``; a head's keys and values are ``c W_kv_b``.
Written in ``jax.numpy`` over key blocks (``gpt_model.
_latent_attention_blocks``, which this equals) a block's ``heads x queries x
keys`` float32 scores go through the chip's memory three times (the row
maximum, the exponentials, their sum and product with the values): 4.3 ms a
layer a block of 1,024 keys at DeepSeek-V2's 128 heads, of which the matrix
unit works 0.6 ms (PERF.md, PR 32).  Here they never leave the kernel's
fast memory:

Grid ``(rows, heads, key blocks)``.  One program holds ONE head's queries
(all ``Sq`` of them, ``q_nope`` and ``q_pe``) and that head's slice of
``W_kv_b``; the key-block axis is the innermost, sequential one, and the
running maximum, sum and weighted values of the online softmax live in
scratch across it (the structure of ``ops/flash_attention.py``'s streaming
kernel).  A step fetches one block of latents ``(block_k, r)`` and of shared
keys ``(dr, block_k)``, EXPANDS the block for its head in the kernel (``c
W``: ``block_k x (dn + dv)``, no per-head key or value ever in HBM), scores
``q_nope k_nope^T + q_pe k_pe``, masks by position and folds the block in.

How far a row's queries see is data (a chunk's start): ``blocks[b]``, the
key blocks row ``b`` needs, and ``offset[b]``, the position of its first
query, are prefetched scalars.  Steps past ``blocks[b]`` compute nothing
and, their block index clamped to the last needed one, fetch nothing.

**Absorbed** (``absorbed``).  What a decode needs: ONE new query a row
against the row's cache, the expansion absorbed into the query
(``gpt_model.latent_attention_absorbed``).  In ``jax.numpy`` it is two
products over every row's whole cache with a float32 softmax between them:
each reads the latents of all ``Sk`` positions whatever the rows hold, and
the ``rows x heads x Sk`` scores go through memory (18 ms of a 26.5 ms
tick at 32 rows of 16,384 positions, PERF.md, PR 32).  Here the grid is
``(rows, key blocks)``: a program holds one row's ``heads x (r + dr)``
query, a step fetches a block of the row's latents and shared keys ONCE
for all heads, scores, and folds ``probabilities x latents`` into the
running ``heads x r`` output; steps past the row's newest position fetch
and compute nothing, so a tick reads what its rows hold and no more.  At
128 heads a step's operations and bytes balance on a v5e (242 a byte).

**Under a selection** (``expanded(selected=...)``, ``index_scores``).  A
latent layer that selects its positions (``GPTConfig.index_topk``) scores
every position a row holds for every query, ``I[t, s] = sum_j w[t, j]
relu(q[t, j] . k[s])`` over 64 index heads, and attends over the 2,048
best.  In ``jax.numpy`` a chunk's products are ``queries x heads x keys``
float32 before the heads are summed, 8.6 GB at 1,024 queries of 32,768
keys; ``index_scores`` reduces them in fast memory: a chunk's grid is
``(rows, query blocks, key blocks)``, a step 64 products of one head's
block of queries with a block of index keys, weighted and summed; a
decode's is ``(rows, key blocks)``, a step ONE product of all heads'
queries with a block of the row's keys, reduced over the heads in the
kernel (a few queries a row, a verify of a tick that drafts: the same
grid, the block fetched once and scored a query in turn).  Key blocks past a row's last query are filled with ``-inf`` and
not fetched.  A chunk then runs ``expanded`` with the selection as one more
operand, a block of (queries, keys) int8 a head a step beside the block's
latents and shared keys, in key blocks of 1,024 (``SELECTED_BLOCK_K``).
What a step costs beside its products is what it does a ROW of the scores,
above all the reductions across lanes (at 1,024 queries of 512 keys 2.2 of
a step's 5.3 us, the exponentials and the mask nothing; PERF.md, PR 62):
key blocks of 1,024 halve those a key.  With them out of the way the mask's
passes over the scores count, so a hidden key is hidden by ONE ``where`` on
the scores (``_HIDDEN``) and the block folded in as if all were seen.  A
step of 1,024 keys takes its queries in four parts, one after another
(``SELECTED_PARTS``), so that its unrolled code is no longer than a step's
of 512.  A decode (one query a row, or a verify's few) has two cores
(``gpt_model.
latent_attention_over_selection`` says which a call takes): it gathers each
query's selected rows and runs ``absorbed`` over the copy as over a cache
of 2,048 positions, the real ones first, at a fixed price a (row, query)
(84 MB of copies a GLM-5 block at a tenth of the chip's bandwidth, PERF.md,
PR 54); or it runs ``absorbed_under_mask`` over the cache as it lies, the
selection one more operand, a block of (queries, keys) int8 a step: no
copy, a row's key blocks read once for all its queries and heads and only
as far as the row has written, a price by what the rows hold.

The kernels are compiled where the program is lowered for a TPU
(``gpt_model`` chooses between each and its ``jax.numpy`` twin with
``lax.platform_dependent``); ``interpret=True`` runs them anywhere, for the
tests.
"""
import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# keys a step of the expanded kernel: with 1,024 queries a step's float32
# scores are 2 MB
BLOCK_K = 512
# and of the kernel under a selection: what a step does a ROW of its scores
# (the reductions across lanes of the running maximum and sum, the
# rescaling of what it holds) it does half as often a key; at dots3-note's
# widths that is a third of the kernel (PERF.md, PR 62)
SELECTED_BLOCK_K = 1024
# and the parts a step under a selection takes its queries in: a step's
# code is unrolled over its scores, and a program's code lies in the chip's
# memory, which GLM-5's cell fills to the last MB; in parts of a quarter a
# step of 1,024 keys is less code than one of 512 was, at a twelfth more
# time.  ``scripts/time_dsa_parts.py --chunk-core --sweep`` times both
SELECTED_PARTS = 4
# and of the absorbed one, whose step is a microsecond of work: 1 MB of
# latents, so that a step's fixed cost stays a small part of it
DECODE_BLOCK_K = 1024
# of the chip's 128 MiB of fast memory, what one program may take: the
# queries, the scores and their exponentials, two blocks in flight
VMEM_LIMIT = 48 * 2**20
_FLOOR = -1e30
# the score of a key that a query of the chunk's kernel under a selection
# does not see: below any running maximum, so it never is one
_HIDDEN = 2 * _FLOOR
# what a device trace calls the decode's kernel under a selection's mask,
# and the chunk's
UNDER_MASK_NAME = "latent_decode_under_mask"
CHUNK_UNDER_MASK_NAME = "latent_chunk_under_mask"


def fits(q_nope, c, w_kv_b, masked: bool = False) -> bool:
    """Whether the kernel takes these shapes: the heads' channels and the
    latent's in whole lanes of 128, the queries (``masked``, under a
    selection: each part of them a step takes) in whole sublanes, the cache
    in whole key blocks."""
    sq, dn = q_nope.shape[1], q_nope.shape[3]
    rank, dv = w_kv_b.shape[0], w_kv_b.shape[2] - dn
    block_k, parts = (SELECTED_BLOCK_K, SELECTED_PARTS) if masked else \
        (BLOCK_K, 1)
    return (dn % 128 == 0 and dv % 128 == 0 and rank % 128 == 0 and
            sq % (16 * parts) == 0 and c.shape[1] % block_k == 0)


def _start(m_ref, l_ref, acc_ref):
    m_ref[:] = jnp.full_like(m_ref, _FLOOR)
    l_ref[:] = jnp.zeros_like(l_ref)
    acc_ref[:] = jnp.zeros_like(acc_ref)


def _fold_in(s, seen, values, m_ref, l_ref, acc_ref, keys_last=False,
             at=slice(None)):
    """One block of the online softmax: scores ``s`` (queries, keys)
    float32 of which ``seen`` count (None: all), and the keys' ``values``
    (keys, d), or (d, keys) with ``keys_last``, into the running maximum,
    sum and weighted values (the rows ``at`` of each: all)."""
    m_prev = m_ref[at]
    if seen is None:
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        p = jnp.exp(s - m_new)
    else:
        m_new = jnp.maximum(
            m_prev,
            jnp.max(jnp.where(seen, s, _FLOOR), axis=1, keepdims=True))
        p = jnp.where(seen, jnp.exp(s - m_new), 0.0)
    keep = jnp.exp(m_prev - m_new)
    m_ref[at] = m_new
    l_ref[at] = l_ref[at] * keep + jnp.sum(p, axis=1, keepdims=True)
    acc_ref[at] = acc_ref[at] * keep + lax.dot_general(
        p.astype(values.dtype), values,
        (((1,), (1 if keys_last else 0,)), ((), ())),
        preferred_element_type=jnp.float32)


def _finish(o_ref, l_ref, acc_ref):
    o_ref[:] = (acc_ref[:] / jnp.maximum(l_ref[:], 1e-30)).astype(
        o_ref.dtype)


def _block_of(b, kb, blocks_ref):
    """The key block step ``kb`` of row ``b`` fetches: its own while the
    row needs it, the last needed one after (the same again: no fetch)."""
    return jnp.minimum(kb, blocks_ref[b] - 1)


def _kernel(blocks_ref, offset_ref, qn_ref, qp_ref, c_ref, kpe_ref, w_ref,
            *rest, scale: float, dn: int, masked: bool = False,
            parts: int = 1):
    # with ``masked`` one more operand before the output: the block's
    # (queries, keys) int8 of the keys each query may see at all; a step
    # takes its queries in ``parts``, one after another
    sel_ref = rest[0] if masked else None
    o_ref, m_ref, l_ref, acc_ref = rest[masked:]
    b, kb = pl.program_id(0), pl.program_id(2)
    block_k, tq = c_ref.shape[0], qn_ref.shape[0] // parts
    pl.when(kb == 0)(lambda: _start(m_ref, l_ref, acc_ref))

    @pl.when(kb < blocks_ref[b])
    def _block():
        # this head's keys and values of the block, from its latents
        kv = jnp.dot(c_ref[:], w_ref[:],
                     preferred_element_type=jnp.float32).astype(c_ref.dtype)

        def part(mine, start=None):
            """The queries ``mine``, the first the chunk's ``start``-th
            (None: its first)."""
            s = scale * (
                lax.dot_general(qn_ref[mine], kv[:, :dn],
                                (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) +
                jnp.dot(qp_ref[mine], kpe_ref[:],
                        preferred_element_type=jnp.float32))
            first = offset_ref[b] if start is None else offset_ref[b] + start
            q_pos = first + lax.broadcasted_iota(jnp.int32, (tq, block_k), 0)
            k_pos = kb * block_k + lax.broadcasted_iota(
                jnp.int32, (tq, block_k), 1)
            seen = k_pos <= q_pos
            if masked:
                # one pass over the scores for the mask, where ``_fold_in``
                # makes two: a hidden key's exponential is exactly 0
                # against any running maximum, which starts at the floor
                s = jnp.where(seen & (sel_ref[mine].astype(jnp.int32) != 0),
                              s, _HIDDEN)
                seen = None
            _fold_in(s, seen, kv[:, dn:], m_ref, l_ref, acc_ref, at=mine)

        if parts == 1:
            part(slice(None))
        else:
            def in_turn(i, _):
                start = pl.multiple_of(i * tq, tq)
                part(pl.ds(start, tq), start)
                return _

            lax.fori_loop(0, parts, in_turn, None)

    pl.when(kb == pl.num_programs(2) - 1)(
        lambda: _finish(o_ref, l_ref, acc_ref))


def expanded(q_nope, q_pe, c, k_pe, w_kv_b, offset, *, scale: float,
             selected=None, interpret: bool = False):
    """``q_nope`` (B, Sq, H, dn), ``q_pe`` (B, Sq, H, dr) against the cache
    ``c`` (B, Sk, r), ``k_pe`` (B, dr, Sk) through ``w_kv_b`` (r, H, dn +
    dv); row ``b``'s query i sits at ``offset[b] + i`` ((B,) int32) and
    sees the keys at or before it.  Returns (B, Sq, H, dv) in the queries'
    dtype.  ``c`` may be wider than ``r`` (a selecting layer's rows): its
    first ``r`` channels are the latent.  ``selected`` ((B, Sq, Sk) int8,
    None: all): of the keys at or before a query, those it sees; the
    kernel walks the same keys in blocks of ``SELECTED_BLOCK_K`` and fetches
    a block of the mask a head beside them."""
    b, sq, nh, dn = q_nope.shape
    dr, sk = k_pe.shape[1], c.shape[1]
    rank, dv = w_kv_b.shape[0], w_kv_b.shape[2] - dn
    masked = selected is not None
    block_k = SELECTED_BLOCK_K if masked else BLOCK_K
    nk = sk // block_k
    offset = offset.astype(jnp.int32)
    # the key blocks a row's last query reaches into
    blocks = jnp.clip((offset + sq - 1) // block_k + 1, 1, nk)

    def per_head(b_, h, kb, blocks_ref, offset_ref):
        return b_, h, 0, 0

    mask_spec = [pl.BlockSpec(
        (None, sq, block_k),
        lambda b_, h, kb, blocks_ref, offset_ref:
        (b_, 0, _block_of(b_, kb, blocks_ref)))] if masked else []
    out = pl.pallas_call(
        functools.partial(_kernel, scale=scale, dn=dn, masked=True,
                          parts=SELECTED_PARTS)
        if masked else functools.partial(_kernel, scale=scale, dn=dn),
        out_shape=jax.ShapeDtypeStruct((b, nh, sq, dv), q_nope.dtype),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(b, nh, nk),
            in_specs=[
                pl.BlockSpec((None, None, sq, dn), per_head),
                pl.BlockSpec((None, None, sq, dr), per_head),
                pl.BlockSpec(
                    (None, block_k, rank),
                    lambda b_, h, kb, blocks_ref, offset_ref:
                    (b_, _block_of(b_, kb, blocks_ref), 0)),
                pl.BlockSpec(
                    (None, dr, block_k),
                    lambda b_, h, kb, blocks_ref, offset_ref:
                    (b_, 0, _block_of(b_, kb, blocks_ref))),
                pl.BlockSpec(
                    (None, rank, dn + dv),
                    lambda b_, h, kb, blocks_ref, offset_ref: (h, 0, 0)),
            ] + mask_spec,
            out_specs=pl.BlockSpec((None, None, sq, dv), per_head),
            scratch_shapes=[pltpu.VMEM((sq, 1), jnp.float32),
                            pltpu.VMEM((sq, 1), jnp.float32),
                            pltpu.VMEM((sq, dv), jnp.float32)]),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=VMEM_LIMIT),
        interpret=interpret,
        name=CHUNK_UNDER_MASK_NAME if masked else None,
    )(blocks, offset, q_nope.transpose(0, 2, 1, 3),
      q_pe.transpose(0, 2, 1, 3), c, k_pe, w_kv_b.transpose(1, 0, 2),
      *([selected] if masked else []))
    return out.transpose(0, 2, 1, 3)


def absorbed_fits(q_lat, c) -> bool:
    """Whether the absorbed kernel takes these shapes: the latent in whole
    lanes, the heads in whole sublanes, the cache in whole key blocks."""
    return (q_lat.shape[1] == 1 and q_lat.shape[3] % 128 == 0 and
            q_lat.shape[2] % 16 == 0 and c.shape[1] % DECODE_BLOCK_K == 0)


def _absorbed_kernel(blocks_ref, index_ref, ql_ref, qp_ref, c_ref, kpe_ref,
                     o_ref, m_ref, l_ref, acc_ref, *, scale: float):
    b, kb = pl.program_id(0), pl.program_id(1)
    nh, block_k = ql_ref.shape[0], c_ref.shape[0]
    pl.when(kb == 0)(lambda: _start(m_ref, l_ref, acc_ref))

    @pl.when(kb < blocks_ref[b])
    def _block():
        latents = c_ref[:]
        s = scale * (
            lax.dot_general(ql_ref[:], latents, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) +
            jnp.dot(qp_ref[:], kpe_ref[:],
                    preferred_element_type=jnp.float32))
        k_pos = kb * block_k + lax.broadcasted_iota(
            jnp.int32, (nh, block_k), 1)
        _fold_in(s, k_pos <= index_ref[b], latents, m_ref, l_ref, acc_ref)

    pl.when(kb == pl.num_programs(1) - 1)(
        lambda: _finish(o_ref, l_ref, acc_ref))


def absorbed(q_lat, q_pe, c, k_pe, index, *, scale: float,
             interpret: bool = False):
    """``q_lat`` (B, 1, H, r) (a row's one query a head, already through
    ``W_uk``) and ``q_pe`` (B, 1, H, dr) against the cache ``c`` (B, Sk,
    r), ``k_pe`` (B, dr, Sk); row ``b``'s query sits at ``index[b]`` ((B,)
    int32) and sees the positions up to it.  Returns the probabilities'
    weighted latents (B, 1, H, r), for ``W_uv`` to expand."""
    b, _, nh, rank = q_lat.shape
    dr, sk = k_pe.shape[1], c.shape[1]
    nk = sk // DECODE_BLOCK_K
    index = index.astype(jnp.int32)
    blocks = jnp.clip(index // DECODE_BLOCK_K + 1, 1, nk)

    def per_row(b_, kb, blocks_ref, index_ref):
        return b_, 0, 0

    out = pl.pallas_call(
        functools.partial(_absorbed_kernel, scale=scale),
        out_shape=jax.ShapeDtypeStruct((b, nh, rank), q_lat.dtype),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(b, nk),
            in_specs=[
                pl.BlockSpec((None, nh, rank), per_row),
                pl.BlockSpec((None, nh, dr), per_row),
                pl.BlockSpec(
                    (None, DECODE_BLOCK_K, rank),
                    lambda b_, kb, blocks_ref, index_ref:
                    (b_, _block_of(b_, kb, blocks_ref), 0)),
                pl.BlockSpec(
                    (None, dr, DECODE_BLOCK_K),
                    lambda b_, kb, blocks_ref, index_ref:
                    (b_, 0, _block_of(b_, kb, blocks_ref))),
            ],
            out_specs=pl.BlockSpec((None, nh, rank), per_row),
            scratch_shapes=[pltpu.VMEM((nh, 1), jnp.float32),
                            pltpu.VMEM((nh, 1), jnp.float32),
                            pltpu.VMEM((nh, rank), jnp.float32)]),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=VMEM_LIMIT),
        interpret=interpret,
    )(blocks, index, q_lat[:, 0], q_pe[:, 0], c, k_pe)
    return out[:, None]


# --- a decode over a selection, the cache read as it lies ---

def under_mask_fits(q_pe, rows, rank: int) -> bool:
    """Whether ``absorbed_under_mask`` takes these shapes (``q_pe`` (B, s,
    H, dr), the cache's ``rows`` (B, Sk, width), a latent of ``rank``
    channels): a few queries a row, the latent and the cache's rows in
    whole lanes, the latent and the key inside a row, the heads in whole
    sublanes, the cache in whole key blocks."""
    queries, heads, dr = q_pe.shape[1:]
    sk, width = rows.shape[1:]
    return (queries <= INDEX_FEW_Q and rank % 128 == 0 and
            heads % 16 == 0 and width % 128 == 0 and rank + dr <= width and
            sk % DECODE_BLOCK_K == 0)


def decode_blocks(index, queries: int, sk: int):
    """(B,) int32: the key blocks of ``DECODE_BLOCK_K`` that the last of a
    row's ``queries`` new positions, the first at ``index``, reaches into."""
    return jnp.clip((index.astype(jnp.int32) + queries - 1) // DECODE_BLOCK_K
                    + 1, 1, sk // DECODE_BLOCK_K)


def _under_mask_kernel(blocks_ref, q_ref, rows_ref, sel_ref, o_ref, m_ref,
                       l_ref, acc_ref, *, scale: float, rank: int):
    b, kb = pl.program_id(0), pl.program_id(1)
    queries, block_k = sel_ref.shape
    heads = q_ref.shape[0] // queries
    pl.when(kb == 0)(lambda: _start(m_ref, l_ref, acc_ref))

    @pl.when(kb < blocks_ref[b])
    def _block():
        held = rows_ref[:]
        # [q_lat | q_pe | 0] against [c | k_pe | spare]: one product
        s = scale * lax.dot_general(q_ref[:], held, (((1,), (1,)), ((), ())),
                                    preferred_element_type=jnp.float32)
        chosen = sel_ref[:].astype(jnp.int32) != 0
        seen = jnp.concatenate(
            [jnp.broadcast_to(chosen[i:i + 1], (heads, block_k))
             for i in range(queries)], axis=0)
        _fold_in(s, seen, held[:, :rank], m_ref, l_ref, acc_ref)

    pl.when(kb == pl.num_programs(1) - 1)(
        lambda: _finish(o_ref, l_ref, acc_ref))


def absorbed_under_mask(q_lat, q_pe, rows, selected, index, *, scale: float,
                        interpret: bool = False):
    """A few queries a row, each over a selection of its own, with no copy
    of what it selected: ``q_lat`` (B, s, H, r) and ``q_pe`` (B, s, H, dr)
    against a selecting layer's cache ``rows`` (B, Sk, width), a row ``[c
    | k_pe | spare]`` as ``gpt_model.update_latent_index_cache`` writes it,
    under ``selected`` ((B, s, Sk) int8: of the positions at or before
    each query, those it attends over); the first of row ``b``'s queries
    sits at ``index[b]``.  Returns the probabilities' weighted latents (B,
    s, H, r), for ``W_uv`` to expand.

    Grid ``(rows, key blocks)`` as ``absorbed``'s: a program holds ALL of
    its row's queries, ``(s H, width)`` as ``[q_lat | q_pe | zeros]``, so a
    step's scores are one product against the block of the cache as it
    lies, fetched once for every query and head; the block's mask goes
    beside it, ``(s, block_k)``, and the values are the block's first
    ``r`` channels.  Steps past the row's newest position fetch and
    compute nothing."""
    b, queries, nh, rank = q_lat.shape
    sk, width = rows.shape[1:]
    nk = sk // DECODE_BLOCK_K
    q = jnp.concatenate(
        [q_lat, q_pe, jnp.zeros(
            q_lat.shape[:3] + (width - rank - q_pe.shape[3],), q_lat.dtype)],
        axis=-1).reshape(b, queries * nh, width)

    def per_row(b_, kb, blocks_ref):
        return b_, 0, 0

    out = pl.pallas_call(
        functools.partial(_under_mask_kernel, scale=scale, rank=rank),
        out_shape=jax.ShapeDtypeStruct((b, queries * nh, rank), q_lat.dtype),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(b, nk),
            in_specs=[
                pl.BlockSpec((None, queries * nh, width), per_row),
                pl.BlockSpec(
                    (None, DECODE_BLOCK_K, width),
                    lambda b_, kb, blocks_ref:
                    (b_, _block_of(b_, kb, blocks_ref), 0)),
                pl.BlockSpec(
                    (None, queries, DECODE_BLOCK_K),
                    lambda b_, kb, blocks_ref:
                    (b_, 0, _block_of(b_, kb, blocks_ref))),
            ],
            out_specs=pl.BlockSpec((None, queries * nh, rank), per_row),
            scratch_shapes=[pltpu.VMEM((queries * nh, 1), jnp.float32),
                            pltpu.VMEM((queries * nh, 1), jnp.float32),
                            pltpu.VMEM((queries * nh, rank), jnp.float32)]),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=VMEM_LIMIT),
        interpret=interpret,
        name=UNDER_MASK_NAME,
    )(decode_blocks(index, queries, sk), q, rows, selected)
    return out.reshape(b, queries, nh, rank)


# --- the indexer's scores (a layer that selects its positions) ---

# queries and keys a step of the chunk's kernel: 64 heads' queries of a
# block are 4 MB, a step's float32 scores 512 KB
INDEX_BLOCK_Q = 256
INDEX_BLOCK_K = 512
# the most queries a row the decode's kernel takes at once (a verify of a
# tick that drafts: the sure position and its drafts)
INDEX_FEW_Q = 8


def index_scores_fits(q_index, keys) -> bool:
    """Whether the kernels take these shapes: the index heads' channels in
    whole lanes, the heads in whole sublanes, the keys in whole key blocks
    of either kernel, a few queries a row or whole blocks of them."""
    sq, heads, dim = q_index.shape[1:]
    return (dim % 128 == 0 and heads % 8 == 0 and
            keys.shape[1] % DECODE_BLOCK_K == 0 and
            (sq <= INDEX_FEW_Q or sq % INDEX_BLOCK_Q == 0))


def _index_chunk_kernel(blocks_ref, offset_ref, q_ref, w_ref, k_ref, o_ref):
    b, qb, kb = pl.program_id(0), pl.program_id(1), pl.program_id(2)
    heads = q_ref.shape[0]
    tq, tk = o_ref.shape

    @pl.when(kb < blocks_ref[b])
    def _block():
        keys = k_ref[:]
        weights = w_ref[:]
        total = jnp.zeros((tq, tk), jnp.float32)
        for j in range(heads):
            total += weights[:, j:j + 1] * jnp.maximum(
                lax.dot_general(q_ref[j], keys, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32), 0.0)
        q_pos = offset_ref[b] + qb * tq + lax.broadcasted_iota(
            jnp.int32, (tq, tk), 0)
        k_pos = kb * tk + lax.broadcasted_iota(jnp.int32, (tq, tk), 1)
        o_ref[:] = jnp.where(k_pos <= q_pos, total, -jnp.inf)

    @pl.when(kb >= blocks_ref[b])
    def _unseen():
        o_ref[:] = jnp.full_like(o_ref, -jnp.inf)


def _index_decode_kernel(blocks_ref, index_ref, q_ref, w_ref, k_ref, o_ref):
    b, kb = pl.program_id(0), pl.program_id(1)
    tk = o_ref.shape[1]

    @pl.when(kb < blocks_ref[b])
    def _block():
        products = lax.dot_general(q_ref[:], k_ref[:],
                                   (((1,), (1,)), ((), ())),
                                   preferred_element_type=jnp.float32)
        total = jnp.sum(w_ref[:] * jnp.maximum(products, 0.0), axis=0,
                        keepdims=True)
        k_pos = kb * tk + lax.broadcasted_iota(jnp.int32, (1, tk), 1)
        o_ref[:] = jnp.where(k_pos <= index_ref[b], total, -jnp.inf)

    @pl.when(kb >= blocks_ref[b])
    def _unseen():
        o_ref[:] = jnp.full_like(o_ref, -jnp.inf)


def _index_few_kernel(blocks_ref, index_ref, q_ref, w_ref, k_ref, o_ref):
    """``_index_decode_kernel`` for a few queries a row at consecutive
    positions: the block of the row's keys is fetched once and scored for
    each query in turn."""
    b, kb = pl.program_id(0), pl.program_id(1)
    sq, tk = o_ref.shape

    @pl.when(kb < blocks_ref[b])
    def _block():
        keys = k_ref[:]
        k_pos = kb * tk + lax.broadcasted_iota(jnp.int32, (1, tk), 1)
        for i in range(sq):
            products = lax.dot_general(q_ref[i], keys,
                                       (((1,), (1,)), ((), ())),
                                       preferred_element_type=jnp.float32)
            total = jnp.sum(w_ref[i] * jnp.maximum(products, 0.0), axis=0,
                            keepdims=True)
            o_ref[i:i + 1, :] = jnp.where(k_pos <= index_ref[b] + i, total,
                                          -jnp.inf)

    @pl.when(kb >= blocks_ref[b])
    def _unseen():
        o_ref[:] = jnp.full_like(o_ref, -jnp.inf)


def index_scores(q_index, weights, keys, q_pos, *, interpret: bool = False):
    """``gpt_model.index_scores`` (which says what it computes): ``q_index``
    (B, Sq, J, di), ``weights`` (B, Sq, J) float32, ``keys`` (B, Sk, di),
    ``q_pos`` (B, Sq) int32, a row's queries at consecutive positions;
    (B, Sq, Sk) float32, ``-inf`` past each query.  One query a row (a
    decode): grid ``(rows, key blocks)``, a step one product of all heads'
    queries with a block of the row's index keys, reduced over the heads
    in the kernel.  Several (a chunk): grid ``(rows, query blocks, key
    blocks)``, a step 64 products of one head's block of queries with the
    block of keys, summed in fast memory.  Key blocks past a row's last
    query are filled and not fetched."""
    b, sq, heads, dim = q_index.shape
    sk = keys.shape[1]
    offset = q_pos[:, 0].astype(jnp.int32)
    weights = weights.astype(jnp.float32)
    if sq == 1:
        tk = DECODE_BLOCK_K
        blocks = jnp.clip(offset // tk + 1, 1, sk // tk)

        def per_row(b_, kb, blocks_ref, index_ref):
            return b_, 0, 0

        return pl.pallas_call(
            _index_decode_kernel,
            out_shape=jax.ShapeDtypeStruct((b, 1, sk), jnp.float32),
            grid_spec=pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=2,
                grid=(b, sk // tk),
                in_specs=[
                    pl.BlockSpec((None, heads, dim), per_row),
                    pl.BlockSpec((None, heads, 1), per_row),
                    pl.BlockSpec(
                        (None, tk, dim),
                        lambda b_, kb, blocks_ref, index_ref:
                        (b_, _block_of(b_, kb, blocks_ref), 0)),
                ],
                out_specs=pl.BlockSpec(
                    (None, 1, tk),
                    lambda b_, kb, blocks_ref, index_ref: (b_, 0, kb))),
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("parallel", "arbitrary"),
                vmem_limit_bytes=VMEM_LIMIT),
            interpret=interpret,
        )(blocks, offset, q_index[:, 0], weights[:, 0, :, None], keys)
    if sq <= INDEX_FEW_Q:
        # a few queries a row (a verify of a tick that drafts): the
        # decode's grid, a step one product a query with the block of keys
        tk = DECODE_BLOCK_K
        blocks = jnp.clip((offset + sq - 1) // tk + 1, 1, sk // tk)

        def per_row(b_, kb, blocks_ref, index_ref):
            return b_, 0, 0, 0

        return pl.pallas_call(
            _index_few_kernel,
            out_shape=jax.ShapeDtypeStruct((b, sq, sk), jnp.float32),
            grid_spec=pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=2,
                grid=(b, sk // tk),
                in_specs=[
                    pl.BlockSpec((None, sq, heads, dim), per_row),
                    pl.BlockSpec((None, sq, heads, 1), per_row),
                    pl.BlockSpec(
                        (None, tk, dim),
                        lambda b_, kb, blocks_ref, index_ref:
                        (b_, _block_of(b_, kb, blocks_ref), 0)),
                ],
                out_specs=pl.BlockSpec(
                    (None, sq, tk),
                    lambda b_, kb, blocks_ref, index_ref: (b_, 0, kb))),
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("parallel", "arbitrary"),
                vmem_limit_bytes=VMEM_LIMIT),
            interpret=interpret,
        )(blocks, offset, q_index, weights[..., None], keys)
    tq, tk = INDEX_BLOCK_Q, INDEX_BLOCK_K
    blocks = jnp.clip((offset + sq - 1) // tk + 1, 1, sk // tk)
    return pl.pallas_call(
        _index_chunk_kernel,
        out_shape=jax.ShapeDtypeStruct((b, sq, sk), jnp.float32),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(b, sq // tq, sk // tk),
            in_specs=[
                pl.BlockSpec(
                    (None, heads, tq, dim),
                    lambda b_, qb, kb, blocks_ref, offset_ref:
                    (b_, 0, qb, 0)),
                pl.BlockSpec(
                    (None, tq, heads),
                    lambda b_, qb, kb, blocks_ref, offset_ref:
                    (b_, qb, 0)),
                pl.BlockSpec(
                    (None, tk, dim),
                    lambda b_, qb, kb, blocks_ref, offset_ref:
                    (b_, _block_of(b_, kb, blocks_ref), 0)),
            ],
            out_specs=pl.BlockSpec(
                (None, tq, tk),
                lambda b_, qb, kb, blocks_ref, offset_ref: (b_, qb, kb))),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=VMEM_LIMIT),
        interpret=interpret,
    )(blocks, offset, q_index.transpose(0, 2, 1, 3), weights, keys)
