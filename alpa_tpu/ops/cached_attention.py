"""Attention of new queries over the row's written cache of per-head keys
and values (grouped-query or multi-head), in Pallas kernels that read of
each row's cache what the row holds and keep the scores in fast memory: one
over key blocks for a FEW new queries a row, one over query blocks and key
blocks for MANY (a prefill's chunk; at the end of this text).

What a decode tick (one new position a row) and a block step of
generation by diffusion (``block_length`` new positions a row) need of
``model/gpt_model.py`` ``SelfAttention``: ``s`` queries a head against
the cache ``update_kv_cache`` has just written.  ``reference_attention``
scores them against every position the cache can hold and lets the
causal offset mask what a row has not reached: two products over all
``S`` positions with a float32 softmax between them whatever the rows
hold (6.6 ms of an 18.1 ms block step at 32 rows that hold 940 of 8,192
positions, PERF.md, PR 40).  Here, as in ``ops/latent_attention.py``
``absorbed``, how far a row reads is data: ``blocks[b]``, the key blocks
that hold every position row ``b``'s queries see, and ``offset[b]``, the
position of its first query, are prefetched scalars.  The grid is ``(rows,
key blocks)``, the key blocks innermost and sequential, the running
maximum, sum and weighted values of the online softmax in float32 scratch
across them (``latent_attention``'s ``_start``, ``_fold_in``,
``_finish``); a step past ``blocks[b]`` computes nothing and, its block
index clamped to the last needed one, fetches nothing.

The cache is read AS IT LIES.  Heads of whole lanes (128 channels): the
compiler keeps (B, S, Hkv, D) as named, a position's ``Hkv`` heads in the
sublanes of one tile, and (B, S Hkv, D) is the same bytes.  A step
fetches a block of positions once for all heads, scores ALL of a row's
queries (``s H`` of them) against all ``block_k Hkv`` keys of the block
in one product, and the mask hides from a query the keys of the other
key/value heads along with the positions past its reach: no head's keys
are ever picked out of the tiles they share (a sublane gather a position),
at the price of ``Hkv`` times the softmax's elementwise work, which at
``Hkv`` = 4 still hides under the block's fetch.

The result is the reference's: the softmax over the same visible keys
accumulated in float32, the probabilities cast to the cache's dtype
before the values' product, a position past a row's reach contributing
exactly nothing (its probability is an exact 0 whatever finite value lies
there).

Keys wider than the values, neither in whole lanes a head
(``folded_cached_attention``: keys of 192 channels, values of 128).  A
cache kept a head, (B, S, Hkv, 192), the compiler lays out with its
positions minor-most, as it does narrow heads, beside values of 128 that
lie as named: two orders in one layer, and 192 x 4 rows of positions a
block.  Such a layer's caches hold the heads FOLDED into the channels,
(B, S, Hkv 192) and (B, S, Hkv 128), whole lanes both
(``gpt_model.kv_cache_shapes``), so both lie as named, and the kernel
reads them as they lie: a key block is (positions, Hkv 192), each query
sits in the channels of its head's group with zeros in the others' (as
with narrow heads above), so one product over all ``Hkv 192`` channels
scores every head against its own group's keys and a column is a
position; the values' product gives every group's channels, of which a
head keeps its own.  The matrix unit loads each key and value element
once, as it does when the heads' keys are rows, and the softmax works on
``s H x positions`` scores, not on ``Hkv`` times as many.

Many new queries a row (``chunk_attention``: a prefill's chunk of 1,024
positions, from a cached prefix too).  The ``Hkv`` times of either way
above, products a tick hides under its fetch, a chunk does not hide: its
products ARE its time.  Its kernel's grid is ``(rows, key/value heads,
query blocks, key blocks)``, a program one key/value head's queries of one
query block against that head's own keys (of folded caches the whole
lane tiles they lie in, of per-head caches every ``Hkv``-th row of the
block); a query block reads the key blocks up to its last query's reach
and no further.  In its place ``gpt_model`` ran ``jax.numpy``: over
folded caches a loop over key blocks whose (H, s, block) float32 scores
went through the chip's memory three times a block (1.2 ms a block of
1,024 keys at MiMo-V2-Flash's 64 heads, 18 % of the matrix unit's peak),
over per-head caches ``reference_attention`` against every position the
cache can hold (PERF.md, PR 56).

The kernels are compiled where the program is lowered for a TPU
(``gpt_model.cached_attention`` chooses between each and its ``jax.numpy``
twin with ``lax.platform_dependent``); ``interpret=True`` runs them
anywhere, for the tests.
"""
import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from alpa_tpu.ops.latent_attention import (VMEM_LIMIT, _block_of, _finish,
                                           _fold_in, _start)

# the lanes of a vector register: heads of whole lanes lie as named,
# narrower ones with the positions in the lanes
LANES = 128
# elements of a key block (positions x key/value heads x channels): 1 MB
# of keys and 1 MB of values in bfloat16, so that a step's fixed cost
# stays a small part of its fetch
BLOCK_ELEMENTS = 4096 * 128
# new positions a row of the kernels over key blocks: a decode tick's one,
# a diffusion block's few.  More (a prefill's chunk) go to the kernel over
# query blocks too, ``chunk_attention``
MAX_QUERIES = 16
# key/value heads of a cache that lies as named: every query is scored
# against the keys of all of them
MAX_KV_HEADS = 8


def block_k(kv_heads: int, dim: int) -> int:
    """Positions a key block holds."""
    return BLOCK_ELEMENTS // (kv_heads * dim)


def fits(q, k_cache) -> bool:
    """Whether the kernel takes these shapes: a few new queries a row, a
    row's queries in whole sublanes, the cache in whole key blocks; heads
    of whole lanes and few enough key/value heads to score against all of
    them, or narrower heads whose key blocks fill whole lanes with
    positions."""
    _, s, nh, dim = q.shape
    seq_len, nkv = k_cache.shape[1], k_cache.shape[2]
    if (s > MAX_QUERIES or nh % nkv or (s * nh) % 16 or
            BLOCK_ELEMENTS % (nkv * dim)):
        return False
    per_block = block_k(nkv, dim)
    if dim < LANES:
        return ((nkv * dim) % 16 == 0 and per_block % LANES == 0 and
                seq_len % per_block == 0)
    return (dim % LANES == 0 and nkv <= MAX_KV_HEADS and
            seq_len % per_block == 0)


def blocks_read(last, per_block: int, seq_len: int):
    """Key blocks of ``per_block`` positions that hold the positions ``0
    .. last`` of a cache of ``seq_len``: what the kernel fetches for a row
    whose queries see that far."""
    return jnp.clip(last // per_block + 1, 1, seq_len // per_block)


def _last_seen(pos, block: int):
    """The last position a query at ``pos`` sees (``reference_attention``:
    its own, or the end of its block of ``block``)."""
    return (pos // block + 1) * block - 1 if block else pos


def _kernel(blocks_ref, offset_ref, q_ref, k_ref, v_ref, o_ref, m_ref,
            l_ref, acc_ref, *, scale: float, heads: int, kv_heads: int,
            block: int, keys_last: bool):
    b, kb = pl.program_id(0), pl.program_id(1)
    pl.when(kb == 0)(lambda: _start(m_ref, l_ref, acc_ref))

    @pl.when(kb < blocks_ref[b])
    def _block():
        # a query row is (new position, head)
        row = lax.broadcasted_iota(jnp.int32, (q_ref.shape[0], 1), 0)
        reach = _last_seen(offset_ref[b] + row // heads, block)
        if keys_last:
            # keys (key/value head x channel, position), the queries each
            # in the channels of its head's group: a column is a position
            per_block = k_ref.shape[1]
            s = scale * jnp.dot(q_ref[:], k_ref[:],
                                preferred_element_type=jnp.float32)
            k_pos = kb * per_block + lax.broadcasted_iota(
                jnp.int32, (1, per_block), 1)
            seen = k_pos <= reach
        else:
            # keys (position x key/value head, channel): a query sees the
            # columns of its head's group up to where its position lets it
            keys = k_ref.shape[0]
            s = scale * lax.dot_general(
                q_ref[:], k_ref[:], (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32)
            col = lax.broadcasted_iota(jnp.int32, (1, keys), 1)
            group = row % heads // (heads // kv_heads)
            k_pos = kb * (keys // kv_heads) + col // kv_heads
            seen = (col % kv_heads == group) & (k_pos <= reach)
        _fold_in(s, seen, v_ref[:], m_ref, l_ref, acc_ref, keys_last)

    pl.when(kb == pl.num_programs(1) - 1)(
        lambda: _finish(o_ref, l_ref, acc_ref))


def cached_attention(q, k_cache, v_cache, offset, *, block: int = 0,
                     interpret: bool = False):
    """``q`` (B, s, H, D) against the written caches ``k_cache``,
    ``v_cache`` (B, S, Hkv, D); row ``b``'s query i sits at ``offset[b] +
    i`` ((B,) int32) and sees the keys at or before it, or with ``block``
    > 0 up to the end of its block of ``block`` positions
    (``reference_attention``'s masks).  Returns (B, s, H, D) in the
    queries' dtype."""
    b, s, nh, dim = q.shape
    seq_len, nkv = k_cache.shape[1], k_cache.shape[2]
    per_block = block_k(nkv, dim)
    keys_last = dim < LANES
    offset = offset.astype(jnp.int32)
    blocks = blocks_read(_last_seen(offset + s - 1, block), per_block,
                         seq_len)
    if keys_last:
        # the cache as it lies: (B, Hkv D, S).  Each query in the channels
        # of its head's group and zeros in the others': one product scores
        # all heads, and the values' product gives every group's channels,
        # of which a head keeps its own
        width = nkv * dim
        # which key/value head a head reads
        group = jax.nn.one_hot(jnp.arange(nh) // (nh // nkv), nkv,
                               dtype=q.dtype)
        q = (q[:, :, :, None, :] * group[:, :, None]).reshape(
            b, s * nh, width)

        def as_it_lies(cache):
            return cache.transpose(0, 2, 3, 1).reshape(b, width, seq_len)

        cache_block = (None, width, per_block)

        def key_block(b_, kb, blocks_ref, offset_ref):
            return b_, 0, _block_of(b_, kb, blocks_ref)
    else:
        # the cache as it lies: (B, S Hkv, D)
        width = dim
        q = q.reshape(b, s * nh, dim)

        def as_it_lies(cache):
            return cache.reshape(b, seq_len * nkv, dim)

        cache_block = (None, per_block * nkv, dim)

        def key_block(b_, kb, blocks_ref, offset_ref):
            return b_, _block_of(b_, kb, blocks_ref), 0

    def per_row(b_, kb, blocks_ref, offset_ref):
        return b_, 0, 0

    out = pl.pallas_call(
        functools.partial(_kernel, scale=float(1 / np.sqrt(dim)), heads=nh,
                          kv_heads=nkv, block=block, keys_last=keys_last),
        out_shape=jax.ShapeDtypeStruct((b, s * nh, width), q.dtype),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(b, seq_len // per_block),
            in_specs=[
                pl.BlockSpec((None, s * nh, width), per_row),
                pl.BlockSpec(cache_block, key_block),
                pl.BlockSpec(cache_block, key_block),
            ],
            out_specs=pl.BlockSpec((None, s * nh, width), per_row),
            scratch_shapes=[pltpu.VMEM((s * nh, 1), jnp.float32),
                            pltpu.VMEM((s * nh, 1), jnp.float32),
                            pltpu.VMEM((s * nh, width), jnp.float32)]),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=VMEM_LIMIT),
        interpret=interpret,
        # what a device trace calls the kernel's events
        name="cached_attention_key_blocks",
    )(blocks, offset, q, as_it_lies(k_cache), as_it_lies(v_cache))
    if keys_last:
        out = jnp.einsum("bshgd,hg->bshd",
                         out.reshape(b, s, nh, nkv, dim), group)
    return out.reshape(b, s, nh, dim)


def folded_block_k(k_cache, v_cache) -> int:
    """Positions a key block of folded caches holds: the largest power of
    two whose keys stay within ``BLOCK_ELEMENTS``."""
    per_block = BLOCK_ELEMENTS // k_cache.shape[2]
    return 1 << (per_block.bit_length() - 1)


def folded_fits(q, k_cache, v_cache) -> bool:
    """Whether ``folded_cached_attention`` takes these shapes: a few new
    queries a row (padded to whole sublanes there), both caches' channels
    in whole lanes (the key/value heads together), few enough key/value
    heads, the cache in whole key blocks."""
    _, s, nh, dim = q.shape
    seq_len, width = k_cache.shape[1], k_cache.shape[2]
    if width % dim:
        return False
    nkv = width // dim
    if (s > MAX_QUERIES or nh % nkv or
            nkv > MAX_KV_HEADS or v_cache.shape[2] % nkv or
            width % LANES or v_cache.shape[2] % LANES or
            width > BLOCK_ELEMENTS // 16):
        return False
    return seq_len % folded_block_k(k_cache, v_cache) == 0


def eva_fits(q, k_cache, held: int) -> bool:
    """Whether ``folded_cached_attention`` takes these shapes with the
    cache in two parts (``seen``): a few new queries a row (padded to
    whole sublanes there), heads of whole lanes folded into the channels,
    one key/value
    head a query head (any number of them: a key block of such a cache is
    ``BLOCK_ELEMENTS`` whatever the heads, and a step's scores are ``H x
    positions``, not ``H`` times as many), and both parts in whole key
    blocks, the first ``held`` slots and the rest."""
    _, s, nh, dim = q.shape
    slots, width = k_cache.shape[1], k_cache.shape[2]
    if (k_cache.ndim != 3 or s > MAX_QUERIES or width != nh * dim or
            dim % LANES or width > BLOCK_ELEMENTS // 16):
        return False
    per_block = folded_block_k(k_cache, k_cache)
    return held % per_block == 0 and slots % per_block == 0


def _two_parts(kb, per_block: int, seen, exact_from: int, blocks):
    """Of a cache in two parts (every slot under ``seen`` visible, the
    slots from ``exact_from`` on causal, ``blocks`` key blocks of them
    needed, the slots between visible to none), whether step ``kb`` has a
    key block to fold in, and the block it fetches: its own where it has,
    and where it has not the last one fetched before it (the same again:
    no fetch), or the causal part's first while nothing was."""
    under = -(-seen // per_block)
    first = exact_from // per_block
    wanted = (kb < under) | ((kb >= first) & (kb < first + blocks))
    at = jnp.where(
        kb < under, kb, jnp.where(
            kb < first, jnp.where(under > 0, under - 1, first),
            jnp.minimum(kb, first + blocks - 1)))
    return wanted, at


def _folded_kernel(blocks_ref, offset_ref, *refs, scale: float, heads: int,
                   exact_from=None):
    # with ``exact_from`` one more prefetched scalar a row before the
    # operands: the leading slots every query of the row sees
    seen_ref = None
    if exact_from is not None:
        seen_ref, refs = refs[0], refs[1:]
    q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref = refs
    b, kb = pl.program_id(0), pl.program_id(1)
    pl.when(kb == 0)(lambda: _start(m_ref, l_ref, acc_ref))
    if seen_ref is None:
        wanted = kb < blocks_ref[b]
    else:
        wanted, _ = _two_parts(kb, k_ref.shape[0], seen_ref[b], exact_from,
                               blocks_ref[b])

    @pl.when(wanted)
    def _block():
        # keys (position, key/value head x channel), the queries each in
        # the channels of its head's group: a column is a position
        per_block = k_ref.shape[0]
        row = lax.broadcasted_iota(jnp.int32, (q_ref.shape[0], 1), 0)
        s = scale * lax.dot_general(
            q_ref[:], k_ref[:], (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)
        k_pos = kb * per_block + lax.broadcasted_iota(
            jnp.int32, (1, per_block), 1)
        reach = offset_ref[b] + row // heads
        if seen_ref is None:
            seen = k_pos <= reach
        else:
            seen = (k_pos < seen_ref[b]) | (
                (k_pos >= exact_from) & (k_pos - exact_from <= reach))
        _fold_in(s, seen, v_ref[:], m_ref, l_ref, acc_ref)

    pl.when(kb == pl.num_programs(1) - 1)(
        lambda: _finish(o_ref, l_ref, acc_ref))


def folded_cached_attention(q, k_cache, v_cache, offset, *, seen=None,
                            exact_from: int = 0, interpret: bool = False):
    """``q`` (B, s, H, D) against written caches with the heads folded into
    the channels, ``k_cache`` (B, S, Hkv D) and ``v_cache`` (B, S, Hkv
    Dv); row ``b``'s query i sits at ``offset[b] + i`` ((B,) int32) and
    sees the keys at or before it.  Returns (B, s, H, Dv) in the queries'
    dtype.

    ``seen`` ((B,) int32): the cache is in two parts (an "eva" layer's:
    ``gpt_model.update_eva_cache``).  Every query of row ``b`` sees the
    slots under ``seen[b]``; the slots from ``exact_from`` on are the
    causal part, slot ``exact_from + j`` seen by the queries with ``j <=
    offset[b] + i``; the slots between are seen by none, and their key
    blocks are not fetched (``_two_parts``).  One more prefetched scalar a
    row and one more term in the mask: the walk over key blocks and the
    online softmax are the one kernel's."""
    b, s, nh, dim = q.shape
    seq_len, k_width = k_cache.shape[1], k_cache.shape[2]
    nkv, v_width = k_width // dim, v_cache.shape[2]
    dv = v_width // nkv
    per_block = folded_block_k(k_cache, v_cache)
    offset = offset.astype(jnp.int32)
    blocks = blocks_read(offset + s - 1, per_block,
                         seq_len - (exact_from if seen is not None else 0))
    # which key/value head a head reads
    group = jax.nn.one_hot(jnp.arange(nh) // (nh // nkv), nkv, dtype=q.dtype)
    q = (q[:, :, :, None, :] * group[:, :, None]).reshape(b, s * nh, k_width)
    # a row's queries in whole sublanes: rows of zeros behind them (20
    # query heads on one key/value head are 32 rows), which score 0 against
    # every key they are shown and are dropped from the result
    rows = -(-s * nh // 16) * 16
    q = jnp.pad(q, ((0, 0), (0, rows - s * nh), (0, 0)))

    def per_row(b_, kb, *scalars):
        return b_, 0, 0

    if seen is None:
        scalars, two_parts = (blocks, offset), {}

        def key_block(b_, kb, blocks_ref, offset_ref):
            return b_, _block_of(b_, kb, blocks_ref), 0
    else:
        scalars = (blocks, offset, seen.astype(jnp.int32))
        two_parts = {"exact_from": exact_from}

        def key_block(b_, kb, blocks_ref, offset_ref, seen_ref):
            return b_, _two_parts(kb, per_block, seen_ref[b_], exact_from,
                                  blocks_ref[b_])[1], 0

    out = pl.pallas_call(
        functools.partial(_folded_kernel, scale=float(1 / np.sqrt(dim)),
                          heads=nh, **two_parts),
        out_shape=jax.ShapeDtypeStruct((b, rows, v_width), q.dtype),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=len(scalars),
            grid=(b, seq_len // per_block),
            in_specs=[
                pl.BlockSpec((None, rows, k_width), per_row),
                pl.BlockSpec((None, per_block, k_width), key_block),
                pl.BlockSpec((None, per_block, v_width), key_block),
            ],
            out_specs=pl.BlockSpec((None, rows, v_width), per_row),
            scratch_shapes=[pltpu.VMEM((rows, 1), jnp.float32),
                            pltpu.VMEM((rows, 1), jnp.float32),
                            pltpu.VMEM((rows, v_width), jnp.float32)]),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=VMEM_LIMIT),
        interpret=interpret,
        # what a device trace calls the kernel's events
        name="cached_attention_folded_key_blocks",
    )(*scalars, q, k_cache, v_cache)
    return jnp.einsum("bshgd,hg->bshd",
                      out[:, :s * nh].reshape(b, s, nh, nkv, dv), group)


# ---- many new queries a row (a prefill's chunk) ----

# rows of a step's two products: the new positions of a query block times
# the query heads that share a key/value head.  4,096 are a tenth faster at
# Trinity's shape and no faster at MiMo's, and take the compiler 16 s where
# these take 8 (PERF.md, PR 56)
QUERY_ROWS = 2048
# positions of a key block of the chunk's kernel.  What a step does a ROW
# (the running maximum and sum, the rescaling of the weighted values) it
# does in vectors of one useful lane, as dear a row as 128 scores: blocks
# of 1,024 keys halve that against 512 (16.0 -> 9.5 ms at MiMo's shape, a
# chunk at 27,648; 2,048 gain no more and spend the fast memory).  With
# ``QUERY_ROWS`` rows a step's float32 scores are 8 MB
CHUNK_BLOCK_K = 1024


def _chunk_shapes(q, k_cache, v_cache):
    """(key/value heads, a head's value channels, positions a query block)
    of a chunk-shaped call, or None where the caches' channels do not
    divide into the queries' heads."""
    _, s, nh, dim = q.shape
    if k_cache.ndim == 3:
        nkv, left = divmod(k_cache.shape[2], dim)
        if left or not nkv or v_cache.shape[2] % nkv:
            return None
        dv = v_cache.shape[2] // nkv
    else:
        nkv, dv = k_cache.shape[2], v_cache.shape[3]
    if nh % nkv or nh // nkv > QUERY_ROWS:
        return None
    # the most positions, a power of two, whose rows stay within
    # ``QUERY_ROWS`` (20 heads on one key/value head: 64 positions, 1,280
    # rows)
    block_q = QUERY_ROWS // (nh // nkv)
    return nkv, dv, min(s, 1 << (block_q.bit_length() - 1))


def _lane_tiles(dim: int, kv_heads: int) -> int:
    """Whole lane tiles that hold any one head's ``dim`` channels of keys
    folded into the channels: head ``g``'s start ``dim g`` channels in,
    so within a tile at ``dim g % LANES``."""
    return max(-(-(dim * g % LANES + dim) // LANES) for g in range(kv_heads))


def chunk_fits(q, k_cache, v_cache) -> bool:
    """Whether ``chunk_attention`` takes these shapes: more new queries a
    row than the kernels over key blocks take, in whole query blocks, the
    cache in whole key blocks, and the heads in whole lanes as the cache
    lies: folded caches (B, S, Hkv D) whose values are whole lanes a head
    and whose every head's keys lie within the same number of whole lane
    tiles of the cache, or per-head caches (B, S, Hkv, D) of whole lanes
    (in 16 bits with an even number of key/value heads: a pair of heads
    shares a 32-bit sublane).  Narrower per-head caches lie with their
    positions in the lanes, in key blocks of many thousand positions: they
    keep ``reference_attention``."""
    _, s, nh, dim = q.shape
    shapes = _chunk_shapes(q, k_cache, v_cache)
    if shapes is None or s <= MAX_QUERIES:
        return False
    nkv, dv, block_q = shapes
    if k_cache.ndim == 3:
        width = k_cache.shape[2]
        lanes = (width % LANES == 0 and dv % LANES == 0 and
                 (dim * (nkv - 1) // LANES + _lane_tiles(dim, nkv)) * LANES
                 <= width)
    else:
        lanes = (dim % LANES == 0 and dv == dim and
                 nkv % (4 // k_cache.dtype.itemsize) == 0)
    return (lanes and s % block_q == 0 and (block_q * nh // nkv) % 16 == 0
            and k_cache.shape[1] % CHUNK_BLOCK_K == 0)


def _chunk_kernel(offset_ref, *refs, scale: float, group: int,
                  kv_heads: int, block: int, folded: bool, needed,
                  exact_from=None):
    # with ``exact_from`` one more prefetched scalar a row before the
    # operands: the leading slots every query of the row sees
    seen_ref = None
    if exact_from is not None:
        seen_ref, refs = refs[0], refs[1:]
    q_ref, refs = refs[0], refs[1:]
    k_refs = refs[:-5]
    v_ref, o_ref, m_ref, l_ref, acc_ref = refs[-5:]
    b, g, qb, kb = (pl.program_id(i) for i in range(4))
    rows = q_ref.shape[0]
    block_q = rows // group
    block_k = v_ref.shape[0] if folded else v_ref.shape[1] // kv_heads
    first = offset_ref[b] + qb * block_q
    pl.when(kb == 0)(lambda: _start(m_ref, l_ref, acc_ref))

    def of_head(ref, head):
        """Key/value head ``head``'s (positions, channels) of a block of
        per-head caches, whose rows are (position, head): every
        ``kv_heads``-th row.  Two rows of 16 bits share a 32-bit sublane:
        there the pair's rows are every ``kv_heads / 2``-th of the block
        seen as 32 bits, and a head is a half of each."""
        if ref.dtype.itemsize == 4:
            return ref[0, pl.ds(head, block_k, stride=kv_heads), :]
        pairs = ref.bitcast(jnp.uint32)
        pair = pairs[0, pl.ds(head // 2, block_k, stride=kv_heads // 2), :]
        half = pair << 16 if head % 2 == 0 else pair & jnp.uint32(0xffff0000)
        return pltpu.bitcast(half, jnp.float32).astype(ref.dtype)

    def fold_in(masked):
        if folded:
            # the whole lane tiles its head's keys lie in, side by side
            keys = jnp.concatenate([ref[:] for ref in k_refs], axis=1)
            values = v_ref[:]
        else:
            # the head is static where its rows are picked out of a block
            keys, values = lax.switch(
                g, [lambda i=i: (of_head(k_refs[0], i), of_head(v_ref, i))
                    for i in range(kv_heads)])
        s = scale * lax.dot_general(q_ref[:], keys, (((1,), (1,)), ((), ())),
                                    preferred_element_type=jnp.float32)
        seen = None
        if masked:
            # a query row is (new position, head of the group)
            row = lax.broadcasted_iota(jnp.int32, (rows, 1), 0)
            k_pos = kb * block_k + lax.broadcasted_iota(
                jnp.int32, (1, block_k), 1)
            if seen_ref is None:
                seen = k_pos <= _last_seen(first + row // group, block)
            else:
                seen = (k_pos < seen_ref[b]) | (
                    (k_pos >= exact_from) &
                    (k_pos - exact_from <= first + row // group))
        _fold_in(s, seen, values, m_ref, l_ref, acc_ref)

    # the key blocks wholly before the query block's first query need no
    # mask; those past its last query's reach compute nothing
    if seen_ref is None:
        whole = (kb + 1) * block_k - 1 <= _last_seen(first, block)
        wanted = kb < needed(offset_ref[b], qb)
    else:
        # (wholly under what every query sees, or wholly in the causal
        # part at or before the first query)
        whole = ((kb + 1) * block_k <= seen_ref[b]) | (
            (kb * block_k >= exact_from) &
            ((kb + 1) * block_k - 1 - exact_from <= first))
        wanted, _ = _two_parts(kb, block_k, seen_ref[b], exact_from,
                               needed(offset_ref[b], qb))
    pl.when(wanted & whole)(lambda: fold_in(False))
    pl.when(wanted & ~whole)(lambda: fold_in(True))
    pl.when(kb == pl.num_programs(3) - 1)(
        lambda: _finish(o_ref, l_ref, acc_ref))


def chunk_attention(q, k_cache, v_cache, offset, *, block: int = 0,
                    seen=None, exact_from: int = 0,
                    interpret: bool = False):
    """MANY new queries a row (a prefill's chunk): ``q`` (B, s, H, D)
    against the written caches, per head (B, S, Hkv, D) or with the heads
    folded into the channels, (B, S, Hkv D) and (B, S, Hkv Dv); row ``b``'s
    query i sits at ``offset[b] + i`` (a scalar, or (B,) int32) and sees
    what ``reference_attention``'s causal mask shows it (``block`` > 0: up
    to the end of its block).  Returns (B, s, H, Dv) in the queries' dtype.

    Grid ``(rows, key/value heads, query blocks, key blocks)``, the key
    blocks innermost and sequential with the online softmax's state in
    float32 scratch: a block's scores never leave fast memory.  A program
    holds one key/value head's queries of one query block, ``block_q``
    positions x the group's heads, as the ROWS of both products, so the
    matrix unit loads a key or value element once a group.  How far a
    query block reads is data: the key blocks up to its last query's
    reach (``offset`` is a prefetched scalar); later steps compute nothing
    and, their block index clamped, fetch nothing; earlier ones wholly
    before its first query skip the mask.

    The caches are read as they lie.  Folded: a head's ``D`` channels
    start ``D g`` channels in, for MiMo's 192 at lane 0 or 64 of a tile,
    and a ``BlockSpec`` cuts whole tiles.  A step fetches the whole lane
    tiles its head's keys lie in (two of the six), and the head's queries
    are zero-padded into the same tiles, so the channels of a neighbour
    that come along meet zeros: the scores' product contracts 256
    channels for 192, (256 + 128) / (192 + 128) = 1.2 times the heads' own
    products, with no slice or shift inside the kernel.  (Padding a query
    into a PAIR of heads' 384 channels costs 1.6 times, into all heads'
    channels, as the decode's kernel does under its fetch, ``Hkv``
    times.)  The values come a head a block.  Per head: a step fetches the
    block's (positions x Hkv, D) rows and takes its head's, every
    ``Hkv``-th, by a strided read of 32-bit pairs.

    ``seen`` ((B,) int32, no ``block``): the cache is in two parts, as
    ``folded_cached_attention``'s: every query of row ``b`` sees the slots
    under ``seen[b]``, slot ``exact_from + j`` is seen by the queries with
    ``j <= offset[b] + i``, the slots between by none and their key blocks
    are not fetched; ``exact_from`` is a whole number of key blocks."""
    b, s, nh, dim = q.shape
    seq_len = k_cache.shape[1]
    nkv, dv, block_q = _chunk_shapes(q, k_cache, v_cache)
    group, block_k = nh // nkv, CHUNK_BLOCK_K
    rows = block_q * group
    offset = jnp.broadcast_to(jnp.asarray(offset, jnp.int32), (b,))

    if seen is None:
        scalars, two_parts, causal = (offset,), {}, seq_len
    else:
        if block or exact_from % block_k:
            raise ValueError("a cache in two parts goes with a causal mask "
                             "and a first part of whole key blocks")
        scalars = (offset, jnp.broadcast_to(
            jnp.asarray(seen, jnp.int32), (b,)))
        two_parts, causal = {"exact_from": exact_from}, seq_len - exact_from

    def needed(first, qb):
        """Key blocks query block ``qb`` of a row that starts at ``first``
        reads: up to its last query's reach."""
        return blocks_read(
            _last_seen(first + (qb + 1) * block_q - 1, block), block_k,
            causal)

    def queries(b_, g, qb, kb, *scalar_refs):
        return b_, g, qb, 0

    def key_block(channels=lambda g: 0):
        """The key block a step fetches, and of its channels the block
        ``channels(g)``: its own while the query block needs it, the last
        needed one after (the same again: no fetch)."""
        def index(b_, g, qb, kb, offset_ref, *seen_ref):
            blocks = needed(offset_ref[b_], qb)
            if seen_ref:
                at = _two_parts(kb, block_k, seen_ref[0][b_], exact_from,
                                blocks)[1]
            else:
                at = jnp.minimum(kb, blocks - 1)
            return b_, at, channels(g)
        return index

    # a key/value head's queries, a row (new position, head of the group)
    q = q.reshape(b, s, nkv, group, dim).transpose(0, 2, 1, 3, 4).reshape(
        b, nkv, s * group, dim)
    folded = k_cache.ndim == 3
    if folded:
        tiles = _lane_tiles(dim, nkv)
        # each head's queries in the whole lane tiles its keys lie in
        padded = jnp.zeros(q.shape[:3] + (tiles * LANES,), q.dtype)
        for g in range(nkv):
            at = dim * g % LANES
            padded = padded.at[:, g, :, at:at + dim].set(q[:, g])
        q = padded
        keys = [pl.BlockSpec((None, block_k, LANES),
                             key_block(lambda g, t=t: dim * g // LANES + t))
                for t in range(tiles)]
        values = pl.BlockSpec((None, block_k, dv), key_block(lambda g: g))
    else:
        # the cache as it lies: (B, S Hkv, D); a block keeps its rank for
        # the view of 32 bits
        k_cache, v_cache = (x.reshape(b, seq_len * nkv, dim)
                            for x in (k_cache, v_cache))
        keys = [pl.BlockSpec((1, block_k * nkv, dim), key_block())]
        values = keys[0]
    out = pl.pallas_call(
        functools.partial(_chunk_kernel, scale=float(1 / np.sqrt(dim)),
                          group=group, kv_heads=nkv, block=block,
                          folded=folded, needed=needed, **two_parts),
        out_shape=jax.ShapeDtypeStruct((b, nkv, s * group, dv), q.dtype),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=len(scalars),
            grid=(b, nkv, s // block_q, seq_len // block_k),
            in_specs=[pl.BlockSpec((None, None, rows, q.shape[-1]), queries),
                      *keys, values],
            out_specs=pl.BlockSpec((None, None, rows, dv), queries),
            scratch_shapes=[pltpu.VMEM((rows, 1), jnp.float32),
                            pltpu.VMEM((rows, 1), jnp.float32),
                            pltpu.VMEM((rows, dv), jnp.float32)]),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary"),
            vmem_limit_bytes=VMEM_LIMIT),
        interpret=interpret,
        # what a device trace calls the kernel's events
        name="cached_attention_query_key_blocks",
    )(*scalars, q, *[k_cache] * len(keys), v_cache)
    return out.reshape(b, nkv, s, group, dv).transpose(0, 2, 1, 3, 4).reshape(
        b, s, nh, dv)
