"""Attention of a few new queries a row over the row's written cache of
per-head keys and values (grouped-query or multi-head): one Pallas kernel
over key blocks that reads of each row's cache what the row holds.

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

The kernel is compiled where the program is lowered for a TPU
(``gpt_model.cached_attention`` chooses between it and
``reference_attention`` with ``lax.platform_dependent``);
``interpret=True`` runs it anywhere, for the tests.
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
# new positions a row: a decode tick's one, a diffusion block's few; a
# prefill's chunk wants a kernel over query blocks too
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
    queries a row in whole sublanes, both caches' channels in whole lanes
    (the key/value heads together), few enough key/value heads, the cache
    in whole key blocks."""
    _, s, nh, dim = q.shape
    seq_len, width = k_cache.shape[1], k_cache.shape[2]
    if width % dim:
        return False
    nkv = width // dim
    if (s > MAX_QUERIES or nh % nkv or (s * nh) % 16 or
            nkv > MAX_KV_HEADS or v_cache.shape[2] % nkv or
            width % LANES or v_cache.shape[2] % LANES or
            width > BLOCK_ELEMENTS // 16):
        return False
    return seq_len % folded_block_k(k_cache, v_cache) == 0


def _folded_kernel(blocks_ref, offset_ref, q_ref, k_ref, v_ref, o_ref,
                   m_ref, l_ref, acc_ref, *, scale: float, heads: int):
    b, kb = pl.program_id(0), pl.program_id(1)
    pl.when(kb == 0)(lambda: _start(m_ref, l_ref, acc_ref))

    @pl.when(kb < blocks_ref[b])
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
        _fold_in(s, k_pos <= offset_ref[b] + row // heads, v_ref[:], m_ref,
                 l_ref, acc_ref)

    pl.when(kb == pl.num_programs(1) - 1)(
        lambda: _finish(o_ref, l_ref, acc_ref))


def folded_cached_attention(q, k_cache, v_cache, offset, *,
                            interpret: bool = False):
    """``q`` (B, s, H, D) against written caches with the heads folded into
    the channels, ``k_cache`` (B, S, Hkv D) and ``v_cache`` (B, S, Hkv
    Dv); row ``b``'s query i sits at ``offset[b] + i`` ((B,) int32) and
    sees the keys at or before it.  Returns (B, s, H, Dv) in the queries'
    dtype."""
    b, s, nh, dim = q.shape
    seq_len, k_width = k_cache.shape[1], k_cache.shape[2]
    nkv, v_width = k_width // dim, v_cache.shape[2]
    dv = v_width // nkv
    per_block = folded_block_k(k_cache, v_cache)
    offset = offset.astype(jnp.int32)
    blocks = blocks_read(offset + s - 1, per_block, seq_len)
    # which key/value head a head reads
    group = jax.nn.one_hot(jnp.arange(nh) // (nh // nkv), nkv, dtype=q.dtype)
    q = (q[:, :, :, None, :] * group[:, :, None]).reshape(b, s * nh, k_width)

    def per_row(b_, kb, blocks_ref, offset_ref):
        return b_, 0, 0

    def key_block(b_, kb, blocks_ref, offset_ref):
        return b_, _block_of(b_, kb, blocks_ref), 0

    out = pl.pallas_call(
        functools.partial(_folded_kernel, scale=float(1 / np.sqrt(dim)),
                          heads=nh),
        out_shape=jax.ShapeDtypeStruct((b, s * nh, v_width), q.dtype),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(b, seq_len // per_block),
            in_specs=[
                pl.BlockSpec((None, s * nh, k_width), per_row),
                pl.BlockSpec((None, per_block, k_width), key_block),
                pl.BlockSpec((None, per_block, v_width), key_block),
            ],
            out_specs=pl.BlockSpec((None, s * nh, v_width), per_row),
            scratch_shapes=[pltpu.VMEM((s * nh, 1), jnp.float32),
                            pltpu.VMEM((s * nh, 1), jnp.float32),
                            pltpu.VMEM((s * nh, v_width), jnp.float32)]),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=VMEM_LIMIT),
        interpret=interpret,
        # what a device trace calls the kernel's events
        name="cached_attention_folded_key_blocks",
    )(blocks, offset, q, k_cache, v_cache)
    return jnp.einsum("bshgd,hg->bshd", out.reshape(b, s, nh, nkv, dv),
                      group)
