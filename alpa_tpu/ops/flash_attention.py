"""Flash attention forward kernels in pallas (TPU), with recompute backward.

Blocked online-softmax attention: the q-block stays in VMEM, the softmax
normalizer is maintained incrementally, and the S x S score matrix never
materializes in HBM.  Two forward paths, picked by k/v size:

* **resident** (short sequences): k/v for one (batch, head) live in VMEM;
  grid (B*H, q blocks) with a fori_loop over k blocks and causal
  early-exit.
* **streaming** (k/v > ~4MB): grid (B*H, q blocks, k blocks) — k/v blocks
  stream from HBM via BlockSpec index maps, the (m, l, acc) state persists
  in VMEM scratch across the sequential innermost grid dim, and causal
  blocks above the diagonal are skipped with ``pl.when``.  Per-chip
  sequence length is then HBM-bound, and ring attention shards beyond
  that.

Backward: real pallas kernels in the VMEM-resident regime — the standard
two-kernel flash backward (dq over q blocks; dk/dv over k blocks) off the
saved (out, logsumexp) residuals, never materializing S x S scores.  In
the HBM-streaming regime (k/v beyond the VMEM budget) the backward falls
back to q-chunked recompute with the einsum reference implementation —
the remat-style tradeoff (XLA fuses the recomputed backward well).
"""
import functools
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e9


def _online_softmax_update(q, k_blk, v_blk, m_prev, l_prev, acc, *,
                           causal: bool, q_start, k_start):
    """One flash-attention block update, shared by both kernels:
    (m, l, acc) -> (m', l', acc') after attending q to one k/v block.
    m and l are (block_q, 1) columns: the TPU lowering has no 1-D
    vector layout, and a column broadcasts against the scores as is."""
    block_q = q.shape[0]
    block_k = k_blk.shape[0]
    s = jax.lax.dot_general(q, k_blk, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32)
    if causal:
        q_pos = q_start + lax.broadcasted_iota(jnp.int32,
                                               (block_q, block_k), 0)
        k_pos = k_start + lax.broadcasted_iota(jnp.int32,
                                               (block_q, block_k), 1)
        s = jnp.where(q_pos >= k_pos, s, NEG_INF)
    m_cur = jnp.max(s, axis=1, keepdims=True)
    m_new = jnp.maximum(m_prev, m_cur)
    p = jnp.exp(s - m_new)
    alpha = jnp.exp(m_prev - m_new)
    l_new = l_prev * alpha + jnp.sum(p, axis=1, keepdims=True)
    acc_new = acc * alpha + jax.lax.dot_general(
        p, v_blk, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)
    return m_new, l_new, acc_new


def _flash_fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, *,
                      block_k: int, causal: bool, sm_scale: float,
                      q_offset: int):
    """One (batch*head, q-block) program instance.

    q_ref: (block_q, d); k_ref/v_ref: (s_k, d); o_ref: (block_q, d);
    lse_ref: (block_q, 1) — per-row logsumexp of the scaled scores, the
    residual the backward kernels reconstruct P from.
    """
    block_q, d = q_ref.shape
    s_k = k_ref.shape[0]
    q = q_ref[:].astype(jnp.float32) * sm_scale

    q_blk = pl.program_id(1)
    q_start = q_blk * block_q + q_offset

    m0 = jnp.full((block_q, 1), NEG_INF, jnp.float32)
    l0 = jnp.zeros((block_q, 1), jnp.float32)
    acc0 = jnp.zeros((block_q, d), jnp.float32)

    num_k_blocks = pl.cdiv(s_k, block_k)

    def body(kb, carry):
        m_prev, l_prev, acc = carry
        k_start = kb * block_k
        k_blk = k_ref[pl.ds(k_start, block_k), :].astype(jnp.float32)
        v_blk = v_ref[pl.ds(k_start, block_k), :].astype(jnp.float32)
        return _online_softmax_update(q, k_blk, v_blk, m_prev, l_prev,
                                      acc, causal=causal, q_start=q_start,
                                      k_start=k_start)

    if causal:
        # skip fully-masked k blocks beyond the diagonal
        last_needed = lax.div(q_start + block_q - 1, block_k) + 1
        n_iter = jnp.minimum(last_needed, num_k_blocks)
    else:
        n_iter = num_k_blocks
    m, l, acc = lax.fori_loop(0, n_iter, body, (m0, l0, acc0))
    l = jnp.maximum(l, 1e-20)
    o_ref[:] = (acc / l).astype(o_ref.dtype)
    lse_ref[:] = m + jnp.log(l)


# above this many k/v bytes per (batch, head), stream blocks from HBM
# instead of keeping k/v VMEM-resident.  The compiler gives one kernel
# 16 MiB of VMEM on a v5e and double-buffers every blocked operand, so
# 4 MiB of resident pairs is what fits beside the q/o blocks and the
# f32 block intermediates (compiled for v5e in tests/ops/test_tpu_compile.py)
VMEM_RESIDENT_LIMIT = 4 * 1024 * 1024


def _resident_bytes(seq: int, d: int, dtype) -> int:
    """VMEM bytes of one resident (seq, d) pair (k+v, or q+do): the
    minor dim is laid out in 128 lanes, so head dim 64 costs what 128
    does."""
    lanes = -(-d // 128) * 128
    return 2 * seq * lanes * jnp.dtype(dtype).itemsize


def _flash_streaming_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, m_ref,
                            l_ref, acc_ref, *, causal: bool,
                            sm_scale: float, q_offset: int, nk: int,
                            block_q: int, block_k: int):
    """Grid (B*H, q blocks, k blocks): k/v blocks stream from HBM; the
    online-softmax state (m, l, acc) lives in VMEM scratch that persists
    across the sequential innermost grid dim."""
    kb = pl.program_id(2)
    qb = pl.program_id(1)

    @pl.when(kb == 0)
    def _init():
        m_ref[:] = jnp.full_like(m_ref, NEG_INF)
        l_ref[:] = jnp.zeros_like(l_ref)
        acc_ref[:] = jnp.zeros_like(acc_ref)

    q_start = qb * block_q + q_offset
    k_start = kb * block_k
    # blocks entirely above the diagonal contribute nothing (their DMA is
    # also suppressed by the clamped k index map in _flash_forward)
    run = (q_start + block_q - 1 >= k_start) if causal else True

    @pl.when(run)
    def _body():
        q = q_ref[:].astype(jnp.float32) * sm_scale
        k_blk = k_ref[:].astype(jnp.float32)
        v_blk = v_ref[:].astype(jnp.float32)
        m_new, l_new, acc_new = _online_softmax_update(
            q, k_blk, v_blk, m_ref[:], l_ref[:], acc_ref[:],
            causal=causal, q_start=q_start, k_start=k_start)
        m_ref[:] = m_new
        l_ref[:] = l_new
        acc_ref[:] = acc_new

    @pl.when(kb == nk - 1)
    def _finalize():
        l = jnp.maximum(l_ref[:], 1e-20)
        o_ref[:] = (acc_ref[:] / l).astype(o_ref.dtype)
        lse_ref[:] = m_ref[:] + jnp.log(l)


def _pick_block(size: int, target: int) -> int:
    """Largest divisor of ``size`` not exceeding ``target`` — blocks must
    tile the sequence exactly (no partial-block masking implemented)."""
    b = min(target, size)
    while size % b != 0:
        b -= 1
    return b


def _pallas_call(kernel, **kwargs):
    """``pl.pallas_call`` that compiles the kernel when the program is
    lowered for a TPU and interprets it on any other platform.  The
    choice is made at lowering time from the platform compiled for
    (``lax.platform_dependent``), not from ``jax.default_backend()``:
    on a TPU the kernel compiles or raises, it never interprets."""
    compiled = pl.pallas_call(kernel, **kwargs)
    interpreted = pl.pallas_call(kernel, interpret=True, **kwargs)
    return lambda *args: lax.platform_dependent(
        *args, tpu=compiled, default=interpreted)


def _flash_forward(q, k, v, *, causal: bool, q_offset: int = 0,
                   block_q: int = 256, block_k: int = 256):
    """q: (B, Sq, H, D); k/v: (B, Sk, H, D) -> (out (B, Sq, H, D),
    lse (B*H, Sq))."""
    b, sq, h, d = q.shape
    sk = k.shape[1]
    sm_scale = 1.0 / np.sqrt(d)
    block_q = _pick_block(sq, block_q)
    block_k = _pick_block(sk, block_k)

    # (B, Sq, H, D) -> (B*H, Sq, D)
    qt = q.transpose(0, 2, 1, 3).reshape(b * h, sq, d)
    kt = k.transpose(0, 2, 1, 3).reshape(b * h, sk, d)
    vt = v.transpose(0, 2, 1, 3).reshape(b * h, sk, d)
    # lse leaves the kernel as a (Sq, 1) column per (batch, head): the TPU
    # lowering wants the last two block dims to be multiples of (8, 128)
    # or the whole array dim, which (block_q, 1) is and (block_q,) is not
    out_shape = (jax.ShapeDtypeStruct((b * h, sq, d), q.dtype),
                 jax.ShapeDtypeStruct((b * h, sq, 1), jnp.float32))

    if _resident_bytes(sk, d, k.dtype) > VMEM_RESIDENT_LIMIT:
        # long-sequence path: stream k/v blocks, carry softmax state in
        # scratch across the innermost (sequential) grid dim
        nk = sk // block_k
        grid = (b * h, sq // block_q, nk)
        if causal:
            # clamp the k index for fully-masked blocks to the last needed
            # block: pl.when skips their compute, and the clamp means no
            # fresh DMA is issued for them either (the previous block's
            # buffer is reused) — saves ~half the k/v HBM traffic
            def kv_index(i, j, kb):
                last_needed = (j * block_q + block_q - 1 + q_offset) \
                    // block_k
                return (i, jnp.minimum(kb, last_needed), 0)
        else:
            def kv_index(i, j, kb):
                return (i, kb, 0)
        out, lse = _pallas_call(
            partial(_flash_streaming_kernel, causal=causal,
                    sm_scale=sm_scale, q_offset=q_offset, nk=nk,
                    block_q=block_q, block_k=block_k),
            out_shape=out_shape,
            grid=grid,
            in_specs=[
                pl.BlockSpec((None, block_q, d),
                             lambda i, j, kb: (i, j, 0)),
                pl.BlockSpec((None, block_k, d), kv_index),
                pl.BlockSpec((None, block_k, d), kv_index),
            ],
            out_specs=(
                pl.BlockSpec((None, block_q, d),
                             lambda i, j, kb: (i, j, 0)),
                pl.BlockSpec((None, block_q, 1),
                             lambda i, j, kb: (i, j, 0)),
            ),
            scratch_shapes=[
                pltpu.VMEM((block_q, 1), jnp.float32),
                pltpu.VMEM((block_q, 1), jnp.float32),
                pltpu.VMEM((block_q, d), jnp.float32),
            ],
        )(qt, kt, vt)
        return (out.reshape(b, h, sq, d).transpose(0, 2, 1, 3),
                lse.reshape(b * h, sq))

    grid = (b * h, pl.cdiv(sq, block_q))
    out, lse = _pallas_call(
        partial(_flash_fwd_kernel, block_k=block_k, causal=causal,
                sm_scale=sm_scale, q_offset=q_offset),
        out_shape=out_shape,
        grid=grid,
        in_specs=[
            pl.BlockSpec((None, block_q, d), lambda i, j: (i, j, 0)),
            pl.BlockSpec((None, sk, d), lambda i, j: (i, 0, 0)),
            pl.BlockSpec((None, sk, d), lambda i, j: (i, 0, 0)),
        ],
        out_specs=(
            pl.BlockSpec((None, block_q, d), lambda i, j: (i, j, 0)),
            pl.BlockSpec((None, block_q, 1), lambda i, j: (i, j, 0)),
        ),
    )(qt, kt, vt)
    return (out.reshape(b, h, sq, d).transpose(0, 2, 1, 3),
            lse.reshape(b * h, sq))


def _flash_bwd_dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                         dq_ref, *, block_k: int, causal: bool,
                         sm_scale: float, q_offset: int):
    """dq for one (batch*head, q-block): loop over k/v blocks up to the
    diagonal.  P is rebuilt from the saved logsumexp; delta is the
    precomputed rowsum(dO * O).  lse_ref/delta_ref: (block_q, 1)."""
    block_q, d = q_ref.shape
    s_k = k_ref.shape[0]
    q = q_ref[:].astype(jnp.float32)
    do = do_ref[:].astype(jnp.float32)
    lse = lse_ref[:]
    delta = delta_ref[:]
    q_start = pl.program_id(1) * block_q + q_offset

    def body(kb, dq_acc):
        k_start = kb * block_k
        k_blk = k_ref[pl.ds(k_start, block_k), :].astype(jnp.float32)
        v_blk = v_ref[pl.ds(k_start, block_k), :].astype(jnp.float32)
        s = sm_scale * jax.lax.dot_general(
            q, k_blk, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)
        if causal:
            q_pos = q_start + lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 0)
            k_pos = k_start + lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 1)
            s = jnp.where(q_pos >= k_pos, s, NEG_INF)
        p = jnp.exp(s - lse)
        dp = jax.lax.dot_general(do, v_blk, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        ds = p * (dp - delta)
        return dq_acc + jax.lax.dot_general(
            ds, k_blk, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    num_k_blocks = pl.cdiv(s_k, block_k)
    if causal:
        last_needed = lax.div(q_start + block_q - 1, block_k) + 1
        n_iter = jnp.minimum(last_needed, num_k_blocks)
    else:
        n_iter = num_k_blocks
    dq = lax.fori_loop(0, n_iter, body,
                       jnp.zeros((block_q, d), jnp.float32))
    dq_ref[:] = (dq * sm_scale).astype(dq_ref.dtype)


def _flash_bwd_dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                          dk_ref, dv_ref, *, block_q: int, causal: bool,
                          sm_scale: float, q_offset: int):
    """dk/dv for one (batch*head, k-block): loop over q blocks from the
    diagonal down.  Works on the transposed scores S^T (block_k, block_q),
    so lse/delta come in as rows — lse_ref/delta_ref: (num q blocks,
    block_q), one row per q block — and every product is a plain matmul."""
    block_k, d = k_ref.shape
    s_q = q_ref.shape[0]
    k_blk = k_ref[:].astype(jnp.float32)
    v_blk = v_ref[:].astype(jnp.float32)
    k_start = pl.program_id(1) * block_k

    def body(qb, carry):
        dk_acc, dv_acc = carry
        q_start_local = qb * block_q
        q_start = q_start_local + q_offset
        q = q_ref[pl.ds(q_start_local, block_q), :].astype(jnp.float32)
        do = do_ref[pl.ds(q_start_local, block_q), :].astype(jnp.float32)
        lse = lse_ref[pl.ds(qb, 1), :]
        delta = delta_ref[pl.ds(qb, 1), :]
        s_t = sm_scale * jax.lax.dot_general(
            k_blk, q, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)
        if causal:
            k_pos = k_start + lax.broadcasted_iota(
                jnp.int32, (block_k, block_q), 0)
            q_pos = q_start + lax.broadcasted_iota(
                jnp.int32, (block_k, block_q), 1)
            s_t = jnp.where(q_pos >= k_pos, s_t, NEG_INF)
        p_t = jnp.exp(s_t - lse)
        dv_acc = dv_acc + jax.lax.dot_general(
            p_t, do, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        dp_t = jax.lax.dot_general(v_blk, do, (((1,), (1,)), ((), ())),
                                   preferred_element_type=jnp.float32)
        ds_t = p_t * (dp_t - delta)
        dk_acc = dk_acc + jax.lax.dot_general(
            ds_t, q, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        return dk_acc, dv_acc

    num_q_blocks = pl.cdiv(s_q, block_q)
    if causal:
        # the first q block whose rows can see this k block
        first = lax.div(jnp.maximum(k_start - q_offset, 0), block_q)
    else:
        first = 0
    z = jnp.zeros((block_k, d), jnp.float32)
    dk, dv = lax.fori_loop(first, num_q_blocks, body, (z, z))
    dk_ref[:] = (dk * sm_scale).astype(dk_ref.dtype)
    dv_ref[:] = dv.astype(dv_ref.dtype)


def _flash_backward_kernels(q, k, v, out, lse, do, *, causal: bool,
                            q_offset: int, block_q: int = 256,
                            block_k: int = 256):
    """Two-pass flash backward (dq; dk/dv), VMEM-resident regime."""
    b, sq, h, d = q.shape
    sk = k.shape[1]
    sm_scale = 1.0 / np.sqrt(d)
    block_q = _pick_block(sq, block_q)
    block_k = _pick_block(sk, block_k)
    nq = sq // block_q

    qt = q.transpose(0, 2, 1, 3).reshape(b * h, sq, d)
    kt = k.transpose(0, 2, 1, 3).reshape(b * h, sk, d)
    vt = v.transpose(0, 2, 1, 3).reshape(b * h, sk, d)
    dot = do.transpose(0, 2, 1, 3).reshape(b * h, sq, d)
    ot = out.transpose(0, 2, 1, 3).reshape(b * h, sq, d)
    # delta = rowsum(dO * O): cheap elementwise reduce, XLA-fused
    delta = jnp.sum(dot.astype(jnp.float32) * ot.astype(jnp.float32),
                    axis=-1)
    # the per-row vectors in the two layouts the TPU lowering accepts
    # (see _flash_forward): (Sq, 1) columns for the dq kernel, and one
    # (block_q,) row per q block for the dkv kernel
    as_col = lambda x: x.reshape(b * h, sq, 1)        # noqa: E731
    as_rows = lambda x: x.reshape(b * h, nq, block_q)  # noqa: E731

    full = lambda i, j: (i, 0, 0)  # noqa: E731
    blk = lambda i, j: (i, j, 0)   # noqa: E731

    dq = _pallas_call(
        partial(_flash_bwd_dq_kernel, block_k=block_k, causal=causal,
                sm_scale=sm_scale, q_offset=q_offset),
        out_shape=jax.ShapeDtypeStruct((b * h, sq, d), q.dtype),
        grid=(b * h, nq),
        in_specs=[
            pl.BlockSpec((None, block_q, d), blk),       # q
            pl.BlockSpec((None, sk, d), full),           # k
            pl.BlockSpec((None, sk, d), full),           # v
            pl.BlockSpec((None, block_q, d), blk),       # do
            pl.BlockSpec((None, block_q, 1), blk),       # lse
            pl.BlockSpec((None, block_q, 1), blk),       # delta
        ],
        out_specs=pl.BlockSpec((None, block_q, d), blk),
    )(qt, kt, vt, dot, as_col(lse), as_col(delta))

    dk, dv = _pallas_call(
        partial(_flash_bwd_dkv_kernel, block_q=block_q, causal=causal,
                sm_scale=sm_scale, q_offset=q_offset),
        out_shape=(jax.ShapeDtypeStruct((b * h, sk, d), k.dtype),
                   jax.ShapeDtypeStruct((b * h, sk, d), v.dtype)),
        grid=(b * h, sk // block_k),
        in_specs=[
            pl.BlockSpec((None, sq, d), full),           # q
            pl.BlockSpec((None, block_k, d), blk),       # k
            pl.BlockSpec((None, block_k, d), blk),       # v
            pl.BlockSpec((None, sq, d), full),           # do
            pl.BlockSpec((None, nq, block_q), full),     # lse
            pl.BlockSpec((None, nq, block_q), full),     # delta
        ],
        out_specs=(pl.BlockSpec((None, block_k, d), blk),
                   pl.BlockSpec((None, block_k, d), blk)),
    )(qt, kt, vt, dot, as_rows(lse), as_rows(delta))

    unt = lambda x, s: x.reshape(b, h, s, d).transpose(0, 2, 1, 3)  # noqa: E731
    return unt(dq, sq), unt(dk, sk), unt(dv, sk)


def _bwd_kernels_feasible(q, k) -> bool:
    """Static predicate: the dq kernel keeps k+v (and the dkv kernel
    q+do) resident per (batch, head) — beyond the VMEM budget the
    backward recomputes instead."""
    d = q.shape[-1]
    return _resident_bytes(max(q.shape[1], k.shape[1]), d,
                           q.dtype) <= VMEM_RESIDENT_LIMIT


@partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6))
def _flash_attention(q, k, v, causal, q_offset, block_q, block_k):
    return _flash_forward(q, k, v, causal=causal, q_offset=q_offset,
                          block_q=block_q, block_k=block_k)[0]


def _flash_fwd_rule(q, k, v, causal, q_offset, block_q, block_k):
    out, lse = _flash_forward(q, k, v, causal=causal, q_offset=q_offset,
                              block_q=block_q, block_k=block_k)
    if _bwd_kernels_feasible(q, k):
        return out, (q, k, v, out, lse)
    # streaming regime: the recompute backward reads only (q, k, v) —
    # do not hold activation-sized out/lse residuals exactly where
    # memory is tightest
    return out, (q, k, v, None, None)


def _chunked_reference_attention(q, k, v, *, causal: bool, offset: int,
                                 chunk: int = 512):
    """Reference attention computed q-chunk-wise with lax.map: peak score
    memory is chunk x S instead of S x S, so the recompute backward stays
    feasible at the long sequence lengths the streaming forward unlocks."""
    from alpa_tpu.model.gpt_model import reference_attention
    b, s, h, d = q.shape
    if s % chunk != 0 or s <= chunk:
        return reference_attention(q, k, v, causal=causal, offset=offset)
    n = s // chunk
    qc = q.reshape(b, n, chunk, h, d).transpose(1, 0, 2, 3, 4)

    def one_chunk(args):
        i, q_i = args
        return reference_attention(q_i, k, v, causal=causal,
                                   offset=offset + i * chunk)

    # checkpointed: the vjp of the map keeps each chunk's inputs, not its
    # (chunk, S) scores stacked over all chunks (S x S again)
    outs = jax.lax.map(jax.checkpoint(one_chunk), (jnp.arange(n), qc))
    return outs.transpose(1, 0, 2, 3, 4).reshape(b, s, h, d)


def _flash_bwd_rule(causal, q_offset, block_q, block_k, res, do):
    q, k, v, out, lse = res
    if out is not None:  # resident regime (see _flash_fwd_rule)
        return _flash_backward_kernels(q, k, v, out, lse, do,
                                       causal=causal, q_offset=q_offset,
                                       block_q=block_q, block_k=block_k)
    _, vjp = jax.vjp(
        lambda q_, k_, v_: _chunked_reference_attention(
            q_, k_, v_, causal=causal, offset=q_offset), q, k, v)
    return vjp(do)


_flash_attention.defvjp(_flash_fwd_rule, _flash_bwd_rule)


def flash_attention(q, k, v, *, causal: bool = True, offset: int = 0,
                    block_q: int = 256, block_k: int = 256):
    """Drop-in replacement for ``reference_attention`` (gpt_model.py).
    ``block_q``/``block_k`` tune the kernel tiling (targets; clipped to
    divisors of the sequence lengths)."""
    return _flash_attention(q, k, v, causal, offset, block_q, block_k)
