"""Fused attention over one sequence a row, forward and backward: two
Pallas kernels that never write the scores to memory.

What a training step needs of ``model/gpt_model.py`` ``SelfAttention``
without a cache: ``q``, ``k``, ``v`` (B, S, H, D) of one length, as many
key/value heads as query heads, a causal mask or none.
``reference_attention`` writes the (B, H, S, S) float32 scores, reads them
for the softmax, writes the probabilities and reads them for the values,
in the forward pass, in a rematerialised block and in the backward pass:
at 1,024 positions that traffic, not the products, is what the core costs
(PERF.md section 6, PR 45).  Here a block of scores lives in fast memory
only (Dao et al., "FlashAttention", 2022; the backward in one pass as in
"FlashAttention-2", 2023).

The mathematics is ``reference_attention``'s at the configuration's
precision: operand blocks go to the matrix unit in the dtype they arrive
in (bfloat16 in the models) and every product accumulates in float32; the
scale is applied to the float32 scores; maxima, sums, the saved
log-sum-exp and ``delta`` are float32; the probabilities are cast to
``v``'s dtype before the values' product.  No operand of a product is
cast to float32: the matrix unit would multiply it in several bfloat16
passes.

**Forward**, grid (batch, group of heads, query block): the row's keys and
values stay in fast memory, a ``fori_loop`` walks the key blocks a query block
can see with the running maximum, sum and weighted values of the online
softmax in its carry; under a causal mask the blocks wholly below the
diagonal are not masked (nothing to hide) and those above it are not
visited.  It returns the output and, a query, the log-sum-exp of its
scaled scores, laid out with the positions in the lanes.

**Backward**, grid (batch x group of heads, key block), the key blocks
sequential:
for a key block, a ``fori_loop`` over the query blocks that see it
rebuilds the transposed probabilities from the saved log-sum-exp and
accumulates the block's ``dk`` and ``dv``; each pair's ``dq`` goes into a
float32 scratch of the whole row that stays in fast memory across the key
blocks and is written out after the last.  Scores, probabilities and their
gradients are computed once a pair of blocks, five products in all.

Both read ``q``, ``k``, ``v`` and write their results AS THEY LIE, (B, S,
H D) with the heads side by side in the lanes: a kernel instance takes one
group of lanes a row, which is one head of 128 channels or more and
``128 / D`` narrower heads (two of 64), and no array is transposed to
heads-first and back around a kernel (eight transposes a layer of the
first form of these kernels: a tenth of the GPT cell's step, PERF.md
section 6, PR 45).  The narrower heads of a group are never picked out of
the lanes they share: a head's products run over the whole group with the
other heads' lanes of ONE operand zeroed (``q`` forward; ``k``, ``v``
backward), which contracts over its own channels only and costs the
matrix unit what a product over 64 of its 128 lanes costs anyway; what
lands in the other heads' lanes of a result is masked off as it is
written.

The blocks follow the head width and the length (``blocks``); ``fits``
says which calls the kernels take.  They are compiled where the program
is lowered for a TPU (``gpt_model.attention`` chooses between them and
``reference_attention`` with ``lax.platform_dependent``);
``interpret=True`` runs them anywhere, for the tests.
"""
import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from alpa_tpu.ops.latent_attention import VMEM_LIMIT

# what a masked score is replaced by (``reference_attention``'s)
NEG_INF = -1e9
# the lanes of a vector register
LANES = 128
# positions of a query block and of a key block at their largest: the
# fastest of the blocks tried on the v5e at both head widths (PERF.md
# section 6, PR 45)
BLOCK = 512
# the length from which the kernels beat ``reference_attention``, forward
# and backward, on the v5e (same place): below it the scores are small
# enough for the compiler's own fusions
MIN_SEQ = 512
# the longest row whose backward pass keeps its queries, their gradients
# and the float32 ``dq`` in the kernel's fast memory beside the blocks'
# intermediates
MAX_SEQ = 16384
# a product of (rows, d) x (columns, d) over d, and of (k, rows) x (k,
# columns) over k
_NT = (((1,), (1,)), ((), ()))
_TN = (((0,), (0,)), ((), ()))


def blocks(seq: int, dim: int):
    """(query block, key block) of a call: ``BLOCK`` positions, half of
    that for heads wider than the lanes (their float32 accumulators are
    twice the size), never more than the row."""
    block = min(seq, BLOCK if dim <= LANES else BLOCK // 2)
    return block, block


def heads_a_group(dim: int) -> int:
    """Heads that lie side by side in one group of lanes: one of 128
    channels or more, ``128 / dim`` narrower ones."""
    return max(1, LANES // dim)


def fits(q, k) -> bool:
    """Whether the kernels take these shapes: as many key/value heads as
    query heads, ``q`` and ``k`` of one length that the blocks divide,
    between ``MIN_SEQ`` and ``MAX_SEQ``, the heads in whole groups of
    lanes."""
    _, seq, heads, dim = q.shape
    group = heads_a_group(dim)
    if k.shape[1] != seq or k.shape[2] != heads or \
            (group * dim) % LANES or heads % group:
        return False
    return MIN_SEQ <= seq <= MAX_SEQ and seq % blocks(seq, dim)[0] == 0


def _row_of(col):
    """A (n, 1) float32 column as a (1, n) row: the entries move from the
    sublanes to the lanes through the diagonal of an (n, n) mask, 128
    columns at a time."""
    n = col.shape[0]
    step = min(n, LANES)
    eye = (lax.broadcasted_iota(jnp.int32, (step, step), 0) ==
           lax.broadcasted_iota(jnp.int32, (step, step), 1))
    return jnp.concatenate([
        jnp.sum(jnp.where(eye, col[at:at + step], 0.0), axis=0,
                keepdims=True) for at in range(0, n, step)], axis=1)


def _seen(q_start, k_start, block_q: int, block_k: int, keys_first: bool):
    """Which of a block's (query, key) pairs the causal mask shows, as
    (block_q, block_k), or transposed with ``keys_first``."""
    shape = (block_k, block_q) if keys_first else (block_q, block_k)
    q_pos = q_start + lax.broadcasted_iota(jnp.int32, shape,
                                           1 if keys_first else 0)
    k_pos = k_start + lax.broadcasted_iota(jnp.int32, shape,
                                           0 if keys_first else 1)
    return q_pos >= k_pos


def _of_head(x, head: int, dim: int):
    """``x`` (rows, group's lanes) with the lanes of every head of the
    group but ``head`` zeroed; ``x`` itself where the group is one head."""
    if x.shape[1] == dim:
        return x
    lane = lax.broadcasted_iota(jnp.int32, x.shape, 1)
    return jnp.where((lane >= head * dim) & (lane < (head + 1) * dim), x,
                     jnp.zeros_like(x))


def _forward_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, *, dim: int,
                    block_k: int, causal: bool, scale: float):
    """One (batch, group of heads, query block).  q_ref, o_ref: (block_q,
    W), the group's W lanes; k_ref, v_ref: (S, W); lse_ref: (heads of the
    group, block_q)."""
    block_q, width = q_ref.shape
    q_start = pl.program_id(2) * block_q
    n_blocks = k_ref.shape[0] // block_k

    def fold_in(q, masked, kb, carry):
        m_prev, l_prev, acc = carry
        k_start = pl.multiple_of(kb * block_k, block_k)
        s = scale * lax.dot_general(q, k_ref[pl.ds(k_start, block_k), :],
                                    _NT, preferred_element_type=jnp.float32)
        if masked:
            s = jnp.where(_seen(q_start, k_start, block_q, block_k, False),
                          s, NEG_INF)
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m_prev - m_new)
        v_blk = v_ref[pl.ds(k_start, block_k), :]
        return (m_new, l_prev * alpha + jnp.sum(p, axis=1, keepdims=True),
                acc * alpha + jnp.dot(p.astype(v_blk.dtype), v_blk,
                                      preferred_element_type=jnp.float32))

    out = None
    for head in range(width // dim):
        # the scores contract over this head's channels: the others' are
        # zeros in ``q``
        q = _of_head(q_ref[:], head, dim)
        carry = (jnp.full((block_q, 1), NEG_INF, jnp.float32),
                 jnp.zeros((block_q, 1), jnp.float32),
                 jnp.zeros((block_q, width), jnp.float32))
        if causal:
            # the key blocks wholly at or before the block's first query,
            # then those the diagonal crosses
            below = q_start // block_k
            carry = lax.fori_loop(
                0, below, functools.partial(fold_in, q, False), carry)
            carry = lax.fori_loop(
                below, jnp.minimum((q_start + block_q - 1) // block_k + 1,
                                   n_blocks),
                functools.partial(fold_in, q, True), carry)
        else:
            carry = lax.fori_loop(
                0, n_blocks, functools.partial(fold_in, q, False), carry)
        m, l, acc = carry
        # the head's own lanes of the weighted values
        mine = _of_head(acc / l, head, dim)
        out = mine if out is None else out + mine
        lse_ref[pl.ds(head, 1), :] = _row_of(m + jnp.log(l))
    o_ref[:] = out.astype(o_ref.dtype)


def _backward_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                     dq_ref, dk_ref, dv_ref, dq_acc, *, dim: int,
                     block_q: int, causal: bool, scale: float):
    """One (batch x group of heads, key block).  k_ref, v_ref, dk_ref,
    dv_ref: (block_k, W), the group's W lanes; q_ref, do_ref, dq_ref: (S,
    W); lse_ref, delta_ref: (heads of the group x S / block_q, block_q), a
    row a head and query block; dq_acc: (S, W) float32.  Works on the
    transposed scores (block_k, block_q): the per-query vectors come in as
    rows and every product but ``dq``'s is plain."""
    block_k, width = k_ref.shape
    kb = pl.program_id(1)
    k_start = kb * block_k
    n_blocks = q_ref.shape[0] // block_q

    @pl.when(kb == 0)
    def _start():
        dq_acc[:] = jnp.zeros_like(dq_acc)

    def fold_in(k_blk, v_blk, first_row, masked, qb, carry):
        dk, dv = carry
        q_start = pl.multiple_of(qb * block_q, block_q)
        q = q_ref[pl.ds(q_start, block_q), :]
        do = do_ref[pl.ds(q_start, block_q), :]
        s_t = scale * lax.dot_general(k_blk, q, _NT,
                                      preferred_element_type=jnp.float32)
        if masked:
            s_t = jnp.where(_seen(q_start, k_start, block_q, block_k, True),
                            s_t, NEG_INF)
        p_t = jnp.exp(s_t - lse_ref[pl.ds(first_row + qb, 1), :])
        dv = dv + jnp.dot(p_t.astype(do.dtype), do,
                          preferred_element_type=jnp.float32)
        dp_t = lax.dot_general(v_blk, do, _NT,
                               preferred_element_type=jnp.float32)
        ds_t = (p_t * (dp_t - delta_ref[pl.ds(first_row + qb, 1), :])
                ).astype(q.dtype)
        dk = dk + jnp.dot(ds_t, q, preferred_element_type=jnp.float32)
        dq_acc[pl.ds(q_start, block_q), :] += lax.dot_general(
            ds_t, k_blk, _TN, preferred_element_type=jnp.float32)
        return dk, dv

    dk_out = dv_out = None
    for head in range(width // dim):
        # every product contracts over, or lands in, this head's channels:
        # the others' are zeros in ``k`` and ``v``
        fold = functools.partial(fold_in, _of_head(k_ref[:], head, dim),
                                 _of_head(v_ref[:], head, dim),
                                 head * n_blocks)
        carry = (jnp.zeros((block_k, width), jnp.float32),
                 jnp.zeros((block_k, width), jnp.float32))
        if causal:
            # the query blocks the diagonal crosses, then those wholly
            # after the block's last key
            first = k_start // block_q
            after = jnp.minimum((k_start + block_k - 1) // block_q + 1,
                                n_blocks)
            carry = lax.fori_loop(first, after,
                                  functools.partial(fold, True), carry)
            carry = lax.fori_loop(after, n_blocks,
                                  functools.partial(fold, False), carry)
        else:
            carry = lax.fori_loop(0, n_blocks,
                                  functools.partial(fold, False), carry)
        dk, dv = (_of_head(x, head, dim) for x in carry)
        dk_out = dk if dk_out is None else dk_out + dk
        dv_out = dv if dv_out is None else dv_out + dv
    dk_ref[:] = (scale * dk_out).astype(dk_ref.dtype)
    dv_ref[:] = dv_out.astype(dv_ref.dtype)

    @pl.when(kb == pl.num_programs(1) - 1)
    def _finish():
        dq_ref[:] = (scale * dq_acc[:]).astype(dq_ref.dtype)


def _groups(heads: int, dim: int):
    """(heads a group of lanes, the group's lanes, groups a row)."""
    group = heads_a_group(dim)
    return group, group * dim, heads // group


def _forward(q, k, v, causal: bool, block_q: int, block_k: int,
             interpret: bool):
    """-> (out (B, S, H, D), lse (B, H, S) float32)."""
    b, seq, h, d = q.shape
    group, width, groups = _groups(h, d)
    as_it_lies = lambda x: x.reshape(b, seq, h * d)   # noqa: E731
    block = lambda i, g, j: (i, j, g)   # noqa: E731
    row = lambda i, g, j: (i, 0, g)   # noqa: E731
    out, lse = pl.pallas_call(
        functools.partial(_forward_kernel, dim=d, block_k=block_k,
                          causal=causal, scale=float(1 / np.sqrt(d))),
        out_shape=(jax.ShapeDtypeStruct((b, seq, h * d), q.dtype),
                   jax.ShapeDtypeStruct((b, groups, group, seq),
                                        jnp.float32)),
        grid=(b, groups, seq // block_q),
        in_specs=[pl.BlockSpec((None, block_q, width), block),
                  pl.BlockSpec((None, seq, width), row),
                  pl.BlockSpec((None, seq, width), row)],
        out_specs=(pl.BlockSpec((None, block_q, width), block),
                   pl.BlockSpec((None, None, group, block_q),
                                lambda i, g, j: (i, g, 0, j))),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel"),
            vmem_limit_bytes=VMEM_LIMIT),
        interpret=interpret,
        # what a device trace calls the kernel's events
        name="flash_attention_forward",
    )(as_it_lies(q), as_it_lies(k), as_it_lies(v))
    return out.reshape(q.shape), lse.reshape(b, h, seq)


def _backward(q, k, v, out, lse, do, causal: bool, block_q: int,
              block_k: int, interpret: bool):
    """-> (dq, dk, dv), each (B, S, H, D)."""
    b, seq, h, d = q.shape
    group, width, groups = _groups(h, d)
    # delta = rowsum(dO * O): one fused pass of the compiler's own
    delta = jnp.einsum("bshd,bshd->bhs", do.astype(jnp.float32),
                       out.astype(jnp.float32))
    # a row a head of the group and query block
    rows = (b * groups, group * (seq // block_q), block_q)
    as_it_lies = lambda x: x.reshape(b, seq, h * d)   # noqa: E731
    row = lambda i, j: (i // groups, 0, i % groups)   # noqa: E731
    blk = lambda i, j: (i // groups, j, i % groups)   # noqa: E731
    vec = lambda i, j: (i, 0, 0)   # noqa: E731
    dq, dk, dv = pl.pallas_call(
        functools.partial(_backward_kernel, dim=d, block_q=block_q,
                          causal=causal, scale=float(1 / np.sqrt(d))),
        out_shape=tuple(jax.ShapeDtypeStruct((b, seq, h * d), x.dtype)
                        for x in (q, k, v)),
        grid=(b * groups, seq // block_k),
        in_specs=[pl.BlockSpec((None, seq, width), row),          # q
                  pl.BlockSpec((None, block_k, width), blk),      # k
                  pl.BlockSpec((None, block_k, width), blk),      # v
                  pl.BlockSpec((None, seq, width), row),          # do
                  pl.BlockSpec((None,) + rows[1:], vec),          # lse
                  pl.BlockSpec((None,) + rows[1:], vec)],         # delta
        out_specs=(pl.BlockSpec((None, seq, width), row),
                   pl.BlockSpec((None, block_k, width), blk),
                   pl.BlockSpec((None, block_k, width), blk)),
        scratch_shapes=[pltpu.VMEM((seq, width), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=VMEM_LIMIT),
        interpret=interpret,
        name="flash_attention_backward",
    )(as_it_lies(q), as_it_lies(k), as_it_lies(v), as_it_lies(do),
      lse.reshape(rows), delta.reshape(rows))
    return tuple(x.reshape(q.shape) for x in (dq, dk, dv))


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6))
def _flash_attention(q, k, v, causal, block_q, block_k, interpret):
    return _forward(q, k, v, causal, block_q, block_k, interpret)[0]


def _forward_rule(q, k, v, causal, block_q, block_k, interpret):
    out, lse = _forward(q, k, v, causal, block_q, block_k, interpret)
    return out, (q, k, v, out, lse)


def _backward_rule(causal, block_q, block_k, interpret, residuals, do):
    return _backward(*residuals, do, causal, block_q, block_k, interpret)


_flash_attention.defvjp(_forward_rule, _backward_rule)


def flash_attention(q, k, v, *, causal: bool = True, block_q: int = 0,
                    block_k: int = 0, interpret: bool = False):
    """``reference_attention(q, k, v, causal=causal)`` for shapes that
    ``fits`` takes, differentiable in all three.  ``block_q``, ``block_k``:
    0 is ``blocks``' (the tests and the timings pass others)."""
    seq, dim = q.shape[1], q.shape[3]
    return _flash_attention(q, k, v, causal,
                            block_q or blocks(seq, dim)[0],
                            block_k or blocks(seq, dim)[1], interpret)
