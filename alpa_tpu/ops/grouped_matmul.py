"""Grouped matrix multiplication over uneven groups of rows.

``grouped_matmul(lhs, rhs, group_sizes)``: the rows of ``lhs`` (M, K) come in
G consecutive groups, ``group_sizes[g]`` rows in group g (they sum to M; a
group may be empty), and the rows of group g are multiplied by ``rhs[g]``
(K, N).  It is what a dropless mixture-of-experts layer does once its
token-expert rows are sorted by expert: every row meets one expert's
weights, whatever the experts' loads.

One path: the Pallas kernels ``gmm`` and ``tgmm`` of
``jax.experimental.pallas.ops.tpu.megablox`` (Gale et al., "MegaBlocks",
2022), which walk the groups' row tiles, so a row is multiplied by its own
group's weights only.  Forward ``gmm(lhs, rhs)``; backward ``gmm(g,
rhs^T)`` for the rows and ``tgmm(lhs^T, g)`` for the weights.  Inputs are
multiplied in their own dtype (bfloat16 in the models) and accumulated in
float32; results come in the inputs' dtypes.

``jax.lax.ragged_dot`` is the same algorithm inside XLA (on the TPU it
lowers to a Mosaic kernel of its own with 512 x 512 x 512 tiles, not to 64
dense products), and was measured against this on the chip at the expert
layer's shapes, forward and backward: 32.0 ms against 23.4 ms for 131,072
rows of 2048 x 2048 over 64 groups (PERF.md, PR 26).  The larger tiles
below are the difference; the kernel's default of 128 x 128 x 128 is 7
times slower than either.

The kernels are compiled where the program is lowered for a TPU and
interpreted on any other platform (``lax.platform_dependent``, at lowering
time): on a TPU they compile or raise.
"""
import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental.pallas.ops.tpu.megablox.gmm import gmm, tgmm

# the scope every call is traced under: the benchmark finds the kernels'
# device events by it (HLO metadata ``op_name``)
SCOPE = "grouped_matmul"

# rows, contracted and produced columns of one tile: the fastest of the
# tilings tried on the v5e (PERF.md, PR 26), and the largest whose
# backward pass fits the kernel's 16 MB of fast memory
TILING = (512, 1024, 1024)


def _tiles(*dims):
    """The tiling for a product of these (m, k, n): ``TILING`` cut down to
    divisors of smaller dimensions."""
    tiles = []
    for size, tile in zip(dims, TILING):
        tile = min(tile, size)
        while size % tile:
            tile //= 2
        tiles.append(tile)
    return tuple(tiles)


def _run(kernel, *args, **static):
    return lax.platform_dependent(
        *args, tpu=functools.partial(kernel, interpret=False, **static),
        default=functools.partial(kernel, interpret=True, **static))


@jax.custom_vjp
def _grouped_matmul(lhs, rhs, group_sizes):
    (m, k), n = lhs.shape, rhs.shape[2]
    with jax.named_scope(SCOPE):
        return _run(gmm, lhs, rhs, group_sizes,
                    preferred_element_type=lhs.dtype, tiling=_tiles(m, k, n))


def _forward(lhs, rhs, group_sizes):
    return _grouped_matmul(lhs, rhs, group_sizes), (lhs, rhs, group_sizes)


def _backward(residuals, g):
    lhs, rhs, group_sizes = residuals
    (m, k), n = lhs.shape, rhs.shape[2]
    with jax.named_scope(SCOPE):
        d_lhs = _run(gmm, g, rhs, group_sizes,
                     preferred_element_type=lhs.dtype,
                     tiling=_tiles(m, n, k), transpose_rhs=True)
        d_rhs = _run(tgmm, lhs.swapaxes(0, 1), g, group_sizes,
                     preferred_element_type=rhs.dtype,
                     tiling=_tiles(m, k, n), num_actual_groups=rhs.shape[0])
    return d_lhs, d_rhs, None


_grouped_matmul.defvjp(_forward, _backward)


def grouped_matmul(lhs, rhs, group_sizes):
    """(M, K) x (G, K, N) -> (M, N) in ``lhs.dtype``: group g's rows
    against ``rhs[g]``.  ``rhs`` is cast to ``lhs.dtype`` first (float32
    parameters under bfloat16 activations)."""
    return _grouped_matmul(lhs, rhs.astype(lhs.dtype),
                           group_sizes.astype(jnp.int32))
