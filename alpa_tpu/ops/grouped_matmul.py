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
rows of 2048 x 2048 over 64 groups (PERF.md, PR 26).  The larger tiles are
the difference; the kernel's default of 128 x 128 x 128 is 7 times slower
than either.

The tiles of a call follow from its static shape alone (``tiling``; the
sweeps are in PERF.md, PR 26 and PR 31).  The kernel walks (row tile,
group) pairs: every pair that shares a row is one grid step of a whole
row tile's multiply-adds with the other groups' rows masked, up to
``m / tm + groups - 1`` steps.  So the row tile follows ``m / groups``, the
rows a group can expect: 512 where a group holds a thousand rows (a
training step), 128 where it holds 64 (a served prompt's chunk of 1,024
positions over 128 experts, where 512 multiplies nine tiles of padding
for every tile of rows).  The contracted dimension is the grid's
innermost, so while it is tiled every step fetches its group's weights
anew; walked whole, consecutive steps of one group keep their block and
a group's weights are read once a produced column.  It is walked whole
where the blocks then fit the kernel's fast memory and there is more than
one row tile to fetch them for.

The kernels are compiled where the program is lowered for a TPU and
interpreted on any other platform (``lax.platform_dependent``, at lowering
time): on a TPU they compile or raise.
"""
import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental.pallas.ops.tpu.megablox.gmm import gmm, tgmm

from alpa_tpu.telemetry import metrics as tmetrics

# the scope every call is traced under: the benchmark finds the kernels'
# device events by it (HLO metadata ``op_name``)
SCOPE = "grouped_matmul"

# rows, contracted and produced columns of one tile at their largest: the
# fastest of the tilings tried on the v5e where a group holds a thousand
# rows (PERF.md, PR 26), and the largest whose backward pass fits the
# kernel's 16 MB of fast memory
TILING = (512, 1024, 1024)
# no row tile under the matrix unit's 128 rows: at 64 rows a group, 64 was
# no faster than 128 on the chip (PERF.md, PR 31)
MIN_ROW_TILE = 128
# channels in a lane tile
LANES = 128
# of the kernel's 16 MiB, what its blocks may take: the rest is the
# compiler's (the store's mask and select over a float32 tile)
VMEM_BUDGET = 12 * 2**20


def _gmm_vmem(tm, tk, tn):
    """Bytes of ``gmm``'s blocks in fast memory: the three bfloat16 blocks
    twice (the pipeline fetches a step ahead) and the float32
    accumulator."""
    return 2 * 2 * (tm * tk + tk * tn + tm * tn) + 4 * tm * tn


def _lane_divisors(size, cap):
    """The whole numbers of lane tiles that divide ``size``, at most
    ``cap``, the largest first."""
    return [t for t in range(cap - cap % LANES, 0, -LANES) if size % t == 0]


def _divisor(size, tile):
    cap = tile = min(tile, size)
    while size % tile:
        tile //= 2
    if tile == LANES:
        # halving came down to ONE lane tile (2,688 = 21 x 128): the most
        # whole lane tiles under the cap that divide the dimension (896)
        tile = _lane_divisors(size, cap)[0]
    return tile


def tiling(m, k, n, groups):
    """(tm, tk, tn) for ``gmm`` of (m, k) x (groups, k, n), from the shape
    alone (module docstring).  The row tile is the rows a group can expect,
    ``m / groups``, up to the next power of two, between ``MIN_ROW_TILE``
    and ``TILING``'s; the other two are ``TILING``'s, then, where the rows
    are more than one tile, the contracted dimension whole and the produced
    one whole, each if the blocks still fit ``VMEM_BUDGET``.  Every tile is
    cut down to a divisor of its dimension by halving (to the most whole
    lane tiles that divide it where halving leaves one); a dimension that
    so comes under a lane tile (1,856 = 29 x 64) is walked whole, and the
    other's tile then shrinks by whole lane tiles until the blocks fit."""
    rows = -(-m // groups)
    tm = _divisor(m, min(TILING[0],
                         max(MIN_ROW_TILE, 1 << (rows - 1).bit_length())))
    tk, tn = _divisor(k, TILING[1]), _divisor(n, TILING[2])
    # a block's last dimension is whole lane tiles or the array's own
    tk, tn = (k if tk < LANES else tk), (n if tn < LANES else tn)
    # (one of the two walked whole and the blocks too large: the other's)
    while _gmm_vmem(tm, tk, tn) > VMEM_BUDGET and (tk == k) != (tn == n):
        smaller = _lane_divisors(k, tk - 1) if tn == n else \
            _lane_divisors(n, tn - 1)
        if not smaller:
            break
        tk, tn = (smaller[0], tn) if tn == n else (tk, smaller[0])
    if m > tm:
        if _gmm_vmem(tm, k, tn) <= VMEM_BUDGET:
            tk = k
        if _gmm_vmem(tm, tk, n) <= VMEM_BUDGET:
            tn = n
    return tm, tk, tn


def padded_work_ratio(m, groups, tm):
    """The bound on the rows the kernel multiplies over the rows it has:
    ``m / tm + groups - 1`` grid steps of ``tm`` rows each."""
    return (m // tm + groups - 1) * tm / m


def _run(kernel, *args, **static):
    return lax.platform_dependent(
        *args, tpu=functools.partial(kernel, interpret=False, **static),
        default=functools.partial(kernel, interpret=True, **static))


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def _grouped_matmul(lhs, rhs, group_sizes, transposed):
    (m, k), groups = lhs.shape, rhs.shape[0]
    n = rhs.shape[1 if transposed else 2]
    tiles = tiling(m, k, n, groups)
    # at trace time: what the shape made of the row tile
    tmetrics.get_registry().gauge(
        "alpa_grouped_matmul_padded_work_ratio",
        "bound on the rows the grouped matmul multiplies over the rows it "
        "has, by the call's shape", ("m", "groups")).labels(m, groups).set(
            padded_work_ratio(m, groups, tiles[0]))
    with jax.named_scope(SCOPE):
        return _run(gmm, lhs, rhs, group_sizes,
                    preferred_element_type=lhs.dtype, tiling=tiles,
                    transpose_rhs=transposed)


def _forward(lhs, rhs, group_sizes, transposed):
    return (_grouped_matmul(lhs, rhs, group_sizes, transposed),
            (lhs, rhs, group_sizes))


def _backward(transposed, residuals, g):
    lhs, rhs, group_sizes = residuals
    (m, k), groups = lhs.shape, rhs.shape[0]
    n = rhs.shape[1 if transposed else 2]
    # tgmm produces (tk, tn) tiles with a float32 accumulator each: they
    # stay TILING's, which is what fits (PERF.md, PR 26), under the rule's
    # row tile
    tm = tiling(m, k, n, groups)[0]
    tk, tn = _divisor(k, TILING[1]), _divisor(n, TILING[2])
    with jax.named_scope(SCOPE):
        # the rows' gradient meets the weights the other way round
        d_lhs = _run(gmm, g, rhs, group_sizes,
                     preferred_element_type=lhs.dtype,
                     tiling=tiling(m, n, k, groups),
                     transpose_rhs=not transposed)
        if transposed:
            d_rhs = _run(tgmm, g.swapaxes(0, 1), lhs, group_sizes,
                         preferred_element_type=rhs.dtype,
                         tiling=(tm, tn, tk), num_actual_groups=groups)
        else:
            d_rhs = _run(tgmm, lhs.swapaxes(0, 1), g, group_sizes,
                         preferred_element_type=rhs.dtype,
                         tiling=(tm, tk, tn), num_actual_groups=groups)
    return d_lhs, d_rhs, None


_grouped_matmul.defvjp(_forward, _backward)


def grouped_matmul(lhs, rhs, group_sizes, transposed: bool = False):
    """(M, K) x (G, K, N) -> (M, N) in ``lhs.dtype``: group g's rows
    against ``rhs[g]``.  ``rhs`` is cast to ``lhs.dtype`` first (float32
    parameters under bfloat16 activations).  ``transposed``: ``rhs`` is
    (G, N, K) and group g's rows meet ``rhs[g]^T`` (the kernel contracts
    both operands' last dimension; nothing is transposed in memory)."""
    return _grouped_matmul(lhs, rhs.astype(lhs.dtype),
                           group_sizes.astype(jnp.int32), transposed)
