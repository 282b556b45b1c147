"""The recurrence of a Mamba-1 mixer (Gu & Dao 2023, the selective scan
"S6"): channel ``d`` of ``D`` holds ``N`` state values (float32), and

    h_t[n, d] = exp(dt_t[d] A[n, d]) h_{t-1}[n, d] + dt_t[d] B_t[n] x_t[d]
    y_t[d]    = sum_n h_t[n, d] C_t[n]

with ``dt_t > 0`` the channel's step, ``A < 0`` a channel's and a state
value's own, ``B_t`` and ``C_t`` (N,) shared by all channels.  The decay
between two positions depends on the channel AND on the state value, so no
(positions x positions) product gives the weights between them, as one
does where a head has one decay a position (``ops/ssm_scan.py``): what is
left is a walk over the positions, on the vector and transcendental units.

The state lies (B, N, D), the CHANNELS minor-most: 16 state values in the
chip's 128 lanes would be laid out at eight times their bytes.

* ``s6_step``: one new position a row (a served tick): the state read
  once and written once.
* ``s6_chunk_scan``: ``s`` new positions a row FROM the row's state (a
  prefill's chunk).  Where the program is lowered for a TPU and the shapes
  fit (``chunk_fits``) a Pallas kernel: grid ``(rows, position blocks,
  channel tiles)``, a program ``POSITIONS`` positions of one tile of
  ``CHANNELS`` channels, the tile's state (N x CHANNELS float32, eight
  vector registers) carried through the positions in registers and kept
  between position blocks in the output's own block, which stays in fast
  memory for the whole row; ``B_t`` and ``C_t`` come with their ``N``
  values in the sublanes and spread over the lanes, so a position's
  products with the state are plain vector products.  Anywhere else
  ``lax.scan`` over the positions, one ``s6_step`` each.

A position whose ``dt`` is 0 neither decays the state nor adds to it: that
is how a caller hides right-padding (``model/gpt_model.py`` ``Mamba1``).
"""

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from alpa_tpu.ops.latent_attention import VMEM_LIMIT

LANES = 128
# channels of a program's tile of the state, and positions a program walks
# (of 256 to 1,024 channels and 128 or 256 positions these are the fastest
# at Jamba2-3B's 5,120 x 16, by 1 to 12 %: 0.46 ms a layer a chunk of 1,024
# where the loop over positions takes 2.37 and ``lax.associative_scan``
# 13.0, scripts/time_selective_scan.py, PERF.md, PR 61)
CHANNELS = 512
POSITIONS = 256
# positions the kernel's loop body unrolls: a block of 16 rows of ``x`` is
# one packed tile of 16 bits, read and widened once
UNROLL = 16


def s6_step(state, x, dt, a, b, c):
    """One position a row.  ``state`` (B, N, D) float32; ``x`` (B, D);
    ``dt`` (B, D) float32; ``a`` (N, D) float32; ``b``, ``c`` (B, N).
    Returns ``(y (B, D) float32, new state)``."""
    b32, c32 = b.astype(jnp.float32), c.astype(jnp.float32)
    decay = jnp.exp(dt[:, None, :] * a)
    new = state * decay + \
        (dt * x.astype(jnp.float32))[:, None, :] * b32[:, :, None]
    return (new * c32[:, :, None]).sum(1), new


def _scan_positions(state, x, dt, a, b, c):
    """``s6_chunk_scan`` as a loop over the positions."""
    def one(state, at):
        x_t, dt_t, b_t, c_t = at
        y, state = s6_step(state, x_t, dt_t, a, b_t, c_t)
        return state, y

    last, ys = lax.scan(one, state, tuple(
        jnp.moveaxis(v, 1, 0) for v in (x, dt, b, c)))
    return jnp.moveaxis(ys, 0, 1), last


def _tile(channels: int) -> int:
    """Channels of a program's tile: the most whole lane tiles up to
    ``CHANNELS`` that divide the channels."""
    return max((w for w in range(LANES, CHANNELS + 1, LANES)
                if channels % w == 0), default=0)


def chunk_fits(state, x) -> bool:
    """Whether the kernel takes these shapes: the positions in whole
    unrolled groups, the channels in whole lane tiles, the state values
    in whole sublanes."""
    n, d = state.shape[1:]
    s = x.shape[1]
    return s % UNROLL == 0 and n % 8 == 0 and _tile(d) > 0


def _kernel(x_ref, dt_ref, b_ref, c_ref, a_ref, h0_ref, y_ref, h_ref):
    tb, j = pl.program_id(1), pl.program_id(2)
    width = x_ref.shape[1]

    @pl.when(tb == 0)
    def _first():
        h_ref[j] = h0_ref[j]

    a = a_ref[j]                                              # (N, W)

    def over_lanes(v):
        """(N, 128), the same in every lane -> (N, W)."""
        return jnp.concatenate([v] * (width // LANES), axis=1)

    def group(g, h):
        at = pl.multiple_of(g * UNROLL, UNROLL)
        xs = x_ref[pl.ds(at, UNROLL), :].astype(jnp.float32)  # (U, W)
        dts = dt_ref[pl.ds(at, UNROLL), :]
        dtx = dts * xs
        rows = []
        for i in range(UNROLL):
            bt, ct = over_lanes(b_ref[at + i]), over_lanes(c_ref[at + i])
            h = jnp.exp(dts[i:i + 1] * a) * h + dtx[i:i + 1] * bt
            rows.append(jnp.sum(h * ct, axis=0, keepdims=True))
        y_ref[pl.ds(at, UNROLL), :] = jnp.concatenate(rows, axis=0)
        return h

    h_ref[j] = lax.fori_loop(0, x_ref.shape[0] // UNROLL, group, h_ref[j])


def chunk_scan_kernel(state, x, dt, a, b, c, *, interpret: bool = False):
    """``s6_chunk_scan`` as a Pallas kernel (module docstring); the shapes
    are ``chunk_fits``'s."""
    bsz, s, d = x.shape
    n = state.shape[1]
    width = _tile(d)
    tiles = d // width
    positions = POSITIONS if s % POSITIONS == 0 else UNROLL

    def tiled(v):
        """(..., N, D) -> (..., D / W, N, W): a tile's state together."""
        return jnp.swapaxes(v.reshape(v.shape[:-1] + (tiles, width)), -3, -2)

    def spread(v):
        """(B, s, N) -> (B, s, N, 128) float32: a position's values in the
        sublanes, the same in every lane."""
        return jnp.broadcast_to(v.astype(jnp.float32)[..., None],
                                v.shape + (LANES,))

    def block(b_, tb, j):
        return b_, tb, j

    def position_block(b_, tb, j):
        return b_, tb, 0, 0

    def whole_row(b_, tb, j):
        return b_, 0, 0, 0

    y, last = pl.pallas_call(
        _kernel,
        out_shape=(jax.ShapeDtypeStruct((bsz, s, d), jnp.float32),
                   jax.ShapeDtypeStruct((bsz, tiles, n, width),
                                        jnp.float32)),
        grid=(bsz, s // positions, tiles),
        in_specs=[
            pl.BlockSpec((None, positions, width), block),
            pl.BlockSpec((None, positions, width), block),
            pl.BlockSpec((None, positions, n, LANES), position_block),
            pl.BlockSpec((None, positions, n, LANES), position_block),
            pl.BlockSpec((tiles, n, width), lambda b_, tb, j: (0, 0, 0)),
            pl.BlockSpec((None, tiles, n, width), whole_row),
        ],
        out_specs=(pl.BlockSpec((None, positions, width), block),
                   pl.BlockSpec((None, tiles, n, width), whole_row)),
        # the state is updated in place
        input_output_aliases={5: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary", "arbitrary"),
            vmem_limit_bytes=VMEM_LIMIT),
        interpret=interpret,
        # what a device trace calls the kernel's events
        name="selective_scan_positions",
    )(x, dt.astype(jnp.float32), spread(b), spread(c),
      tiled(a.astype(jnp.float32)), tiled(state))
    return y, jnp.swapaxes(last, -3, -2).reshape(state.shape)


@jax.jit
def _chunk_scan(state, x, dt, a, b, c):
    """The kernel where the program is lowered for a TPU, the loop over
    positions anywhere else.  A ``jit`` of its own, so that a program of
    many layers traces and lowers the kernel once."""
    return lax.platform_dependent(
        state, x, dt, a, b, c, tpu=chunk_scan_kernel,
        default=_scan_positions)


def s6_chunk_scan(state, x, dt, a, b, c):
    """``s`` positions a row from ``state``.  ``state`` (B, N, D) float32;
    ``x`` (B, s, D); ``dt`` (B, s, D) float32, 0 at a position that is not
    real; ``a`` (N, D) float32; ``b``, ``c`` (B, s, N).  Returns ``(y (B,
    s, D) float32, the state after the last position)``."""
    if chunk_fits(state, x):
        return _chunk_scan(state, x, dt, a, b, c)
    return _scan_positions(state, x, dt, a, b, c)
