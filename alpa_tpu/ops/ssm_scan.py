"""The recurrence of a Mamba-2 mixer (Dao & Gu 2024, "Transformers are
SSMs"), in ``jax.numpy``: a head ``h`` of group ``g`` holds a state ``S``
(P x N, float32) and

    S_t = exp(dt_t A_h) S_{t-1} + dt_t x_t B_t^T        y_t = S_t C_t

with ``x_t`` (P,) the head's channels, ``B_t`` and ``C_t`` (N,) its group's,
``dt_t > 0`` the head's step and ``A_h < 0``.  Two forms of the same sum:

* ``ssm_step``: one new position a row: the state is read once and
  written once, and nothing of its size is made besides (a served tick,
  whose states are donated and updated in place).
* ``ssm_chunk_scan``: ``s`` new positions a row in sub-chunks of ``chunk``
  (the SSD algorithm): inside a sub-chunk position ``l`` reads position
  ``s <= l`` through ``C_l . B_s`` times the decay between them, one
  (chunk x chunk) product a group; every sub-chunk's own part of its end
  state is one product more; the state is carried from sub-chunk to
  sub-chunk by a loop of ``s / chunk`` steps, and what a sub-chunk's
  positions read of the state it started from is a third product.  The
  products multiply in ``dtype`` (bfloat16 in a served model, as the
  family's kernels do) and accumulate in float32; the steps, the decays,
  their cumulative sums and the carried state are float32 throughout.

A position whose ``dt`` is 0 neither decays the state nor adds to it: that
is how a caller hides right-padding (``model/gpt_model.py`` ``Mamba2``),
and how ``ssm_chunk_scan`` pads ``s`` to whole sub-chunks itself.
"""
import jax
import jax.numpy as jnp


def _grouped(state, groups: int):
    """(B, H, P, N) -> (B, G, H / G, P, N): the heads by their group."""
    b, h, p, n = state.shape
    return state.reshape(b, groups, h // groups, p, n)


def ssm_step(state, x, dt, a, b, c):
    """One position a row.  ``state`` (B, H, P, N) float32; ``x`` (B, H, P);
    ``dt`` (B, H) float32; ``a`` (H,) float32; ``b``, ``c`` (B, G, N).
    Returns ``(y (B, H, P) float32, new state)``."""
    groups = b.shape[1]
    shape = state.shape
    s = _grouped(state, groups)
    per_group = s.shape[:3]                                   # (B, G, H/G)
    decay = jnp.exp(dt * a).reshape(per_group)
    xdt = (x.astype(jnp.float32) * dt[..., None]).reshape(
        per_group + shape[2:3])
    b32, c32 = b.astype(jnp.float32), c.astype(jnp.float32)
    new = s * decay[..., None, None] + \
        xdt[..., None] * b32[:, :, None, None, :]
    y = (new * c32[:, :, None, None, :]).sum(-1)
    return y.reshape(shape[:3]), new.reshape(shape)


def ssm_chunk_scan(state, x, dt, a, b, c, chunk: int, dtype):
    """``s`` positions a row from ``state``.  ``state`` (B, H, P, N)
    float32; ``x`` (B, S, H, P); ``dt`` (B, S, H) float32, 0 at a position
    that is not real; ``a`` (H,) float32; ``b``, ``c`` (B, S, G, N);
    ``dtype``: what the products multiply in.  Returns ``(y (B, S, H, P)
    float32, the state after the last position)``."""
    bsz, s, h, p = x.shape
    g, n = b.shape[2:]
    r = h // g
    pad = -s % chunk
    if pad:
        # (dt 0: the state passes them as it is)
        x, dt, b, c = (jnp.pad(v, ((0, 0), (0, pad)) + ((0, 0),) *
                               (v.ndim - 2)) for v in (x, dt, b, c))
    nc = (s + pad) // chunk
    x = x.reshape(bsz, nc, chunk, g, r, p)
    dt = dt.reshape(bsz, nc, chunk, g, r)
    b = b.reshape(bsz, nc, chunk, g, n).astype(dtype)
    c = c.reshape(bsz, nc, chunk, g, n).astype(dtype)
    # the log of every position's decay, and its sums inside a sub-chunk,
    # the positions minor-most
    cum = jnp.cumsum(jnp.moveaxis(dt * a.reshape(g, r), 2, -1), axis=-1)
    xdt = x.astype(jnp.float32) * dt[..., None]               # (B,c,Q,G,R,P)
    # inside a sub-chunk: position l reads s <= l
    scores = jnp.einsum("bclgn,bcsgn->bcgls", c, b,
                        preferred_element_type=jnp.float32)
    between = cum[..., :, None] - cum[..., None, :]           # (B,c,G,R,l,s)
    seen = jnp.tril(jnp.ones((chunk, chunk), bool))
    # (masked before the exponential: above the diagonal the sum is > 0)
    weights = scores[:, :, :, None] * \
        jnp.exp(jnp.where(seen, between, -jnp.inf))
    y = jnp.einsum("bcgrls,bcsgrp->bclgrp", weights.astype(dtype),
                   xdt.astype(dtype), preferred_element_type=jnp.float32)
    # a sub-chunk's own part of the state at its end
    to_end = jnp.exp(cum[..., -1:] - cum)                     # (B,c,G,R,Q)
    own = jnp.einsum(
        "bcsgrp,bcsgn->bcgrpn",
        (xdt * jnp.moveaxis(to_end, -1, 2)[..., None]).astype(dtype), b,
        preferred_element_type=jnp.float32)
    whole = jnp.exp(cum[..., -1])                             # (B,c,G,R)

    def carry(state, sub):
        """The state at a sub-chunk's end from the one at its start."""
        decay, own = sub
        return state * decay[..., None, None] + own, state

    last, starts = jax.lax.scan(
        carry, _grouped(state, g),
        (jnp.moveaxis(whole, 1, 0), jnp.moveaxis(own, 1, 0)))
    # what every position reads of the state its sub-chunk started from
    y = y + jnp.einsum("bclgn,cbgrpn->bclgrp", c, starts.astype(dtype),
                       preferred_element_type=jnp.float32) * \
        jnp.moveaxis(jnp.exp(cum), -1, 2)[..., None]
    return (y.reshape(bsz, nc * chunk, h, p)[:, :s],
            last.reshape(state.shape))
