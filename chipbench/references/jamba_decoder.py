"""Plain reference of the Jamba decoder (``ai21labs/AI21-Jamba2-3B``
``config.json``, ``model_type`` ``jamba``): the forward pass in
straightforward ``jax.numpy``, float32, full matmul precision.  No cache,
no state carried between calls, no chunk form of the recurrence, no
kernel, no batching: one sequence at a time, the Mamba-1 recurrence as the
plain loop over positions (``lax.scan``, one position a step), the
convolution as the sum over its shifted copies of the whole sequence, and
every query attending over the whole sequence under a mask.  Queries and
the MLP's positions go in blocks and a mixer's channels in ``channel_blocks``
groups (a channel's recurrence needs no other channel's), walked from
Python, so that 63,488 positions fit beside the served model.

Written from the published ``config.json`` (the sizes) and the family's
report and public modelling code (``modeling_jamba.py``: the wiring,
marked (*) where the configuration does not fix it), not from the
program's model file.  With ``eps`` = ``rms_norm_eps`` and ``n(x; w) = w *
x / sqrt(mean(x^2) + eps)``:

* ``x = E[ids]``; every layer is ``x = x + mixer(n_1(x)); x = x +
  mlp(n_2(x))``; layer i's mixer is attention where ``i % attn_layer_period
  == attn_layer_offset`` and Mamba otherwise (*: the family's rule for
  those two keys); ``logits = n_f(x) E^T`` (one final norm, the head tied
  to the table).  No bias anywhere but the convolution's and ``dt_proj``'s.
* Mamba (D = ``mamba_expand`` x hidden channels, N = ``mamba_d_state``, R =
  ``mamba_dt_rank``, L = ``mamba_d_conv`` taps): ``[x | z] = u W_in``, each
  D wide, ``x`` FIRST; ``x_t = silu(sum_{j<L} k[j] * x_{t-(L-1)+j} + b)``
  with zeros before the sequence (depthwise, causal); ``[dt | B | C] = x
  W_x`` of widths R, N, N; ``dt = n(dt; w_dt)``, ``B = n(B; w_B)``, ``C =
  n(C; w_C)``: three norms of their own (*: the family's addition to
  Mamba); ``dt = softplus(dt W_dt + b_dt)``, no clamp; ``A = -exp(A_log)``
  a channel and a state value; from ``h = 0``: ``h_t[n, d] = exp(dt_t[d]
  A[n, d]) h_{t-1}[n, d] + dt_t[d] B_t[n] x_t[d]``, ``y_t[d] = sum_n
  h_t[n, d] C_t[n] + D[d] x_t[d]``; ``y = y * silu(z)``, no norm behind
  the gate; ``y W_out``.
* Attention: ``q = u Wq`` (heads of hidden / heads channels), ``k = u Wk``,
  ``v = u Wv`` (fewer heads: query head i reads key/value head i // (heads
  / kv heads)); scores ``q k^T / sqrt(head size)``, causal, softmax;
  ``Wo``.  NO positions of any kind (*: the family's code applies none,
  the Mamba layers carry order; the file has no rotary key).
* MLP: ``(silu(u W_gate) * (u W_up)) W_down`` in EVERY layer (``num_experts``
  1: nothing routes).

Departures from the published model, none in the mathematics: weights are
random, from the benchmark's seed (the checkpoint is not in the
repository); the head's table is made orthogonal to the mean final hidden
state at set-up (``drivers/serve_s6.py``), which a trained head does not
need; the states are kept channels minor-most, (N, D), where the published
code keeps (D, N).

The program keeps q, k and v in one matrix laid out [q | k | v], the taps
as (L, channels), tap j a row, and ``A_log`` as (N, D);
``weights_from_program`` splits the first and hands on the others as they
are.
"""
import math

import jax
import jax.numpy as jnp

_PRECISION = "highest"   # a float32 matmul on the TPU is one bf16 pass otherwise


def weights_from_program(params) -> dict:
    """The program's parameter tree (flax names of ``GPTModel`` in its
    jamba kinds) as the plain names used here.  Arrays are shared, not
    copied, except the three slices of the fused projection."""
    p = params["params"]
    blocks = []
    i = 0
    while f"h{i}" in p:
        b = p[f"h{i}"]
        mlp = b["mlp"]
        block = {"n1": b["ln1"]["scale"], "n2": b["ln2"]["scale"],
                 "w_gate": mlp["gate"]["kernel"], "w_up": mlp["up"]["kernel"],
                 "w_down": mlp["down"]["kernel"]}
        if "ssm" in b:
            m = b["ssm"]
            block.update(
                kind="mamba", w_in=m["in_proj"]["kernel"],
                taps=m["conv_kernel"], conv_b=m["conv_bias"],
                w_x=m["x_proj"]["kernel"], n_dt=m["dt_norm"]["scale"],
                n_b=m["b_norm"]["scale"], n_c=m["c_norm"]["scale"],
                w_dt=m["dt_proj"], b_dt=m["dt_bias"], a_log=m["A_log"],
                d=m["D"], w_out=m["out_proj"]["kernel"])
        elif "attn" in b:
            attn = b["attn"]
            n_q = attn["out"]["kernel"].shape[0]
            n_kv = (attn["qkv"]["kernel"].shape[1] - n_q) // 2
            w_q, w_k, w_v = jnp.split(attn["qkv"]["kernel"],
                                      [n_q, n_q + n_kv], axis=-1)
            block.update(kind="attention", w_q=w_q, w_k=w_k, w_v=w_v,
                         w_o=attn["out"]["kernel"])
        else:
            raise ValueError(f"layer {i} is neither a Mamba mixer nor "
                             f"attention: {sorted(b)}")
        blocks.append(block)
        i += 1
    return {"wte": p["wte"]["embedding"], "blocks": blocks,
            "wf": p["ln_f"]["scale"]}


def _f32(tree):
    return jax.tree_util.tree_map(lambda a: jnp.asarray(a, jnp.float32), tree)


def rms(x, w, eps):
    return w * x / jnp.sqrt((x * x).mean(-1, keepdims=True) + eps)


def shifted(g, by: int):
    """``g`` (S, c) moved ``by`` positions later, zeros in front."""
    if by == 0:
        return g
    return jnp.concatenate([jnp.zeros_like(g[:by]), g[:-by]], axis=0)


def mamba_inputs(x, b, eps):
    """What a Mamba layer's recurrence reads, of one sequence ``x`` (S, h):
    ``(u, x after the convolution, dt before dt_proj, B, C)``."""
    with jax.default_matmul_precision(_PRECISION):
        b = _f32(b)
        inner, n = b["d"].shape[0], b["a_log"].shape[0]
        rank = b["w_dt"].shape[0]
        u = rms(x, b["n1"], eps)
        taps = b["taps"]                                  # (L, D)
        n_taps = taps.shape[0]
        xs = u @ b["w_in"][:, :inner]
        # tap j meets x at t - (L - 1) + j: the copy moved L - 1 - j later
        xs = jax.nn.silu(sum(taps[j] * shifted(xs, n_taps - 1 - j)
                             for j in range(n_taps)) + b["conv_b"])
        low, bs, cs = jnp.split(xs @ b["w_x"], [rank, rank + n], axis=-1)
        return (u, xs, rms(low, b["n_dt"], eps), rms(bs, b["n_b"], eps),
                rms(cs, b["n_c"], eps))


def steps(low, w_dt, b_dt):
    """``dt = softplus(dt_proj(low))`` of a group of channels, (S, Dc)."""
    with jax.default_matmul_precision(_PRECISION):
        return jax.nn.softplus(jnp.asarray(low, jnp.float32) @
                               jnp.asarray(w_dt, jnp.float32) +
                               jnp.asarray(b_dt, jnp.float32))


def recurrence(xs, dt, bs, cs, a_log, at):
    """The loop over positions of a group of channels, from a zero state:
    ``xs``, ``dt`` (S, Dc), ``bs``, ``cs`` (S, N), ``a_log`` (N, Dc).
    Returns ``y`` (S, Dc) without ``D x`` and the state after ``at[k]``
    positions, (K, N, Dc)."""
    a = -jnp.exp(jnp.asarray(a_log, jnp.float32))

    def position(carry, inputs):
        h, kept = carry
        t, x_t, dt_t, b_t, c_t = inputs        # (Dc,), (Dc,), (N,), (N,)
        h = jnp.exp(dt_t[None, :] * a) * h + \
            (dt_t * x_t)[None, :] * b_t[:, None]
        # (the state after t + 1 positions, where one was asked for)
        kept = jnp.where((at == t + 1)[:, None, None], h, kept)
        return (h, kept), (h * c_t[:, None]).sum(0)

    zeros = jnp.zeros(a.shape, jnp.float32)
    (_, kept), y = jax.lax.scan(
        position, (zeros, jnp.zeros(at.shape + zeros.shape)),
        (jnp.arange(xs.shape[0]), xs, dt, bs, cs))
    return y, kept


def mamba_output(out, u, xs, y, w_z, d, w_out):
    """``out + ((y + D x) * silu(z)) W_out`` of a group of channels."""
    with jax.default_matmul_precision(_PRECISION):
        w_z, d, w_out = _f32((w_z, d, w_out))
        return out + ((y + d * xs) * jax.nn.silu(u @ w_z)) @ w_out


def attention_inputs(x, b, heads, eps):
    """``(q (S, heads, d), k, v (S, kv heads, d))`` of one sequence."""
    with jax.default_matmul_precision(_PRECISION):
        b = _f32(b)
        s = x.shape[0]
        d = b["w_q"].shape[1] // heads
        u = rms(x, b["n1"], eps)
        return ((u @ b["w_q"]).reshape(s, heads, d),
                (u @ b["w_k"]).reshape(s, -1, d),
                (u @ b["w_v"]).reshape(s, -1, d))


def attention_block(qb, first, k, v):
    """A block of queries ``qb`` (T, heads, d), the first at position
    ``first``, against all keys: (T, heads d)."""
    with jax.default_matmul_precision(_PRECISION):
        t, heads, d = qb.shape
        group = heads // k.shape[1]
        # query head i beside key/value head i // group
        qb = qb.reshape(t, -1, group, d)
        scores = jnp.einsum("qjgd,kjd->jgqk", qb, k) / math.sqrt(d)
        seen = jnp.arange(k.shape[0])[None, :] <= \
            (first + jnp.arange(t))[:, None]
        probs = jax.nn.softmax(
            jnp.where(seen[None, None], scores, -jnp.inf), -1)
        return jnp.einsum("jgqk,kjd->qjgd", probs, v).reshape(t, -1)


def attention_output(x, heads_out, w_o):
    with jax.default_matmul_precision(_PRECISION):
        return x + heads_out @ jnp.asarray(w_o, jnp.float32)


def mlp(x, b, eps):
    """``x + mlp(n_2(x))`` of a block of positions."""
    with jax.default_matmul_precision(_PRECISION):
        b = _f32(b)
        u = rms(x, b["n2"], eps)
        return x + (jax.nn.silu(u @ b["w_gate"]) * (u @ b["w_up"])) @ \
            b["w_down"]


def head(x, wf, wte, eps):
    """``n_f(x) E^T``: the final norm and the head tied to the table."""
    with jax.default_matmul_precision(_PRECISION):
        return rms(x, jnp.asarray(wf, jnp.float32), eps) @ \
            jnp.asarray(wte, jnp.float32).T


class Reference:
    """The reference bound to one configuration: ``heads`` (query heads),
    ``eps``, ``query_block`` (how many queries meet all keys at once, and
    how many positions an MLP takes at once) and ``channel_blocks`` (the
    groups a mixer's channels are walked in).  What a layer is comes with
    the weights.  Each piece is jitted by itself and called layer after
    layer, block after block, from Python; weights are arguments, never
    constants.

    No compiled piece holds a loop beside the products that feed it: the
    recurrence's program is the loop alone, over arrays made before it,
    and the blocks of queries and of the MLP's positions are walked from
    Python.  One program of the whole mixer, its loop behind its
    projections, read a few positions' worth of wrong input into the
    state a third and two thirds of the way through a sequence of 8,192
    on the TPU (a state value 0.105 off, where the same loop over the same
    arrays made beforehand agrees with a float64 recurrence on the host to
    8e-6: my chip runs, PR 61)."""

    def __init__(self, settings: dict):
        self.s = settings
        self._mamba_inputs = jax.jit(mamba_inputs, static_argnums=2)
        self._steps = jax.jit(steps)
        self._recurrence = jax.jit(recurrence)
        self._mamba_output = jax.jit(mamba_output)
        self._attention_inputs = jax.jit(attention_inputs,
                                         static_argnums=(2, 3))
        self._attention_block = jax.jit(attention_block)
        self._attention_output = jax.jit(attention_output)
        self._mlp = jax.jit(mlp, static_argnums=2)
        self._head = jax.jit(head, static_argnums=3)

    @staticmethod
    def _block_of(n: int, block: int) -> int:
        block = min(block, n)
        while n % block:
            block -= 1
        return block

    def mamba(self, x, b, at):
        """``x + mamba(n_1(x))`` of one sequence ``x`` (S, h): the
        recurrence as the loop over positions, from a zero state; and the
        state ``h`` as it stands after ``at[k]`` positions, (K, N, D)."""
        eps, inner = self.s["eps"], b["d"].shape[0]
        u, xs, low, bs, cs = self._mamba_inputs(x, {
            k: b[k] for k in ("n1", "w_in", "taps", "conv_b", "w_x", "n_dt",
                              "n_b", "n_c", "w_dt", "d", "a_log")}, eps)
        out, kept = x, []
        width = inner // self.s["channel_blocks"]
        for c in range(self.s["channel_blocks"]):
            cols = slice(c * width, (c + 1) * width)
            dt = self._steps(low, b["w_dt"][:, cols], b["b_dt"][cols])
            y, kept_c = self._recurrence(xs[:, cols], dt, bs, cs,
                                         b["a_log"][:, cols], at)
            kept.append(kept_c)
            out = self._mamba_output(
                out, u, xs[:, cols], y,
                b["w_in"][:, inner + c * width:inner + (c + 1) * width],
                b["d"][cols], b["w_out"][cols])
        return out, jnp.concatenate(kept, axis=-1)

    def attention(self, x, b, block):
        """``x + attn(n_1(x))`` of one sequence, the queries in blocks of
        ``block`` against all keys; and the layer's keys and values, each
        (S, kv heads, d)."""
        q, k, v = self._attention_inputs(
            x, {name: b[name] for name in ("n1", "w_q", "w_k", "w_v")},
            self.s["heads"], self.s["eps"])
        out = jnp.concatenate([
            self._attention_block(q[first:first + block], first, k, v)
            for first in range(0, x.shape[0], block)])
        return self._attention_output(x, out, b["w_o"]), (k, v)

    def hidden(self, w: dict, ids, at=()):
        """(S,) ids -> the last hidden states (S, h); a Mamba layer, the
        state after ``at[k]`` positions, (K, N, D); an attention layer,
        its keys and values."""
        x = jnp.asarray(w["wte"][jnp.asarray(ids, jnp.int32)], jnp.float32)
        block = self._block_of(x.shape[0], self.s["query_block"])
        at = jnp.asarray(at, jnp.int32).reshape(-1)
        states, caches = [], []
        for b in w["blocks"]:
            if b["kind"] == "mamba":
                x, kept = self.mamba(x, b, at)
                states.append(kept)
            else:
                x, written = self.attention(x, b, block)
                caches.append(written)
            weights = {k: b[k] for k in ("n2", "w_gate", "w_up", "w_down")}
            x = jnp.concatenate([
                self._mlp(x[first:first + block], weights, self.s["eps"])
                for first in range(0, x.shape[0], block)])
        return x, states, caches

    def logits(self, w: dict, ids, rows=None):
        """(S,) token ids -> (S, V) float32 logits; with ``rows`` =
        (first, count) only those positions' logits, (count, V)."""
        return self.logits_states_and_caches(w, ids, rows)[0]

    def logits_and_states(self, w: dict, ids, rows=None, at=()):
        """``logits(rows=...)`` and the states of every Mamba layer after
        ``at[k]`` positions, (Mamba layers, K, N, D)."""
        return self.logits_states_and_caches(w, ids, rows, at)[:2]

    def logits_states_and_caches(self, w: dict, ids, rows=None, at=()):
        """``logits_and_states`` and every attention layer's keys and
        values at all positions, (S, kv heads, d) each: what a cache of
        the layer holds."""
        x, states, caches = self.hidden(w, ids, at)
        if rows is not None:
            x = jax.lax.dynamic_slice_in_dim(x, rows[0], rows[1], axis=0)
        return self._head(x, w["wf"], w["wte"], self.s["eps"]), states, \
            caches
