"""Plain reference of the EvaByte decoder (``EvaByte/EvaByte``
``config.json``, ``model_type`` ``evabyte``, ``attention_class`` ``eva``):
the forward pass in straightforward ``jax.numpy``, float32, full matmul
precision.  No cache, no kernel, no batching: one sequence at a time, all
keys and values of a layer at once, the summaries of all its full chunks
by the two softmaxes below, and then every block of queries scored against
the keys of its own window and against every summary under explicit masks
built from the queries' positions, with ONE softmax over the two kinds of
score side by side.  Blocks of queries and of the MLP's positions are
walked from Python, so that 32,768 positions fit beside the served model.

Written from the published ``config.json`` (the sizes and switches) and
EVA's paper (Zheng et al., "Efficient Attention via Control Variates",
ICLR 2023, arXiv:2302.04542: the chunk summaries and the one softmax over
exact and pooled keys, in the deterministic form the model was published
with), marked (*) where neither fixes the form; not from the program's
model file, and sharing no code with it.  With ``eps`` = ``rms_norm_eps``
and ``n(x; w) = x / sqrt(mean(x^2) + eps) * (1 + w)``
(``norm_add_unit_offset``):

* ``x = E[ids]``, kept in float32 (``fp32_skip_add``); every layer is ``x
  = x + attn(n_1(x)); x = x + mlp(n_2(x))``; after the last layer ``n_f``
  and the head, ``hidden -> num_pred_heads x vocab_size`` without a bias,
  untied, read as (heads, vocabulary): head ``j``'s row predicts the byte
  ``j + 1`` positions ahead.  No bias anywhere.
* Attention, H heads of D channels, one key/value head a query head:
  ``q, k, v`` three projections of the normed input; ``q`` and ``k`` turned
  by rotary positions over all D channels, channel i paired with i + D/2
  (*: rotate-half), angle ``t * theta^(-2i/D)`` at the ABSOLUTE position
  ``t``; scale ``s = D^-0.5``.  Positions lie in windows of W
  (``window_size``) and chunks of C (``chunk_size``).  A head has two
  learned vectors ``mu``, ``phi`` (D each).  The summary of a FULL chunk
  ``c`` (positions ``C c .. C c + C - 1``), from its rotated keys and its
  values (*: rotary before pooling; ``mu`` pools the keys and ``phi`` the
  values, both weights off the KEYS, both with the scale):
  ``a_j = softmax_j(s k_j . mu)``, ``k~_c = sum_j a_j k_j``;
  ``b_j = softmax_j(s k_j . phi)``, ``v~_c = sum_j b_j v_j``.
  The query at ``t``, in window ``w = t // W``, sees the keys ``j`` with
  ``W w <= j <= t`` exactly and the summaries ``c`` with ``c < (W / C) w``:
  ``out = (sum_j e^{s q.k_j} v_j + sum_c e^{s q.k~_c} v~_c) / (sum_j e^{s
  q.k_j} + sum_c e^{s q.k~_c})``; then the output projection.  Never a
  summary of its own window's chunks, never an exact key of an earlier
  window.
* MLP: ``(silu(u W_gate) * (u W_up)) W_down``.

Departures from the published model, none in the mathematics: weights are
random, from the benchmark's seed (the checkpoint is not in the
repository); the norms' stored weights are drawn away from 0 and the
pooling vectors wide enough that a chunk's pooling weights are uneven
(``drivers/serve_eva.py``: at the published initialisation a pooling is a
plain mean, and a norm that forgot its offset gives nothing); the head is
made orthogonal to the mean final hidden state at set-up, which a trained
head does not need.  Everything here is float32 where the published model
computes its products in bfloat16: that is the comparison's point.

The program keeps q, k and v in one matrix laid out [q | k | v];
``weights_from_program`` splits it and hands on the rest as it is.
"""
import jax
import jax.numpy as jnp

_PRECISION = "highest"   # a float32 matmul on the TPU is one bf16 pass otherwise


def weights_from_program(params) -> dict:
    """The program's parameter tree (flax names of ``GPTModel`` in its
    evabyte kinds) as the plain names used here.  Arrays are shared, not
    copied, except the three slices of the fused projection."""
    p = params["params"]
    blocks = []
    i = 0
    while f"h{i}" in p:
        b = p[f"h{i}"]
        attn, mlp = b["attn"], b["mlp"]
        n_q = attn["out"]["kernel"].shape[0]
        w_q, w_k, w_v = jnp.split(attn["qkv"]["kernel"], [n_q, 2 * n_q],
                                  axis=-1)
        blocks.append({
            "n1": b["ln1"]["scale"], "n2": b["ln2"]["scale"],
            "w_q": w_q, "w_k": w_k, "w_v": w_v,
            "w_o": attn["out"]["kernel"], "mu": attn["mu"],
            "phi": attn["phi"], "w_gate": mlp["gate"]["kernel"],
            "w_up": mlp["up"]["kernel"], "w_down": mlp["down"]["kernel"]})
        i += 1
    return {"wte": p["wte"]["embedding"], "blocks": blocks,
            "wf": p["ln_f"]["scale"], "w_head": p["lm_head"]["kernel"]}


def _f32(tree):
    return jax.tree_util.tree_map(lambda a: jnp.asarray(a, jnp.float32), tree)


def rms(x, w, eps):
    """The norm with its unit offset."""
    return x / jnp.sqrt((x * x).mean(-1, keepdims=True) + eps) * (1.0 + w)


def turned(x, theta):
    """``x`` (S, H, D) at positions 0 .. S - 1 under rotary positions,
    channel i paired with channel i + D/2."""
    half = x.shape[-1] // 2
    freq = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    angle = jnp.arange(x.shape[0], dtype=jnp.float32)[:, None] * freq
    cos, sin = jnp.cos(angle)[:, None, :], jnp.sin(angle)[:, None, :]
    lo, hi = x[..., :half], x[..., half:]
    return jnp.concatenate([lo * cos - hi * sin, hi * cos + lo * sin], -1)


def attention_inputs(x, b, heads, eps, theta, chunk):
    """Of one sequence ``x`` (S, h): its queries, keys and values, (S, H,
    D) each, the queries and keys turned, and the summaries of its FULL
    chunks, ``(k~, v~)`` (S // C, H, D) each."""
    with jax.default_matmul_precision(_PRECISION):
        b = _f32(b)
        u = rms(x, b["n1"], eps)
        s = x.shape[0]
        q, k, v = ((u @ b[name]).reshape(s, heads, -1)
                   for name in ("w_q", "w_k", "w_v"))
        q, k = turned(q, theta), turned(k, theta)
        scale = q.shape[-1] ** -0.5
        full = s // chunk
        kc = k[:full * chunk].reshape(full, chunk, heads, -1)
        vc = v[:full * chunk].reshape(full, chunk, heads, -1)
        a = jax.nn.softmax(scale * (kc * b["mu"]).sum(-1), axis=1)
        bw = jax.nn.softmax(scale * (kc * b["phi"]).sum(-1), axis=1)
        return q, k, v, ((a[..., None] * kc).sum(1),
                         (bw[..., None] * vc).sum(1))


def attention_block(q, first, k, v, k_sum, v_sum, window, chunk):
    """The heads' outputs (block, H D) of the queries ``q`` (block, H, D)
    at positions ``first ..`` (one window's: the caller's blocks divide
    the window) against that window's keys and values ``k``, ``v`` (W, H,
    D; the sequence's, zeros past its end) and ALL the summaries, under
    masks built from the positions; one softmax over both kinds."""
    with jax.default_matmul_precision(_PRECISION):
        t = first + jnp.arange(q.shape[0])[:, None]               # (block, 1)
        start = (first // window) * window
        j = start + jnp.arange(k.shape[0])[None, :]               # positions
        c = jnp.arange(k_sum.shape[0])[None, :]                   # chunks
        scale = q.shape[-1] ** -0.5
        exact = jnp.where((j <= t) & (j >= (t // window) * window),
                          scale * jnp.einsum("qhd,khd->hqk", q, k), -jnp.inf)
        pooled = jnp.where(c < (window // chunk) * (t // window),
                           scale * jnp.einsum("qhd,chd->hqc", q, k_sum),
                           -jnp.inf)
        probs = jax.nn.softmax(jnp.concatenate([exact, pooled], -1), axis=-1)
        out = jnp.einsum("hqk,khd->qhd", probs[..., :k.shape[0]], v) + \
            jnp.einsum("hqc,chd->qhd", probs[..., k.shape[0]:], v_sum)
        return out.reshape(q.shape[0], -1)


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


def head(x, wf, w_head, eps, pred_heads):
    """``n_f(x) W``: (positions, prediction heads, vocabulary)."""
    with jax.default_matmul_precision(_PRECISION):
        logits = rms(x, jnp.asarray(wf, jnp.float32), eps) @ \
            jnp.asarray(w_head, jnp.float32)
        return logits.reshape(x.shape[0], pred_heads, -1)


class Reference:
    """The reference bound to one configuration: ``heads``, ``eps``,
    ``theta`` (the rotary base), ``window``, ``chunk``, ``pred_heads`` and
    ``query_block`` (how many queries meet their keys at once, and how
    many positions an MLP takes at once; it divides the window).  Each
    piece is jitted by itself and called layer after layer, block after
    block, from Python; weights are arguments, never constants."""

    def __init__(self, settings: dict):
        self.s = settings
        self._attention_inputs = jax.jit(attention_inputs,
                                         static_argnums=(2, 3, 4, 5))
        self._attention_block = jax.jit(attention_block,
                                        static_argnums=(6, 7))
        self._attention_output = jax.jit(attention_output)
        self._mlp = jax.jit(mlp, static_argnums=2)
        self._head = jax.jit(head, static_argnums=(3, 4))

    def _block_of(self, n: int) -> int:
        block = min(self.s["query_block"], n, self.s["window"])
        while n % block or self.s["window"] % block:
            block -= 1
        return block

    def attention(self, x, b, block):
        """``x + attn(n_1(x))`` of one sequence, and what a cache of the
        layer holds: ``(k, v, k~, v~)``, the turned keys and the values
        (S, H, D), the full chunks' summaries (S // C, H, D)."""
        s, window = x.shape[0], self.s["window"]
        q, k, v, (k_sum, v_sum) = self._attention_inputs(
            x, {name: b[name] for name in ("n1", "w_q", "w_k", "w_v", "mu",
                                           "phi")},
            self.s["heads"], self.s["eps"], float(self.s["theta"]),
            self.s["chunk"])
        # whole windows: zeros past the sequence's end, which no mask shows
        pad = -s % window
        k_w = jnp.pad(k, ((0, pad), (0, 0), (0, 0)))
        v_w = jnp.pad(v, ((0, pad), (0, 0), (0, 0)))
        outs = []
        for first in range(0, s, block):
            start = first // window * window
            outs.append(self._attention_block(
                q[first:first + block], first, k_w[start:start + window],
                v_w[start:start + window], k_sum, v_sum, window,
                self.s["chunk"]))
        return self._attention_output(x, jnp.concatenate(outs), b["w_o"]), \
            (k, v, k_sum, v_sum)

    def hidden(self, w: dict, ids, keep=None):
        """(S,) ids -> the last hidden states (S, h), and a layer ``(k, v,
        k~, v~)``: with ``keep`` = (first, count) the keys and values of
        those positions alone (a request's current window), the summaries
        whole."""
        x = jnp.asarray(w["wte"][jnp.asarray(ids, jnp.int32)], jnp.float32)
        block = self._block_of(x.shape[0])
        caches = []
        for b in w["blocks"]:
            x, (k, v, k_sum, v_sum) = self.attention(x, b, block)
            if keep is not None:
                k, v = (jax.lax.dynamic_slice_in_dim(a, keep[0], keep[1], 0)
                        for a in (k, v))
            caches.append((k, v, k_sum, v_sum))
            weights = {name: b[name]
                       for name in ("n2", "w_gate", "w_up", "w_down")}
            x = jnp.concatenate([
                self._mlp(x[first:first + block], weights, self.s["eps"])
                for first in range(0, x.shape[0], block)])
        return x, caches

    def logits(self, w: dict, ids, rows=None):
        """(S,) byte ids -> (S, prediction heads, V) float32 logits; with
        ``rows`` = (first, count) only those positions', (count, heads,
        V)."""
        return self.logits_and_caches(w, ids, rows)[0]

    def logits_and_caches(self, w: dict, ids, rows=None, keep=None):
        """``logits(rows=...)`` and what every layer's cache holds
        (``hidden``)."""
        x, caches = self.hidden(w, ids, keep)
        if rows is not None:
            x = jax.lax.dynamic_slice_in_dim(x, rows[0], rows[1], axis=0)
        return self._head(x, w["wf"], w["w_head"], self.s["eps"],
                          self.s["pred_heads"]), caches
