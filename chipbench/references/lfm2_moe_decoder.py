"""Plain reference of the LFM2 mixture-of-experts decoder
(``LiquidAI/LFM2-8B-A1B`` ``config.json``, ``model_type`` ``lfm2_moe``):
the forward pass and the next-token loss in straightforward ``jax.numpy``,
float32, full matmul precision.  No cache, no state, no sort, no grouped
matmul, no kernel, no batching: one sequence at a time, the short
convolution written as the sum over its shifted copies of the whole
sequence, every query attending over the whole sequence under a mask, and
every expert applied to every token with a routing weight that is zero for
the experts the token did not choose.  Queries go in blocks and the
experts one after another, so that 5,120 positions fit beside the served
model.

Written from the published ``config.json`` (the sizes) and the public
modelling code of ``model_type`` lfm2_moe (Hugging Face ``transformers``,
``models/lfm2_moe/modeling_lfm2_moe.py``: the wiring, marked (*) where the
configuration does not fix it), not from the program's model file.  With
``h`` the hidden size, ``L`` = ``conv_L_cache``, ``eps`` = ``norm_eps`` and
``n(x; w) = w * x / sqrt(mean(x^2) + eps)``:

* ``x = E[ids]``; a layer is ``x = x + mixer(n_op(x))``, then ``x = x +
  ffn(n_ffn(x))`` (*); ``logits = n_f(x) E^T``: the final norm (published
  as ``embedding_norm``) and a head tied to the embedding (*; 8.34 B
  parameters come out only with one table).
* a ``conv`` layer's mixer, no bias (``conv_bias`` false): ``[B, C, X] =
  split3(u W_in)``, ``W_in`` h x 3h; ``g = B * X``; ``c_t = sum_{j<L}
  k[j] * g_{t-(L-1)+j}`` with ``g_s = 0`` for ``s < 0`` (depthwise and
  causal: one tap a channel a shift); ``y = (C * c) W_out``.
* a ``full_attention`` layer's mixer: ``q = u Wq`` (heads of d = h / heads
  channels (*)), ``k = u Wk``, ``v = u Wv`` (fewer heads: query head i
  reads key/value head i // (heads / kv heads)), no bias; q and k are
  RMS-normalised over the d channels of every head, one weight vector for
  all heads (*), then rotated (channel i of a head pairs with channel i +
  d/2 at the angle ``position * theta^(-2i/d)``, rotate-half (*)); scores
  ``q k^T / sqrt(d)``, causal, softmax; ``Wo``.
* ``ffn`` of the first ``num_dense_layers`` layers: ``down(silu(gate(u)) *
  up(u))`` (silu (*): the file has no ``hidden_act``).
* ``ffn`` of the others: ``s = sigmoid(u Wr)``; the k experts are the k
  largest of ``s + b`` (``use_expert_bias``), ties to the lower index;
  their weights are ``s_i / (sum of the chosen s + 1e-6)``
  (``norm_topk_prob``) times ``routed_scaling_factor``, the bias not in
  them; ``y = sum_i w_i expert_i(u)``, every expert a gated SiLU MLP, no
  shared expert.  No token is dropped.

Departures from the published model, none in the mathematics: weights are
random, from the benchmark's seed; ``b`` is set by load at set-up (a
trained model's is whatever load balancing left it at); dropout 0.  The
PROGRAM departs from the published constant in one place: its
``moe.topk_routing`` divides by ``sum + 1e-20`` where this file keeps the
published ``1e-6``; the sum of four sigmoids of which one is a largest
lies above 0.1 here, so the weights differ by under 1e-5 relative.

The program keeps q, k and v in one matrix laid out [q | k | v], the
experts' gate and up matrices in one laid out [gate | up] and the taps as
(L, h), tap j a row; ``weights_from_program`` splits the first and hands on
the others as they are.
"""
import math

import jax
import jax.numpy as jnp

_PRECISION = "highest"   # a float32 matmul on the TPU is one bf16 pass otherwise
ROUTE_EPS = 1e-6         # the published renormalisation's constant


def weights_from_program(params) -> dict:
    """The program's parameter tree (flax names of ``GPTModel`` in its
    lfm2_moe kinds) as the plain names used here.  Arrays are shared, not
    copied, except the three slices of the fused projection; the experts'
    [gate | up] is split where it is used, an expert at a time."""
    p = params["params"]
    blocks = []
    i = 0
    while f"h{i}" in p:
        b = p[f"h{i}"]
        mlp = b["mlp"]
        block = {"n_op": b["ln1"]["scale"], "n_ffn": b["ln2"]["scale"]}
        if "conv" in b:
            block.update(w_in=b["conv"]["in_proj"]["kernel"],
                         taps=b["conv"]["kernel"],
                         w_out=b["conv"]["out_proj"]["kernel"])
        else:
            attn = b["attn"]
            n_q = attn["out"]["kernel"].shape[0]
            n_kv = (attn["qkv"]["kernel"].shape[1] - n_q) // 2
            w_q, w_k, w_v = jnp.split(attn["qkv"]["kernel"],
                                      [n_q, n_q + n_kv], axis=-1)
            block.update(w_q=w_q, w_k=w_k, w_v=w_v,
                         wq_n=attn["q_norm"]["scale"],
                         wk_n=attn["k_norm"]["scale"],
                         w_o=attn["out"]["kernel"])
        if "router" in mlp:
            block.update(w_r=mlp["router"]["kernel"], b_r=mlp["router_bias"],
                         w_gate_up=mlp["w_gate_up"], w_down=mlp["w_down"])
        else:
            block.update(d_gate=mlp["gate"]["kernel"],
                         d_up=mlp["up"]["kernel"],
                         d_down=mlp["down"]["kernel"])
        blocks.append(block)
        i += 1
    return {"wte": p["wte"]["embedding"], "blocks": blocks,
            "wf": p["ln_f"]["scale"]}


def _f32(tree):
    return jax.tree_util.tree_map(lambda a: jnp.asarray(a, jnp.float32), tree)


def rms(x, w, eps):
    return w * x / jnp.sqrt((x * x).mean(-1, keepdims=True) + eps)


def rotate(x, theta):
    """x (S, heads, d) at positions 0..S-1."""
    s, _, d = x.shape
    half = d // 2
    inv_freq = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    angles = jnp.arange(s, dtype=jnp.float32)[:, None] * inv_freq
    cos = jnp.concatenate([jnp.cos(angles)] * 2, -1)[:, None, :]
    sin = jnp.concatenate([jnp.sin(angles)] * 2, -1)[:, None, :]
    rotated = jnp.concatenate([-x[..., half:], x[..., :half]], -1)
    return x * cos + rotated * sin


def shifted(g, by: int):
    """``g`` (S, h) moved ``by`` positions later, zeros in front."""
    if by == 0:
        return g
    return jnp.concatenate([jnp.zeros_like(g[:by]), g[:-by]], axis=0)


def short_conv(x, b, eps):
    """``x + mixer(n_op(x))`` of a ``conv`` layer, one sequence ``x``
    (S, h): the convolution as the sum over its ``L`` shifted copies."""
    with jax.default_matmul_precision(_PRECISION):
        b = _f32(b)
        u = rms(x, b["n_op"], eps)
        b_gate, c_gate, xs = jnp.split(u @ b["w_in"], 3, axis=-1)
        g = b_gate * xs
        taps = b["taps"]                                  # (L, h)
        n_taps = taps.shape[0]
        # tap j meets g at t - (L - 1) + j: the copy moved L - 1 - j later
        c = sum(taps[j] * shifted(g, n_taps - 1 - j) for j in range(n_taps))
        return x + (c_gate * c) @ b["w_out"]


def attention(x, b, d, eps, theta, block):
    """``x + attn(n_op(x))`` of one sequence ``x`` (S, h) with heads of
    ``d`` channels; the queries in blocks of ``block`` against all keys."""
    with jax.default_matmul_precision(_PRECISION):
        b = _f32(b)
        s = x.shape[0]
        u = rms(x, b["n_op"], eps)
        q = rms((u @ b["w_q"]).reshape(s, -1, d), b["wq_n"], eps)
        k = rms((u @ b["w_k"]).reshape(s, -1, d), b["wk_n"], eps)
        v = (u @ b["w_v"]).reshape(s, -1, d)
        q, k = rotate(q, theta), rotate(k, theta)
        group = q.shape[1] // k.shape[1]
        # every query head beside its own key/value head
        k = jnp.repeat(k, group, axis=1)
        v = jnp.repeat(v, group, axis=1)
        k_pos = jnp.arange(s)[None, :]

        def one_block(args):
            qb, q_pos = args                     # (T, heads, d), (T,)
            scores = jnp.einsum("qhd,khd->hqk", qb, k) / math.sqrt(d)
            seen = k_pos <= q_pos[:, None]
            probs = jax.nn.softmax(jnp.where(seen[None], scores, -jnp.inf),
                                   -1)
            return jnp.einsum("hqk,khd->qhd", probs, v)

        heads = jax.lax.map(one_block, (
            q.reshape(s // block, block, -1, d),
            jnp.arange(s).reshape(s // block, block))).reshape(s, -1)
        return x + heads @ b["w_o"]


def dense_mlp(x, b, eps):
    """``x + ffn(n_ffn(x))`` of a dense layer."""
    with jax.default_matmul_precision(_PRECISION):
        b = _f32(b)
        u = rms(x, b["n_ffn"], eps)
        return x + (jax.nn.silu(u @ b["d_gate"]) * (u @ b["d_up"])) @ \
            b["d_down"]


def route(u, w_r, b_r, k, norm_topk_prob, scale):
    """(S, E) routing weights, zero but for each token's ``k`` experts,
    and the (S, k) experts chosen, largest first: k times the largest of
    what is left of ``sigmoid(u Wr) + b`` (no sort)."""
    scores = jax.nn.sigmoid(u @ w_r)
    left, chosen = scores + b_r, []
    for _ in range(k):
        best = jnp.argmax(left, axis=-1)
        chosen.append(best)
        left = left.at[jnp.arange(u.shape[0]), best].set(-jnp.inf)
    # the weights are the scores themselves: the bias chose, and no more
    weights = jnp.where(jnp.isinf(left), scores, 0.0)
    if norm_topk_prob:
        weights = weights / (weights.sum(-1, keepdims=True) + ROUTE_EPS)
    return weights * scale, jnp.stack(chosen, -1)


def experts(x, b, k, norm_topk_prob, scale, eps):
    """``x + routed(n_ffn(x))`` of one sequence and the (S, k) experts its
    router chose.  Expert after expert: each is applied to all tokens and
    its result added with the tokens' routing weights for it (its weights
    become float32 one expert at a time)."""
    with jax.default_matmul_precision(_PRECISION):
        experts_w = (b["w_gate_up"], b["w_down"])
        b = _f32({name: a for name, a in b.items()
                  if name not in ("w_gate_up", "w_down")})
        u = rms(x, b["n_ffn"], eps)
        weights, chosen = route(u, b["w_r"], b["b_r"], k, norm_topk_prob,
                                scale)

        def one_expert(y, args):
            w_gate_up, w_down, w_e = _f32(args)   # (h, 2W), (W, h), (S,)
            width = w_down.shape[0]
            gate_up = u @ w_gate_up
            out = (jax.nn.silu(gate_up[:, :width]) *
                   gate_up[:, width:]) @ w_down
            return y + out * w_e[:, None], None

        routed, _ = jax.lax.scan(one_expert, jnp.zeros_like(u),
                                 experts_w + (weights.T,))
        return x + routed, chosen


def head(x, wf, wte, eps):
    """``n_f(x) E^T``: the final norm and the tied head."""
    with jax.default_matmul_precision(_PRECISION):
        return rms(x, jnp.asarray(wf, jnp.float32), eps) @ \
            jnp.asarray(wte, jnp.float32).T


def token_losses(logits, labels):
    """(S, V) logits and (S,) labels -> (S,) -log softmax(logits)[label]."""
    top = logits.max(-1, keepdims=True)
    lse = jnp.log(jnp.exp(logits - top).sum(-1)) + top[:, 0]
    return lse - jnp.take_along_axis(logits, labels[:, None], axis=-1)[:, 0]


class Reference:
    """The reference bound to one configuration: ``head_dim`` (hidden size
    / attention heads), ``norm_eps``, ``rope_theta``,
    ``num_experts_per_tok``, ``norm_topk_prob``, ``routed_scaling_factor``
    and ``query_block`` (how many queries meet all keys at once).  Which
    layer is a convolution and which routes is read off the weights.  Each
    piece is jitted by itself and called layer after layer; weights are
    arguments, never constants."""

    def __init__(self, settings: dict):
        self.s = settings
        self._conv = jax.jit(short_conv, static_argnums=(2,))
        self._attention = jax.jit(attention, static_argnums=(2, 3, 4, 5))
        self._dense = jax.jit(dense_mlp, static_argnums=(2,))
        self._experts = jax.jit(experts, static_argnums=(2, 3, 4, 5))
        self._head = jax.jit(head, static_argnums=3)

    @staticmethod
    def _block_of(n: int, block: int) -> int:
        block = min(block, n)
        while n % block:
            block -= 1
        return block

    def hidden(self, w: dict, ids):
        """(S,) ids -> the last hidden states (S, h) and, per expert
        layer, every token's experts (S, k)."""
        s = self.s
        x = jnp.asarray(w["wte"][jnp.asarray(ids, jnp.int32)], jnp.float32)
        n = x.shape[0]
        chosen = []
        for b in w["blocks"]:
            if "w_in" in b:
                x = self._conv(x, b, s["norm_eps"])
            else:
                x = self._attention(
                    x, b, s["head_dim"], s["norm_eps"], s["rope_theta"],
                    self._block_of(n, s["query_block"]))
            if "w_r" in b:
                x, what = self._experts(
                    x, b, s["num_experts_per_tok"], s["norm_topk_prob"],
                    s["routed_scaling_factor"], s["norm_eps"])
                chosen.append(what)
            else:
                x = self._dense(x, b, s["norm_eps"])
        return x, chosen

    def logits(self, w: dict, ids, rows=None):
        """(S,) token ids -> (S, V) float32 logits; with ``rows`` =
        (first, count) only those positions' logits, (count, V)."""
        x, _ = self.hidden(w, ids)
        if rows is not None:
            x = jax.lax.dynamic_slice_in_dim(x, rows[0], rows[1], axis=0)
        return self._head(x, w["wf"], w["wte"], self.s["norm_eps"])

    def logits_and_experts(self, w: dict, ids, rows):
        """``logits(rows=...)`` and the experts of those positions in
        every expert layer, (layers, count, k)."""
        x, chosen = self.hidden(w, ids)
        x = jax.lax.dynamic_slice_in_dim(x, rows[0], rows[1], axis=0)
        picked = jnp.stack([jax.lax.dynamic_slice_in_dim(
            c, rows[0], rows[1], axis=0) for c in chosen])
        return (self._head(x, w["wf"], w["wte"], self.s["norm_eps"]),
                picked)

    def lm_loss(self, w: dict, input_ids, labels) -> float:
        """Mean next-token loss of a (B, S) batch.  No auxiliary term: the
        published model balances its experts by the stored bias, which no
        gradient reaches."""
        total = 0.0
        for ids, lab in zip(input_ids, labels):
            total = total + token_losses(
                self.logits(w, ids), jnp.asarray(lab, jnp.int32)).sum()
        return float(total / jnp.asarray(labels).size)
