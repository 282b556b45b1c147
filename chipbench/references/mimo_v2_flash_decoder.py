"""Plain reference of the MiMo-V2-Flash decoder (``XiaomiMiMo/MiMo-V2-Flash``
``config.json``, ``model_type`` ``mimo_v2_flash``): the forward pass in
straightforward ``jax.numpy``, float32, full matmul precision.  No cache,
no ring, no folded heads, no sort, no grouped matmul, no kernel, no
batching: one sequence at a time, every query attends over the whole
sequence under a mask, and every held expert is applied to every token
with a routing weight that is zero for the experts the token did not
choose.  Queries and the MLPs' rows go in blocks and the experts one after
another, so that 32,768 positions fit beside the served model.

Written from the published ``config.json`` (the sizes and switches) and the
catalog's description of the family; each reading of a key that the file
only names is marked (*) and listed under ``assumed`` in the configuration
file, not taken from the program's model file:

* a block is pre-norm with two RMSNorms (``layernorm_epsilon``):
  ``h = x + Attn_t(rms(x))``, ``y = h + FF(rms(h))``; ``t`` is full where
  ``hybrid_layer_pattern[l]`` is 0 and window where 1; ``FF`` is a SwiGLU MLP
  of ``intermediate_size`` where ``moe_layer_freq[l]`` is 0 and the routed
  experts where 1.  No bias anywhere, an untied head, a final RMSNorm.
* attention of kind ``t``: ``num_attention_heads`` query heads of
  ``head_dim`` channels; ``num_key_value_heads`` (full) or
  ``swa_num_key_value_heads`` (window) key heads of ``head_dim`` and value
  heads of ``v_head_dim``; query head i reads key/value head
  ``i // (heads / kv heads)``.  Rotate-half rotary positions (*) on the
  first ``int(head_dim * partial_rotary_factor)`` channels of every q and k
  head (channel i of them pairs with channel i + half at the angle
  ``position * theta^(-i/half)``), base ``rope_theta`` (full) or
  ``swa_rope_theta`` (window); the other channels pass.  Scores
  ``q . k / sqrt(head_dim)``, visible where ``j <= i`` and on window layers
  also ``i - j < sliding_window``.  Full layers: ``p = softmax_j(s)``.
  Window layers (``add_swa_attention_sink_bias``): a learned logit ``b_h`` a
  query head joins the denominator (*): ``p_ij = exp(s_ij) / (exp(b_h) +
  sum_j' exp(s_ij'))``; the sink takes mass and carries no value.  ``o_i =
  attention_value_scale * sum_j p_ij v_j`` (*), then the output projection.
  No q/k norm (the file has no key for one).  ``attention_chunk_size``
  changes no equation and is not used.
* experts (``scoring_func`` sigmoid, ``topk_method`` noaux_tc, one group):
  ``s = sigmoid(n Wr)`` over all the layer's experts; the k experts are the
  k largest of ``s + bias`` (*), ties to the lower index; their weights are
  ``s_i / sum of the chosen s`` (``norm_topk_prob``), times
  ``routed_scaling_factor`` (null: 1); expert e is ``W_down(silu(W_gate n) *
  W_up n)``; no shared expert.

**The share.**  Where the layer's experts are divided over several chips
the reference is given what one chip holds: the experts from
``experts_first`` on, as many as the weights have, of a router that is
still as wide as the layer; what the absent experts would have added is
left out, and that partial result goes on.  Given all the experts
(``experts_first`` 0) it is the whole layer.  The vocabulary's slice is
simply a smaller vocabulary.

Departures from the published model, none in the mathematics of what is
built: weights are random, from the benchmark's seed, the sinks among them;
the router's bias is whatever the driver's balancing left it at; dropout 0;
the three multi-token-prediction modules have no key in ``config`` and are
not built.

The program keeps q, k and v in one matrix laid out [q | k | v] and gate
and up of the routed experts in one laid out [gate | up]; both are split
where they are used.
"""
import math

import jax
import jax.numpy as jnp

_PRECISION = "highest"   # a float32 matmul on the TPU is one bf16 pass otherwise


def weights_from_program(params) -> dict:
    """The program's parameter tree (flax names of ``GPTModel`` in its
    mimo_v2_flash kinds) as the plain names used here.  Arrays are shared,
    not copied: the fused projection ``w_qkv`` stays whole and is split
    where it is used, by the settings' heads and widths."""
    p = params["params"]
    blocks = []
    i = 0
    while f"h{i}" in p:
        b = p[f"h{i}"]
        attn, mlp = b["attn"], b["mlp"]
        block = {"n1": b["ln1"]["scale"], "n2": b["ln2"]["scale"],
                 "w_qkv": attn["qkv"]["kernel"],
                 "w_o": attn["out"]["kernel"]}
        if "sink" in attn:
            block["sink"] = attn["sink"]
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
            "wf": p["ln_f"]["scale"], "w_head": p["lm_head"]["kernel"]}


def _f32(tree):
    return jax.tree_util.tree_map(lambda a: jnp.asarray(a, jnp.float32), tree)


def rms(x, w, eps):
    return w * x / jnp.sqrt((x * x).mean(-1, keepdims=True) + eps)


def rotate(x, theta, turned):
    """x (S, heads, d) at positions 0..S-1: the first ``turned`` channels of
    every head rotated (rotate-half among themselves), the others as they
    are."""
    s = x.shape[0]
    half = turned // 2
    inv_freq = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    angles = jnp.arange(s, dtype=jnp.float32)[:, None] * inv_freq
    cos = jnp.concatenate([jnp.cos(angles)] * 2, -1)[:, None, :]
    sin = jnp.concatenate([jnp.sin(angles)] * 2, -1)[:, None, :]
    head = x[..., :turned]
    rotated = jnp.concatenate([-head[..., half:], head[..., :half]], -1)
    return jnp.concatenate([head * cos + rotated * sin, x[..., turned:]], -1)


def attention(x, b, heads, kv_heads, d, dv, turned, theta, window,
              value_scale, eps, block):
    """``x + Attn(rms(x))`` of one sequence ``x`` (S, H): ``heads`` query
    heads and ``kv_heads`` key heads of ``d`` channels, value heads of
    ``dv``; ``window`` 0: a full layer; ``b["sink"]`` (heads,), where the
    layer has one, joins the softmax's denominator.  The queries in blocks
    of ``block`` against all keys."""
    with jax.default_matmul_precision(_PRECISION):
        b = _f32(b)
        s = x.shape[0]
        h = rms(x, b["n1"], eps)
        q, k, v = jnp.split(h @ b["w_qkv"],
                            [heads * d, (heads + kv_heads) * d], axis=-1)
        q = rotate(q.reshape(s, heads, d), theta, turned)
        k = rotate(k.reshape(s, kv_heads, d), theta, turned)
        v = v.reshape(s, kv_heads, dv)
        group = heads // kv_heads
        k_pos = jnp.arange(s)[None, :]
        sink = b["sink"].reshape(kv_heads, group, 1, 1) \
            if "sink" in b else None

        def one_block(args):
            qb, q_pos = args                     # (T, heads, d), (T,)
            scores = jnp.einsum(
                "qhgd,khd->hgqk", qb.reshape(-1, kv_heads, group, d),
                k) / math.sqrt(d)
            seen = k_pos <= q_pos[:, None]
            if window:
                seen &= q_pos[:, None] - k_pos < window
            scores = jnp.where(seen[None, None], scores, -jnp.inf)
            top = scores.max(-1, keepdims=True)
            if sink is not None:
                top = jnp.maximum(top, sink)
            weights = jnp.exp(scores - top)
            total = weights.sum(-1, keepdims=True)
            if sink is not None:
                # the sink takes its share and carries no value
                total = total + jnp.exp(sink - top)
            return jnp.einsum("hgqk,khd->qhgd", weights / total, v)

        out = jax.lax.map(one_block, (
            q.reshape(s // block, block, heads, d),
            jnp.arange(s).reshape(s // block, block))).reshape(s, heads * dv)
        return x + (value_scale * out) @ b["w_o"]


def dense_mlp(x, b, eps, block):
    """``x + mlp(rms(x))`` of a dense layer, ``block`` rows at a time."""
    with jax.default_matmul_precision(_PRECISION):
        b = _f32({name: b[name] for name in ("n2", "d_gate", "d_up",
                                             "d_down")})

        def one_block(xb):
            h = rms(xb, b["n2"], eps)
            return xb + (jax.nn.silu(h @ b["d_gate"]) *
                         (h @ b["d_up"])) @ b["d_down"]

        return jax.lax.map(one_block, x.reshape(-1, block, x.shape[1])
                           ).reshape(x.shape)


def route(h, w_r, b_r, k, norm, scale):
    """(S, E) routing weights, zero but for each token's ``k`` experts,
    and the (S, k) experts chosen, largest first: k times the largest of
    what is left of ``sigmoid(h Wr) + b`` (no sort)."""
    scores = jax.nn.sigmoid(h @ w_r)
    left, chosen = scores + b_r, []
    for _ in range(k):
        best = jnp.argmax(left, axis=-1)
        chosen.append(best)
        left = left.at[jnp.arange(h.shape[0]), best].set(-jnp.inf)
    # the weights are the scores themselves: the bias chose, and no more
    weights = jnp.where(jnp.isinf(left), scores, 0.0)
    if norm:
        weights = weights / weights.sum(-1, keepdims=True)
    return weights * scale, jnp.stack(chosen, -1)


def routed_mlp(x, b, eps, k, norm, scale, first, block):
    """``x + Routed(rms(x))`` of one sequence with the routed experts the
    weights hold (the layer's experts ``first`` ..), and the (S, k) picks
    of its router among ALL the layer's experts.  ``block`` rows at a time,
    expert after expert: each is applied to all the block's tokens and its
    result added with the tokens' routing weights for it."""
    with jax.default_matmul_precision(_PRECISION):
        held = b["w_down"].shape[0]
        rest = _f32({name: b[name] for name in ("n2", "w_r", "b_r")})

        def one_block(xb):
            u = rms(xb, rest["n2"], eps)
            weights, chosen = route(u, rest["w_r"], rest["b_r"], k, norm,
                                    scale)

            def one_expert(y, args):
                w_gate_up, w_down, w_e = _f32(args)   # (H, 2W), (W, H), (T,)
                width = w_down.shape[0]
                gate_up = u @ w_gate_up
                out = (jax.nn.silu(gate_up[:, :width]) *
                       gate_up[:, width:]) @ w_down
                return y + out * w_e[:, None], None

            routed, _ = jax.lax.scan(
                one_expert, jnp.zeros_like(u),
                (b["w_gate_up"], b["w_down"],
                 weights[:, first:first + held].T))
            return xb + routed, chosen

        out, chosen = jax.lax.map(one_block,
                                  x.reshape(-1, block, x.shape[1]))
        return out.reshape(x.shape), chosen.reshape(x.shape[0], k)


def head(x, wf, w_head, eps):
    with jax.default_matmul_precision(_PRECISION):
        return rms(x, jnp.asarray(wf, jnp.float32), eps) @ \
            jnp.asarray(w_head, jnp.float32)


class Reference:
    """The reference bound to one configuration: ``heads``, ``head_dim``,
    ``v_head_dim``, ``kv_heads`` and ``theta`` (``{"full": ..,
    "sliding": ..}`` each), ``rotary_dim`` (the leading channels rotated),
    ``window``, ``value_scale``, ``sink_kinds`` (the kinds whose softmax
    has the sink), ``pattern`` (0 full, 1 sliding a layer), ``eps``,
    ``num_experts_per_tok``, ``norm_topk_prob``, ``route_scale``,
    ``experts_first`` (the layer's expert that the weights' first is) and
    ``query_block`` (how many queries meet all keys at once; eight times as
    many rows go through an MLP at once).  Each piece is jitted by itself
    and called layer after layer; weights are arguments, never constants."""

    def __init__(self, settings: dict):
        self.s = settings
        self._attention = jax.jit(attention,
                                  static_argnums=tuple(range(2, 12)))
        self._dense = jax.jit(dense_mlp, static_argnums=(2, 3))
        self._routed = jax.jit(routed_mlp,
                               static_argnums=(2, 3, 4, 5, 6, 7))
        self._head = jax.jit(head, static_argnums=3)

    @staticmethod
    def _block_of(n: int, block: int) -> int:
        block = min(block, n)
        while n % block:
            block -= 1
        return block

    def layer(self, x, b, sliding: bool):
        """One block: ``(x, picks)``, ``picks`` None on a dense layer."""
        s, n = self.s, x.shape[0]
        kind = "sliding" if sliding else "full"
        if kind not in s["sink_kinds"]:
            b = {name: a for name, a in b.items() if name != "sink"}
        x = self._attention(
            x, b, s["heads"], s["kv_heads"][kind], s["head_dim"],
            s["v_head_dim"], s["rotary_dim"], float(s["theta"][kind]),
            s["window"] if sliding else 0, float(s["value_scale"]),
            s["eps"], self._block_of(n, s["query_block"]))
        rows = self._block_of(n, 8 * s["query_block"])
        if "w_r" not in b:
            return self._dense(x, b, s["eps"], rows), None
        return self._routed(
            x, b, s["eps"], s["num_experts_per_tok"],
            bool(s["norm_topk_prob"]), float(s["route_scale"]),
            s["experts_first"], rows)

    def hidden(self, w: dict, ids, rows=None):
        """(S,) ids -> the last hidden states (S, H) and, per routed layer,
        every token's picks (S, k); with ``rows`` = (first, count) the
        picks of those positions alone."""
        x = jnp.asarray(w["wte"], jnp.float32)[jnp.asarray(ids, jnp.int32)]
        picks = []
        for b, sliding in zip(w["blocks"], self.s["pattern"]):
            x, what = self.layer(x, b, bool(sliding))
            if what is not None:
                picks.append(what if rows is None else
                             jax.lax.dynamic_slice_in_dim(
                                 what, rows[0], rows[1], axis=0))
        return x, picks

    def logits(self, w: dict, ids, rows=None):
        """(S,) token ids -> (S, V) float32 logits; with ``rows`` =
        (first, count) only those positions' logits, (count, V)."""
        x, _ = self.hidden(w, ids)
        if rows is not None:
            x = jax.lax.dynamic_slice_in_dim(x, rows[0], rows[1], axis=0)
        return self._head(x, w["wf"], w["w_head"], self.s["eps"])

    def logits_and_experts(self, w: dict, ids, rows):
        """``logits(rows=...)`` and the picks of those positions in every
        routed layer, (layers, count, k)."""
        x, picks = self.hidden(w, ids, rows)
        x = jax.lax.dynamic_slice_in_dim(x, rows[0], rows[1], axis=0)
        return (self._head(x, w["wf"], w["w_head"], self.s["eps"]),
                jnp.stack(picks))
