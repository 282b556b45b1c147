"""Plain reference of the Trinity decoder (``arcee-ai/Trinity-Mini``
``config.json``, ``model_type`` ``afmoe``): the forward pass in
straightforward ``jax.numpy``, float32, full matmul precision.  No cache,
no ring, no sort, no grouped matmul, no kernel, no batching: one sequence
at a time, every query attends over the whole sequence under a mask, and
every expert is applied to every token with a routing weight that is zero
for the experts the token did not choose.  Queries go in blocks and the
experts one after another, so that 15,360 positions fit beside the served
model.

Written from the published ``config.json`` (the sizes) and the public
modelling code of ``model_type`` afmoe (Hugging Face ``transformers``,
``models/afmoe/modeling_afmoe.py``: the wiring, marked (*) where the
configuration does not fix it), not from the program's model file:

* ``x = E[ids] * sqrt(hidden)`` (``mup_enabled``) (*).
* a block has four RMSNorms (*): ``x += n2(attn(n1(x)))``,
  ``x += n4(mlp(n3(x)))``; ``rms(x; w) = w * x / sqrt(mean(x^2) + eps)``.
* attention: ``q = h Wq`` (heads of d channels), ``k = h Wk``,
  ``v = h Wv`` (fewer heads: query head i reads key/value head
  i // (heads / kv heads)), no bias; q and k are RMS-normalised over the d
  channels of every head (*), one weight vector for all heads; in a
  ``sliding`` layer q and k are then rotated (channel i of a head pairs
  with channel i + d/2 at the angle ``position * theta^(-2i/d)``,
  rotate-half), in a ``full`` layer they see no positions at all (*);
  scores ``q k^T / sqrt(d)``; causal, and in a ``sliding`` layer position
  q sees k only where ``q - k < window``; softmax; ``out = (heads *
  sigmoid(h Wg)) Wo`` (*).
* a dense layer's MLP: ``down(silu(gate(h)) * up(h))``.
* an expert layer: ``s = sigmoid(h Wr)``; the k experts are the k largest
  of ``s + b`` (*), ties to the lower index; their weights are
  ``s_i / (sum of the chosen s + 1e-20) * route_scale``, the bias not in
  them (*); ``y = shared(h) + sum_i w_i expert_i(h)``, the shared expert
  and every routed one a gated SiLU MLP.  No token is dropped.
* a final RMSNorm and an untied head.

Departures from the published model, none in the mathematics: weights are
random, from the benchmark's seed; ``b`` is drawn from the seed too (a
trained model's is whatever load balancing left it at); dropout 0.

The program keeps q, k and v in one matrix laid out [q | k | v], and the
experts' gate and up matrices in one laid out [gate | up];
``weights_from_program`` splits them.
"""
import math

import jax
import jax.numpy as jnp

_PRECISION = "highest"   # a float32 matmul on the TPU is one bf16 pass otherwise


def weights_from_program(params) -> dict:
    """The program's parameter tree (flax names of ``GPTModel`` in its
    afmoe kinds) as the plain names used here.  Arrays are shared, not
    copied, except the three slices of the fused projection; the experts'
    [gate | up] is split where it is used, an expert at a time."""
    p = params["params"]
    blocks = []
    i = 0
    while f"h{i}" in p:
        b = p[f"h{i}"]
        attn, mlp = b["attn"], b["mlp"]
        n_q = attn["gate"]["kernel"].shape[1]
        n_kv = (attn["qkv"]["kernel"].shape[1] - n_q) // 2
        w_q, w_k, w_v = jnp.split(attn["qkv"]["kernel"],
                                  [n_q, n_q + n_kv], axis=-1)
        block = {
            "n1": b["ln1"]["scale"], "n2": b["ln1_post"]["scale"],
            "n3": b["ln2"]["scale"], "n4": b["ln2_post"]["scale"],
            "w_q": w_q, "w_k": w_k, "w_v": w_v,
            "wq_n": attn["q_norm"]["scale"], "wk_n": attn["k_norm"]["scale"],
            "w_g": attn["gate"]["kernel"], "w_o": attn["out"]["kernel"],
        }
        if "router" in mlp:
            block.update(
                w_r=mlp["router"]["kernel"], b_r=mlp["router_bias"],
                w_gate_up=mlp["w_gate_up"], w_down=mlp["w_down"],
                s_gate=mlp["shared0"]["gate"]["kernel"],
                s_up=mlp["shared0"]["up"]["kernel"],
                s_down=mlp["shared0"]["down"]["kernel"])
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


def attention(x, b, d, sliding, window, eps, theta, block):
    """``x + n2(attn(n1(x)))`` of one sequence ``x`` (S, H) with heads of
    ``d`` channels; the queries in blocks of ``block`` against all keys."""
    with jax.default_matmul_precision(_PRECISION):
        b = _f32(b)
        s = x.shape[0]
        h = rms(x, b["n1"], eps)
        q = rms((h @ b["w_q"]).reshape(s, -1, d), b["wq_n"], eps)
        k = rms((h @ b["w_k"]).reshape(s, -1, d), b["wk_n"], eps)
        v = (h @ b["w_v"]).reshape(s, -1, d)
        if sliding:
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
            if sliding:
                seen &= q_pos[:, None] - k_pos < window
            probs = jax.nn.softmax(jnp.where(seen[None], scores, -jnp.inf),
                                   -1)
            return jnp.einsum("hqk,khd->qhd", probs, v)

        heads = jax.lax.map(one_block, (
            q.reshape(s // block, block, -1, d),
            jnp.arange(s).reshape(s // block, block))).reshape(s, -1)
        out = (heads * jax.nn.sigmoid(h @ b["w_g"])) @ b["w_o"]
        return x + rms(out, b["n2"], eps)


def dense_mlp(x, b, eps):
    """``x + n4(mlp(n3(x)))`` of a dense layer."""
    with jax.default_matmul_precision(_PRECISION):
        b = _f32(b)
        h = rms(x, b["n3"], eps)
        y = (jax.nn.silu(h @ b["d_gate"]) * (h @ b["d_up"])) @ b["d_down"]
        return x + rms(y, b["n4"], eps)


def route(h, w_r, b_r, k, route_norm, route_scale):
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
    if route_norm:
        weights = weights / (weights.sum(-1, keepdims=True) + 1e-20)
    return weights * route_scale, jnp.stack(chosen, -1)


def experts(x, b, k, route_norm, route_scale, eps):
    """``x + n4(shared(h) + routed(h))`` of one sequence, h = n3(x), and
    the (S, k) experts its router chose.  Expert after expert: each is
    applied to all tokens and its result added with the tokens' routing
    weights for it (its weights become float32 one expert at a time)."""
    with jax.default_matmul_precision(_PRECISION):
        experts_w = (b["w_gate_up"], b["w_down"])
        b = _f32({name: a for name, a in b.items()
                  if name not in ("w_gate_up", "w_down")})
        h = rms(x, b["n3"], eps)
        weights, chosen = route(h, b["w_r"], b["b_r"], k, route_norm,
                                route_scale)

        def one_expert(y, args):
            w_gate_up, w_down, w_e = _f32(args)   # (H, 2W), (W, H), (S,)
            width = w_down.shape[0]
            gate_up = h @ w_gate_up
            out = (jax.nn.silu(gate_up[:, :width]) *
                   gate_up[:, width:]) @ w_down
            return y + out * w_e[:, None], None

        routed, _ = jax.lax.scan(one_expert, jnp.zeros_like(h),
                                 experts_w + (weights.T,))
        shared = (jax.nn.silu(h @ b["s_gate"]) * (h @ b["s_up"])) @ \
            b["s_down"]
        return x + rms(shared + routed, b["n4"], eps), chosen


def head(x, wf, w_head, eps):
    with jax.default_matmul_precision(_PRECISION):
        return rms(x, jnp.asarray(wf, jnp.float32), eps) @ \
            jnp.asarray(w_head, jnp.float32)


class Reference:
    """The reference bound to one configuration: ``head_dim``,
    ``layer_types`` (a
    ``sliding_attention`` or ``full_attention`` a layer), ``sliding_window``,
    ``rms_norm_eps``, ``rope_theta``, ``num_experts_per_tok``,
    ``route_norm``, ``route_scale``, ``scale_embedding`` and
    ``query_block`` (how many queries meet all keys at once).  Each piece is jitted by itself and
    called layer after layer; weights are arguments, never constants."""

    def __init__(self, settings: dict):
        self.s = settings
        self._attention = jax.jit(attention,
                                  static_argnums=(2, 3, 4, 5, 6, 7))
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
        """(S,) ids -> the last hidden states (S, H) and, per expert
        layer, every token's experts (S, k)."""
        s = self.s
        x = jnp.asarray(w["wte"], jnp.float32)[jnp.asarray(ids, jnp.int32)]
        if s["scale_embedding"]:
            x = x * math.sqrt(x.shape[-1])
        n = x.shape[0]
        chosen = []
        for b, kind in zip(w["blocks"], s["layer_types"]):
            x = self._attention(
                x, b, s["head_dim"], kind == "sliding_attention",
                s["sliding_window"], s["rms_norm_eps"], s["rope_theta"],
                self._block_of(n, s["query_block"]))
            if "w_r" in b:
                x, what = self._experts(
                    x, b, s["num_experts_per_tok"],
                    s["route_norm"], s["route_scale"], s["rms_norm_eps"])
                chosen.append(what)
            else:
                x = self._dense(x, b, s["rms_norm_eps"])
        return x, chosen

    def logits(self, w: dict, ids, rows=None):
        """(S,) token ids -> (S, V) float32 logits; with ``rows`` =
        (first, count) only those positions' logits, (count, V)."""
        x, _ = self.hidden(w, ids)
        if rows is not None:
            x = jax.lax.dynamic_slice_in_dim(x, rows[0], rows[1], axis=0)
        return self._head(x, w["wf"], w["w_head"], self.s["rms_norm_eps"])

    def logits_and_experts(self, w: dict, ids, rows):
        """``logits(rows=...)`` and the experts of those positions in
        every expert layer, (layers, count, k)."""
        x, chosen = self.hidden(w, ids)
        x = jax.lax.dynamic_slice_in_dim(x, rows[0], rows[1], axis=0)
        picked = jnp.stack([jax.lax.dynamic_slice_in_dim(
            c, rows[0], rows[1], axis=0) for c in chosen])
        return (self._head(x, w["wf"], w["w_head"],
                           self.s["rms_norm_eps"]), picked)
