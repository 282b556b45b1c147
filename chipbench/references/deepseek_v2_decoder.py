"""Plain reference of the DeepSeek-V2 decoder (``deepseek-ai/DeepSeek-V2``
``config.json``, ``model_type`` ``deepseek_v2``): the forward pass in
straightforward ``jax.numpy``, float32, full matmul precision.  No cache,
no absorption, no sort, no grouped matmul, no kernel, no batching: one
sequence at a time, latent attention in its expanded (published) form
with every position's per-head keys and values made from its latent,
every query attending over the whole sequence under a mask, and every
held expert applied to every token with a routing weight that is zero for
the experts the token did not choose.  Queries go in blocks and the
experts one after another, so that 16,384 positions fit beside the served
model.

Written from the published ``config.json`` (the sizes) and the public
modelling code (``modeling_deepseek.py`` beside it, and Hugging Face
``transformers`` ``models/deepseek_v2``: the wiring, marked (*) where the
configuration does not fix it), not from the program's model file:

* ``x = E[ids]``, no scale.  A block has two RMSNorms: ``x += attn(n1(x))``,
  ``x += mlp(n2(x))``; ``rms(x; w) = w * x / sqrt(mean(x^2) + eps)``.  A
  final RMSNorm and an untied head; no bias anywhere.
* attention (MLA), ``h`` the normed input, H heads: ``c_q = rms(h Wq_a)``,
  ``q = c_q Wq_b`` a head ``[q_nope (dn) | q_pe (dr)]``; ``[c | k_pe] = h
  Wkv_a``, ``c = rms(c)``, ``k_pe`` ONE key of dr channels a position for
  all heads; ``[k_nope (dn) | v (dv)] = c Wkv_b`` a head.  Rotary positions
  on ``q_pe`` and ``k_pe`` only, on the interleaved pairs (2i, 2i + 1) (*),
  at YaRN's frequencies (*): ``f_i = theta^(-2i/dr)``; ``pair(t) = dr
  ln(L / (2 pi t)) / (2 ln theta)`` with L the original context; ``low =
  floor(pair(beta_fast))``, ``high = ceil(pair(beta_slow))``; ``ramp_i =
  clip((i - low) / (high - low), 0, 1)``; ``inv_freq_i = f_i (1 - ramp_i) +
  f_i / factor * ramp_i``; cosine and sine times ``m(factor, mscale) /
  m(factor, mscale_all_dim)``, ``m(s, a) = 0.1 a ln s + 1``.  ``scores =
  (q_nope . k_nope + q_pe . k_pe) * (dn + dr)^-0.5 * m(factor,
  mscale_all_dim)^2`` (*), causal, softmax; ``out = (probs v) Wo``.
* the leading dense layers' MLP: ``down(silu(gate(h)) * up(h))``.
* an expert layer: ``s = softmax(h Wr)`` over ALL the layer's experts; a
  group of consecutive experts scores as its best expert; the
  ``topk_group`` best groups; the k largest ``s`` among their experts
  (``group_limited_greedy``) (*), ties to the lower index; weights
  ``s[chosen] * routed_scaling_factor``, divided by their sum first only
  where ``norm_topk_prob``; ``y = shared(h) + sum_i w_i expert_i(h)``, the
  shared experts one gated MLP of their widths together (*), every routed
  expert a gated SiLU MLP.  No token is dropped.

**The share.**  Where the layer's experts are divided over several chips
the reference is given what one chip holds: the experts from
``experts_first`` on, as many as the weights have, of a router that is
still as wide as the layer; what the absent experts would have added is
left out, and that partial result goes on to the next layer.  Given all
the experts (``experts_first`` 0) it is the whole layer.  The vocabulary's
slice is simply a smaller vocabulary.

Departures from the published model, none in the mathematics: weights are
random, from the benchmark's seed; dropout 0.

The program keeps the shared experts as MLPs of one expert's width each,
and the routed experts' gate and up matrices in one laid out [gate | up];
``weights_from_program`` joins the first and splits the second where it is
used.
"""
import math

import jax
import jax.numpy as jnp

_PRECISION = "highest"   # a float32 matmul on the TPU is one bf16 pass otherwise


def weights_from_program(params) -> dict:
    """The program's parameter tree (flax names of ``GPTModel`` in its
    deepseek_v2 kinds) as the plain names used here.  Arrays are shared,
    not copied, except the joined shared experts."""
    p = params["params"]
    blocks = []
    i = 0
    while f"h{i}" in p:
        b = p[f"h{i}"]
        attn, mlp = b["attn"], b["mlp"]
        block = {
            "n1": b["ln1"]["scale"], "n2": b["ln2"]["scale"],
            "w_q_a": attn["q_a"]["kernel"], "n_q": attn["q_a_norm"]["scale"],
            "w_q_b": attn["q_b"]["kernel"],
            "w_kv_a": attn["kv_a"]["kernel"],
            "n_kv": attn["kv_a_norm"]["scale"], "w_kv_b": attn["kv_b"],
            "w_o": attn["out"]["kernel"],
        }
        if "router" in mlp:
            shared = [mlp[k] for k in sorted(mlp) if k.startswith("shared")]
            block.update(
                w_r=mlp["router"]["kernel"],
                w_gate_up=mlp["w_gate_up"], w_down=mlp["w_down"],
                s_gate=jnp.concatenate(
                    [s["gate"]["kernel"] for s in shared], axis=1),
                s_up=jnp.concatenate(
                    [s["up"]["kernel"] for s in shared], axis=1),
                s_down=jnp.concatenate(
                    [s["down"]["kernel"] for s in shared], axis=0))
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


def mscale(factor: float, m: float) -> float:
    return 1.0 if factor <= 1 else 0.1 * m * math.log(factor) + 1.0


def yarn_frequencies(dr: int, theta: float, scaling: dict):
    """(inv_freq (dr / 2,), low, high) of the module docstring; ``scaling``
    is the configuration's ``rope_scaling`` (None: plain frequencies)."""
    f = [theta ** (-2.0 * i / dr) for i in range(dr // 2)]
    if not scaling:
        return jnp.asarray(f, jnp.float32), None, None

    def pair(turns):
        return dr * math.log(scaling["original_max_position_embeddings"] /
                             (2 * math.pi * turns)) / (2 * math.log(theta))

    low = max(math.floor(pair(scaling["beta_fast"])), 0)
    high = min(math.ceil(pair(scaling["beta_slow"])), dr - 1)
    if high == low:
        high += 0.001       # as the modelling code does: no division by 0
    out = []
    for i, f_i in enumerate(f):
        ramp = min(max((i - low) / (high - low), 0.0), 1.0)
        out.append(f_i * (1 - ramp) + f_i / scaling["factor"] * ramp)
    return jnp.asarray(out, jnp.float32), low, high


def softmax_scale(dn: int, dr: int, scaling: dict) -> float:
    scale = (dn + dr) ** -0.5
    if scaling:
        scale *= mscale(scaling["factor"], scaling["mscale_all_dim"]) ** 2
    return scale


def rotate(x, theta, scaling):
    """x (S, heads, dr) at positions 0..S-1: the pairs (2i, 2i + 1) turned
    in place."""
    s, _, dr = x.shape
    inv_freq, _, _ = yarn_frequencies(dr, theta, scaling)
    angles = jnp.arange(s, dtype=jnp.float32)[:, None] * inv_freq
    m = 1.0
    if scaling:
        m = mscale(scaling["factor"], scaling["mscale"]) / \
            mscale(scaling["factor"], scaling["mscale_all_dim"])
    cos, sin = (m * jnp.cos(angles))[:, None, :], \
        (m * jnp.sin(angles))[:, None, :]
    even, odd = x[..., 0::2], x[..., 1::2]
    return jnp.stack([even * cos - odd * sin, odd * cos + even * sin],
                     axis=-1).reshape(x.shape)


def attention(x, b, heads, dn, dr, dv, eps, theta, scaling, block,
              head_block):
    """``x + attn(n1(x))`` of one sequence ``x`` (S, hidden): the heads in
    groups of ``head_block`` one after another (each group's queries, keys
    and values made from the latents, its part of ``Wo`` applied and
    added), the queries in blocks of ``block`` against all keys.
    ``scaling`` is a tuple of the ``rope_scaling`` items (hashable for
    jit) or None."""
    scaling = dict(scaling) if scaling else None
    with jax.default_matmul_precision(_PRECISION):
        b = _f32(b)
        s = x.shape[0]
        h = rms(x, b["n1"], eps)
        c_q = rms(h @ b["w_q_a"], b["n_q"], eps)
        kv_a = h @ b["w_kv_a"]
        rank = kv_a.shape[1] - dr
        c = rms(kv_a[:, :rank], b["n_kv"], eps)
        k_pe = rotate(kv_a[:, None, rank:], theta, scaling)   # one key
        scale = softmax_scale(dn, dr, scaling)
        k_pos = jnp.arange(s)[None, :]
        q_positions = jnp.arange(s).reshape(s // block, block)
        hb = head_block

        def one_group(y, args):
            w_q, w_kv, w_o = args    # (., hb (dn+dr)), (., hb (dn+dv)), ..
            q = (c_q @ w_q).reshape(s, hb, dn + dr)
            kv = (c @ w_kv).reshape(s, hb, dn + dv)
            q = jnp.concatenate(
                [q[..., :dn], rotate(q[..., dn:], theta, scaling)], -1)
            # every head's key: its own k_nope beside the shared k_pe
            k = jnp.concatenate(
                [kv[..., :dn], jnp.broadcast_to(k_pe, (s, hb, dr))], -1)
            v = kv[..., dn:]

            def one_block(args):
                qb, q_pos = args                 # (T, hb, d), (T,)
                scores = jnp.einsum("qhd,khd->hqk", qb, k) * scale
                seen = k_pos <= q_pos[:, None]
                probs = jax.nn.softmax(
                    jnp.where(seen[None], scores, -jnp.inf), -1)
                return jnp.einsum("hqk,khd->qhd", probs, v)

            out = jax.lax.map(one_block, (
                q.reshape(s // block, block, hb, dn + dr),
                q_positions)).reshape(s, hb * dv)
            return y + out @ w_o, None

        groups = heads // hb
        attn, _ = jax.lax.scan(one_group, jnp.zeros_like(x), (
            b["w_q_b"].reshape(-1, groups, hb * (dn + dr)).swapaxes(0, 1),
            b["w_kv_b"].reshape(-1, groups, hb * (dn + dv)).swapaxes(0, 1),
            b["w_o"].reshape(groups, hb * dv, -1)))
        return x + attn


def dense_mlp(x, b, eps):
    """``x + mlp(n2(x))`` of a dense layer."""
    with jax.default_matmul_precision(_PRECISION):
        b = _f32(b)
        h = rms(x, b["n2"], eps)
        return x + (jax.nn.silu(h @ b["d_gate"]) * (h @ b["d_up"])) @ \
            b["d_down"]


def route(h, w_r, k, n_group, topk_group, norm_topk_prob, scale):
    """(S, E) routing weights, zero but for each token's ``k`` experts,
    and the (S, k) experts chosen, largest first: the best groups one
    after another, then k times the largest of what is left inside them
    (no sort)."""
    scores = jax.nn.softmax(h @ w_r, axis=-1)
    s, e = scores.shape
    rows = jnp.arange(s)
    best = scores.reshape(s, n_group, e // n_group).max(-1)
    allowed = jnp.zeros((s, n_group), bool)
    for _ in range(topk_group):
        g = jnp.argmax(best, axis=-1)
        allowed = allowed.at[rows, g].set(True)
        best = best.at[rows, g].set(-jnp.inf)
    left = jnp.where(jnp.repeat(allowed, e // n_group, axis=1), scores, 0.0)
    chosen = []
    for _ in range(k):
        pick = jnp.argmax(left, axis=-1)
        chosen.append(pick)
        left = left.at[rows, pick].set(-jnp.inf)
    weights = jnp.where(jnp.isinf(left), scores, 0.0)
    if norm_topk_prob:
        weights = weights / (weights.sum(-1, keepdims=True) + 1e-20)
    return weights * scale, jnp.stack(chosen, -1)


def experts(x, b, k, n_group, topk_group, norm_topk_prob, scale, first,
            eps):
    """``x + shared(h) + routed(h)`` of one sequence, h = n2(x), with the
    routed experts the weights hold (the layer's experts ``first`` ..),
    and the (S, k) experts its router chose among ALL of them.  Expert
    after expert: each is applied to all tokens and its result added with
    the tokens' routing weights for it."""
    with jax.default_matmul_precision(_PRECISION):
        experts_w = (b["w_gate_up"], b["w_down"])
        b = _f32({name: a for name, a in b.items()
                  if name not in ("w_gate_up", "w_down")})
        h = rms(x, b["n2"], eps)
        weights, chosen = route(h, b["w_r"], k, n_group, topk_group,
                                norm_topk_prob, scale)
        held = experts_w[1].shape[0]

        def one_expert(y, args):
            w_gate_up, w_down, w_e = _f32(args)   # (H, 2W), (W, H), (S,)
            width = w_down.shape[0]
            gate_up = h @ w_gate_up
            out = (jax.nn.silu(gate_up[:, :width]) *
                   gate_up[:, width:]) @ w_down
            return y + out * w_e[:, None], None

        routed, _ = jax.lax.scan(
            one_expert, jnp.zeros_like(h),
            experts_w + (weights[:, first:first + held].T,))
        shared = (jax.nn.silu(h @ b["s_gate"]) * (h @ b["s_up"])) @ \
            b["s_down"]
        return x + shared + routed, chosen


def head(x, wf, w_head, eps):
    with jax.default_matmul_precision(_PRECISION):
        return rms(x, jnp.asarray(wf, jnp.float32), eps) @ \
            jnp.asarray(w_head, jnp.float32)


class Reference:
    """The reference bound to one configuration: ``num_attention_heads``,
    ``qk_nope_head_dim``, ``qk_rope_head_dim``, ``v_head_dim``,
    ``rms_norm_eps``, ``rope_theta``, ``rope_scaling`` (the configuration's
    dict, or None), ``num_experts_per_tok``, ``n_group``, ``topk_group``,
    ``norm_topk_prob``, ``routed_scaling_factor``, ``experts_first`` (the
    layer's expert that the weights' first is), ``query_block`` (how many
    queries meet all keys at once) and ``head_block`` (how many heads are
    expanded at once).  Each piece is jitted by itself
    and called layer after layer; weights are arguments, never
    constants."""

    def __init__(self, settings: dict):
        self.s = settings
        self._attention = jax.jit(attention,
                                  static_argnums=tuple(range(2, 11)))
        self._dense = jax.jit(dense_mlp, static_argnums=(2,))
        self._experts = jax.jit(experts, static_argnums=tuple(range(2, 9)))
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
        n = x.shape[0]
        scaling = s["rope_scaling"]
        scaling = tuple(sorted(scaling.items())) if scaling else None
        chosen = []
        for b in w["blocks"]:
            x = self._attention(
                x, b, s["num_attention_heads"], s["qk_nope_head_dim"],
                s["qk_rope_head_dim"], s["v_head_dim"], s["rms_norm_eps"],
                float(s["rope_theta"]), scaling,
                self._block_of(n, s["query_block"]),
                self._block_of(s["num_attention_heads"], s["head_block"]))
            if "w_r" in b:
                x, what = self._experts(
                    x, b, s["num_experts_per_tok"], s["n_group"],
                    s["topk_group"], s["norm_topk_prob"],
                    float(s["routed_scaling_factor"]), s["experts_first"],
                    s["rms_norm_eps"])
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
