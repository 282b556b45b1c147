"""Plain reference of the LongCat-Flash decoder (``meituan-longcat/
LongCat-Flash-Chat`` ``config.json``; ``model_type`` longcat_flash): the
forward pass in straightforward ``jax.numpy``, float32, full matmul
precision.  No cache, no absorption, no sort, no grouped matmul, no kernel,
no batching: one sequence at a time, latent attention in its expanded
(published) form with every position's per-head keys and values made from
its latent, every query attending over the whole sequence under a mask,
and every held expert applied to every token with a routing weight that is
zero for the experts the token did not choose.  Queries go in blocks, heads
in groups and the experts one after another, each matrix upcast where it is
used, so that 8,192 positions fit beside the served model.

Written from the catalog's row of the published ``config.json`` (the sizes)
and from what the model's public modelling code does where the
configuration does not fix it (marked (*); the configuration file lists
each under ``assumed``), not from the program's model file.  ``h`` is the
stream, ``rms(x; w) = w * x / sqrt(mean(x^2) + eps)``, every norm has a
weight of its own, nothing has a bias but the router's choice:

* ``x = E[ids]``, no scale; a final RMSNorm and an untied head (*).
* one LAYER is two attentions, two dense MLPs and one expert branch that
  leaves the stream after the first attention and rejoins it at the end of
  the layer (shortcut-connected MoE) (*)::

      a1 = h  + MLA_0(rms(h))
      u1 = rms(a1)
      s  = ScMoE(u1)                      # held back
      m1 = a1 + MLP_0(u1)
      a2 = m1 + MLA_1(rms(m1))
      h' = a2 + MLP_1(rms(a2)) + s

* ``MLP(u) = (silu(u Wg) * (u Wu)) Wd`` (*: silu).
* ``MLA(x)``, H heads: ``c_q = rms(x Wq_a)``, ``q = (c_q * f_q) Wq_b`` a head
  ``[q_nope (dn) | q_pe (dr)]``; ``[c | k_pe] = x Wkv_a``, ``c = rms(c) *
  f_kv``, ``k_pe`` ONE key of dr channels a position for all heads, not
  scaled; ``f_q = sqrt(hidden / q_lora_rank)`` where ``mla_scale_q_lora``,
  ``f_kv = sqrt(hidden / kv_lora_rank)`` where ``mla_scale_kv_lora`` (*: the
  factors and where each is applied); ``[k_nope (dn) | v (dv)] = c Wkv_b`` a
  head.  Rotary positions on ``q_pe`` and ``k_pe`` only, on the interleaved
  pairs (2i, 2i + 1) (*), at the plain frequencies ``theta^(-2i/dr)``.
  ``scores = (q_nope . k_nope + q_pe . k_pe) * (dn + dr)^-0.5`` (*), causal,
  softmax; ``out = (probs v) Wo``.
* ``ScMoE(u)``: ``p = softmax(u Wr)`` over ALL the router's outputs, the
  ``n_routed_experts`` experts and the ``zero_expert_num`` identity experts
  after them; the k largest of ``p + b`` are chosen, ``b`` a stored bias
  that enters the choice only (*), ties to the lower index; the weights are
  ``routed_scaling_factor * p[chosen]``, not renormalised (*); ``s = sum
  over the chosen experts j of w_j expert_j(u) + (sum over the chosen
  identity experts of w_j) u`` (``zero_expert_type`` identity), every expert
  a gated SiLU MLP.  No token is dropped.

**The share.**  Where the layer's experts are divided over several chips
the reference is given what one chip holds: the experts from
``experts_first`` on, as many as the weights have, of a router that is
still as wide as the layer; what the absent experts would have added is
left out, and that partial result goes on.  The identity experts hold no
weights, so every chip applies them to its own tokens: they are in every
share.  Given all the experts (``experts_first`` 0) it is the whole layer.
The vocabulary's slice is simply a smaller vocabulary.

Departures from the published model, none in the mathematics of what is
built: weights are random, from the benchmark's seed; dropout 0; the
multi-token-prediction head of the release is not in the catalog's row and
is not built.

The program keeps a published layer as two blocks of its one decoder
definition (``h<2i>``, ``h<2i+1>``), the experts as the first block's
module ``moe`` with gate and up in one matrix laid out [gate | up];
``weights_from_program`` pairs the blocks and the split is made where the
matrix is used.
"""
import math

import jax
import jax.numpy as jnp

_PRECISION = "highest"   # a float32 matmul on the TPU is one bf16 pass otherwise


def _half(block: dict) -> dict:
    """One attention and one dense MLP of the program's block."""
    attn, mlp = block["attn"], block["mlp"]
    return {"n_attn": block["ln1"]["scale"], "n_mlp": block["ln2"]["scale"],
            "w_q_a": attn["q_a"]["kernel"], "n_q": attn["q_a_norm"]["scale"],
            "w_q_b": attn["q_b"]["kernel"], "w_kv_a": attn["kv_a"]["kernel"],
            "n_kv": attn["kv_a_norm"]["scale"], "w_kv_b": attn["kv_b"],
            "w_o": attn["out"]["kernel"], "d_gate": mlp["gate"]["kernel"],
            "d_up": mlp["up"]["kernel"], "d_down": mlp["down"]["kernel"]}


def weights_from_program(params) -> dict:
    """The program's parameter tree (flax names of ``GPTModel`` in its
    longcat_flash kinds) as the plain names used here: ``layers`` a list of
    ``{"first", "second"}`` (``_half``) ``+ {"experts"}`` (the router, its
    bias, the held experts).  Arrays are shared, not copied."""
    p = params["params"]
    layers = []
    i = 0
    while f"h{2 * i}" in p:
        first, second = p[f"h{2 * i}"], p[f"h{2 * i + 1}"]
        moe = first["moe"]
        layers.append({
            "first": _half(first), "second": _half(second),
            "experts": {"w_r": moe["router"]["kernel"],
                        "b_r": moe["router_bias"],
                        "w_gate_up": moe["w_gate_up"],
                        "w_down": moe["w_down"]}})
        i += 1
    return {"wte": p["wte"]["embedding"], "layers": layers,
            "wf": p["ln_f"]["scale"], "w_head": p["lm_head"]["kernel"]}


def _f32(tree):
    """Every weight the reference applies passes through here, where it is
    used."""
    return jax.tree_util.tree_map(lambda a: jnp.asarray(a, jnp.float32), tree)


def rms(x, w, eps):
    return w * x / jnp.sqrt((x * x).mean(-1, keepdims=True) + eps)


def rotate(x, theta):
    """x (S, heads, dr) at positions 0..S-1: the pairs (2i, 2i + 1) turned
    in place by ``position * theta^(-2i/dr)``."""
    s, _, dr = x.shape
    inv_freq = jnp.asarray([theta ** (-2.0 * i / dr) for i in range(dr // 2)],
                           jnp.float32)
    angles = jnp.arange(s, dtype=jnp.float32)[:, None] * inv_freq
    cos, sin = jnp.cos(angles)[:, None, :], jnp.sin(angles)[:, None, :]
    even, odd = x[..., 0::2], x[..., 1::2]
    return jnp.stack([even * cos - odd * sin, odd * cos + even * sin],
                     axis=-1).reshape(x.shape)


def attention(x, b, heads, dn, dr, dv, eps, theta, scale_q, scale_kv, block,
              head_block):
    """``x + MLA(rms(x))`` of one sequence ``x`` (S, hidden): the heads in
    groups of ``head_block`` one after another (each group's queries, keys
    and values made from the latents, its part of ``Wo`` applied and
    added), the queries in blocks of ``block`` against all keys.
    ``scale_q`` / ``scale_kv``: whether the latents carry their factor."""
    with jax.default_matmul_precision(_PRECISION):
        b = _f32({k: b[k] for k in ("n_attn", "w_q_a", "n_q", "w_q_b",
                                    "w_kv_a", "n_kv", "w_kv_b", "w_o")})
        s, hidden = x.shape
        h = rms(x, b["n_attn"], eps)
        c_q = rms(h @ b["w_q_a"], b["n_q"], eps)
        if scale_q:
            c_q = c_q * math.sqrt(hidden / c_q.shape[1])
        kv_a = h @ b["w_kv_a"]
        rank = kv_a.shape[1] - dr
        c = rms(kv_a[:, :rank], b["n_kv"], eps)
        if scale_kv:
            c = c * math.sqrt(hidden / rank)
        k_pe = rotate(kv_a[:, None, rank:], theta)            # one key
        scale = (dn + dr) ** -0.5
        k_pos = jnp.arange(s)[None, :]
        q_positions = jnp.arange(s).reshape(s // block, block)
        hb = head_block

        def one_group(y, args):
            w_q, w_kv, w_o = args    # (., hb (dn+dr)), (., hb (dn+dv)), ..
            q = (c_q @ w_q).reshape(s, hb, dn + dr)
            kv = (c @ w_kv).reshape(s, hb, dn + dv)
            q = jnp.concatenate(
                [q[..., :dn], rotate(q[..., dn:], theta)], -1)
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
    """``MLP(rms(x))`` of one half of a layer and the normed input itself
    (what the first half's experts read)."""
    with jax.default_matmul_precision(_PRECISION):
        u = rms(x, _f32(b["n_mlp"]), eps)
        hidden = jax.nn.silu(u @ _f32(b["d_gate"])) * (u @ _f32(b["d_up"]))
        return hidden @ _f32(b["d_down"]), u


def route(u, w_r, b_r, k, scale):
    """(S, E + Z) routing weights, zero but for each token's ``k`` picks,
    and the (S, k) picks, largest ``p + b`` first: k times the largest of
    what is left (no sort)."""
    probs = jax.nn.softmax(u @ w_r, axis=-1)
    rows = jnp.arange(probs.shape[0])
    left = probs + b_r
    chosen = []
    for _ in range(k):
        pick = jnp.argmax(left, axis=-1)
        chosen.append(pick)
        left = left.at[rows, pick].set(-jnp.inf)
    return jnp.where(jnp.isinf(left), probs, 0.0) * scale, \
        jnp.stack(chosen, -1)


def scmoe(u, b, k, scale, n_experts, first):
    """``ScMoE(u)`` of one sequence's normed stream ``u`` (S, hidden) with
    the routed experts the weights hold (the layer's experts ``first`` ..)
    and the identity experts (the router's outputs from ``n_experts`` on),
    and the (S, k) picks of its router among ALL its outputs.  Expert after
    expert: each is applied to all tokens and its result added with the
    tokens' routing weights for it."""
    with jax.default_matmul_precision(_PRECISION):
        weights, chosen = route(u, _f32(b["w_r"]), _f32(b["b_r"]), k, scale)
        held = b["w_down"].shape[0]

        def one_expert(y, args):
            w_gate_up, w_down, w_e = _f32(args)   # (H, 2W), (W, H), (S,)
            width = w_down.shape[0]
            gate_up = u @ w_gate_up
            out = (jax.nn.silu(gate_up[:, :width]) *
                   gate_up[:, width:]) @ w_down
            return y + out * w_e[:, None], None

        routed, _ = jax.lax.scan(
            one_expert, jnp.zeros_like(u),
            (b["w_gate_up"], b["w_down"],
             weights[:, first:first + held].T))
        identity = weights[:, n_experts:].sum(-1, keepdims=True) * u
        return routed + identity, chosen


def head(x, wf, w_head, eps):
    with jax.default_matmul_precision(_PRECISION):
        return rms(x, _f32(wf), eps) @ _f32(w_head)


class Reference:
    """The reference bound to one configuration: ``num_attention_heads``,
    ``qk_nope_head_dim``, ``qk_rope_head_dim``, ``v_head_dim``,
    ``rms_norm_eps``, ``rope_theta``, ``mla_scale_q_lora``,
    ``mla_scale_kv_lora``, ``moe_topk``, ``routed_scaling_factor``,
    ``n_routed_experts`` (of the whole layer: where the identity experts
    start), ``experts_first`` (the layer's expert that the weights' first
    is), ``query_block`` (how many queries meet all keys at once) and
    ``head_block`` (how many heads are expanded at once).  Each piece is
    jitted by itself and called layer after layer; weights are arguments,
    never constants."""

    def __init__(self, settings: dict):
        self.s = settings
        self._attention = jax.jit(attention,
                                  static_argnums=tuple(range(2, 12)))
        self._dense = jax.jit(dense_mlp, static_argnums=(2,))
        self._scmoe = jax.jit(scmoe, static_argnums=(2, 3, 4, 5))
        self._head = jax.jit(head, static_argnums=3)

    @staticmethod
    def _block_of(n: int, block: int) -> int:
        block = min(block, n)
        while n % block:
            block -= 1
        return block

    def _attend(self, x, b):
        s, n = self.s, x.shape[0]
        return self._attention(
            x, b, s["num_attention_heads"], s["qk_nope_head_dim"],
            s["qk_rope_head_dim"], s["v_head_dim"], s["rms_norm_eps"],
            float(s["rope_theta"]), bool(s["mla_scale_q_lora"]),
            bool(s["mla_scale_kv_lora"]),
            self._block_of(n, s["query_block"]),
            self._block_of(s["num_attention_heads"], s["head_block"]))

    def layer(self, x, layer: dict):
        """One published layer of one sequence: (S, hidden) -> the same,
        and every token's (S, k) picks."""
        s = self.s
        a1 = self._attend(x, layer["first"])
        mlp, u1 = self._dense(a1, layer["first"], s["rms_norm_eps"])
        held_back, chosen = self._scmoe(
            u1, layer["experts"], s["moe_topk"],
            float(s["routed_scaling_factor"]), s["n_routed_experts"],
            s["experts_first"])
        m1 = a1 + mlp
        a2 = self._attend(m1, layer["second"])
        mlp, _ = self._dense(a2, layer["second"], s["rms_norm_eps"])
        return a2 + mlp + held_back, chosen

    def hidden(self, w: dict, ids):
        """(S,) ids -> the last hidden states (S, H) and, per layer, every
        token's picks (S, k)."""
        x = jnp.asarray(w["wte"], jnp.float32)[jnp.asarray(ids, jnp.int32)]
        chosen = []
        for layer in w["layers"]:
            x, what = self.layer(x, layer)
            chosen.append(what)
        return x, chosen

    def logits(self, w: dict, ids, rows=None):
        """(S,) token ids -> (S, V) float32 logits; with ``rows`` =
        (first, count) only those positions' logits, (count, V)."""
        x, _ = self.hidden(w, ids)
        if rows is not None:
            x = jax.lax.dynamic_slice_in_dim(x, rows[0], rows[1], axis=0)
        return self._head(x, w["wf"], w["w_head"], self.s["rms_norm_eps"])

    def logits_and_experts(self, w: dict, ids, rows):
        """``logits(rows=...)`` and the picks of those positions in every
        layer, (layers, count, k)."""
        x, chosen = self.hidden(w, ids)
        x = jax.lax.dynamic_slice_in_dim(x, rows[0], rows[1], axis=0)
        picked = jnp.stack([jax.lax.dynamic_slice_in_dim(
            c, rows[0], rows[1], axis=0) for c in chosen])
        return (self._head(x, w["wf"], w["w_head"],
                           self.s["rms_norm_eps"]), picked)
