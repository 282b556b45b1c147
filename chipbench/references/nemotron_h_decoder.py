"""Plain reference of the Nemotron-H decoder
(``nvidia/NVIDIA-Nemotron-3-Nano-30B-A3B-BF16`` ``config.json``,
``model_type`` ``nemotron_h``): the forward pass in straightforward
``jax.numpy``, float32, full matmul precision.  No cache, no state carried
between calls, no chunked scan, no sort, no grouped matmul, no kernel, no
batching: one sequence at a time, the Mamba-2 recurrence as the plain loop
over positions (``lax.scan``), the convolution as the sum over its shifted
copies of the whole sequence, every query attending over the whole
sequence under a mask, and every held expert applied to every token with a
routing weight that is zero for the experts the token did not choose.
Queries go in blocks and the experts one after another, so that 8,192
positions fit beside the served model.

Written from the published ``config.json`` (the sizes) and the family's
report and public modelling code (``modeling_nemotron_h.py``: the wiring,
marked (*) where the configuration does not fix it), not from the
program's model file.  With ``eps`` = ``layer_norm_epsilon`` and ``n(x; w)
= w * x / sqrt(mean(x^2) + eps)``:

* ``x = E[ids]``; layer i is ``x = x + f_i(n_i(x))`` with ONE norm and ONE
  ``f`` a layer, by letter i of ``hybrid_override_pattern``; ``logits =
  n_f(x) W_head`` (one final norm, an untied head).  No bias anywhere but
  the convolution's.
* ``M``, Mamba-2 (H = ``mamba_num_heads`` heads of P = ``mamba_head_dim``
  channels; N = ``ssm_state_size``; G = ``n_groups`` groups of H / G
  consecutive heads; L = ``conv_kernel`` taps): ``[z | xBC | dt] = u W_in``
  of widths H P, H P + 2 G N, H, in that order; ``xBC_t = silu(sum_{j<L}
  k[j] * xBC_{t-(L-1)+j} + b)`` with zeros before the sequence (depthwise,
  causal); ``[x | B | C] = xBC``; ``dt = softplus(dt + dt_bias)`` a head,
  with no clamp above (*: ``time_step_min`` / ``max`` / ``floor`` only
  shape ``dt_bias``'s initial values); ``A = -exp(A_log)`` a head; for head
  h of group g, from ``S = 0``: ``S_t = exp(dt_t A) S_{t-1} + dt_t x_t
  B_t^T`` (P x N) and ``y_t = S_t C_t + D_h x_t``; ``y = n_groups(y *
  silu(z); w)``: the gate FIRST, then the norm over each of the G groups
  of H P / G channels, one weight vector of H P (*); ``y W_out``.
* ``*``, attention: ``q = u Wq`` (heads of ``head_dim``), ``k = u Wk``, ``v
  = u Wv`` (fewer heads: query head i reads key/value head i // (heads /
  kv heads)); scores ``q k^T / sqrt(head_dim)``, causal, softmax; ``Wo``.
  NO positions of any kind (*: the family's code applies none, the Mamba
  layers carry order; ``rope_theta`` and ``partial_rotary_factor`` are
  read by nothing).
* ``E``, experts: ``s = sigmoid(u Wr)``, ``n_routed_experts`` wide; the k
  experts are the k largest of ``s + b`` (``e_score_correction_bias``; one
  group: ``n_group`` = ``topk_group`` = 1), ties to the lower index; their
  weights ``s_i / (sum of the chosen s + 1e-20)`` (``norm_topk_prob``)
  times ``routed_scaling_factor``, the bias not in them; an expert is
  ``relu(u W_up)^2 W_down``, no gate; one shared expert of the same form
  and a width of its own on every token, added to the routed sum.

Departures from the published model, none in the mathematics: weights are
random, from the benchmark's seed; ``b`` is set by load at set-up (a
trained model's is whatever load balancing left it at).  Where the program
holds a share of a layer's experts (``experts_first`` and the held
experts' matrices), this reference is given the same share: the router
stays at its published width and the picks of an absent expert add
nothing, in both.

The program keeps q, k and v in one matrix laid out [q | k | v], the taps
as (L, channels), tap j a row, and an expert's ``W_up`` as its transpose
(width x hidden, the order ``W_down`` has); ``weights_from_program`` splits
the first and hands on the others as they are.
"""
import math

import jax
import jax.numpy as jnp

_PRECISION = "highest"   # a float32 matmul on the TPU is one bf16 pass otherwise
ROUTE_EPS = 1e-20        # the published renormalisation's constant


def weights_from_program(params) -> dict:
    """The program's parameter tree (flax names of ``GPTModel`` in its
    nemotron_h kinds) as the plain names used here.  Arrays are shared, not
    copied, except the three slices of the fused projection."""
    p = params["params"]
    blocks = []
    i = 0
    while f"h{i}" in p:
        b = p[f"h{i}"]
        if "ssm" in b:
            m = b["ssm"]
            block = {"kind": "M", "n": b["ln1"]["scale"],
                     "w_in": m["in_proj"]["kernel"],
                     "taps": m["conv_kernel"], "conv_b": m["conv_bias"],
                     "dt_bias": m["dt_bias"], "a_log": m["A_log"],
                     "d": m["D"], "w_norm": m["norm"],
                     "w_out": m["out_proj"]["kernel"]}
        elif "attn" in b:
            attn = b["attn"]
            n_q = attn["out"]["kernel"].shape[0]
            n_kv = (attn["qkv"]["kernel"].shape[1] - n_q) // 2
            w_q, w_k, w_v = jnp.split(attn["qkv"]["kernel"],
                                      [n_q, n_q + n_kv], axis=-1)
            block = {"kind": "*", "n": b["ln1"]["scale"], "w_q": w_q,
                     "w_k": w_k, "w_v": w_v, "w_o": attn["out"]["kernel"]}
        elif "router" in b.get("mlp", {}):
            mlp = b["mlp"]
            block = {"kind": "E", "n": b["ln2"]["scale"],
                     "w_r": mlp["router"]["kernel"],
                     "b_r": mlp["router_bias"], "w_up": mlp["w_up"],
                     "w_down": mlp["w_down"]}
            if "shared0" in mlp:
                block.update(s_up=mlp["shared0"]["fc_in"]["kernel"],
                             s_down=mlp["shared0"]["fc_out"]["kernel"])
        else:
            raise ValueError(f"layer {i} is no Mamba-2 mixer, attention or "
                             f"routed experts: {sorted(b)}")
        blocks.append(block)
        i += 1
    return {"wte": p["wte"]["embedding"], "blocks": blocks,
            "wf": p["ln_f"]["scale"], "w_head": p["lm_head"]["kernel"]}


def _f32(tree):
    return jax.tree_util.tree_map(lambda a: jnp.asarray(a, jnp.float32), tree)


def rms(x, w, eps):
    return w * x / jnp.sqrt((x * x).mean(-1, keepdims=True) + eps)


def relu2(x):
    return jnp.square(jnp.maximum(x, 0.0))


def shifted(g, by: int):
    """``g`` (S, c) moved ``by`` positions later, zeros in front."""
    if by == 0:
        return g
    return jnp.concatenate([jnp.zeros_like(g[:by]), g[:-by]], axis=0)


def mamba(x, b, heads, groups, eps, at):
    """``x + mamba2(n(x))`` of an ``M`` layer, one sequence ``x`` (S, h):
    the recurrence as the loop over positions, from a zero state; and the
    state ``S`` of every head as it stands after ``at[k]`` positions, (K,
    H, P, N), for every entry of ``at`` (K,) int32."""
    with jax.default_matmul_precision(_PRECISION):
        b = _f32(b)
        s = x.shape[0]
        inner = b["w_norm"].shape[0]
        p = inner // heads
        n = (b["taps"].shape[1] - inner) // (2 * groups)
        u = rms(x, b["n"], eps)
        z, xbc, dt = jnp.split(u @ b["w_in"],
                               [inner, inner + b["taps"].shape[1]], axis=-1)
        taps = b["taps"]                                  # (L, channels)
        n_taps = taps.shape[0]
        # tap j meets xBC at t - (L - 1) + j: the copy moved L - 1 - j later
        xbc = jax.nn.silu(sum(taps[j] * shifted(xbc, n_taps - 1 - j)
                              for j in range(n_taps)) + b["conv_b"])
        xs, bs, cs = jnp.split(xbc, [inner, inner + groups * n], axis=-1)
        xs = xs.reshape(s, heads, p)
        # every head beside its own group's B and C
        bs = jnp.repeat(bs.reshape(s, groups, n), heads // groups, axis=1)
        cs = jnp.repeat(cs.reshape(s, groups, n), heads // groups, axis=1)
        dt = jax.nn.softplus(dt + b["dt_bias"])           # (S, H)
        a = -jnp.exp(b["a_log"])                          # (H,)

        def position(carry, inputs):
            state, kept = carry
            t, x_t, b_t, c_t, dt_t = inputs   # (H, P), (H, N), (H, N), (H,)
            state = jnp.exp(dt_t * a)[:, None, None] * state + \
                (dt_t[:, None] * x_t)[:, :, None] * b_t[:, None, :]
            # (the state after t + 1 positions, where one was asked for)
            kept = jnp.where((at == t + 1)[:, None, None, None], state, kept)
            return (state, kept), (state * c_t[:, None, :]).sum(-1)

        zeros = jnp.zeros((heads, p, n), jnp.float32)
        (_, kept), y = jax.lax.scan(
            position, (zeros, jnp.zeros(at.shape + zeros.shape)),
            (jnp.arange(s), xs, bs, cs, dt))
        y = (y + b["d"][:, None] * xs).reshape(s, inner)
        # the gate first, then the norm a group
        y = (y * jax.nn.silu(z)).reshape(s, groups, inner // groups)
        y = y / jnp.sqrt((y * y).mean(-1, keepdims=True) + eps)
        return x + (y.reshape(s, inner) * b["w_norm"]) @ b["w_out"], kept


def attention(x, b, d, eps, block):
    """``x + attn(n(x))`` of one sequence ``x`` (S, h) with heads of ``d``
    channels and no positions; the queries in blocks of ``block`` against
    all keys."""
    with jax.default_matmul_precision(_PRECISION):
        b = _f32(b)
        s = x.shape[0]
        u = rms(x, b["n"], eps)
        q = (u @ b["w_q"]).reshape(s, -1, d)
        k = (u @ b["w_k"]).reshape(s, -1, d)
        v = (u @ b["w_v"]).reshape(s, -1, d)
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


def experts(x, b, k, norm_topk_prob, scale, first, eps):
    """``x + routed(n(x)) + shared(n(x))`` of one sequence and the (S, k)
    experts its router chose.  ``b["w_up"]`` holds the experts ``first ..
    first + held - 1`` of the router's; expert after expert, each applied
    to all tokens and added with the tokens' routing weights for it."""
    with jax.default_matmul_precision(_PRECISION):
        experts_w = (b["w_up"], b["w_down"])
        held = experts_w[0].shape[0]
        b = _f32({name: a for name, a in b.items()
                  if name not in ("w_up", "w_down")})
        u = rms(x, b["n"], eps)
        weights, chosen = route(u, b["w_r"], b["b_r"], k, norm_topk_prob,
                                scale)

        def one_expert(y, args):
            w_up, w_down, w_e = _f32(args)        # (W, h), (W, h), (S,)
            return y + (relu2(u @ w_up.T) @ w_down) * w_e[:, None], None

        routed, _ = jax.lax.scan(
            one_expert, jnp.zeros_like(u),
            experts_w + (weights[:, first:first + held].T,))
        if "s_up" in b:
            routed = routed + relu2(u @ b["s_up"]) @ b["s_down"]
        return x + routed, chosen


def head(x, wf, w_head, eps):
    """``n_f(x) W_head``: the final norm and the untied head."""
    with jax.default_matmul_precision(_PRECISION):
        return rms(x, jnp.asarray(wf, jnp.float32), eps) @ \
            jnp.asarray(w_head, jnp.float32)


class Reference:
    """The reference bound to one configuration: ``mamba_heads``,
    ``mamba_groups``, ``head_dim`` (attention), ``eps``,
    ``num_experts_per_tok``, ``norm_topk_prob``, ``routed_scaling_factor``,
    ``experts_first`` (the first of the router's experts whose matrices the
    weights hold) and ``query_block`` (how many queries meet all keys at
    once).  What a layer is comes with the weights.  Each piece is jitted by
    itself and called layer after layer; weights are arguments, never
    constants."""

    def __init__(self, settings: dict):
        self.s = settings
        self._mamba = jax.jit(mamba, static_argnums=(2, 3, 4))
        self._attention = jax.jit(attention, static_argnums=(2, 3, 4))
        self._experts = jax.jit(experts, static_argnums=(2, 3, 4, 5, 6))
        self._head = jax.jit(head, static_argnums=3)

    @staticmethod
    def _block_of(n: int, block: int) -> int:
        block = min(block, n)
        while n % block:
            block -= 1
        return block

    def hidden(self, w: dict, ids, at=()):
        """(S,) ids -> the last hidden states (S, h), per expert layer
        every token's experts (S, k), and per ``M`` layer the heads' states
        after ``at[k]`` positions, (K, H, P, N)."""
        s = self.s
        x = jnp.asarray(w["wte"][jnp.asarray(ids, jnp.int32)], jnp.float32)
        n = x.shape[0]
        at = jnp.asarray(at, jnp.int32).reshape(-1)
        chosen, states = [], []
        for b in w["blocks"]:
            kind = b["kind"]
            b = {name: a for name, a in b.items() if name != "kind"}
            if kind == "M":
                x, kept = self._mamba(x, b, s["mamba_heads"],
                                      s["mamba_groups"], s["eps"], at)
                states.append(kept)
            elif kind == "*":
                x = self._attention(x, b, s["head_dim"], s["eps"],
                                    self._block_of(n, s["query_block"]))
            else:
                x, what = self._experts(
                    x, b, s["num_experts_per_tok"], s["norm_topk_prob"],
                    s["routed_scaling_factor"], s["experts_first"], s["eps"])
                chosen.append(what)
        return x, chosen, states

    def logits(self, w: dict, ids, rows=None):
        """(S,) token ids -> (S, V) float32 logits; with ``rows`` =
        (first, count) only those positions' logits, (count, V)."""
        x, _, _ = self.hidden(w, ids)
        if rows is not None:
            x = jax.lax.dynamic_slice_in_dim(x, rows[0], rows[1], axis=0)
        return self._head(x, w["wf"], w["w_head"], self.s["eps"])

    def logits_experts_and_states(self, w: dict, ids, rows, at=()):
        """``logits(rows=...)``, the experts of those positions in every
        expert layer, (layers, count, k), and the states of every ``M``
        layer's heads after ``at[k]`` positions, (M layers, K, H, P, N)."""
        x, chosen, states = self.hidden(w, ids, at)
        x = jax.lax.dynamic_slice_in_dim(x, rows[0], rows[1], axis=0)
        picked = jnp.stack([jax.lax.dynamic_slice_in_dim(
            c, rows[0], rows[1], axis=0) for c in chosen])
        return (self._head(x, w["wf"], w["w_head"], self.s["eps"]), picked,
                states)

    def logits_and_experts(self, w: dict, ids, rows):
        """``logits_experts_and_states`` without the states."""
        return self.logits_experts_and_states(w, ids, rows)[:2]
