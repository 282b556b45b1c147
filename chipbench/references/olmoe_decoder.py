"""Plain reference of the OLMoE decoder (Muennighoff et al., "OLMoE: Open
Mixture-of-Experts Language Models", 2024; ``allenai/OLMoE-1B-7B-0125-
Instruct`` ``config.json``, ``model_type`` ``olmoe``): the forward pass, the
next-token loss and the routers' load-balancing term in straightforward
``jax.numpy``, float32, full matmul precision.  No sort, no grouped matmul,
no kernel, no cache, no sharding: every expert is applied to every token
and the results are summed with the routing weights, which are zero for
the experts a token did not choose.  One sequence at a time, the experts
over blocks of tokens so that it fits beside a train state.

Written from the published description and Hugging Face's ``OlmoeModel``,
not from the program's model file:

* ``x = E[ids]``; per layer ``x += attn(rms(x; w1))``, ``x += moe(rms(x;
  w2))``; ``logits = rms(x; wf) Whead`` (an untied head).
* ``rms(x; w) = w * x / sqrt(mean(x^2) + eps)``.
* attention: ``q, k, v = h Wq, h Wk, h Wv`` without bias; ``q`` and ``k``
  are RMS-normalised over the whole hidden vector (before the heads are
  split), then rotated: channel i of a head pairs with channel i + d/2 at
  the angle ``position * theta^(-2i/d)`` (rotate-half); causal softmax of
  ``q k^T / sqrt(d)``; an output projection without bias.
* experts: ``p = softmax(h Wr)`` over all experts; the ``k`` largest
  ``p_i`` with their experts ``e_i``, not renormalised unless
  ``norm_topk_prob``; ``y = sum_i p_i Wdown[e_i] (silu(Wgate[e_i] h) *
  (Wup[e_i] h))``.  Ties go to the expert of lower index.
* loss: mean cross-entropy plus ``router_aux_loss_coef`` times Hugging
  Face's ``load_balancing_loss_func``: the tokens of all layers pooled,
  ``E * sum_e (token-slots that chose e / tokens) * (mean p_e)``.

Departures from the paper, none from the published model: no router z-loss
(the paper trains with one at 0.001; the published ``config.json`` and the
Hugging Face model carry none); dropout 0; weights are random, from the
benchmark's seed.

The program keeps the three attention projections in one matrix laid out
[q | k | v]; ``weights_from_program`` splits it.
"""
import math

import jax
import jax.numpy as jnp

_PRECISION = "highest"   # a float32 matmul on the TPU is one bf16 pass otherwise


def weights_from_program(params) -> dict:
    """The program's parameter tree (flax names of ``GPTModel`` in its
    OLMoE kinds) as the plain names used here.  Arrays are shared, not
    copied, except the three slices of the fused projection."""
    p = params["params"]
    blocks = []
    i = 0
    while f"h{i}" in p:
        b = p[f"h{i}"]
        w_q, w_k, w_v = jnp.split(b["attn"]["qkv"]["kernel"], 3, axis=-1)
        blocks.append({
            "w1": b["ln1"]["scale"], "w_q": w_q, "w_k": w_k, "w_v": w_v,
            "wq_n": b["attn"]["q_norm"]["scale"],
            "wk_n": b["attn"]["k_norm"]["scale"],
            "w_o": b["attn"]["out"]["kernel"],
            "w2": b["ln2"]["scale"],
            "w_r": b["mlp"]["router"]["kernel"],
            "w_gate": b["mlp"]["w_gate"], "w_up": b["mlp"]["w_up"],
            "w_down": b["mlp"]["w_down"],
        })
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


def attention(x, b, num_heads, eps, theta):
    """``x + attn(rms(x))`` of one sequence ``x`` (S, H)."""
    with jax.default_matmul_precision(_PRECISION):
        b = _f32(b)
        s, h = x.shape
        d = h // num_heads
        y = rms(x, b["w1"], eps)
        q = rms(y @ b["w_q"], b["wq_n"], eps).reshape(s, num_heads, d)
        k = rms(y @ b["w_k"], b["wk_n"], eps).reshape(s, num_heads, d)
        v = (y @ b["w_v"]).reshape(s, num_heads, d)
        q, k = rotate(q, theta), rotate(k, theta)
        scores = jnp.einsum("qhd,khd->hqk", q, k) / math.sqrt(d)
        causal = jnp.tril(jnp.ones((s, s), bool))
        probs = jax.nn.softmax(jnp.where(causal[None], scores, -jnp.inf), -1)
        out = jnp.einsum("hqk,khd->qhd", probs, v).reshape(s, h)
        return x + out @ b["w_o"]


def route(h, w_r, k, norm_topk_prob):
    """(S, E) routing weights, zero but for each token's ``k`` largest
    probabilities, and the (S, k) experts chosen, largest first: k times
    the largest of what is left (no sort)."""
    probs = jax.nn.softmax(h @ w_r, axis=-1)
    left, chosen = probs, []
    for _ in range(k):
        best = jnp.argmax(left, axis=-1)
        chosen.append(best)
        left = left.at[jnp.arange(h.shape[0]), best].set(-1.0)
    weights = jnp.where(left < 0, probs, 0.0)
    if norm_topk_prob:
        weights = weights / weights.sum(-1, keepdims=True)
    return weights, jnp.stack(chosen, -1), probs


def experts(x, b, k, norm_topk_prob, eps, block):
    """``x + moe(rms(x))`` of one sequence, and what its router did:
    (x, experts (S, k), probability sums (E,), counts (E,))."""
    with jax.default_matmul_precision(_PRECISION):
        b = _f32(b)
        s, hdim = x.shape
        h = rms(x, b["w2"], eps)
        weights, chosen, probs = route(h, b["w_r"], k, norm_topk_prob)

        def one_block(args):
            hb, wb = args                               # (T, H), (T, E)
            gate = jnp.einsum("th,ehi->eti", hb, b["w_gate"])
            up = jnp.einsum("th,ehi->eti", hb, b["w_up"])
            out = jnp.einsum("eti,eih->eth", jax.nn.silu(gate) * up,
                             b["w_down"])
            return jnp.einsum("eth,te->th", out, wb)

        y = jax.lax.map(one_block, (h.reshape(s // block, block, hdim),
                                    weights.reshape(s // block, block, -1)))
        counts = (weights > 0).sum(0)
        return x + y.reshape(s, hdim), chosen, probs.sum(0), counts


def head(x, wf, w_head, eps):
    with jax.default_matmul_precision(_PRECISION):
        return rms(x, jnp.asarray(wf, jnp.float32), eps) @ \
            jnp.asarray(w_head, jnp.float32)


def token_losses(logits, labels):
    """Cross-entropy of each position's label: logsumexp minus its logit."""
    lse = jax.nn.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, labels[:, None], axis=-1)[:, 0]
    return lse - gold


class Reference:
    """The reference bound to one configuration: ``num_heads``,
    ``rms_norm_eps``, ``rope_theta``, ``num_experts_per_tok``,
    ``norm_topk_prob``, ``router_aux_loss_coef`` and ``token_block`` (how
    many tokens meet all experts at once).  Each piece is jitted by itself
    and called layer after layer; weights are arguments, never constants."""

    def __init__(self, settings: dict):
        self.s = settings
        self._attention = jax.jit(attention, static_argnums=(2, 3, 4))
        self._experts = jax.jit(experts, static_argnums=(2, 3, 4, 5))
        self._head = jax.jit(head, static_argnums=3)
        self._losses = jax.jit(token_losses)

    def _block_of(self, n_tokens: int) -> int:
        block = min(self.s["token_block"], n_tokens)
        while n_tokens % block:
            block -= 1
        return block

    def hidden(self, w: dict, ids):
        """(S,) ids -> the last hidden states (S, H) and, per layer, what
        the router did."""
        s = self.s
        x = jnp.asarray(w["wte"], jnp.float32)[jnp.asarray(ids, jnp.int32)]
        routing = []
        for b in w["blocks"]:
            x = self._attention(x, b, s["num_heads"], s["rms_norm_eps"],
                                s["rope_theta"])
            x, *what = self._experts(
                x, b, s["num_experts_per_tok"], s["norm_topk_prob"],
                s["rms_norm_eps"], self._block_of(x.shape[0]))
            routing.append(what)
        return x, routing

    def logits(self, w: dict, ids, rows=None):
        """(S,) token ids -> (S, V) float32 logits; with ``rows`` =
        (first, count) only those positions' logits, (count, V)."""
        x, _ = self.hidden(w, ids)
        if rows is not None:
            x = jax.lax.dynamic_slice_in_dim(x, rows[0], rows[1], axis=0)
        return self._head(x, w["wf"], w["w_head"], self.s["rms_norm_eps"])

    def position_losses(self, w: dict, ids, labels):
        """One sequence: the loss of every position (S,), the experts of
        every token in every layer (L, S, k), and the routers' sums."""
        x, routing = self.hidden(w, ids)
        logits = self._head(x, w["wf"], w["w_head"], self.s["rms_norm_eps"])
        losses = self._losses(logits, jnp.asarray(labels, jnp.int32))
        chosen = jnp.stack([r[0] for r in routing])
        prob_sums = jnp.stack([r[1] for r in routing])
        counts = jnp.stack([r[2] for r in routing])
        return losses, chosen, prob_sums, counts

    def batch_losses(self, w: dict, input_ids, labels) -> tuple:
        """A (B, S) batch, sequence by sequence: (its loss as ``lm_loss``
        gives it, every position's loss (B, S), every token's experts
        (L, B, S, k))."""
        losses, chosen, prob_sums, counts = zip(*(
            self.position_losses(w, ids, lab)
            for ids, lab in zip(input_ids, labels)))
        losses, prob_sums, counts = (jnp.stack(losses), sum(prob_sums),
                                     sum(counts))
        n_rows = prob_sums.shape[0] * losses.size       # layers x tokens
        n_experts = prob_sums.shape[-1]
        balance = n_experts * float(
            ((counts.sum(0) / n_rows) * (prob_sums.sum(0) / n_rows)).sum())
        loss = float(losses.mean()) + \
            self.s["router_aux_loss_coef"] * balance
        return loss, losses, jnp.stack(chosen, 1)

    def lm_loss(self, w: dict, input_ids, labels) -> float:
        """Mean next-token loss of a (B, S) batch plus the load-balancing
        term over all its tokens and layers."""
        return self.batch_losses(w, input_ids, labels)[0]
