"""Plain reference of the SDAR decoder (``JetLM/SDAR-30B-A3B-Chat``
``config.json``, ``model_type`` ``sdar_moe``) and of its generation by
diffusion over blocks: the forward pass in straightforward ``jax.numpy``,
float32, full matmul precision, and the generation loop as a plain Python
loop around it.  No cache, no sort, no grouped matmul, no kernel, no
batching: one sequence at a time, every query attends over the whole
sequence under a mask, every expert is applied to every token with a
routing weight that is zero for the experts the token did not choose, and
every forward of the loop is a whole pass over the prompt and the blocks so
far.  Queries go in blocks (``query_block``) and the experts one after
another, so that 6,144 positions fit beside the served model.

Written from the published ``config.json`` (the sizes) and the public
modelling code the ``model_type`` follows (the Qwen3-MoE block, Hugging
Face ``transformers`` ``models/qwen3_moe/modeling_qwen3_moe.py``: the
wiring, marked (*) where the configuration does not fix it), not from the
program's model file:

* ``x = E[ids]``; a masked position's id is the mask token's, an ordinary
  row of the embedding.
* a block has two RMSNorms: ``x += attn(n1(x))``, ``x += moe(n2(x))``;
  ``rms(x; w) = w * x / sqrt(mean(x^2) + eps)``.
* attention: ``q = h Wq`` (heads of d channels), ``k = h Wk``, ``v = h Wv``
  (fewer heads: query head i reads key/value head i // (heads / kv
  heads)), no bias; q and k are RMS-normalised over the d channels of
  every head (*), one weight vector for all heads, and then rotated in
  every layer (channel i of a head pairs with channel i + d/2 at the angle
  ``position * theta^(-2i/d)``, rotate-half); scores ``q k^T / sqrt(d)``;
  **the mask: a query at position p sees the keys at positions
  ``< (p // L + 1) * L``**, L the block length: its whole block, the
  later positions of it too, and every block before it; softmax;
  ``out = heads Wo``.
* an expert layer: ``s = softmax(h Wr)`` over the experts; the k experts
  are the k largest, ties to the lower index; their weights
  ``s_i / (sum of the chosen s)`` (``norm_topk_prob``);
  ``y = sum_i w_i expert_i(h)``, every expert a gated SiLU MLP.  No bias,
  no shared expert, no scale; no token is dropped.
* a final RMSNorm and an untied head.  **The logits are not shifted**: the
  logit at position p is the model's prediction of the token AT p.

The generation loop (``generate``) follows the release's ``generate.py``
(``block_diffusion_generate``) as its description in the configuration
file's ``assumed`` has it: the sequence is the prompt and masks up to a
multiple of L; block after block, the block's still masked positions take
``x0`` (the argmax) and its confidence ``softmax(logits)[x0]``, and a
forward unmasks, of the ``m`` positions still masked with ``r`` forwards of
the block's budget left, the ``ceil(m / r)`` of highest confidence
(``low_confidence_static``), or every one whose confidence is over the
threshold and at least that many (``low_confidence_dynamic``); ties go to
the lower position; a position that is not masked is never changed.  A
finished block takes one more forward, the commit, which the serving
program needs to store the block's keys and values: without a cache it
changes nothing, and the loop only counts it, so that its forwards number
as the program's do.

Departures from the published description.  (1) A first block that the
prompt's last ``len % L`` tokens head has fewer than L masked positions:
its quota is ``ceil(m / r)`` of what IS masked (the release's
``get_num_transfer_tokens`` is written for full blocks).  (2) Greedy only:
a sampled position would need the program's random stream.  (3) Weights
are random, from the benchmark's seed; dropout 0.

The program keeps q, k and v in one matrix laid out [q | k | v], and the
experts' gate and up matrices in one laid out [gate | up];
``weights_from_program`` splits them.
"""
import math

import jax
import jax.numpy as jnp
import numpy as np

_PRECISION = "highest"   # a float32 matmul on the TPU is one bf16 pass otherwise


def weights_from_program(params) -> dict:
    """The program's parameter tree (flax names of ``GPTModel`` in its
    sdar_moe kinds) as the plain names used here.  Arrays are shared, not
    copied, except the three slices of the fused projection; the experts'
    [gate | up] is split where it is used, an expert at a time."""
    p = params["params"]
    blocks = []
    i = 0
    while f"h{i}" in p:
        b = p[f"h{i}"]
        attn, mlp = b["attn"], b["mlp"]
        n_q = attn["out"]["kernel"].shape[0]
        n_kv = (attn["qkv"]["kernel"].shape[1] - n_q) // 2
        w_q, w_k, w_v = jnp.split(attn["qkv"]["kernel"],
                                  [n_q, n_q + n_kv], axis=-1)
        blocks.append({
            "n1": b["ln1"]["scale"], "n2": b["ln2"]["scale"],
            "w_q": w_q, "w_k": w_k, "w_v": w_v,
            "wq_n": attn["q_norm"]["scale"], "wk_n": attn["k_norm"]["scale"],
            "w_o": attn["out"]["kernel"],
            "w_r": mlp["router"]["kernel"],
            "w_gate_up": mlp["w_gate_up"], "w_down": mlp["w_down"]})
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


def block_causal_mask(q_pos, k_pos, length: int):
    """(Q, K) bool: which keys a query sees, positions in blocks of
    ``length``."""
    return k_pos[None, :] < (q_pos[:, None] // length + 1) * length


def attention(x, b, d, length, eps, theta, block):
    """``x + attn(n1(x))`` of one sequence ``x`` (S, H) with heads of ``d``
    channels under the block-causal mask of blocks of ``length``; the
    queries in blocks of ``block`` against all keys."""
    with jax.default_matmul_precision(_PRECISION):
        b = _f32(b)
        s = x.shape[0]
        h = rms(x, b["n1"], eps)
        q = rms((h @ b["w_q"]).reshape(s, -1, d), b["wq_n"], eps)
        k = rms((h @ b["w_k"]).reshape(s, -1, d), b["wk_n"], eps)
        v = (h @ b["w_v"]).reshape(s, -1, d)
        q, k = rotate(q, theta), rotate(k, theta)
        group = q.shape[1] // k.shape[1]
        # every query head beside its own key/value head
        k = jnp.repeat(k, group, axis=1)
        v = jnp.repeat(v, group, axis=1)
        k_pos = jnp.arange(s)

        def one_block(args):
            qb, q_pos = args                     # (T, heads, d), (T,)
            scores = jnp.einsum("qhd,khd->hqk", qb, k) / math.sqrt(d)
            seen = block_causal_mask(q_pos, k_pos, length)
            probs = jax.nn.softmax(jnp.where(seen[None], scores, -jnp.inf),
                                   -1)
            return jnp.einsum("hqk,khd->qhd", probs, v)

        heads = jax.lax.map(one_block, (
            q.reshape(s // block, block, -1, d),
            jnp.arange(s).reshape(s // block, block))).reshape(s, -1)
        return x + heads @ b["w_o"]


def route(h, w_r, k, norm_topk_prob):
    """(S, E) routing weights, zero but for each token's ``k`` experts,
    and the (S, k) experts chosen, largest first: k times the largest of
    what is left of ``softmax(h Wr)`` (no sort)."""
    scores = jax.nn.softmax(h @ w_r, axis=-1)
    left, chosen = scores, []
    for _ in range(k):
        best = jnp.argmax(left, axis=-1)
        chosen.append(best)
        left = left.at[jnp.arange(h.shape[0]), best].set(-jnp.inf)
    weights = jnp.where(jnp.isinf(left), scores, 0.0)
    if norm_topk_prob:
        weights = weights / weights.sum(-1, keepdims=True)
    return weights, jnp.stack(chosen, -1)


def experts(x, b, k, norm_topk_prob, eps):
    """``x + routed(n2(x))`` of one sequence and the (S, k) experts its
    router chose.  Expert after expert: each is applied to all tokens and
    its result added with the tokens' routing weights for it (its weights
    become float32 one expert at a time)."""
    with jax.default_matmul_precision(_PRECISION):
        experts_w = (b["w_gate_up"], b["w_down"])
        b = _f32({name: a for name, a in b.items()
                  if name not in ("w_gate_up", "w_down")})
        h = rms(x, b["n2"], eps)
        weights, chosen = route(h, b["w_r"], k, norm_topk_prob)

        def one_expert(y, args):
            w_gate_up, w_down, w_e = _f32(args)   # (H, 2W), (W, H), (S,)
            width = w_down.shape[0]
            gate_up = h @ w_gate_up
            out = (jax.nn.silu(gate_up[:, :width]) *
                   gate_up[:, width:]) @ w_down
            return y + out * w_e[:, None], None

        routed, _ = jax.lax.scan(one_expert, jnp.zeros_like(h),
                                 experts_w + (weights.T,))
        return x + routed, chosen


def head(x, wf, w_head, eps):
    """The final norm and the head: (S, H) -> (S, V) float32."""
    with jax.default_matmul_precision(_PRECISION):
        return rms(jnp.asarray(x, jnp.float32),
                   jnp.asarray(wf, jnp.float32), eps) @ \
            jnp.asarray(w_head, jnp.float32)


def unmask_quota(masked: int, forwards_left: int) -> int:
    """``ceil(m / r)``: the m masked positions spread over the r forwards
    of the budget that are left."""
    return -(-masked // max(forwards_left, 1))


def choose_unmasked(masked, confidence, forwards_left: int, remasking: str,
                    threshold: float):
    """Which positions of one block a denoising forward unmasks:
    ``masked`` (L,) bool, ``confidence`` (L,) float.  The quota's positions
    of highest confidence among the masked, ties to the lower position;
    under the dynamic rule every masked position over ``threshold``
    besides.  (L,) bool."""
    masked = np.asarray(masked, bool)
    confidence = np.asarray(confidence, np.float64)
    quota = unmask_quota(int(masked.sum()), forwards_left)
    # a stable sort of the negated confidences: ties stay in position order
    order = [i for i in np.argsort(-confidence, kind="stable") if masked[i]]
    take = np.zeros_like(masked)
    take[order[:quota]] = True
    if remasking == "low_confidence_dynamic":
        take |= masked & (confidence > threshold)
    elif remasking != "low_confidence_static":
        raise ValueError(f"unknown remasking {remasking!r}")
    return take


class Reference:
    """The reference bound to one configuration: ``head_dim``,
    ``block_length``, ``rms_norm_eps``, ``rope_theta``,
    ``num_experts_per_tok``, ``norm_topk_prob`` and ``query_block`` (how
    many queries meet all keys at once).  Each piece is jitted by itself
    and called layer after layer; weights are arguments, never constants."""

    def __init__(self, settings: dict):
        self.s = settings
        self._attention = jax.jit(attention, static_argnums=(2, 3, 4, 5, 6))
        self._experts = jax.jit(experts, static_argnums=(2, 3, 4))
        self._head = jax.jit(head, static_argnums=3)

    @staticmethod
    def _block_of(n: int, block: int) -> int:
        block = min(block, n)
        while n % block:
            block -= 1
        return block

    def hidden(self, w: dict, ids):
        """(S,) ids -> the last hidden states (S, H), before the final
        norm, and, per layer, every token's experts (S, k)."""
        s = self.s
        x = jnp.asarray(w["wte"], jnp.float32)[jnp.asarray(ids, jnp.int32)]
        n = x.shape[0]
        chosen = []
        for b in w["blocks"]:
            x = self._attention(
                x, b, s["head_dim"], s["block_length"], s["rms_norm_eps"],
                s["rope_theta"], self._block_of(n, s["query_block"]))
            x, what = self._experts(x, b, s["num_experts_per_tok"],
                                    s["norm_topk_prob"], s["rms_norm_eps"])
            chosen.append(what)
        return x, chosen

    def head(self, w: dict, x):
        """Hidden states (S, H) of ``hidden`` -> logits (S, V)."""
        return self._head(x, w["wf"], w["w_head"], self.s["rms_norm_eps"])

    def logits(self, w: dict, ids, rows=None):
        """(S,) token ids -> (S, V) float32 logits; with ``rows`` =
        (first, count) only those positions' logits, (count, V)."""
        x, _ = self.hidden(w, ids)
        if rows is not None:
            x = jax.lax.dynamic_slice_in_dim(x, rows[0], rows[1], axis=0)
        return self.head(w, x)

    def logits_and_experts(self, w: dict, ids, rows):
        """``logits(rows=...)`` and the experts of those positions in
        every layer, (layers, count, k)."""
        x, chosen = self.hidden(w, ids)
        x = jax.lax.dynamic_slice_in_dim(x, rows[0], rows[1], axis=0)
        picked = jnp.stack([jax.lax.dynamic_slice_in_dim(
            c, rows[0], rows[1], axis=0) for c in chosen])
        return self.head(w, x), picked

    def generate(self, w: dict, prompt, max_new_tokens: int, *,
                 mask_token_id: int, denoising_steps: int,
                 remasking: str = "low_confidence_static",
                 threshold: float = 0.9, eos_token_id=None,
                 pad_to: int = 0):
        """Greedy generation by diffusion over blocks (module docstring),
        a plain loop: every forward is ``logits`` over the prompt and the
        blocks so far.  ``pad_to``: the ids are filled with masks up to
        that length, which changes nothing (no position sees a later
        block) and keeps the pass one shape.

        Returns ``(tokens, forwards, seen)``: the generated tokens in
        position order (``max_new_tokens`` of them, or up to the first
        EOS), the number of the forward that unmasked each (from 1,
        denoising and committing forwards of the sequence counted alike),
        and a list, a denoising forward each, of ``(forward, first
        position of the block, the block's ids as the forward met them,
        its logits (L, V), the positions it unmasked (L,) bool)``."""
        length = self.s["block_length"]
        prompt = [int(t) for t in prompt]
        done = len(prompt) // length * length
        ids = prompt + [mask_token_id] * (done + length - len(prompt))
        tokens, forwards, seen = [], [], []
        since = {}
        n = 0
        while True:
            block = np.array(ids[done:done + length])
            masked = block == mask_token_id
            left = denoising_steps
            while masked.any():
                n += 1
                padded = ids + [mask_token_id] * max(0, pad_to - len(ids))
                logits = np.asarray(self.logits(w, np.array(padded, np.int32),
                                                rows=(done, length)))
                x0 = logits.argmax(-1)
                shifted = logits - logits.max(-1, keepdims=True)
                confidence = np.exp(shifted)[np.arange(length), x0] / \
                    np.exp(shifted).sum(-1)
                take = choose_unmasked(masked, confidence, left, remasking,
                                       threshold)
                seen.append((n, done, block.copy(), logits, take))
                block = np.where(take, x0, block)
                for at in np.nonzero(take)[0]:
                    since[done + int(at)] = n
                ids[done:done + length] = block.tolist()
                masked = block == mask_token_id
                left -= 1
            n += 1                              # the commit
            for at in range(max(done, len(prompt)), done + length):
                tokens.append(ids[at])
                forwards.append(since[at])
                if len(tokens) >= max_new_tokens or ids[at] == eos_token_id:
                    return tokens, forwards, seen
            done += length
            ids += [mask_token_id] * length
