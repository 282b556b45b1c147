"""Plain reference of a pre-LayerNorm decoder-only language model: the
forward pass and the next-token loss in straightforward ``jax.numpy``,
float32, full matmul precision; no kernels, no cache, no batching, no
sharding.  One sequence at a time.

Written from the published descriptions, not from the program's model file:

* GPT-2 / Megatron-LM GPT (Radford et al. 2019; Shoeybi et al. 2019): token
  plus learned position embeddings; per block ``x += attn(ln1(x))``,
  ``x += mlp(ln2(x))``; causal softmax attention over ``heads`` heads of
  ``hidden / heads`` channels with scores scaled by 1/sqrt(head size); MLP
  hidden -> 4 hidden -> hidden with tanh-approximated GELU; a final
  LayerNorm; logits against the transposed token embedding (tied head).
* OPT (Zhang et al. 2022, ``facebook/opt-1.3b`` config.json): the same with
  ReLU in the MLP and the learned positions looked up at ``position + 2``.

The fused query/key/value projection is laid out [q | k | v], each
``hidden`` wide, as in GPT-2's ``c_attn``.

Departure from the papers: none in the mathematics.  Dropout is 0 (as in
the cells' configurations).

A configuration file names this module under ``reference`` and gives
``activation`` ("gelu" or "relu"), ``pos_offset``, ``num_heads`` and
``layer_norm_eps``.
"""
import math

import jax
import jax.numpy as jnp

_PRECISION = "highest"   # a float32 matmul on the TPU is one bf16 pass otherwise


def weights_from_program(params) -> dict:
    """The program's parameter tree (flax names of ``GPTModel``) as the
    plain names used here.  Arrays are shared, not copied; every use below
    casts to float32."""
    p = params["params"]
    blocks = []
    i = 0
    while f"h{i}" in p:
        b = p[f"h{i}"]
        blocks.append({
            "ln1_g": b["ln1"]["scale"], "ln1_b": b["ln1"]["bias"],
            "w_qkv": b["attn"]["qkv"]["kernel"],
            "b_qkv": b["attn"]["qkv"]["bias"],
            "w_o": b["attn"]["out"]["kernel"], "b_o": b["attn"]["out"]["bias"],
            "ln2_g": b["ln2"]["scale"], "ln2_b": b["ln2"]["bias"],
            "w_fc": b["mlp"]["fc_in"]["kernel"],
            "b_fc": b["mlp"]["fc_in"]["bias"],
            "w_proj": b["mlp"]["fc_out"]["kernel"],
            "b_proj": b["mlp"]["fc_out"]["bias"],
        })
        i += 1
    return {"wte": p["wte"]["embedding"], "wpe": p["wpe"]["embedding"],
            "blocks": blocks,
            "lnf_g": p["ln_f"]["scale"], "lnf_b": p["ln_f"]["bias"]}


def _f32(tree):
    return jax.tree_util.tree_map(lambda a: jnp.asarray(a, jnp.float32), tree)


def layer_norm(x, gain, bias, eps):
    mean = x.mean(-1, keepdims=True)
    var = ((x - mean)**2).mean(-1, keepdims=True)
    return (x - mean) / jnp.sqrt(var + eps) * gain + bias


def gelu_tanh(x):
    return 0.5 * x * (1 + jnp.tanh(
        math.sqrt(2 / math.pi) * (x + 0.044715 * x**3)))


def embed(wte, wpe, ids, pos_offset):
    positions = jnp.arange(ids.shape[0]) + pos_offset
    return jnp.asarray(wte, jnp.float32)[ids] + \
        jnp.asarray(wpe, jnp.float32)[positions]


def block(x, b, num_heads, activation, eps):
    """One transformer block on one sequence ``x`` of shape (S, H)."""
    with jax.default_matmul_precision(_PRECISION):
        b = _f32(b)
        s, h = x.shape
        hd = h // num_heads
        y = layer_norm(x, b["ln1_g"], b["ln1_b"], eps)
        qkv = y @ b["w_qkv"] + b["b_qkv"]
        q, k, v = (t.reshape(s, num_heads, hd)
                   for t in jnp.split(qkv, 3, axis=-1))
        scores = jnp.einsum("qhd,khd->hqk", q, k) / math.sqrt(hd)
        causal = jnp.tril(jnp.ones((s, s), bool))
        scores = jnp.where(causal[None], scores, -jnp.inf)
        probs = jax.nn.softmax(scores, axis=-1)
        attn = jnp.einsum("hqk,khd->qhd", probs, v).reshape(s, h)
        x = x + attn @ b["w_o"] + b["b_o"]
        y = layer_norm(x, b["ln2_g"], b["ln2_b"], eps)
        y = y @ b["w_fc"] + b["b_fc"]
        y = jnp.maximum(y, 0) if activation == "relu" else gelu_tanh(y)
        return x + y @ b["w_proj"] + b["b_proj"]


def head(x, wte, lnf_g, lnf_b, eps):
    """Final LayerNorm and the tied vocabulary head: (S, H) -> (S, V)."""
    with jax.default_matmul_precision(_PRECISION):
        x = layer_norm(x, jnp.asarray(lnf_g, jnp.float32),
                       jnp.asarray(lnf_b, jnp.float32), eps)
        return x @ jnp.asarray(wte, jnp.float32).T


def token_losses(logits, labels):
    """Cross-entropy of each position's label: logsumexp minus its logit."""
    lse = jax.nn.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, labels[:, None], axis=-1)[:, 0]
    return lse - gold


class Reference:
    """The reference bound to one configuration.  Each piece is jitted by
    itself and called block after block, so one small program is compiled
    however deep the model is; weights are arguments, never constants."""

    def __init__(self, config: dict):
        self.num_heads = config["num_heads"]
        self.activation = config["activation"]
        self.eps = config["layer_norm_eps"]
        self.pos_offset = config["pos_offset"]
        self._embed = jax.jit(embed, static_argnums=3)
        self._block = jax.jit(block, static_argnums=(2, 3, 4))
        self._head = jax.jit(head, static_argnums=4)
        self._losses = jax.jit(token_losses)

    def logits(self, w: dict, ids, rows=None):
        """(S,) token ids -> (S, V) float32 logits; with ``rows`` =
        (first, count) only those positions' logits, (count, V)."""
        x = self._embed(w["wte"], w["wpe"], jnp.asarray(ids, jnp.int32),
                        self.pos_offset)
        for b in w["blocks"]:
            x = self._block(x, b, self.num_heads, self.activation, self.eps)
        if rows is not None:
            x = jax.lax.dynamic_slice_in_dim(x, rows[0], rows[1], axis=0)
        return self._head(x, w["wte"], w["lnf_g"], w["lnf_b"], self.eps)

    def lm_loss(self, w: dict, input_ids, labels) -> float:
        """Mean next-token loss of a (B, S) batch, sequence by sequence."""
        total, count = 0.0, 0
        for ids, lab in zip(input_ids, labels):
            losses = self._losses(self.logits(w, ids),
                                  jnp.asarray(lab, jnp.int32))
            total += float(losses.sum())
            count += int(losses.shape[0])
        return total / count
