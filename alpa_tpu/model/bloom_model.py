"""BLOOM decoder-only LM (flax), TPU-first.

Clean-room analog of ref ``examples/llm_serving/model/bloom_model.py``
(the reference's HF-port for serving).  Architectural deltas vs GPT:

* ALiBi attention biases instead of positional embeddings
  (per-head slopes, linear in key-query distance) — no learned position
  table, so any sequence length the cache allows is admissible,
* LayerNorm directly after the word embedding
  (``word_embeddings_layernorm``),
* fused-style QKV whose per-head layout is (head, 3, head_dim) — the HF
  checkpoint convention, honored by ``params_from_hf``.

KV caches follow the gpt_model convention (cache-as-invars, scalar or
per-row vector write indices) so ``serve.generation.Generator`` and the
continuous-batching engine work unchanged.
"""
import dataclasses
from typing import Any, Optional

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np

from alpa_tpu.model.gpt_model import (keep_positions, reference_attention,
                                      update_kv_cache)
from alpa_tpu.pipeline_parallel.primitive_def import mark_pipeline_boundary


@dataclasses.dataclass(frozen=True)
class BloomConfig:
    vocab_size: int = 250880
    hidden_size: int = 1024
    num_layers: int = 24
    num_heads: int = 16
    seq_len: int = 2048          # cache capacity; ALiBi has no hard limit
    mlp_ratio: int = 4
    dtype: Any = jnp.float32
    layer_norm_eps: float = 1e-5
    tie_embeddings: bool = True
    pipeline_boundary_every: int = 0


# name -> (hidden, layers, heads); ref bloom family (HF bigscience/bloom-*)
bloom_specs = {
    "560m": (1024, 24, 16),
    "1b1": (1536, 24, 16),
    "1b7": (2048, 24, 16),
    "3b": (2560, 30, 32),
    "7b1": (4096, 30, 32),
    "176b": (14336, 70, 112),
}


def config_from_bloom_spec(name: str, **kwargs) -> BloomConfig:
    hidden, layers, heads = bloom_specs[name.lower().replace("bloom-", "")]
    return BloomConfig(hidden_size=hidden, num_layers=layers,
                       num_heads=heads, **kwargs)


def alibi_slopes(num_heads: int) -> np.ndarray:
    """Per-head ALiBi slopes (Press et al.; matches HF build_alibi_tensor):
    geometric sequence starting at 2^(-8/n) for the nearest power of two,
    interleaved extras for non-power-of-two head counts."""
    def pow2_slopes(n):
        start = 2.0 ** (-(2.0 ** -(np.log2(n) - 3)))
        return start * (start ** np.arange(n))

    if np.log2(num_heads).is_integer():
        return pow2_slopes(num_heads)
    closest = 2 ** int(np.floor(np.log2(num_heads)))
    base = pow2_slopes(closest)
    extra = pow2_slopes(2 * closest)[0::2][:num_heads - closest]
    return np.concatenate([base, extra])


def alibi_bias(num_heads: int, q_pos, k_pos) -> jnp.ndarray:
    """(H, Sq, Sk) additive score bias: slope_h * -(q - k) for k <= q.
    HF computes slope * k (key position) which is equivalent under the
    softmax's row-wise shift invariance; the distance form is kept here
    because it is also exact for the cached-decode path."""
    slopes = jnp.asarray(alibi_slopes(num_heads), jnp.float32)
    dist = (k_pos[None, :] - q_pos[:, None]).astype(jnp.float32)  # <= 0 kept
    return slopes[:, None, None] * dist[None, :, :]


class BloomAttention(nn.Module):
    config: BloomConfig

    @nn.compact
    def __call__(self, x, kv_cache=None):
        cfg = self.config
        h, nh = cfg.hidden_size, cfg.num_heads
        hd = h // nh
        qkv = nn.Dense(3 * h, dtype=cfg.dtype, name="qkv")(x)
        b, s = x.shape[0], x.shape[1]
        # HF bloom packs qkv per head: (nh, 3, hd)
        qkv = qkv.reshape(b, s, nh, 3, hd)
        q, k, v = qkv[..., 0, :], qkv[..., 1, :], qkv[..., 2, :]

        new_cache = None
        if kv_cache is not None:
            index = jnp.asarray(kv_cache[2], jnp.int32)
            cache_len = kv_cache[0].shape[1]
            new_cache = update_kv_cache(kv_cache, k, v)
            if index.ndim == 0:
                q_pos = index + jnp.arange(s)
            else:
                q_pos = index[:, None] + jnp.arange(s)[None, :]  # (B, S)
            k_pos = jnp.arange(cache_len)
            if q_pos.ndim == 1:
                bias = alibi_bias(nh, q_pos, k_pos)[None]      # (1,H,S,L)
            else:
                bias = jax.vmap(lambda qp: alibi_bias(nh, qp, k_pos))(q_pos)
            out = reference_attention(q, *new_cache[:2], causal=True,
                                      offset=index, bias=bias)
        else:
            pos = jnp.arange(s)
            bias = alibi_bias(nh, pos, pos)[None]              # (1,H,S,S)
            out = reference_attention(q, k, v, causal=True, bias=bias)
        out = out.reshape(b, s, h)
        return nn.Dense(h, dtype=cfg.dtype, name="out")(out), new_cache


class BloomBlock(nn.Module):
    config: BloomConfig

    @nn.compact
    def __call__(self, x, kv_cache=None):
        cfg = self.config
        ln1 = nn.LayerNorm(epsilon=cfg.layer_norm_eps, dtype=jnp.float32,
                           name="ln1")(x)
        attn_out, new_cache = BloomAttention(cfg, name="attn")(ln1, kv_cache)
        x = x + attn_out.astype(x.dtype)
        ln2 = nn.LayerNorm(epsilon=cfg.layer_norm_eps, dtype=jnp.float32,
                           name="ln2")(x)
        y = nn.Dense(cfg.mlp_ratio * cfg.hidden_size, dtype=cfg.dtype,
                     name="fc_in")(ln2)
        y = nn.gelu(y, approximate=True)
        y = nn.Dense(cfg.hidden_size, dtype=cfg.dtype, name="fc_out")(y)
        return x + y.astype(x.dtype), new_cache


class BloomModel(nn.Module):
    """Returns logits (and new KV caches when given)."""
    config: BloomConfig

    @nn.compact
    def __call__(self, input_ids, position_ids=None, kv_caches=None,
                 logits_at=None):
        # position_ids accepted for Generator interface compatibility;
        # ALiBi needs no position table (positions come from cache indices)
        del position_ids
        cfg = self.config
        tok_emb = nn.Embed(cfg.vocab_size, cfg.hidden_size,
                           dtype=cfg.dtype, name="wte")
        x = tok_emb(input_ids)
        x = nn.LayerNorm(epsilon=cfg.layer_norm_eps, dtype=jnp.float32,
                         name="ln_emb")(x).astype(cfg.dtype)
        new_caches = [] if kv_caches is not None else None
        for i in range(cfg.num_layers):
            if (cfg.pipeline_boundary_every and i > 0 and
                    i % cfg.pipeline_boundary_every == 0):
                mark_pipeline_boundary()
            cache_i = kv_caches[i] if kv_caches is not None else None
            x, c = BloomBlock(cfg, name=f"h{i}")(x, cache_i)
            if new_caches is not None:
                new_caches.append(c)
        x = nn.LayerNorm(epsilon=cfg.layer_norm_eps, dtype=jnp.float32,
                         name="ln_f")(keep_positions(x, logits_at))
        if cfg.tie_embeddings:
            logits = tok_emb.attend(x.astype(cfg.dtype))
        else:
            logits = nn.Dense(cfg.vocab_size, dtype=cfg.dtype,
                              use_bias=False, name="lm_head")(x)
        if new_caches is not None:
            return logits, new_caches
        return logits


def init_bloom_kv_caches(config: BloomConfig, batch_size: int,
                         dtype=None) -> list:
    from alpa_tpu.model.gpt_model import init_kv_caches
    return init_kv_caches(config, batch_size, dtype)


def params_from_hf(hf_model, config: BloomConfig):
    """Map a transformers BloomForCausalLM state dict onto BloomModel
    params (ref bloom_model.py load path; layout notes: HF QKV rows are
    (nh, 3, hd) per head — same as this model's packed projection)."""
    sd = {k: np.asarray(v.detach().cpu().numpy(), np.float32)
          for k, v in hf_model.state_dict().items()}
    p = {"wte": {"embedding": sd["transformer.word_embeddings.weight"]},
         "ln_emb": {
             "scale": sd["transformer.word_embeddings_layernorm.weight"],
             "bias": sd["transformer.word_embeddings_layernorm.bias"]},
         "ln_f": {"scale": sd["transformer.ln_f.weight"],
                  "bias": sd["transformer.ln_f.bias"]}}
    for i in range(config.num_layers):
        pre = f"transformer.h.{i}."
        p[f"h{i}"] = {
            "ln1": {"scale": sd[pre + "input_layernorm.weight"],
                    "bias": sd[pre + "input_layernorm.bias"]},
            "ln2": {"scale": sd[pre + "post_attention_layernorm.weight"],
                    "bias": sd[pre + "post_attention_layernorm.bias"]},
            "attn": {
                "qkv": {
                    "kernel": sd[
                        pre + "self_attention.query_key_value.weight"].T,
                    "bias": sd[pre + "self_attention.query_key_value.bias"],
                },
                "out": {"kernel": sd[pre + "self_attention.dense.weight"].T,
                        "bias": sd[pre + "self_attention.dense.bias"]},
            },
            "fc_in": {"kernel": sd[pre + "mlp.dense_h_to_4h.weight"].T,
                      "bias": sd[pre + "mlp.dense_h_to_4h.bias"]},
            "fc_out": {"kernel": sd[pre + "mlp.dense_4h_to_h.weight"].T,
                       "bias": sd[pre + "mlp.dense_4h_to_h.bias"]},
        }
    return {"params": p}
