"""CodeGen (Salesforce) decoder-only LM (flax), TPU-first.

Clean-room analog of ref ``examples/llm_serving/model/codegen_model.py``
(the reference's HF-port for program-synthesis serving).  Architectural
deltas vs GPT:

* rotary position embeddings (GPT-J style rotate-every-two) on the first
  ``rotary_dim`` dims of every head — no learned position table,
* PARALLEL attention + MLP residual off one shared LayerNorm
  (``x + attn(ln(x)) + mlp(ln(x))``),
* bias-free qkv/out projections; untied lm_head with bias.

The HF checkpoint's mp_num-interleaved qkv layout is normalized to plain
head-major [q;k;v] in ``params_from_hf`` so the model itself stays a
straight einsum pipeline (clean mesh targets for the sharding planner).
KV caches follow the gpt_model cache-as-invars convention (scalar or
per-row vector indices) so the serving stack works unchanged.
"""
import dataclasses
from typing import Any

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np

from alpa_tpu.model.gpt_model import (keep_positions, reference_attention,
                                      update_kv_cache)
from alpa_tpu.pipeline_parallel.primitive_def import mark_pipeline_boundary


@dataclasses.dataclass(frozen=True)
class CodeGenConfig:
    vocab_size: int = 50400
    hidden_size: int = 1024
    num_layers: int = 20
    num_heads: int = 16
    seq_len: int = 2048
    rotary_dim: int = 32
    mlp_ratio: int = 4
    dtype: Any = jnp.float32
    layer_norm_eps: float = 1e-5
    pipeline_boundary_every: int = 0

    def __post_init__(self):
        hd = self.hidden_size // self.num_heads
        if self.rotary_dim > hd:
            raise ValueError(
                f"rotary_dim ({self.rotary_dim}) cannot exceed the head "
                f"dim ({hd} = hidden_size {self.hidden_size} / num_heads "
                f"{self.num_heads})")
        if self.rotary_dim % 2 != 0:
            raise ValueError(
                f"rotary_dim ({self.rotary_dim}) must be even: rotary "
                "rotates (2i, 2i+1) dimension pairs")


# name -> (hidden, layers, heads, rotary_dim); ref Salesforce/codegen-*
codegen_specs = {
    "350m": (1024, 20, 16, 32),
    "2b": (2560, 32, 32, 64),
    "6b": (4096, 33, 16, 64),
    "16b": (6144, 34, 24, 64),
}


def config_from_codegen_spec(name: str, **kwargs) -> CodeGenConfig:
    key = name.lower().replace("codegen-", "").split("-")[0]
    hidden, layers, heads, rot = codegen_specs[key]
    return CodeGenConfig(hidden_size=hidden, num_layers=layers,
                         num_heads=heads, rotary_dim=rot, **kwargs)


def apply_rotary(x, offset, rotary_dim: int):
    """GPT-J-style rotate-every-two rotary embedding on the first
    ``rotary_dim`` dims of each head.  x: (B, S, H, D).  ``offset`` is
    the absolute position of x's FIRST token: a scalar (uniform), (B,)
    per-row offsets, or an explicit (B, S) position matrix — token t in
    row b always rotates at offset[b] + t."""
    b, s = x.shape[0], x.shape[1]
    pos = jnp.asarray(offset, jnp.int32)
    if pos.ndim == 0:
        pos = pos + jnp.broadcast_to(jnp.arange(s), (b, s))
    elif pos.ndim == 1:  # (B,) per-row offsets, S tokens each
        pos = pos[:, None] + jnp.arange(s)[None, :]
    inv_freq = 1.0 / (10000.0 ** (np.arange(0, rotary_dim, 2) / rotary_dim))
    ang = pos[..., None].astype(jnp.float32) * inv_freq[None, None, :]
    sin, cos = jnp.sin(ang), jnp.cos(ang)          # (B, S, rot/2)
    rot, rest = x[..., :rotary_dim], x[..., rotary_dim:]
    x1, x2 = rot[..., 0::2], rot[..., 1::2]        # pairs (2i, 2i+1)
    sin = sin[:, :, None, :]
    cos = cos[:, :, None, :]
    r1 = x1 * cos - x2 * sin
    r2 = x2 * cos + x1 * sin
    rot = jnp.stack([r1, r2], axis=-1).reshape(rot.shape)
    return jnp.concatenate([rot, rest], axis=-1).astype(x.dtype)


class CodeGenAttention(nn.Module):
    config: CodeGenConfig

    @nn.compact
    def __call__(self, x, kv_cache=None):
        cfg = self.config
        h, nh = cfg.hidden_size, cfg.num_heads
        hd = h // nh
        qkv = nn.Dense(3 * h, use_bias=False, dtype=cfg.dtype,
                       name="qkv")(x)
        q, k, v = jnp.split(qkv, 3, axis=-1)
        b, s = x.shape[0], x.shape[1]
        q = q.reshape(b, s, nh, hd)
        k = k.reshape(b, s, nh, hd)
        v = v.reshape(b, s, nh, hd)

        new_cache = None
        if kv_cache is not None:
            index = jnp.asarray(kv_cache[2], jnp.int32)
            # rotary positions are absolute: offset by the write index
            q = apply_rotary(q, index, cfg.rotary_dim)
            k = apply_rotary(k, index, cfg.rotary_dim)
            new_cache = update_kv_cache(kv_cache, k, v)
            out = reference_attention(q, *new_cache[:2], causal=True,
                                      offset=index)
        else:
            q = apply_rotary(q, 0, cfg.rotary_dim)
            k = apply_rotary(k, 0, cfg.rotary_dim)
            out = reference_attention(q, k, v, causal=True)
        out = out.reshape(b, s, h)
        return nn.Dense(h, use_bias=False, dtype=cfg.dtype,
                        name="out")(out), new_cache


class CodeGenBlock(nn.Module):
    config: CodeGenConfig

    @nn.compact
    def __call__(self, x, kv_cache=None):
        cfg = self.config
        ln = nn.LayerNorm(epsilon=cfg.layer_norm_eps, dtype=jnp.float32,
                          name="ln1")(x)
        attn_out, new_cache = CodeGenAttention(cfg, name="attn")(ln,
                                                                 kv_cache)
        y = nn.Dense(cfg.mlp_ratio * cfg.hidden_size, dtype=cfg.dtype,
                     name="fc_in")(ln.astype(cfg.dtype))
        y = nn.gelu(y, approximate=True)
        y = nn.Dense(cfg.hidden_size, dtype=cfg.dtype, name="fc_out")(y)
        # parallel residual: one LN feeds both branches (GPT-J layout)
        return x + attn_out.astype(x.dtype) + y.astype(x.dtype), new_cache


class CodeGenModel(nn.Module):
    """Returns logits (and new KV caches when given)."""
    config: CodeGenConfig

    @nn.compact
    def __call__(self, input_ids, position_ids=None, kv_caches=None,
                 logits_at=None):
        # positions come from rotary offsets (cache indices); the argument
        # is accepted for Generator interface compatibility
        del position_ids
        cfg = self.config
        x = nn.Embed(cfg.vocab_size, cfg.hidden_size, dtype=cfg.dtype,
                     name="wte")(input_ids)
        new_caches = [] if kv_caches is not None else None
        for i in range(cfg.num_layers):
            if (cfg.pipeline_boundary_every and i > 0 and
                    i % cfg.pipeline_boundary_every == 0):
                mark_pipeline_boundary()
            cache_i = kv_caches[i] if kv_caches is not None else None
            x, c = CodeGenBlock(cfg, name=f"h{i}")(x, cache_i)
            if new_caches is not None:
                new_caches.append(c)
        x = nn.LayerNorm(epsilon=cfg.layer_norm_eps, dtype=jnp.float32,
                         name="ln_f")(keep_positions(x, logits_at))
        logits = nn.Dense(cfg.vocab_size, dtype=cfg.dtype, use_bias=True,
                          name="lm_head")(x.astype(cfg.dtype))
        if new_caches is not None:
            return logits, new_caches
        return logits


def init_codegen_kv_caches(config: CodeGenConfig, batch_size: int,
                           dtype=None) -> list:
    from alpa_tpu.model.gpt_model import init_kv_caches
    return init_kv_caches(config, batch_size, dtype)


def _qkv_permutation(hidden: int, mp_num: int = 4) -> np.ndarray:
    """Column permutation taking HF CodeGen's qkv layout to plain
    head-major [q; k; v].

    HF packs the 3h output dim as mp_num groups of [query, value, key]
    blocks of h/mp_num columns each (modeling_codegen qkv reshape with
    mp_num=4); perm[j] = the HF column that lands at our column j.
    """
    local = hidden // mp_num
    perm = np.empty(3 * hidden, np.int64)
    for g in range(mp_num):
        base = g * 3 * local
        cols = np.arange(local)
        perm[g * local:(g + 1) * local] = base + cols                # q
        perm[hidden + g * local:hidden + (g + 1) * local] = \
            base + 2 * local + cols                                  # k
        perm[2 * hidden + g * local:2 * hidden + (g + 1) * local] = \
            base + local + cols                                      # v
    return perm


def params_from_hf(hf_model, config: CodeGenConfig):
    """Map a transformers CodeGenForCausalLM state dict onto
    CodeGenModel params (ref codegen_model.py load path)."""
    sd = {k: np.asarray(v.detach().cpu().numpy(), np.float32)
          for k, v in hf_model.state_dict().items()}
    perm = _qkv_permutation(config.hidden_size)
    p = {"wte": {"embedding": sd["transformer.wte.weight"]},
         "ln_f": {"scale": sd["transformer.ln_f.weight"],
                  "bias": sd["transformer.ln_f.bias"]},
         "lm_head": {"kernel": sd["lm_head.weight"].T,
                     "bias": sd["lm_head.bias"]}}
    for i in range(config.num_layers):
        pre = f"transformer.h.{i}."
        p[f"h{i}"] = {
            "ln1": {"scale": sd[pre + "ln_1.weight"],
                    "bias": sd[pre + "ln_1.bias"]},
            "attn": {
                "qkv": {"kernel": sd[pre + "attn.qkv_proj.weight"].T[:,
                                                                     perm]},
                "out": {"kernel": sd[pre + "attn.out_proj.weight"].T},
            },
            "fc_in": {"kernel": sd[pre + "mlp.fc_in.weight"].T,
                      "bias": sd[pre + "mlp.fc_in.bias"]},
            "fc_out": {"kernel": sd[pre + "mlp.fc_out.weight"].T,
                       "bias": sd[pre + "mlp.fc_out.bias"]},
        }
    return {"params": p}
