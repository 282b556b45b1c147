"""GPT-style decoder-only transformer (flax), TPU-first.

Clean-room analog of ref ``alpa/model/gpt_model.py`` (which wraps
``bert_model.py``'s encoder with a causal mask).  Design choices for TPU:

* bfloat16 activations/params option; fp32 layernorm + softmax accumulation,
* einsum-formulated attention so batch/head/seq dims are clean mesh targets
  for the auto-sharding planner,
* pluggable attention implementation (``attention_impl``):
  "reference" (jnp, XLA-fused) | "flash" (pallas kernel, ops/flash_attention)
  | "ring" (sequence-parallel ring attention over a mesh axis),
* optional ``mark_pipeline_boundary()`` between blocks for manual pipeline
  layer construction (ref ManualLayerOption),
* KV-cache threading for autoregressive serving (cache as explicit
  function inputs/outputs, mirroring ref examples/llm_serving/model/
  opt_model.py:605 init_cache_aval design).

The GPT ladder (125M..76B, ref benchmark/alpa/suite_manual_gpt.py:18-26) is
reproduced in ``gpt_specs``.
"""
import dataclasses
from functools import partial
from typing import Any, Callable, Optional, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np

from alpa_tpu.pipeline_parallel.primitive_def import mark_pipeline_boundary


@dataclasses.dataclass(frozen=True)
class GPTConfig:
    vocab_size: int = 51200
    hidden_size: int = 768
    num_layers: int = 12
    num_heads: int = 12
    seq_len: int = 1024
    mlp_ratio: int = 4
    dtype: Any = jnp.float32
    dropout_rate: float = 0.0
    # "reference" | "flash" | "ring"
    attention_impl: str = "reference"
    # insert pipeline boundary markers every k blocks (0 = never)
    pipeline_boundary_every: int = 0
    # mesh axis name for ring attention (sequence parallel)
    sp_axis: Optional[str] = None
    tie_embeddings: bool = True
    # HF GPT-2 uses 1e-5 (transformers layer_norm_epsilon); flax default
    # 1e-6 makes HF-loaded weights diverge slightly
    layer_norm_eps: float = 1e-5
    # rematerialize each transformer block (training memory <-> flops)
    remat_blocks: bool = False
    # remat policy: None = save nothing (max memory savings, full
    # recompute); "dots" = save matmul outputs (bounded memory, skips
    # recomputing the MXU-heavy ops — usually the best throughput point)
    remat_policy: Optional[str] = None
    # decoder (causal) vs encoder (bidirectional, BERT-style)
    causal: bool = True
    # MLP activation: "gelu" (GPT-2) | "relu" (OPT)
    activation: str = "gelu"
    # learned-positional-table offset (OPT reserves the first 2 rows,
    # ref examples/llm_serving/model/opt_model.py position handling)
    pos_offset: int = 0
    # --- the kinds of one decoder block.  The defaults are the GPT-2 /
    # OPT block; OLMoE is norm "rmsnorm", positions "rotary", qk_norm,
    # no bias, mlp "experts", activation "silu", an untied head.
    # "layernorm" | "rmsnorm" (layer_norm_eps is the epsilon of either)
    norm: str = "layernorm"
    # "learned" (a table, wpe) | "rotary" (rotate-half RoPE on q and k)
    positions: str = "learned"
    rope_theta: float = 10000.0
    # True: RMSNorm over the whole flat q and k projections, before the
    # heads are split and rotated (OLMoE); "head": over the channels of
    # every head of q and of k, one weight vector for all heads
    qk_norm: Any = False
    use_bias: bool = True
    # the MLP of every layer, or one kind a layer (a tuple num_layers
    # long): "dense" (in, activation, out) | "gated" (act(gate) * up,
    # down) | "experts" (top-k routed gated experts, no token dropped:
    # model/moe.py DroplessExperts)
    mlp: Any = "dense"
    # width of the MLP, of one expert where routed; None: mlp_ratio * h
    intermediate_size: Optional[int] = None
    num_experts: int = 0
    num_experts_per_tok: int = 0
    norm_topk_prob: bool = False
    # --- what a current served decoder adds to the block (Trinity-Mini,
    # ``model_type`` afmoe).  Every default is the block of today.
    # key/value heads, each read by num_heads / num_kv_heads query heads
    # (grouped-query attention); None: one a query head
    num_kv_heads: Optional[int] = None
    # channels of one head; None: hidden_size / num_heads
    head_dim: Optional[int] = None
    # the attention of every layer, or one kind a layer: "full" (every
    # earlier position; its cache holds seq_len positions) | "sliding"
    # (position q sees k where q - k < sliding_window; its cache is a ring
    # of sliding_window positions, written at position % sliding_window)
    attention: Any = "full"
    sliding_window: int = 0
    # False: rotary positions turn the q and k of "sliding" layers only,
    # "full" layers see no positions at all
    rope_on_full_attention: bool = True
    # sigmoid(gate(h)) on the heads' output, before the output projection
    attn_gate: bool = False
    # four norms a block: one more on what the attention and the MLP
    # return, before it joins the residual stream
    post_norms: bool = False
    # the embedding times sqrt(hidden_size)
    scale_embedding: bool = False
    # width of one routed (and of the shared) expert where it is not the
    # dense layers' ``mlp_width``
    moe_intermediate_size: Optional[int] = None
    # "softmax" | "sigmoid": how the router's logits become scores
    router_score: str = "softmax"
    # a stored bias an expert, added to the scores for the CHOICE of the
    # k experts and not to their weights (no gradient reaches it)
    router_bias: bool = False
    # the (renormalised) routing weights times this
    route_scale: float = 1.0
    # gated MLPs of an expert's width applied to every token and added to
    # the routed sum
    num_shared_experts: int = 0
    # the routed experts' gate and up matrices stored as one (E, h, 2w)
    # parameter: a served decode then concatenates no expert weights
    fused_gate_up: bool = False
    # what the parameters are STORED in (``dtype`` is what is computed in)
    param_dtype: Any = jnp.float32

    def mlp_kind(self, layer: int) -> str:
        return self.mlp if isinstance(self.mlp, str) else self.mlp[layer]

    def attention_kind(self, layer: int) -> str:
        return self.attention if isinstance(self.attention, str) \
            else self.attention[layer]

    @property
    def mlp_width(self) -> int:
        return self.intermediate_size or self.mlp_ratio * self.hidden_size

    @property
    def expert_width(self) -> int:
        return self.moe_intermediate_size or self.mlp_width

    @property
    def kv_heads(self) -> int:
        return self.num_kv_heads or self.num_heads

    @property
    def head_size(self) -> int:
        return self.head_dim or self.hidden_size // self.num_heads


# The reference benchmark ladder: name -> (hidden, layers, heads)
# (ref benchmark/alpa/suite_manual_gpt.py:18-26; seq 1024, vocab 51200)
gpt_specs = {
    "125M": (768, 12, 12),
    "350M": (1024, 24, 16),
    "760M": (1536, 24, 16),
    "1.3B": (2048, 24, 32),
    "2.6B": (2560, 32, 32),
    "6.7B": (4096, 32, 32),
    "15B": (5120, 48, 40),
    "39B": (8192, 48, 64),
    "76B": (10240, 60, 80),
}


def config_from_spec(name: str, **kwargs) -> GPTConfig:
    hidden, layers, heads = gpt_specs[name]
    return GPTConfig(hidden_size=hidden, num_layers=layers, num_heads=heads,
                     **kwargs)


# OPT ladder: name -> (hidden, layers, heads); seq 2048, vocab 50272,
# relu MLP, +2 positional offset (ref examples/llm_serving/model/
# opt_model.py get_opt_config; 350m omitted — post-norm layout)
opt_specs = {
    "125m": (768, 12, 12),
    "1.3b": (2048, 24, 32),
    "2.7b": (2560, 32, 32),
    "6.7b": (4096, 32, 32),
    "13b": (5120, 40, 40),
    "30b": (7168, 48, 56),
    "66b": (9216, 64, 72),
    "175b": (12288, 96, 96),
}


def config_from_opt_spec(name: str, **kwargs) -> GPTConfig:
    """OPT-family GPTConfig (ref opt_model.py model table)."""
    hidden, layers, heads = opt_specs[name.lower().replace("opt-", "")]
    defaults = dict(vocab_size=50272, seq_len=2048, activation="relu",
                    pos_offset=2, tie_embeddings=True)
    defaults.update(kwargs)
    return GPTConfig(hidden_size=hidden, num_layers=layers,
                     num_heads=heads, **defaults)


# The kinds of the decoder block by Hugging Face ``model_type``.
_HF_KINDS = {
    "olmoe": dict(norm="rmsnorm", positions="rotary", qk_norm=True,
                  mlp="experts"),
    # Trinity (arcee-ai): wiring read from transformers'
    # models/afmoe/modeling_afmoe.py where config.json does not fix it
    "afmoe": dict(norm="rmsnorm", positions="rotary", qk_norm="head",
                  rope_on_full_attention=False, attn_gate=True,
                  post_norms=True, router_bias=True, fused_gate_up=True),
}


def _afmoe_fields(hf: dict) -> dict:
    """What ``config.json`` of ``model_type`` afmoe says beyond the keys
    all decoders share: a kind of MLP and of attention a layer, the sizes
    of heads and experts, the router's settings."""
    layers = hf["num_hidden_layers"]
    if len(hf["layer_types"]) != layers:
        raise ValueError("layer_types must name every layer")
    unknown = set(hf["layer_types"]) - {"sliding_attention",
                                        "full_attention"}
    if unknown:
        raise ValueError(f"unknown layer_types {sorted(unknown)}")
    return dict(
        mlp=tuple("gated" if i < hf["num_dense_layers"] else "experts"
                  for i in range(layers)),
        attention=tuple("sliding" if t == "sliding_attention" else "full"
                        for t in hf["layer_types"]),
        sliding_window=hf["sliding_window"], head_dim=hf["head_dim"],
        moe_intermediate_size=hf["moe_intermediate_size"],
        router_score=hf["score_func"], norm_topk_prob=hf["route_norm"],
        route_scale=float(hf["route_scale"]),
        num_shared_experts=hf["num_shared_experts"],
        scale_embedding=hf["mup_enabled"])


def config_from_hf(hf: dict, **kwargs) -> GPTConfig:
    """``GPTConfig`` from the keys of a Hugging Face ``config.json`` (a
    dict), for the model types in ``_HF_KINDS``.  ``kwargs`` override what
    the file says (``seq_len``: the context a deployment serves, where it
    is less than the declared ``max_position_embeddings``)."""
    kinds = _HF_KINDS.get(hf["model_type"])
    if kinds is None:
        raise ValueError(f"no decoder kinds for model_type "
                         f"{hf['model_type']!r} (known: {sorted(_HF_KINDS)})")
    if hf.get("rope_scaling") or hf.get("clip_qkv"):
        raise ValueError("rope_scaling and clip_qkv are not supported")
    fields = dict(
        vocab_size=hf["vocab_size"], hidden_size=hf["hidden_size"],
        num_layers=hf["num_hidden_layers"],
        num_heads=hf["num_attention_heads"],
        seq_len=hf["max_position_embeddings"],
        intermediate_size=hf["intermediate_size"],
        activation=hf["hidden_act"], layer_norm_eps=hf["rms_norm_eps"],
        rope_theta=float(hf["rope_theta"]),
        use_bias=hf.get("attention_bias", False),
        tie_embeddings=hf["tie_word_embeddings"],
        num_experts=hf["num_experts"],
        num_experts_per_tok=hf["num_experts_per_tok"], **kinds)
    if hf["num_key_value_heads"] != hf["num_attention_heads"]:
        fields["num_kv_heads"] = hf["num_key_value_heads"]
    if hf["model_type"] == "afmoe":
        fields.update(_afmoe_fields(hf))
    else:
        fields["norm_topk_prob"] = hf["norm_topk_prob"]
    fields.update(kwargs)
    return GPTConfig(**fields)


def make_norm(config: GPTConfig, name: str) -> nn.Module:
    """The normalisation the configuration names, computed in float32."""
    if config.norm == "rmsnorm":
        # scale * x / sqrt(mean(x^2) + eps)
        return nn.RMSNorm(epsilon=config.layer_norm_eps, dtype=jnp.float32,
                          param_dtype=config.param_dtype, name=name)
    if config.norm != "layernorm":
        raise ValueError(f"unknown norm {config.norm!r}")
    return nn.LayerNorm(epsilon=config.layer_norm_eps, dtype=jnp.float32,
                        param_dtype=config.param_dtype, name=name)


def apply_rotary(x, position_ids, theta: float):
    """Rotate-half rotary position embedding (Su et al. 2021, as in
    Hugging Face's ``apply_rotary_pos_emb``): x (B, S, H, D), positions
    (B, S).  Channel i is paired with channel i + D/2; the angle of pair i
    is position * theta^(-2i/D).  Computed in float32."""
    half = x.shape[-1] // 2
    inv_freq = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    angles = position_ids.astype(jnp.float32)[..., None] * inv_freq
    cos = jnp.cos(angles)[:, :, None, :]
    sin = jnp.sin(angles)[:, :, None, :]
    x32 = x.astype(jnp.float32)
    x1, x2 = x32[..., :half], x32[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                           axis=-1).astype(x.dtype)


def reference_attention(q, k, v, *, causal: bool, offset=0, bias=None,
                        window: int = 0, k_positions=None):
    """Plain einsum attention; XLA fuses this well on TPU for short seqs.

    q: (B, Sq, H, D); k/v: (B, Sk, Hkv, D), H a multiple of Hkv: query head
    i reads key/value head i // (H / Hkv) (grouped-query attention; the
    keys and values are never repeated).  fp32 softmax accumulation.
    ``offset`` shifts query positions for decode-with-cache; a scalar
    applies to every row, a (B,) vector gives per-row offsets (mixed
    prompt lengths in one continuously-batched decode).  ``bias`` is an
    fp32 additive score bias broadcastable to (B, H, Sq, Sk) — e.g. a
    padding mask for encoder models (BERT).

    ``window`` > 0 (causal only): a query at position p sees the keys at
    p - window + 1 .. p.  ``k_positions`` ((1 or B, Sk) int32, causal
    only): the position each key holds where that is not its place in
    ``k`` (a ring cache: ``update_ring_cache``); a negative one holds
    nothing and is seen by no query.
    """
    dim = q.shape[-1]
    b, sq, nh = q.shape[0], q.shape[1], q.shape[2]
    sk, nkv = k.shape[1], k.shape[2]
    if nkv == nh:
        scores = jnp.einsum("bqhd,bkhd->bhqk", q, k).astype(jnp.float32)
    else:
        grouped = q.reshape(b, sq, nkv, nh // nkv, dim)
        scores = jnp.einsum("bqhgd,bkhd->bhgqk", grouped, k).astype(
            jnp.float32).reshape(b, nh, sq, sk)
    scores = scores / np.sqrt(dim)
    if bias is not None:
        scores = scores + bias.astype(jnp.float32)
    if causal:
        offset = jnp.asarray(offset, jnp.int32)
        q_pos = jax.lax.broadcasted_iota(jnp.int32, (sq, sk), 0)
        k_pos = jax.lax.broadcasted_iota(jnp.int32, (sq, sk), 1)
        if k_positions is not None:
            # (1 or B, Sq, Sk): every row of keys has its own positions
            k_pos = jnp.broadcast_to(k_positions[:, None, :],
                                     (k_positions.shape[0], sq, sk))
            q_pos = q_pos[None] + (offset if offset.ndim == 0
                                   else offset[:, None, None])
            mask = (q_pos >= k_pos) & (k_pos >= 0)
            if window:
                mask &= q_pos - k_pos < window
            mask = mask[:, None]                             # (.,1,Sq,Sk)
        elif offset.ndim == 0:
            mask = q_pos + offset >= k_pos
            if window:
                mask &= q_pos + offset - k_pos < window
            mask = mask[None, None]                          # (1,1,Sq,Sk)
        else:
            q_pos = q_pos[None] + offset[:, None, None]
            mask = q_pos >= k_pos[None]
            if window:
                mask &= q_pos - k_pos[None] < window
            mask = mask[:, None]                             # (B,1,Sq,Sk)
        scores = jnp.where(mask, scores, jnp.float32(-1e9))
    probs = jax.nn.softmax(scores, axis=-1).astype(q.dtype)
    if nkv == nh:
        return jnp.einsum("bhqk,bkhd->bqhd", probs, v)
    out = jnp.einsum("bhgqk,bkhd->bqhgd",
                     probs.reshape(b, nkv, nh // nkv, sq, sk), v)
    return out.reshape(b, sq, nh, dim)


def get_attention_fn(config: GPTConfig) -> Callable:
    if config.attention_impl == "flash":
        from alpa_tpu.ops.flash_attention import flash_attention
        return flash_attention
    if config.attention_impl == "ring":
        from alpa_tpu.ops.ring_attention import ring_attention
        return partial(ring_attention, axis_name=config.sp_axis)
    if config.attention_impl == "ulysses":
        from alpa_tpu.ops.ulysses_attention import ulysses_attention
        return partial(ulysses_attention, axis_name=config.sp_axis)
    return reference_attention


# the scope the attention core of every layer is traced under
ATTENTION_SCOPE = "attention"


def _write_rows(cache, new, index):
    """``cache`` (B, S, H, D) with ``new`` (B, s, H, D) written at
    positions ``index[r] .. index[r] + s - 1`` of each row ``r``: one
    ``dynamic_update_slice`` a row.  The TPU compiler runs those in place
    in whatever dimension order it keeps the cache in, where a ``scatter``
    wants its operand row-major and costs a copy of the whole cache into
    that order and one back (``tests/serve/test_decode_in_place.py``).

    A row whose write does not fit (``index[r]`` outside ``[0, S - s]``)
    stays as it was: ``dynamic_update_slice`` clamps its start, so such a
    row writes back the ``s`` positions it read there.
    """
    seq_len, s = cache.shape[1], new.shape[1]
    fits = (index >= 0) & (index <= seq_len - s)
    start = jnp.clip(index, 0, seq_len - s)
    for r in range(cache.shape[0]):
        at = (r, start[r], 0, 0)
        old = jax.lax.dynamic_slice(cache, at, (1,) + new.shape[1:])
        cache = jax.lax.dynamic_update_slice(
            cache, jnp.where(fits[r], new[r:r + 1], old), at)
    return cache


def update_kv_cache(kv_cache, k, v):
    """Write step K/V into a resident cache and return the attendable
    views — the mechanics shared by every decoder family (GPT/OPT,
    Bloom, CodeGen).

    ``kv_cache`` is (k_cache, v_cache, index) with a scalar index
    (uniform write position) or a (B,) vector (per-row positions for
    mixed-length continuous batching).  Returns
    ``(k_use, v_use, new_cache)`` where k_use/v_use are the full-length
    caches with unwritten positions zeroed (masked from attention by the
    caller's causal offset) and ``new_cache`` carries index + s.

    Per-row indices: a row whose ``s`` positions do not all fit in the
    cache is not written at all (``_write_rows``), where the scatter this
    replaced still wrote the positions that fit; its index advances all
    the same.  No caller lets an active row get there (``generate``, the
    speculative rounds and the engine's ``submit`` refuse a request that
    would).  The rows that do are the engine's free rows, which are
    decoded along in every tick with an index that only grows: nothing
    reads them, and the next admission overwrites the whole row and its
    index.
    """
    k_cache, v_cache, index = kv_cache
    b, s = k.shape[0], k.shape[1]
    index = jnp.asarray(index, jnp.int32)
    if index.ndim == 0:
        k_full = jax.lax.dynamic_update_slice_in_dim(
            k_cache, k.astype(k_cache.dtype), index, axis=1)
        v_full = jax.lax.dynamic_update_slice_in_dim(
            v_cache, v.astype(v_cache.dtype), index, axis=1)
        keep_len = index + s
    else:
        k_full = _write_rows(k_cache, k.astype(k_cache.dtype), index)
        v_full = _write_rows(v_cache, v.astype(v_cache.dtype), index)
        keep_len = (index + s)[:, None]
    pos = jax.lax.broadcasted_iota(jnp.int32, (k_full.shape[1],), 0)
    keep = pos < keep_len
    if keep.ndim == 1:
        keep = keep[None]
    k_use = jnp.where(keep[:, :, None, None], k_full,
                      jnp.zeros_like(k_full))
    v_use = jnp.where(keep[:, :, None, None], v_full,
                      jnp.zeros_like(v_full))
    return k_use, v_use, (k_full, v_full, index + s)


def update_ring_cache(kv_cache, k, v, lengths=None):
    """``update_kv_cache`` for a "sliding" layer, whose cache is a ring:
    ``kv_cache`` is (k_cache, v_cache, index) with caches of (B, W, Hkv, D),
    position p lives in slot ``p % W``, and ``index`` (a scalar, or (B,) a
    row) is the position of the first of the ``s`` new tokens.  Returns
    ``(k_use, v_use, k_positions, new_cache)``: the keys and values to
    attend over and the position each of them holds ((1 or B, Sk); negative
    where a slot holds nothing yet), for ``reference_attention``: the mask
    of a ring goes by the position a slot holds, not by the slot.

    One new token (a decode tick): it is written first, one
    ``dynamic_update_slice`` a row as in ``_write_rows``, and the ring is
    what is attended over: slot j holds the latest position at or before
    ``index`` that is congruent to j.

    Several (a prefill chunk, a verify step): writing them first could
    overwrite positions the chunk's early queries still see, so they
    attend over the ring as it was and the new keys behind it, and the
    ring is written after: slot j takes the LAST new token that belongs in
    it.  ``lengths`` ((B,), the rows' whole lengths) says which new tokens
    are real: a right-padded chunk must not write its padding, because
    slot ``p % W`` of a padded position p holds position p - W, which the
    row's next tokens still see (in a full-length cache the padding lands
    past the row's end and is harmless).
    """
    k_cache, v_cache, index = kv_cache
    w, s = k_cache.shape[1], k.shape[1]
    k, v = k.astype(k_cache.dtype), v.astype(v_cache.dtype)
    index = jnp.asarray(index, jnp.int32)
    first = index[:, None] if index.ndim else index[None, None]  # (.,1)
    slots = jax.lax.broadcasted_iota(jnp.int32, (1, w), 1)

    def held(last):
        """The position each slot holds when ``last`` (.,1) is the
        newest position written (negative: none)."""
        return last - (last - slots) % w

    if s == 1:
        if index.ndim:
            k_new = _write_rows(k_cache, k, index % w)
            v_new = _write_rows(v_cache, v, index % w)
        else:
            k_new = jax.lax.dynamic_update_slice_in_dim(
                k_cache, k, index % w, axis=1)
            v_new = jax.lax.dynamic_update_slice_in_dim(
                v_cache, v, index % w, axis=1)
        return k_new, v_new, held(first), (k_new, v_new, index + 1)

    new_pos = first + jax.lax.broadcasted_iota(jnp.int32, (1, s), 1)
    k_positions = jnp.concatenate(
        [jnp.broadcast_to(held(first - 1), (first.shape[0], w)), new_pos],
        axis=1)
    k_use = jnp.concatenate([k_cache, k], axis=1)
    v_use = jnp.concatenate([v_cache, v], axis=1)
    # how many of a row's new tokens are real, and the last of them
    real = jnp.full_like(first, s) if lengths is None else \
        jnp.clip(lengths[:, None] - first, 0, s)
    # the new token slot j is to hold; before the chunk: the slot keeps
    # what it has
    source = jnp.broadcast_to(held(first + real - 1) - first,
                              (k.shape[0], w))
    take = (source >= 0)[:, :, None, None]
    at = jnp.clip(source, 0, s - 1)[:, :, None, None]
    k_new = jnp.where(take, jnp.take_along_axis(k, at, axis=1), k_cache)
    v_new = jnp.where(take, jnp.take_along_axis(v, at, axis=1), v_cache)
    return k_use, v_use, k_positions, (k_new, v_new, index + s)


class SelfAttention(nn.Module):
    """``attention`` is the layer's kind (``GPTConfig.attention``; None:
    the configuration's, which must then be one kind for all layers)."""
    config: GPTConfig
    attention: Optional[str] = None

    @nn.compact
    def __call__(self, x, kv_cache=None, deterministic=True,
                 attn_bias=None, position_ids=None, cache_lengths=None):
        cfg = self.config
        h, nh, nkv, hd = (cfg.hidden_size, cfg.num_heads, cfg.kv_heads,
                          cfg.head_size)
        kind = self.attention or cfg.attention_kind(0)
        if kind not in ("full", "sliding"):
            raise ValueError(f"unknown attention kind {kind!r}")
        window = cfg.sliding_window if kind == "sliding" else 0
        dense = partial(nn.Dense, dtype=cfg.dtype, use_bias=cfg.use_bias,
                        param_dtype=cfg.param_dtype)
        qkv = dense((nh + 2 * nkv) * hd, name="qkv")(x)
        q, k, v = jnp.split(qkv, [nh * hd, (nh + nkv) * hd], axis=-1)
        b, s = x.shape[0], x.shape[1]
        if cfg.qk_norm is True:
            q = make_norm(cfg, "q_norm")(q).astype(cfg.dtype)
            k = make_norm(cfg, "k_norm")(k).astype(cfg.dtype)
        q = q.reshape(b, s, nh, hd)
        k = k.reshape(b, s, nkv, hd)
        v = v.reshape(b, s, nkv, hd)
        if cfg.qk_norm == "head":
            q = make_norm(cfg, "q_norm")(q).astype(cfg.dtype)
            k = make_norm(cfg, "k_norm")(k).astype(cfg.dtype)
        elif cfg.qk_norm not in (True, False):
            raise ValueError(f"unknown qk_norm {cfg.qk_norm!r}")
        if cfg.positions == "rotary" and (kind == "sliding" or
                                          cfg.rope_on_full_attention):
            q = apply_rotary(q, position_ids, cfg.rope_theta)
            k = apply_rotary(k, position_ids, cfg.rope_theta)

        new_cache = None
        # the scope of the attention core (the cache's update, scores,
        # softmax, values; not the projections): the benchmark finds its
        # device events by it (HLO metadata ``op_name``)
        with jax.named_scope(ATTENTION_SCOPE):
            if kv_cache is not None and window:
                if attn_bias is not None:
                    raise ValueError("a sliding-window layer's ring cache "
                                     "takes no score bias (packed prefill)")
                index = jnp.asarray(kv_cache[2], jnp.int32)
                k_use, v_use, k_positions, new_cache = update_ring_cache(
                    kv_cache, k, v, cache_lengths)
                out = reference_attention(
                    q, k_use, v_use, causal=True, offset=index,
                    window=window, k_positions=k_positions)
            elif kv_cache is not None:
                index = jnp.asarray(kv_cache[2], jnp.int32)
                k_use, v_use, new_cache = update_kv_cache(kv_cache, k, v)
                # scores to future positions masked by causal offset;
                # attn_bias (e.g. the packed-prefill segment mask) rides on
                # top of the causal mask over the full cache length
                out = reference_attention(q, k_use, v_use, causal=True,
                                          offset=index, bias=attn_bias)
            elif attn_bias is not None or window or nkv != nh:
                # additive padding/score bias: encoder path only (the
                # flash/ring kernels take no bias operand, no window and
                # no grouped heads)
                if (window or nkv != nh) and \
                        cfg.attention_impl != "reference":
                    raise ValueError(
                        "sliding-window and grouped-query attention need "
                        "attention_impl 'reference'")
                out = reference_attention(q, k, v, causal=cfg.causal,
                                          bias=attn_bias, window=window)
            else:
                attn_fn = get_attention_fn(cfg)
                out = attn_fn(q, k, v, causal=cfg.causal)
        out = out.reshape(b, s, nh * hd)
        if cfg.attn_gate:
            out = out * jax.nn.sigmoid(dense(nh * hd, name="gate")(x))
        out = dense(h, name="out")(out)
        return out, new_cache


def activation_fn(name: str) -> Callable:
    if name == "relu":
        return nn.relu
    if name == "silu":
        return nn.silu
    return partial(nn.gelu, approximate=True)


class MLPBlock(nn.Module):
    """The dense MLP: in, activation, out; ``gated``: act(gate) * up, down
    (Shazeer 2020, the SwiGLU of today's decoders with "silu").  ``width``:
    None is the configuration's ``mlp_width``."""
    config: GPTConfig
    gated: bool = False
    width: Optional[int] = None

    @nn.compact
    def __call__(self, x):
        cfg = self.config
        width = self.width or cfg.mlp_width
        dense = partial(nn.Dense, dtype=cfg.dtype, use_bias=cfg.use_bias,
                        param_dtype=cfg.param_dtype)
        act = activation_fn(cfg.activation)
        if self.gated:
            x = act(dense(width, name="gate")(x)) * \
                dense(width, name="up")(x)
            return dense(cfg.hidden_size, name="down")(x)
        x = act(dense(width, name="fc_in")(x))
        return dense(cfg.hidden_size, name="fc_out")(x)


class TransformerBlock(nn.Module):
    """One pre-norm decoder block.  ``mlp`` is the kind of its MLP
    (``GPTConfig.mlp``) and ``attention`` of its attention
    (``GPTConfig.attention``; None: the configuration's, which must then
    be one kind for all layers).  With ``post_norms`` what the attention
    and the MLP return is normalised once more before it joins the
    residual stream.  Returns ``(x, new_cache)``, and a block of routed
    experts ``(x, new_cache, routing)``: what its router did
    (``moe.DroplessExperts``)."""
    config: GPTConfig
    mlp: Optional[str] = None
    attention: Optional[str] = None

    @nn.compact
    def __call__(self, x, kv_cache=None, deterministic=True,
                 attn_bias=None, position_ids=None, cache_lengths=None):
        cfg = self.config
        kind = self.mlp or cfg.mlp_kind(0)
        ln1 = make_norm(cfg, "ln1")(x)
        attn_out, new_cache = SelfAttention(
            cfg, attention=self.attention, name="attn")(
                ln1, kv_cache, deterministic, attn_bias, position_ids,
                cache_lengths)
        if cfg.post_norms:
            attn_out = make_norm(cfg, "ln1_post")(attn_out)
        x = x + attn_out.astype(x.dtype)
        ln2 = make_norm(cfg, "ln2")(x)
        routing = ()
        if kind == "experts":
            from alpa_tpu.model.moe import DroplessExperts
            y, what = DroplessExperts(cfg, name="mlp")(ln2)
            routing = (what,)
        elif kind in ("dense", "gated"):
            y = MLPBlock(cfg, gated=kind == "gated", name="mlp")(ln2)
        else:
            raise ValueError(f"unknown mlp kind {kind!r}")
        if cfg.post_norms:
            y = make_norm(cfg, "ln2_post")(y)
        return (x + y.astype(x.dtype), new_cache) + routing


class GPTModel(nn.Module):
    """Decoder-only LM.  Returns logits (and new kv caches if given).  A
    configuration with routed-expert layers returns ``(logits, routing)``
    from the training call: ``moe.routing_summary`` of its layers (the
    load-balancing term, each expert's rows, every token's experts)."""
    config: GPTConfig

    @nn.compact
    def __call__(self, input_ids, position_ids=None, kv_caches=None,
                 deterministic=True, return_hidden=False,
                 segment_ids=None, cache_lengths=None,
                 return_routing=False):
        """``return_hidden=True`` returns the final (B, S, H) hidden states
        instead of logits, for a fused/chunked lm-head + loss (see
        model_util.chunked_cross_entropy_loss).

        ``segment_ids`` (B, S) int32 enables PACKED sequences: tokens only
        attend within their own segment (block-diagonal mask on top of
        causal); ids < 0 mark padding that attends to nothing.  This is
        the TPU-native analog of the reference's 1-D packed batching
        (ref opt_model_1d.py fused-MHA prompt packing): one row carries
        many prompts, masked by segments instead of a custom kernel.
        Pass per-segment ``position_ids`` so positional embeddings
        restart at each segment start.

        ``cache_lengths`` ((B,), with ``kv_caches``): the rows' whole
        lengths, where the ids are right-padded past them: a layer whose
        cache is a ring must not write the padding
        (``update_ring_cache``).  ``return_routing`` (with ``kv_caches``,
        routed layers): a third result, what the routed layers' routers
        did: ``experts`` (expert layers, tokens, k) int32, every token's
        experts.
        """
        cfg = self.config
        b, s = input_ids.shape
        if position_ids is None:
            position_ids = jax.lax.broadcasted_iota(jnp.int32, (b, s), 1)
        seg_bias = None
        if segment_ids is not None:
            if kv_caches is not None:
                # The packed chunk is written at the caches' current
                # (scalar) index — 0 for a fresh packed prefill, or the
                # prefix length when packing over a cached system prompt.
                # Keys before that offset are the shared prefix: visible
                # to EVERY real segment; keys past the chunk stay -2.
                cache_len = kv_caches[0][0].shape[1]
                start = jnp.asarray(kv_caches[0][2], jnp.int32)
                seg_k = jnp.full((b, cache_len), -2, jnp.int32)
                seg_k = jax.lax.dynamic_update_slice(
                    seg_k, segment_ids, (0, start))
                kpos = jax.lax.broadcasted_iota(
                    jnp.int32, (1, cache_len), 1)
                prefix_k = kpos < start                      # (1, L)
                same = ((segment_ids[:, :, None] == seg_k[:, None, :]) |
                        prefix_k[:, None, :]) & \
                    (segment_ids[:, :, None] >= 0)
            else:
                same = (segment_ids[:, :, None] ==
                        segment_ids[:, None, :]) & \
                    (segment_ids[:, :, None] >= 0)
            seg_bias = jnp.where(same, 0.0, -1e9)[:, None]  # (B,1,S,L)
        tok_emb = nn.Embed(cfg.vocab_size, cfg.hidden_size,
                           dtype=cfg.dtype, param_dtype=cfg.param_dtype,
                           name="wte")
        x = tok_emb(input_ids)
        if cfg.scale_embedding:
            x = x * jnp.asarray(np.sqrt(cfg.hidden_size), x.dtype)
        if cfg.positions == "learned":
            x = x + nn.Embed(cfg.seq_len + cfg.pos_offset, cfg.hidden_size,
                             dtype=cfg.dtype, param_dtype=cfg.param_dtype,
                             name="wpe")(position_ids + cfg.pos_offset)
        elif cfg.positions != "rotary":
            raise ValueError(f"unknown positions {cfg.positions!r}")
        # the blocks of learned positions never see them
        block_positions = position_ids if cfg.positions == "rotary" else None
        block_cls = TransformerBlock
        if cfg.remat_blocks and kv_caches is None:
            policy = None
            if cfg.remat_policy == "dots":
                policy = jax.checkpoint_policies. \
                    dots_with_no_batch_dims_saveable
            elif cfg.remat_policy is not None:
                raise ValueError(
                    f"unknown remat_policy {cfg.remat_policy!r}")
            # Under nn.remat the module instance is arg 0, so the call
            # (x, cache_i, deterministic, seg_bias) puts kv_cache at 2
            # and deterministic at 3 — mark BOTH static; attn_bias (4)
            # stays a traced pytree (None or the packed segment mask)
            block_cls = nn.remat(TransformerBlock,
                                 static_argnums=(2, 3),
                                 policy=policy)
        new_caches = [] if kv_caches is not None else None
        routings = []
        for i in range(cfg.num_layers):
            if (cfg.pipeline_boundary_every and i > 0 and
                    i % cfg.pipeline_boundary_every == 0):
                mark_pipeline_boundary()
            block = block_cls(cfg, mlp=cfg.mlp_kind(i),
                              attention=cfg.attention_kind(i), name=f"h{i}")
            cache_i = kv_caches[i] if kv_caches is not None else None
            x, new_cache, *routing = block(
                x, cache_i, deterministic, seg_bias, block_positions,
                cache_lengths)
            routings += routing
            if new_caches is not None:
                new_caches.append(new_cache)
        x = make_norm(cfg, "ln_f")(x)
        if return_hidden:
            return x
        if cfg.tie_embeddings:
            logits = tok_emb.attend(x.astype(cfg.dtype))
        else:
            logits = nn.Dense(cfg.vocab_size, dtype=cfg.dtype,
                              use_bias=False, param_dtype=cfg.param_dtype,
                              name="lm_head")(x)
        if new_caches is not None:
            if return_routing:
                return logits, new_caches, {
                    "experts": jnp.stack([r["experts"] for r in routings])}
            return logits, new_caches
        if routings:
            from alpa_tpu.model.moe import routing_summary
            return logits, routing_summary(routings)
        return logits


def kv_cache_shapes(config, batch_size: int) -> list:
    """The (B, positions, key/value heads, head size) of every layer's K
    and V cache: ``seq_len`` positions in a "full" layer, a ring of
    ``sliding_window`` (at most ``seq_len``) in a "sliding" one.  Takes
    any decoder family's configuration: what ``GPTConfig`` alone has reads
    as its default."""
    heads = getattr(config, "num_kv_heads", None) or config.num_heads
    hd = getattr(config, "head_dim", None) or \
        config.hidden_size // config.num_heads
    kinds = getattr(config, "attention", "full")
    shapes = []
    for i in range(config.num_layers):
        kind = kinds if isinstance(kinds, str) else kinds[i]
        length = min(config.sliding_window, config.seq_len) \
            if kind == "sliding" else config.seq_len
        shapes.append((batch_size, length, heads, hd))
    return shapes


def uniform_kv_caches(config) -> bool:
    """Whether every layer's cache has one shape: what the block pool, the
    packed prefill, the speculative verify step and beam search count on
    (one block table, one length and one index for all layers)."""
    return len(set(kv_cache_shapes(config, 1))) == 1


def require_uniform_kv_caches(config, what: str):
    if not uniform_kv_caches(config):
        raise ValueError(
            f"{what} indexes one cache shape for all layers, and this "
            "configuration's layers differ (sliding-window layers hold a "
            "ring of the window's positions, full layers the context): "
            f"{sorted(set(kv_cache_shapes(config, 1)))}")


def init_kv_caches(config: GPTConfig, batch_size: int,
                   dtype=None) -> list:
    """KV caches as explicit arrays (ref opt_model.py:605 init_cache_aval):
    ``[(k, v, index)]`` a layer, each layer's of its own shape
    (``kv_cache_shapes``)."""
    dtype = dtype or config.dtype
    return [(jnp.zeros(shape, dtype), jnp.zeros(shape, dtype), jnp.int32(0))
            for shape in kv_cache_shapes(config, batch_size)]


def init_gpt(config: GPTConfig, batch_size: int, rngkey=None):
    """Initialize model + params on host."""
    rngkey = rngkey if rngkey is not None else jax.random.PRNGKey(0)
    model = GPTModel(config)
    dummy = jnp.ones((batch_size, config.seq_len), jnp.int32)
    params = jax.eval_shape(model.init, rngkey, dummy)
    params = jax.tree_util.tree_map(
        lambda s: jnp.zeros(s.shape, s.dtype), params)
    return model, params


def init_gpt_real(config: GPTConfig, batch_size: int, rngkey=None):
    rngkey = rngkey if rngkey is not None else jax.random.PRNGKey(0)
    model = GPTModel(config)
    dummy = jnp.ones((batch_size, config.seq_len), jnp.int32)
    params = model.init(rngkey, dummy)
    return model, params
