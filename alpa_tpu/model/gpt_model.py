"""GPT-style decoder-only transformer (flax), TPU-first.

Clean-room analog of ref ``alpa/model/gpt_model.py`` (which wraps
``bert_model.py``'s encoder with a causal mask).  Design choices for TPU:

* bfloat16 activations/params option; fp32 layernorm + softmax accumulation,
* einsum-formulated attention so batch/head/seq dims are clean mesh targets
  for the auto-sharding planner,
* the attention core of a layer without a cache (``attention_impl``):
  "reference" is the core the call's shapes choose (``attention``: the
  fused kernels of ops/flash_attention where they fit and the program is
  lowered for a TPU, the einsums of ``reference_attention`` otherwise) |
  "ring" | "ulysses" (sequence-parallel attention over a mesh axis),
* optional ``mark_pipeline_boundary()`` between blocks for manual pipeline
  layer construction (ref ManualLayerOption),
* KV-cache threading for autoregressive serving (cache as explicit
  function inputs/outputs, mirroring ref examples/llm_serving/model/
  opt_model.py:605 init_cache_aval design).

The GPT ladder (125M..76B, ref benchmark/alpa/suite_manual_gpt.py:18-26) is
reproduced in ``gpt_specs``.
"""
import contextlib
import dataclasses
from functools import partial
from typing import Any, Callable, Optional, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np

from alpa_tpu.pipeline_parallel.primitive_def import mark_pipeline_boundary
from alpa_tpu.shard_parallel import kernel_choice
from alpa_tpu.telemetry import metrics as tmetrics


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
    # "reference" (the core the call's shapes choose) | "ring" | "ulysses"
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
    # model/moe.py DroplessExperts) | "gated+shortcut" (a gated MLP that
    # joins the stream as "gated" does, and beside it routed experts on
    # the same normed input whose sum is HELD BACK: the next block adds it
    # with its own MLP's output, ``TransformerBlock``) | "none" (no MLP and
    # no second norm: the layer is its mixer alone, ``x + mixer(ln1(x))``)
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
    # | "latent" (below: its cache holds seq_len positions of a latent)
    # | "latent_sliding" (below: latent attention under the window, with
    # the widths of ``sliding_latent``; its cache is a ring of latents)
    # | "conv" (no attention: a gated short convolution, ``ShortConv``; its
    # state is the last ``conv_taps - 1`` positions of a product, whatever
    # the row's length) | "ssm" (no attention: a Mamba-2 mixer, ``Mamba2``;
    # its state is a matrix a head and the last ``conv_taps - 1`` positions
    # of what its convolution runs over, whatever the row's length) | "s6"
    # (no attention: a Mamba-1 mixer, ``Mamba1``; its state is
    # ``ssm_state_size`` values a channel and the last ``conv_taps - 1``
    # positions of its ``s6_inner`` channels) | "none"
    # (no mixer and no first norm: the layer is its MLP alone, ``x +
    # mlp(ln2(x))``, and its cache entry holds nothing)
    attention: Any = "full"
    sliding_window: int = 0
    # False: rotary positions turn the q and k of "sliding" layers only,
    # "full" layers see no positions at all
    rope_on_full_attention: bool = True
    # sigmoid(gate(h)) on the heads' output, before the output projection:
    # True, a gate a channel of every head (``SelfAttention``); "head", one
    # gate a head (``LatentAttention``)
    attn_gate: Any = False
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
    # --- latent attention (MLA) and group-limited routing over a share of
    # the experts (DeepSeek-V2, ``model_type`` deepseek_v2).  Every default
    # is the block of today.  An ``attention`` kind "latent": queries
    # through a low-rank projection of ``q_lora_rank`` (0: one full
    # matrix), keys and values through one of ``kv_lora_rank`` with an
    # RMSNorm each; a head scores with ``qk_nope_head_dim`` channels that
    # see no positions and ``qk_rope_head_dim`` rotated ones, whose key is
    # ONE for all heads, and carries values of ``v_head_dim``.  The cache
    # of such a layer holds the normed latent and the rotated key of every
    # position (``kv_lora_rank + qk_rope_head_dim`` values) and nothing a
    # head (``LatentAttention``).
    q_lora_rank: int = 0
    kv_lora_rank: int = 0
    qk_nope_head_dim: int = 0
    qk_rope_head_dim: int = 0
    v_head_dim: int = 0
    # what a latent layer's scores are multiplied by; None:
    # (qk_nope_head_dim + qk_rope_head_dim) ** -0.5
    attn_scale: Optional[float] = None
    # rotary pairs: channel i with i + D/2 (rotate-half), or 2i with 2i + 1
    rope_interleaved: bool = False
    # YaRN (Peng et al. 2023): (factor, original context, beta_fast,
    # beta_slow, what cosine and sine are multiplied by); None: plain
    # frequencies (``yarn_inv_freq``)
    rope_yarn: Optional[Tuple[float, ...]] = None
    # group-limited routing: the experts in ``n_group`` groups of
    # consecutive experts, a token's experts chosen inside its best
    # ``topk_group`` groups (``moe.topk_routing``)
    n_group: int = 1
    topk_group: int = 1
    # (first, count): the routed experts THIS program holds, where the
    # layer's experts are divided over several chips; the router stays
    # ``num_experts`` wide (``moe.DroplessExperts``).  None: all of them
    experts_held: Optional[Tuple[int, int]] = None
    # --- a layer of two sub-blocks whose routed experts skip the second
    # attention (shortcut-connected MoE), identity experts and scaled
    # latents (``model_type`` longcat_flash).  Every default is the block
    # of today.  Router outputs ``num_experts .. num_experts +
    # num_zero_experts - 1`` are experts without matrices: a pick of one
    # adds its weight times the expert layer's input
    # (``moe.DroplessExperts``)
    num_zero_experts: int = 0
    # what a latent layer's normed query latent and normed key/value
    # latent are multiplied by, in float32 before the cast (the file's
    # ``mla_scale_q_lora`` / ``mla_scale_kv_lora``: sqrt(hidden_size /
    # rank)); the cache holds the scaled latent
    q_lora_scale: float = 1.0
    kv_lora_scale: float = 1.0
    # --- generation by diffusion over blocks (``model_type`` sdar_moe).
    # Positions come in blocks of ``block_length``; a query sees every key
    # of its own block and of the blocks before it (the later positions of
    # its block too: ``reference_attention``'s ``block``), and the logit at
    # a position predicts the token AT it.  0: the causal mask of today.
    block_length: int = 0
    # --- gated short convolutions among the attention layers
    # (``model_type`` lfm2_moe).  The taps of a "conv" layer's depthwise
    # causal convolution (the file's ``conv_L_cache``); 0: no such layer.
    conv_taps: int = 0
    # --- latent attention over a learned selection of positions, beside
    # windowed latent attention with widths of its own (``model_type``
    # dots3_note).  Every default is the block of today.  A "latent"
    # layer with ``index_topk`` > 0 attends, a query, over the
    # ``index_topk`` positions at or before it that an indexer scores
    # highest (all of them while there are no more): ``index_n_heads``
    # heads of ``index_head_dim`` channels (``LatentAttention``); its cache
    # holds an index key a position beside the latent.
    index_topk: int = 0
    index_n_heads: int = 0
    index_head_dim: int = 0
    # the indexer's rotary pairs: channel i with i + D/2 (rotate-half, as
    # DeepSeek-V3.2-Exp's Indexer has them), or 2i with 2i + 1 (a file's
    # ``indexer_rope_interleave``)
    index_rope_interleaved: bool = False
    # the widths of the ``attention`` kind "latent_sliding" (a latent
    # layer under ``sliding_window``, its cache a ring of the window's
    # latents), which need not be the "latent" layers': heads, ranks, head
    # sizes, rotary base and factors (``LatentWidths``); None: no such layer
    sliding_latent: Optional["LatentWidths"] = None
    # --- window and full layers of per-head keys and values that differ in
    # more than the mask (``model_type`` mimo_v2_flash).  Every default is
    # the block of today.  The key/value heads and the rotary base of the
    # "sliding" layers where they are not the "full" layers' (None:
    # ``num_kv_heads``, ``rope_theta``)
    sliding_kv_heads: Optional[int] = None
    sliding_rope_theta: Optional[float] = None
    # channels of a "full" or "sliding" layer's value heads where they are
    # not the keys' ``head_size`` (``v_head_dim`` above, 0: as the keys).
    # The leading channels of every q and k head that rotary positions
    # turn, the others passing as they are; 0: all of them
    rotary_dim: int = 0
    # what the heads' weighted values are multiplied by before the output
    # projection
    value_scale: float = 1.0
    # the attention kinds ("full", "sliding") whose softmax has a learned
    # logit a query head in its denominator (a sink: it takes probability
    # and carries no value; parameter ``sink``, float32)
    sink_kinds: Tuple[str, ...] = ()
    # --- multi-token-prediction modules (``model_type`` glm_moe_dsa, after
    # DeepSeek-V3's report, section 2.2).  Every default is the model of
    # today.  How many modules the model has behind its last layer (today
    # at most one): a module joins the embedding of a position's NEXT
    # token with the model's final-normed hidden state there, runs one
    # block of the last layer's kinds over it, with a cache entry of its
    # own behind the layers', and reads the token after next off the
    # model's own head (``MultiTokenPredictor``).  A served model drafts
    # with it inside the engine's tick (``serve/generation.py``
    # ``_verify_draft``).
    num_nextn_predict_layers: int = 0
    # --- layers that are a mixer or a feed-forward part alone, Mamba-2
    # mixers and ungated experts (``model_type`` nemotron_h).  Every
    # default is the block of today.  An ``attention`` kind "ssm"
    # (``Mamba2``): ``ssm_heads`` heads of ``ssm_head_dim`` channels (the
    # mixer's inner width is their product, whatever the hidden size), a
    # state of ``ssm_head_dim x ssm_state_size`` float32 a head, ``B`` and
    # ``C`` in ``ssm_groups`` groups of consecutive heads, a causal
    # depthwise convolution of ``conv_taps`` taps with a bias over ``[x | B
    # | C]``, and the scan over ``s`` new positions in sub-chunks of
    # ``ssm_chunk``
    ssm_heads: int = 0
    ssm_head_dim: int = 0
    ssm_state_size: int = 0
    ssm_groups: int = 1
    ssm_chunk: int = 128
    # False: a routed expert (and a shared one) is ``down(act(up(x)))``, two
    # matrices and no gate
    expert_gated: bool = True
    # width of a shared expert where it is not the routed experts'
    shared_expert_width: Optional[int] = None
    # --- Mamba-1 mixers (``model_type`` jamba).  An ``attention`` kind
    # "s6" (``Mamba1``): ``s6_inner`` channels, each with
    # ``ssm_state_size`` state values float32 and a decay a channel AND a
    # state value, the step ``dt`` through a projection of rank
    # ``s6_dt_rank``, a causal depthwise convolution of ``conv_taps`` taps
    # with a bias over the channels
    s6_inner: int = 0
    s6_dt_rank: int = 0
    # --- keys of two kinds under one softmax (``model_type`` evabyte; EVA,
    # Zheng et al. 2023, in its deterministic form).  Every default is the
    # model of today.  An ``attention`` kind "eva": positions lie in
    # aligned windows of ``eva_window`` and chunks of ``eva_chunk``; a
    # query sees the exact keys of its OWN window up to itself and, in the
    # same softmax, one pooled key and value for every full chunk of every
    # window before it, pooled by two learned vectors a head (``mu`` the
    # keys, ``phi`` the values; ``eva_pool``).  Its cache holds both kinds
    # of row in one pair of arrays, the summaries' slots first
    # (``kv_cache_shapes``, ``update_eva_cache``)
    eva_window: int = 0
    eva_chunk: int = 0
    # the residual stream float32 whatever ``dtype``: every sub-block's
    # output is added into it in float32 and each norm reads it
    fp32_residual: bool = False
    # an RMSNorm's gain is ``1 + scale`` (the stored weight starts at 0)
    norm_unit_offset: bool = False
    # the head's logits float32: products of ``dtype``, summed in float32
    # and never rounded to ``dtype``
    fp32_logits: bool = False
    # prediction heads of ONE untied matrix, hidden -> ``num_pred_heads x
    # vocab_size``: the logits are (..., num_pred_heads x vocab_size), head
    # ``j``'s ``vocab_size`` columns (the ``j``-th group) predict the token
    # ``j + 1`` positions ahead off the same final hidden state
    num_pred_heads: int = 1

    def mlp_kind(self, layer: int) -> str:
        """The MLP kind of a layer; a multi-token-prediction module's
        block (``layer >= num_layers``) is of the last layer's kinds."""
        return self.mlp if isinstance(self.mlp, str) \
            else self.mlp[min(layer, self.num_layers - 1)]

    def attention_kind(self, layer: int) -> str:
        return self.attention if isinstance(self.attention, str) \
            else self.attention[min(layer, self.num_layers - 1)]

    @property
    def cache_entries(self) -> int:
        """Entries of the list of caches: one a layer, and behind them one
        a multi-token-prediction module's block."""
        return self.num_layers + self.num_nextn_predict_layers

    @property
    def mlp_width(self) -> int:
        return self.intermediate_size or self.mlp_ratio * self.hidden_size

    @property
    def expert_width(self) -> int:
        return self.moe_intermediate_size or self.mlp_width

    @property
    def ssm_inner(self) -> int:
        """Channels of an "ssm" layer's heads together."""
        return self.ssm_heads * self.ssm_head_dim

    @property
    def ssm_conv_width(self) -> int:
        """Channels an "ssm" layer's convolution runs over: ``[x | B |
        C]``."""
        return self.ssm_inner + 2 * self.ssm_groups * self.ssm_state_size

    @property
    def kv_heads(self) -> int:
        return self.num_kv_heads or self.num_heads

    @property
    def head_size(self) -> int:
        return self.head_dim or self.hidden_size // self.num_heads

    @property
    def value_size(self) -> int:
        """Channels of one value head of a "full" or "sliding" layer."""
        return self.v_head_dim or self.head_size

    @property
    def folds_full_caches(self) -> bool:
        """Whether a "full" layer's caches hold the key/value heads folded
        into the channels, (B, S, Hkv D) and (B, S, Hkv Dv): where the
        values are narrower than the keys, and where there is ONE
        key/value head (a second-minor dimension of 1 is a tile of 16 on
        the chip: kept a head, the cache would lie at sixteen times its
        bytes) (``kv_cache_shapes``)."""
        return self.value_size != self.head_size or self.kv_heads == 1

    @property
    def unlike_kinds(self) -> bool:
        """Whether the "full" and "sliding" layers differ in more than the
        mask: heads or a rotary base of their own, values narrower than the
        keys (caches of unlike shapes a kind AND an array), a sink."""
        return bool(self.sliding_kv_heads or self.sliding_rope_theta or
                    self.sink_kinds or self.value_size != self.head_size)

    def kv_heads_of(self, kind: str) -> int:
        """Key/value heads of a "full" or "sliding" layer."""
        if kind == "sliding" and self.sliding_kv_heads:
            return self.sliding_kv_heads
        return self.kv_heads

    def rope_theta_of(self, kind: str) -> float:
        if kind == "sliding" and self.sliding_rope_theta:
            return self.sliding_rope_theta
        return self.rope_theta

    def latent_widths(self, kind: str) -> "LatentWidths":
        """The widths of a latent layer of this kind: the configuration's
        own for "latent", ``sliding_latent`` for "latent_sliding"."""
        if kind == LATENT_SLIDING:
            if self.sliding_latent is None:
                raise ValueError("a \"latent_sliding\" layer needs "
                                 "GPTConfig.sliding_latent")
            return self.sliding_latent
        if kind != "latent":
            raise ValueError(f"no latent attention of the kind {kind!r}")
        return LatentWidths(
            self.num_heads, self.q_lora_rank, self.kv_lora_rank,
            self.qk_nope_head_dim, self.qk_rope_head_dim, self.v_head_dim,
            self.rope_theta, self.attn_scale, self.q_lora_scale,
            self.kv_lora_scale)

    def selects(self, kind: str) -> bool:
        """Whether a layer of this attention kind reads a selection of
        its positions (``index_topk``)."""
        return kind == "latent" and self.index_topk > 0


@dataclasses.dataclass(frozen=True)
class LatentWidths:
    """What one kind of latent layer is sized by (``GPTConfig``'s fields of
    the same names say what each is)."""
    num_heads: int
    q_lora_rank: int
    kv_lora_rank: int
    qk_nope_head_dim: int
    qk_rope_head_dim: int
    v_head_dim: int
    rope_theta: float
    attn_scale: Optional[float] = None
    q_lora_scale: float = 1.0
    kv_lora_scale: float = 1.0


# the attention kind of a latent layer under the window
LATENT_SLIDING = "latent_sliding"


# the MLP kind of a block whose routed experts are held back for the next
SHORTCUT_MLP = "gated+shortcut"


def routed_mlp(kind: str) -> bool:
    """Whether a block of this MLP kind has a router (and so returns what
    it did)."""
    return kind in ("experts", SHORTCUT_MLP)


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
    # DeepSeek-V2: wiring read from modeling_deepseek.py beside the
    # config.json and transformers' models/deepseek_v2 where config.json
    # does not fix it
    "deepseek_v2": dict(norm="rmsnorm", positions="rotary",
                        attention="latent", rope_interleaved=True,
                        fused_gate_up=True),
    # SDAR (JetLM): the Qwen3-MoE block (transformers'
    # models/qwen3_moe/modeling_qwen3_moe.py) under a block-causal mask
    "sdar_moe": dict(norm="rmsnorm", positions="rotary", qk_norm="head",
                     fused_gate_up=True),
    # LFM2 (LiquidAI): wiring read from transformers'
    # models/lfm2_moe/modeling_lfm2_moe.py where config.json does not fix
    # it (the tied head, the final norm, the head size, silu)
    "lfm2_moe": dict(norm="rmsnorm", positions="rotary", qk_norm="head",
                     router_score="sigmoid", fused_gate_up=True,
                     activation="silu", tie_embeddings=True),
    # LongCat-Flash (meituan-longcat): wiring read from the model's
    # modeling_longcat_flash.py where config.json does not fix it (silu,
    # the untied head, interleaved rotary pairs, the router's bias)
    "longcat_flash": dict(norm="rmsnorm", positions="rotary",
                          attention="latent", rope_interleaved=True,
                          fused_gate_up=True, router_bias=True,
                          activation="silu", tie_embeddings=False),
    # dots3-note: wiring read as the families its keys name where
    # config.json does not fix it (deepseek_v2's latent attention and
    # interleaved rotary pairs, DeepSeek-V3's sigmoid router with a choice
    # bias, DeepSeek-V3.2-Exp's indexer, LongCat-Flash's factors on the
    # latents, gated attention's head-wise gate)
    "dots3_note": dict(norm="rmsnorm", positions="rotary",
                       rope_interleaved=True, fused_gate_up=True,
                       router_score="sigmoid", router_bias=True),
    # MiMo-V2-Flash (XiaomiMiMo): wiring read as the families its keys name
    # where config.json does not fix it (DeepSeek-V3's sigmoid router with
    # a choice bias, rotate-half rotary pairs on the leading channels, the
    # sink as a logit a head in the softmax's denominator)
    "mimo_v2_flash": dict(norm="rmsnorm", positions="rotary",
                          fused_gate_up=True, router_score="sigmoid",
                          router_bias=True),
    # GLM-5 (zai-org): wiring read as the families its keys name where
    # config.json does not fix it (deepseek_v2's latent attention,
    # DeepSeek-V3's sigmoid router with a choice bias and its
    # multi-token-prediction module, DeepSeek-V3.2-Exp's indexer)
    "glm_moe_dsa": dict(norm="rmsnorm", positions="rotary",
                        attention="latent", fused_gate_up=True,
                        router_score="sigmoid", router_bias=True),
    # Nemotron-H (nvidia): wiring read from the family's report and its
    # published modelling code where config.json does not fix it (one
    # RMSNorm and one sub-layer a layer, no positions of any kind in the
    # attention layers, DeepSeek-V3's sigmoid router with a choice bias,
    # ungated experts)
    "nemotron_h": dict(norm="rmsnorm", positions="rotary",
                       rope_on_full_attention=False, router_score="sigmoid",
                       router_bias=True, expert_gated=False),
    # Jamba (ai21labs): wiring read from the family's report and its
    # published modelling code where config.json does not fix it (two
    # RMSNorms a layer, a gated MLP behind every mixer, no positions of
    # any kind in the attention layers, three RMSNorms inside the Mamba-1
    # mixer, no bias but the convolution's and ``dt_proj``'s)
    "jamba": dict(norm="rmsnorm", positions="rotary",
                  rope_on_full_attention=False, mlp="gated"),
    # EvaByte: wiring read from EVA's paper (Zheng et al., ICLR 2023) and
    # the family's description where config.json does not fix it (a
    # pre-norm block of attention and a gated MLP, rotate-half rotary pairs
    # over every channel at the absolute position, the pooling's form)
    "evabyte": dict(norm="rmsnorm", positions="rotary", attention="eva",
                    mlp="gated"),
}


def _depth_and_widths(hf: dict) -> dict:
    """The keys by which every family before longcat_flash names its depth,
    its dense width, its experts a token and its key/value heads."""
    fields = dict(num_layers=hf["num_hidden_layers"],
                  intermediate_size=hf["intermediate_size"],
                  num_experts_per_tok=hf["num_experts_per_tok"])
    if hf["num_key_value_heads"] != hf["num_attention_heads"]:
        fields["num_kv_heads"] = hf["num_key_value_heads"]
    return fields


def _act_eps_tie(hf: dict) -> dict:
    """Activation, the norms' epsilon and the tied head, where the file
    has all three (lfm2_moe's and longcat_flash's have not)."""
    return dict(activation=hf["hidden_act"],
                layer_norm_eps=hf["rms_norm_eps"],
                tie_embeddings=hf["tie_word_embeddings"])


def _olmoe_fields(hf: dict) -> dict:
    """``model_type`` olmoe: every layer routed, the router's settings."""
    return dict(_depth_and_widths(hf), **_act_eps_tie(hf),
                num_experts=hf["num_experts"],
                norm_topk_prob=hf["norm_topk_prob"])


def _lfm2_moe_fields(hf: dict) -> dict:
    """What ``config.json`` of ``model_type`` lfm2_moe says beyond the keys
    all decoders share: which layers are gated short convolutions and which
    attention, the convolution's taps, the leading dense layers, the sizes
    of the experts and the router's settings.  The file says ``norm_eps``,
    and has no ``hidden_act``, no ``head_dim`` and no
    ``tie_word_embeddings`` (``_HF_KINDS`` has what the model's code
    says of them)."""
    layers = hf["num_hidden_layers"]
    if len(hf["layer_types"]) != layers:
        raise ValueError("layer_types must name every layer")
    unknown = set(hf["layer_types"]) - {"conv", "full_attention"}
    if unknown:
        raise ValueError(f"unknown layer_types {sorted(unknown)}")
    if hf["conv_bias"]:
        raise ValueError("lfm2_moe: conv_bias true is not supported (the "
                         "short convolution and its projections have no "
                         "bias)")
    return dict(
        _depth_and_widths(hf),
        mlp=tuple("gated" if i < hf["num_dense_layers"] else "experts"
                  for i in range(layers)),
        attention=tuple("conv" if t == "conv" else "full"
                        for t in hf["layer_types"]),
        conv_taps=hf["conv_L_cache"], layer_norm_eps=hf["norm_eps"],
        moe_intermediate_size=hf["moe_intermediate_size"],
        num_experts=hf["num_experts"],
        router_bias=hf["use_expert_bias"],
        norm_topk_prob=hf["norm_topk_prob"],
        route_scale=float(hf["routed_scaling_factor"]))


def _sdar_moe_fields(hf: dict) -> dict:
    """What ``config.json`` of ``model_type`` sdar_moe says beyond the keys
    all decoders share: which layers route (Qwen3-MoE's rule: layer i has
    experts unless it is in ``mlp_only_layers`` or ``(i + 1) %
    decoder_sparse_step``), the sizes of heads and experts.  The block
    length is not in the file: a deployment states it (``block_length``)."""
    if hf.get("use_sliding_window"):
        raise ValueError("sdar_moe: use_sliding_window is not supported")
    step, dense_only = hf["decoder_sparse_step"], hf["mlp_only_layers"]
    return dict(
        _depth_and_widths(hf), **_act_eps_tie(hf),
        mlp=tuple("experts" if i not in dense_only and hf["num_experts"] > 0
                  and (i + 1) % step == 0 else "gated"
                  for i in range(hf["num_hidden_layers"])),
        head_dim=hf["head_dim"],
        moe_intermediate_size=hf["moe_intermediate_size"],
        num_experts=hf["num_experts"],
        norm_topk_prob=hf["norm_topk_prob"])


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
        _depth_and_widths(hf), **_act_eps_tie(hf),
        mlp=tuple("gated" if i < hf["num_dense_layers"] else "experts"
                  for i in range(layers)),
        attention=tuple("sliding" if t == "sliding_attention" else "full"
                        for t in hf["layer_types"]),
        sliding_window=hf["sliding_window"], head_dim=hf["head_dim"],
        num_experts=hf["num_experts"],
        moe_intermediate_size=hf["moe_intermediate_size"],
        router_score=hf["score_func"], norm_topk_prob=hf["route_norm"],
        route_scale=float(hf["route_scale"]),
        num_shared_experts=hf["num_shared_experts"],
        scale_embedding=hf["mup_enabled"])


def yarn_mscale(factor: float, mscale: float) -> float:
    """YaRN's attention temperature: 0.1 m ln(factor) + 1."""
    return 1.0 if factor <= 1 else 0.1 * mscale * np.log(factor) + 1.0


def yarn_inv_freq(dim: int, theta: float, factor: float, original: int,
                  beta_fast: float, beta_slow: float):
    """The ``dim / 2`` rotary frequencies under YaRN and the pairs
    ``(low, high)`` between which they are blended: pair i turns at
    ``f_i = theta^(-2i/dim)`` where it makes more than ``beta_fast`` turns
    over the ``original`` context (i <= low), at ``f_i / factor`` where
    fewer than ``beta_slow`` (i >= high), and at a linear blend of the two
    between."""
    f = theta ** (-np.arange(0, dim, 2, dtype=np.float64) / dim)

    def pair_of(turns):
        return dim * np.log(original / (turns * 2 * np.pi)) / \
            (2 * np.log(theta))

    low = max(int(np.floor(pair_of(beta_fast))), 0)
    high = min(int(np.ceil(pair_of(beta_slow))), dim - 1)
    ramp = np.clip((np.arange(dim // 2) - low) / max(high - low, 1e-3),
                   0.0, 1.0)
    return f * (1.0 - ramp) + f / factor * ramp, (low, high)


def _deepseek_v2_fields(hf: dict) -> dict:
    """What ``config.json`` of ``model_type`` deepseek_v2 says beyond the
    keys all decoders share: the ranks and head sizes of latent attention,
    YaRN, the leading dense layers, the routed and shared experts and the
    group-limited choice among them."""
    layers = hf["num_hidden_layers"]
    if hf.get("topk_method", "group_limited_greedy") != \
            "group_limited_greedy" or hf["scoring_func"] != "softmax":
        raise ValueError("deepseek_v2: only softmax scores under "
                         "group_limited_greedy are supported")
    fields = dict(
        _depth_and_widths(hf), **_act_eps_tie(hf),
        mlp=tuple("experts" if i >= hf["first_k_dense_replace"] and
                  i % hf["moe_layer_freq"] == 0 else "gated"
                  for i in range(layers)),
        q_lora_rank=hf["q_lora_rank"] or 0,
        kv_lora_rank=hf["kv_lora_rank"],
        qk_nope_head_dim=hf["qk_nope_head_dim"],
        qk_rope_head_dim=hf["qk_rope_head_dim"],
        v_head_dim=hf["v_head_dim"],
        moe_intermediate_size=hf["moe_intermediate_size"],
        num_experts=hf["n_routed_experts"],
        num_shared_experts=hf["n_shared_experts"],
        norm_topk_prob=hf["norm_topk_prob"],
        route_scale=float(hf["routed_scaling_factor"]),
        n_group=hf["n_group"], topk_group=hf["topk_group"])
    scale = (hf["qk_nope_head_dim"] + hf["qk_rope_head_dim"]) ** -0.5
    yarn = hf.get("rope_scaling")
    if yarn:
        if yarn.get("type") != "yarn":
            raise ValueError(f"unknown rope_scaling type "
                             f"{yarn.get('type')!r} (known: 'yarn')")
        all_dim = yarn_mscale(yarn["factor"], yarn["mscale_all_dim"])
        fields["rope_yarn"] = (
            float(yarn["factor"]),
            int(yarn["original_max_position_embeddings"]),
            float(yarn["beta_fast"]), float(yarn["beta_slow"]),
            float(yarn_mscale(yarn["factor"], yarn["mscale"]) / all_dim))
        scale *= all_dim * all_dim
    fields["attn_scale"] = float(scale)
    return fields


def _longcat_flash_fields(hf: dict) -> dict:
    """What ``config.json`` of ``model_type`` longcat_flash says beyond the
    keys all decoders share, under its own names (``num_layers``,
    ``ffn_hidden_size``, ``expert_ffn_hidden_size``, ``moe_topk``; no
    ``hidden_act``, ``tie_word_embeddings`` or ``num_key_value_heads``:
    ``_HF_KINDS`` has what the model's code says of them).  A published
    layer is TWO blocks of the one decoder definition: latent attention
    and a dense gated MLP each, the first with the routed and the identity
    experts beside its MLP (``GPTConfig.mlp`` "gated+shortcut"), whose sum
    joins the stream after the second; so ``GPTConfig.num_layers`` is twice
    the file's ``num_layers``, and a published layer is two cache entries.
    The two latents' factors are ``sqrt(hidden_size / rank)`` where the
    file's flags say so."""
    if hf.get("attention_method", "MLA") != "MLA":
        raise ValueError("longcat_flash: only attention_method MLA is "
                         "supported")
    if hf["zero_expert_num"] and hf["zero_expert_type"] != "identity":
        raise ValueError("longcat_flash: only identity zero experts are "
                         f"supported, not {hf['zero_expert_type']!r}")
    hidden, q_rank = hf["hidden_size"], hf["q_lora_rank"] or 0
    return dict(
        num_layers=2 * hf["num_layers"],
        mlp=(SHORTCUT_MLP, "gated") * hf["num_layers"],
        intermediate_size=hf["ffn_hidden_size"],
        moe_intermediate_size=hf["expert_ffn_hidden_size"],
        num_experts=hf["n_routed_experts"],
        num_zero_experts=hf["zero_expert_num"],
        num_experts_per_tok=hf["moe_topk"],
        route_scale=float(hf["routed_scaling_factor"]),
        layer_norm_eps=hf["rms_norm_eps"],
        q_lora_rank=q_rank, kv_lora_rank=hf["kv_lora_rank"],
        qk_nope_head_dim=hf["qk_nope_head_dim"],
        qk_rope_head_dim=hf["qk_rope_head_dim"],
        v_head_dim=hf["v_head_dim"],
        q_lora_scale=float(np.sqrt(hidden / q_rank))
        if q_rank and hf["mla_scale_q_lora"] else 1.0,
        kv_lora_scale=float(np.sqrt(hidden / hf["kv_lora_rank"]))
        if hf["mla_scale_kv_lora"] else 1.0)


def _dots3_note_fields(hf: dict) -> dict:
    """What ``config.json`` of ``model_type`` dots3_note says beyond the
    keys all decoders share: which layers are full (latent attention over
    the positions an indexer selects) and which sliding (latent attention
    under the window, with the ``swa_`` widths), the indexer's sizes, the
    head-wise gates, the leading dense layers, the routed and shared
    experts and their sigmoid router with a choice bias.  Of a
    ``layer_types`` longer than ``num_hidden_layers`` (a file cut in depth
    that keeps the published list) the leading layers count.
    ``apply_mla_qkv_lora_rescale``: both kinds' normed latents times
    ``sqrt(hidden_size / rank)``."""
    layers, hidden = hf["num_hidden_layers"], hf["hidden_size"]
    # a file cut in depth keeps the published list: its leading layers
    layer_types = hf["layer_types"][:layers]
    if len(layer_types) != layers:
        raise ValueError("layer_types must name every layer")
    unknown = set(layer_types) - {"sliding_attention", "full_attention"}
    if unknown:
        raise ValueError(f"unknown layer_types {sorted(unknown)}")
    if hf["topk_method"] != "noaux_tc" or hf["scoring_func"] != "sigmoid" \
            or hf.get("n_group", 1) != 1:
        raise ValueError("dots3_note: only sigmoid scores under noaux_tc "
                         "in one group are supported")
    gates = {hf["attention_gate_type"], hf["swa_attention_gate_type"]}
    if gates != {"headwise"}:
        raise ValueError("dots3_note: only head-wise attention gates are "
                         f"supported, not {sorted(gates)}")
    if hf["swa_num_key_value_heads"] != hf["swa_num_attention_heads"]:
        raise ValueError("dots3_note: a latent layer has no grouped "
                         "key/value heads")
    rescale = hf["apply_mla_qkv_lora_rescale"]

    def factor(rank):
        return float(np.sqrt(hidden / rank)) if rescale and rank else 1.0

    return dict(
        _depth_and_widths(hf), **_act_eps_tie(hf),
        mlp=tuple("experts" if i >= hf["first_k_dense_replace"] and
                  i % hf["moe_layer_freq"] == 0 else "gated"
                  for i in range(layers)),
        attention=tuple(LATENT_SLIDING if t == "sliding_attention"
                        else "latent" for t in layer_types),
        sliding_window=hf["sliding_window_size"], attn_gate="head",
        q_lora_rank=hf["q_lora_rank"] or 0,
        kv_lora_rank=hf["kv_lora_rank"],
        qk_nope_head_dim=hf["qk_nope_head_dim"],
        qk_rope_head_dim=hf["qk_rope_head_dim"],
        v_head_dim=hf["v_head_dim"],
        q_lora_scale=factor(hf["q_lora_rank"] or 0),
        kv_lora_scale=factor(hf["kv_lora_rank"]),
        sliding_latent=LatentWidths(
            num_heads=hf["swa_num_attention_heads"],
            q_lora_rank=hf["swa_q_lora_rank"] or 0,
            kv_lora_rank=hf["swa_kv_lora_rank"],
            qk_nope_head_dim=hf["swa_qk_nope_head_dim"],
            qk_rope_head_dim=hf["swa_qk_rope_head_dim"],
            v_head_dim=hf["swa_v_head_dim"],
            rope_theta=float(hf["swa_rope_theta"]),
            q_lora_scale=factor(hf["swa_q_lora_rank"] or 0),
            kv_lora_scale=factor(hf["swa_kv_lora_rank"])),
        index_topk=hf["index_topk"], index_n_heads=hf["index_n_heads"],
        index_head_dim=hf["index_head_dim"],
        moe_intermediate_size=hf["moe_intermediate_size"],
        num_experts=hf["n_routed_experts"],
        num_shared_experts=hf["n_shared_experts"],
        norm_topk_prob=hf["norm_topk_prob"],
        route_scale=float(hf["routed_scaling_factor"]))


def _glm_moe_dsa_fields(hf: dict) -> dict:
    """What ``config.json`` of ``model_type`` glm_moe_dsa says beyond the
    keys all decoders share: latent attention in every layer over the
    positions an indexer selects (its sizes, and how its rotary pairs
    lie), the leading dense layers, the routed and shared experts and
    their sigmoid router with a choice bias in one group, and how many
    multi-token-prediction modules follow the last layer.  ``head_dim``
    is the rotary width again (``qk_rope_head_dim``) and sizes nothing."""
    layers = hf["num_hidden_layers"]
    if hf["topk_method"] != "noaux_tc" or hf["scoring_func"] != "sigmoid" \
            or (hf["n_group"], hf["topk_group"]) != (1, 1):
        raise ValueError("glm_moe_dsa: only sigmoid scores under noaux_tc "
                         "in one group are supported")
    if hf["num_nextn_predict_layers"] not in (0, 1):
        raise ValueError("glm_moe_dsa: one multi-token-prediction module "
                         "at most is supported, not "
                         f"{hf['num_nextn_predict_layers']}")
    if hf["qk_head_dim"] != hf["qk_nope_head_dim"] + hf["qk_rope_head_dim"]:
        raise ValueError("glm_moe_dsa: qk_head_dim is not qk_nope_head_dim "
                         "+ qk_rope_head_dim")
    return dict(
        _depth_and_widths(hf), **_act_eps_tie(hf),
        mlp=tuple("experts" if i >= hf["first_k_dense_replace"] and
                  i % hf["moe_layer_freq"] == 0 else "gated"
                  for i in range(layers)),
        rope_interleaved=hf["rope_interleave"],
        q_lora_rank=hf["q_lora_rank"] or 0,
        kv_lora_rank=hf["kv_lora_rank"],
        qk_nope_head_dim=hf["qk_nope_head_dim"],
        qk_rope_head_dim=hf["qk_rope_head_dim"],
        v_head_dim=hf["v_head_dim"],
        index_topk=hf["index_topk"], index_n_heads=hf["index_n_heads"],
        index_head_dim=hf["index_head_dim"],
        index_rope_interleaved=hf["indexer_rope_interleave"],
        moe_intermediate_size=hf["moe_intermediate_size"],
        num_experts=hf["n_routed_experts"],
        num_shared_experts=hf["n_shared_experts"],
        norm_topk_prob=hf["norm_topk_prob"],
        route_scale=float(hf["routed_scaling_factor"]),
        num_nextn_predict_layers=hf["num_nextn_predict_layers"])


def _mimo_v2_flash_fields(hf: dict) -> dict:
    """What ``config.json`` of ``model_type`` mimo_v2_flash says beyond the
    keys all decoders share: which layers are full and which under the
    window (``hybrid_layer_pattern``: 0 full, 1 sliding), each kind's
    key/value heads and rotary base (the ``swa_`` keys), keys of
    ``head_dim`` channels with values of ``v_head_dim``, rotary positions
    on the leading ``int(head_dim * partial_rotary_factor)`` channels, the
    scale on the values, which kinds' softmax has a learned sink, which
    layers route (``moe_layer_freq``: 1 routed) and the sigmoid router with
    a choice bias.  Of lists longer than ``num_hidden_layers`` (a file cut
    in depth may keep the published ones) the leading layers count.
    ``attention_chunk_size`` changes no equation and is not read; the
    multi-token-prediction modules have no key in the file and are not
    built."""
    layers = hf["num_hidden_layers"]
    pattern = hf["hybrid_layer_pattern"][:layers]
    routed = hf["moe_layer_freq"][:layers]
    if len(pattern) != layers or len(routed) != layers:
        raise ValueError("hybrid_layer_pattern and moe_layer_freq must name "
                         "every layer")
    if set(pattern) - {0, 1} or set(routed) - {0, 1}:
        raise ValueError("hybrid_layer_pattern and moe_layer_freq hold 0 "
                         "and 1 only")
    if hf["topk_method"] != "noaux_tc" or hf["scoring_func"] != "sigmoid" \
            or hf.get("n_group", 1) != 1 or hf.get("topk_group", 1) != 1:
        raise ValueError("mimo_v2_flash: only sigmoid scores under noaux_tc "
                         "in one group are supported")
    if hf.get("n_shared_experts"):
        raise ValueError("mimo_v2_flash: shared experts are not supported")
    for swa, full in (("swa_num_attention_heads", "num_attention_heads"),
                      ("swa_head_dim", "head_dim"),
                      ("swa_v_head_dim", "v_head_dim"),
                      ("sliding_window_size", "sliding_window")):
        if hf[swa] != hf[full]:
            raise ValueError(f"mimo_v2_flash: {swa} {hf[swa]} differs from "
                             f"{full} {hf[full]}: the window layers share "
                             "the full layers' query heads and head widths")
    rotary = int(hf["head_dim"] * hf["partial_rotary_factor"])
    if rotary % 2:
        raise ValueError(f"partial_rotary_factor leaves {rotary} channels "
                         "to rotate, which are no pairs")
    return dict(
        _depth_and_widths(hf), activation=hf["hidden_act"],
        layer_norm_eps=hf["layernorm_epsilon"],
        tie_embeddings=hf["tie_word_embeddings"],
        mlp=tuple("experts" if r else "gated" for r in routed),
        attention=tuple("sliding" if p else "full" for p in pattern),
        sliding_window=hf["sliding_window"], head_dim=hf["head_dim"],
        v_head_dim=0 if hf["v_head_dim"] == hf["head_dim"]
        else hf["v_head_dim"],
        rotary_dim=0 if rotary == hf["head_dim"] else rotary,
        value_scale=float(hf["attention_value_scale"] or 1.0),
        sink_kinds=tuple(kind for kind, key in (
            ("full", "add_full_attention_sink_bias"),
            ("sliding", "add_swa_attention_sink_bias")) if hf[key]),
        sliding_kv_heads=hf["swa_num_key_value_heads"],
        sliding_rope_theta=float(hf["swa_rope_theta"]),
        moe_intermediate_size=hf["moe_intermediate_size"],
        num_experts=hf["n_routed_experts"],
        norm_topk_prob=hf["norm_topk_prob"],
        route_scale=float(hf["routed_scaling_factor"] or 1.0))


# ``hybrid_override_pattern``'s letters: (attention kind, mlp kind)
_NEMOTRON_H_LAYERS = {"M": ("ssm", "none"), "*": ("full", "none"),
                      "E": ("none", "experts")}


def _nemotron_h_fields(hf: dict) -> dict:
    """What ``config.json`` of ``model_type`` nemotron_h says beyond the
    keys all decoders share: what every layer is (``hybrid_override_pattern``,
    a letter a layer: ``M`` a Mamba-2 mixer, ``*`` attention, ``E`` routed
    experts; a layer is ONE of them; the family's ``-``, a dense MLP, is in
    no published pattern this reads and is refused), the Mamba-2
    mixer's sizes (``mamba_num_heads`` x ``mamba_head_dim`` is its inner
    width: ``expand`` sizes nothing), the routed experts, the one shared
    expert of a width of its own, and the sigmoid router with a choice
    bias in one group (``n_group`` is the router's, ``n_groups`` the
    mixer's).  Of a pattern longer than ``num_hidden_layers`` (a file cut
    in depth may keep the published one) the leading layers count.  The
    attention layers apply no positions (``_HF_KINDS``), so the file's
    ``rope_theta`` and ``partial_rotary_factor`` are read by nothing;
    ``time_step_min``, ``time_step_max`` and ``time_step_floor`` shape the
    initial ``dt_bias`` (``Mamba2``) and no equation."""
    layers = hf["num_hidden_layers"]
    pattern = hf["hybrid_override_pattern"][:layers]
    if len(pattern) != layers:
        raise ValueError("hybrid_override_pattern must name every layer")
    unknown = set(pattern) - set(_NEMOTRON_H_LAYERS)
    if unknown:
        raise ValueError(f"unknown layers {sorted(unknown)} in "
                         "hybrid_override_pattern (known: "
                         f"{sorted(_NEMOTRON_H_LAYERS)})")
    if (hf["n_group"], hf["topk_group"]) != (1, 1):
        raise ValueError("nemotron_h: only a router of one group is "
                         "supported")
    for key in ("use_bias", "mlp_bias", "mamba_proj_bias"):
        if hf[key]:
            raise ValueError(f"nemotron_h: {key} true is not supported")
    if not hf["use_conv_bias"] or hf["mamba_hidden_act"] != "silu":
        raise ValueError("nemotron_h: only a convolution with a bias under "
                         "silu is supported")
    if hf["n_shared_experts"] not in (0, 1):
        raise ValueError("nemotron_h: one shared expert at most, whose "
                         "width is moe_shared_expert_intermediate_size")
    kinds = [_NEMOTRON_H_LAYERS[letter] for letter in pattern]
    return dict(
        _depth_and_widths(hf),
        attention=tuple(a for a, _ in kinds),
        mlp=tuple(m for _, m in kinds),
        activation=hf["mlp_hidden_act"],
        layer_norm_eps=hf["layer_norm_epsilon"],
        tie_embeddings=hf["tie_word_embeddings"],
        head_dim=hf["head_dim"],
        ssm_heads=hf["mamba_num_heads"], ssm_head_dim=hf["mamba_head_dim"],
        ssm_state_size=hf["ssm_state_size"], ssm_groups=hf["n_groups"],
        ssm_chunk=hf["chunk_size"], conv_taps=hf["conv_kernel"],
        moe_intermediate_size=hf["moe_intermediate_size"],
        num_experts=hf["n_routed_experts"],
        num_shared_experts=hf["n_shared_experts"],
        shared_expert_width=hf["moe_shared_expert_intermediate_size"],
        norm_topk_prob=hf["norm_topk_prob"],
        route_scale=float(hf["routed_scaling_factor"]))


def _jamba_fields(hf: dict) -> dict:
    """What ``config.json`` of ``model_type`` jamba says beyond the keys all
    decoders share: which layers are attention (layer ``i`` where ``i %
    attn_layer_period == attn_layer_offset``; every other is a Mamba-1
    mixer) and the mixer's sizes (``mamba_expand`` times the hidden size
    its inner width, ``mamba_d_state`` state values a channel,
    ``mamba_dt_rank`` the rank ``dt`` comes through, ``mamba_d_conv`` taps).
    Every layer's MLP is the gated one: a file whose ``num_experts`` is
    over 1 routes every ``expert_layer_period``-th, which this does not
    read and refuses; with one expert ``expert_layer_period``,
    ``expert_layer_offset`` and ``num_experts_per_tok`` choose nothing.
    The attention layers apply no positions (``_HF_KINDS``), and the file
    has no rotary key at all."""
    if hf["num_experts"] != 1:
        raise ValueError("jamba: only num_experts 1 (a gated MLP in every "
                         f"layer) is supported, got {hf['num_experts']}")
    if not hf["mamba_conv_bias"] or hf["mamba_proj_bias"]:
        raise ValueError("jamba: only a convolution with a bias and "
                         "projections without one are supported")
    if hf.get("sliding_window"):
        raise ValueError("jamba: sliding_window is not supported")
    layers = hf["num_hidden_layers"]
    period, offset = hf["attn_layer_period"], hf["attn_layer_offset"]
    fields = dict(
        num_layers=layers, intermediate_size=hf["intermediate_size"],
        attention=tuple("full" if i % period == offset else "s6"
                        for i in range(layers)),
        activation=hf["hidden_act"], layer_norm_eps=hf["rms_norm_eps"],
        tie_embeddings=hf["tie_word_embeddings"],
        s6_inner=hf["mamba_expand"] * hf["hidden_size"],
        s6_dt_rank=hf["mamba_dt_rank"],
        ssm_state_size=hf["mamba_d_state"], conv_taps=hf["mamba_d_conv"])
    if hf["num_key_value_heads"] != hf["num_attention_heads"]:
        fields["num_kv_heads"] = hf["num_key_value_heads"]
    return fields


def _evabyte_fields(hf: dict) -> dict:
    """What ``config.json`` of ``model_type`` evabyte says beyond the keys
    all decoders share: the window and the chunk of its attention
    (``window_size``, ``chunk_size``; ``attention_class`` must be "eva",
    with one key/value head a query head), the float32 residual stream
    (``fp32_skip_add``), the norms' unit offset (``norm_add_unit_offset``),
    float32 logits (``fp32_logits``) and the prediction heads of its one
    head matrix (``num_pred_heads``).  ``mixedp_attn`` is what every
    attention core here does (a float32 softmax over products of
    ``dtype``); ``num_chunks``, ``init_fn``, ``init_std``,
    ``init_cutoff_factor``, ``lazy_init``, ``fp32_ln`` (the norm reads the
    float32 stream whatever it says) and ``max_seq_length`` are read by
    nothing."""
    if hf["attention_class"] != "eva":
        raise ValueError("evabyte: only attention_class \"eva\" is "
                         f"supported, got {hf['attention_class']!r}")
    if hf["num_key_value_heads"] != hf["num_attention_heads"]:
        raise ValueError("evabyte: a summary is pooled a key/value head by "
                         "that head's own vectors: only one key/value head "
                         "a query head is supported")
    if hf["window_size"] % hf["chunk_size"]:
        raise ValueError("evabyte: window_size is no multiple of chunk_size")
    return dict(
        num_layers=hf["num_hidden_layers"],
        intermediate_size=hf["intermediate_size"],
        activation=hf["hidden_act"], layer_norm_eps=hf["rms_norm_eps"],
        tie_embeddings=hf["tie_word_embeddings"],
        eva_window=hf["window_size"], eva_chunk=hf["chunk_size"],
        fp32_residual=hf["fp32_skip_add"],
        norm_unit_offset=hf["norm_add_unit_offset"],
        fp32_logits=hf["fp32_logits"], num_pred_heads=hf["num_pred_heads"])


# what each model type's file says beyond the keys all share
_HF_FIELDS = {
    "olmoe": _olmoe_fields, "afmoe": _afmoe_fields,
    "deepseek_v2": _deepseek_v2_fields, "sdar_moe": _sdar_moe_fields,
    "lfm2_moe": _lfm2_moe_fields, "longcat_flash": _longcat_flash_fields,
    "dots3_note": _dots3_note_fields,
    "mimo_v2_flash": _mimo_v2_flash_fields,
    "glm_moe_dsa": _glm_moe_dsa_fields,
    "nemotron_h": _nemotron_h_fields,
    "jamba": _jamba_fields,
    "evabyte": _evabyte_fields,
}


def config_from_hf(hf: dict, **kwargs) -> GPTConfig:
    """``GPTConfig`` from the keys of a Hugging Face ``config.json`` (a
    dict), for the model types in ``_HF_KINDS``.  The keys every type's
    file has are read here; ``_HF_FIELDS`` names the function that reads
    the rest of a type's file, under that file's own names.  ``kwargs``
    override what the file says (``seq_len``: the context a deployment
    serves, where it is less than the declared
    ``max_position_embeddings``; ``experts_held``: this chip's share of the
    routed experts; ``block_length``: the blocks a diffusion decoder
    generates in).  Of ``rope_scaling`` only deepseek_v2's ``yarn`` is
    known; a file that keeps its rotary base under ``rope_parameters``
    (glm_moe_dsa) must name the ``default`` type there; a file of a family
    without positions (jamba) names no base, and none is set."""
    kinds = _HF_KINDS.get(hf["model_type"])
    if kinds is None:
        raise ValueError(f"no decoder kinds for model_type "
                         f"{hf['model_type']!r} (known: {sorted(_HF_KINDS)})")
    if hf.get("clip_qkv") or (hf.get("rope_scaling") and
                              hf["model_type"] != "deepseek_v2"):
        raise ValueError("rope_scaling and clip_qkv are not supported")
    rope = hf.get("rope_parameters") or {"rope_theta": hf.get("rope_theta")}
    if rope.get("rope_type", "default") != "default":
        raise ValueError(f"unknown rope_type {rope['rope_type']!r} "
                         "(known: 'default')")
    fields = dict(
        vocab_size=hf["vocab_size"], hidden_size=hf["hidden_size"],
        num_heads=hf["num_attention_heads"],
        seq_len=hf["max_position_embeddings"],
        use_bias=hf.get("attention_bias", False), **kinds)
    if rope.get("rope_theta") is not None:
        fields["rope_theta"] = float(rope["rope_theta"])
    fields.update(_HF_FIELDS[hf["model_type"]](hf))
    fields.update(kwargs)
    return GPTConfig(**fields)


def norm_gain(scale):
    """What a norm with a unit offset multiplies by
    (``GPTConfig.norm_unit_offset``): one more than its stored weight."""
    return 1.0 + scale


class UnitOffsetRMSNorm(nn.Module):
    """``x / sqrt(mean(x^2) + epsilon) * (1 + scale)`` in float32, the
    stored ``scale`` starting at 0 (``GPTConfig.norm_unit_offset``)."""
    epsilon: float
    param_dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, x):
        scale = self.param("scale", nn.initializers.zeros, (x.shape[-1],),
                           self.param_dtype)
        x = x.astype(jnp.float32)
        y = x * jax.lax.rsqrt(
            jnp.mean(jnp.square(x), axis=-1, keepdims=True) + self.epsilon)
        return y * norm_gain(scale.astype(jnp.float32))


def make_norm(config: GPTConfig, name: str) -> nn.Module:
    """The normalisation the configuration names, computed in float32."""
    if config.norm == "rmsnorm" and config.norm_unit_offset:
        return UnitOffsetRMSNorm(config.layer_norm_eps, config.param_dtype,
                                 name=name)
    if config.norm == "rmsnorm":
        # scale * x / sqrt(mean(x^2) + eps)
        return nn.RMSNorm(epsilon=config.layer_norm_eps, dtype=jnp.float32,
                          param_dtype=config.param_dtype, name=name)
    if config.norm != "layernorm":
        raise ValueError(f"unknown norm {config.norm!r}")
    return nn.LayerNorm(epsilon=config.layer_norm_eps, dtype=jnp.float32,
                        param_dtype=config.param_dtype, name=name)


def apply_rotary(x, position_ids, theta: float, interleaved: bool = False,
                 yarn=None):
    """Rotate-half rotary position embedding (Su et al. 2021, as in
    Hugging Face's ``apply_rotary_pos_emb``): x (B, S, H, D), positions
    (B, S).  Channel i is paired with channel i + D/2; the angle of pair i
    is position * theta^(-2i/D).  Computed in float32.

    ``interleaved``: pair i is channels 2i and 2i + 1 (DeepSeek-V2); the
    result then holds the pairs' first channels in its first half and
    their second in its second (the same order for every vector rotated,
    so no dot product of two of them sees it).  ``yarn``
    (``GPTConfig.rope_yarn``): the frequencies of ``yarn_inv_freq``, and
    cosine and sine times its last entry."""
    half = x.shape[-1] // 2
    if yarn is None:
        inv_freq = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    else:
        inv_freq = jnp.asarray(
            yarn_inv_freq(x.shape[-1], theta, *yarn[:4])[0], jnp.float32)
    angles = position_ids.astype(jnp.float32)[..., None] * inv_freq
    cos = jnp.cos(angles)[:, :, None, :]
    sin = jnp.sin(angles)[:, :, None, :]
    if yarn is not None and yarn[4] != 1.0:
        cos, sin = cos * yarn[4], sin * yarn[4]
    x32 = x.astype(jnp.float32)
    if interleaved:
        x1, x2 = x32[..., 0::2], x32[..., 1::2]
    else:
        x1, x2 = x32[..., :half], x32[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                           axis=-1).astype(x.dtype)


def reference_attention(q, k, v, *, causal: bool, offset=0, bias=None,
                        window: int = 0, k_positions=None, block: int = 0,
                        sink=None):
    """Plain einsum attention; XLA fuses this well on TPU for short seqs.

    q: (B, Sq, H, D); k: (B, Sk, Hkv, D), v: (B, Sk, Hkv, Dv) (the values
    as wide as the keys or not), H a multiple of Hkv: query head
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

    ``block`` > 0 (causal only, no window and no ``k_positions``): the
    positions come in blocks of ``block`` and a query at position p sees
    the keys at positions ``< (p // block + 1) * block``: every key of its
    own block, the later ones too, and of the blocks before it (generation
    by diffusion over blocks, ``GPTConfig.block_length``).

    ``sink`` ((H,) float32): a logit a query head that joins the softmax's
    denominator and carries no value: ``p_ij = exp(s_ij) / (exp(sink_h) +
    sum_j' exp(s_ij'))`` (``GPTConfig.sink_kinds``).
    """
    if block and (not causal or window or k_positions is not None):
        raise ValueError("a block-causal mask goes with a causal mask over "
                         "a full cache, not with a window or a ring")
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

        def last_seen(pos):
            """The last position a query at ``pos`` sees."""
            return (pos // block + 1) * block - 1 if block else pos

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
            mask = last_seen(q_pos + offset) >= k_pos
            if window:
                mask &= q_pos + offset - k_pos < window
            mask = mask[None, None]                          # (1,1,Sq,Sk)
        else:
            q_pos = q_pos[None] + offset[:, None, None]
            mask = last_seen(q_pos) >= k_pos[None]
            if window:
                mask &= q_pos - k_pos[None] < window
            mask = mask[:, None]                             # (B,1,Sq,Sk)
        scores = jnp.where(mask, scores, jnp.float32(-1e9))
    if sink is None:
        probs = jax.nn.softmax(scores, axis=-1).astype(q.dtype)
    else:
        sink = sink.astype(jnp.float32)[None, :, None, None]
        top = jnp.maximum(scores.max(-1, keepdims=True), sink)
        probs = jnp.exp(scores - top)
        probs = (probs / (probs.sum(-1, keepdims=True) +
                          jnp.exp(sink - top))).astype(q.dtype)
    if nkv == nh:
        return jnp.einsum("bhqk,bkhd->bqhd", probs, v)
    out = jnp.einsum("bhgqk,bkhd->bqhgd",
                     probs.reshape(b, nkv, nh // nkv, sq, sk), v)
    return out.reshape(b, sq, nh, v.shape[-1])


def attention(q, k, v, *, causal: bool):
    """The attention of a layer without a cache (a training step, a forward
    pass over whole sequences): ``reference_attention(q, k, v,
    causal=causal)`` by one of two cores, by what the call's shapes say
    and nothing a caller or a configuration sets.  Shapes the kernels of
    ``ops/flash_attention.py`` take (as many key/value heads as query
    heads, one length that their blocks divide, from the length at which
    they win: its ``fits``): a program lowered for a TPU runs those
    kernels, forward and backward, which keep a block of scores in fast
    memory and write none of the (B, H, S, S) scores and probabilities,
    and any other platform ``reference_attention``.  Every other call is
    ``reference_attention``.  The gauge ``alpa_attention_core`` says at
    trace time which one a program's layers took.

    The kernels are no primitive the sharding planner can partition: on
    a mesh of more than one device it plans and binds this choice's
    ``reference_attention`` (``shard_parallel/kernel_choice.py``)."""
    from alpa_tpu.ops import flash_attention as kernel
    fused = kernel.fits(q, k)
    _attention_core_gauge().labels(
        "fused" if fused else "reference", q.shape[2], q.shape[3],
        q.shape[1]).inc()
    if not fused:
        return reference_attention(q, k, v, causal=causal)
    return _fused_attention(q, k, v, causal)


def _attention_core_gauge():
    return tmetrics.get_registry().gauge(
        "alpa_attention_core",
        "layers whose attention without a cache was traced with each core "
        "(fused: where lowered for a TPU, the kernels that keep the scores "
        "in fast memory, forward and backward; reference: the scores "
        "written to memory, which is also what the sharding planner binds "
        "on a mesh of several devices), by the heads, head width and length",
        ("core", "heads", "head_dim", "seq"))


def _planned_as_reference(avals):
    """The sharding planner bound ``reference_attention`` in the layers
    traced as fused at these shapes (``kernel_choice.on_default``;
    ``avals[0]`` is the output's, or ``dq``'s): the gauge says so."""
    _, seq, heads, dim = avals[0].shape
    gauge = _attention_core_gauge()
    fused = gauge.labels("fused", heads, dim, seq)
    gauge.labels("reference", heads, dim, seq).inc(fused.value)
    fused.set(0)


@partial(jax.jit, static_argnames="causal")
def _fused_attention(q, k, v, causal):
    """``attention``'s fused core: the kernels where the program is
    lowered for a TPU, ``reference_attention`` anywhere else.  A ``jit`` of
    its own, so that a step of many layers traces and lowers the kernels
    once a pass and not once a layer and pass (54 times in the 18 layers
    of the GPT cell's step)."""
    from alpa_tpu.ops import flash_attention as kernel
    return jax.lax.platform_dependent(
        q, k, v, tpu=partial(kernel.flash_attention, causal=causal),
        default=partial(reference_attention, causal=causal))


kernel_choice.on_default(_fused_attention.__name__, _planned_as_reference)


def get_attention_fn(config: GPTConfig) -> Callable:
    """The core of a layer without a cache, by ``attention_impl``:
    "reference" is ``attention`` (the core the call's shapes choose),
    "ring" and "ulysses" shard the sequence over ``sp_axis``."""
    if config.attention_impl == "reference":
        return attention
    if config.attention_impl == "ring":
        from alpa_tpu.ops.ring_attention import ring_attention
        return partial(ring_attention, axis_name=config.sp_axis)
    if config.attention_impl == "ulysses":
        from alpa_tpu.ops.ulysses_attention import ulysses_attention
        return partial(ulysses_attention, axis_name=config.sp_axis)
    raise ValueError(f"unknown attention_impl {config.attention_impl!r}: "
                     "\"reference\", \"ring\" or \"ulysses\"")


# the scope the attention core of every layer is traced under
ATTENTION_SCOPE = "attention"
# inside it, the scope of the step's keys and values written into the
# cache (a capture reads both: telemetry/device_time.py)
CACHE_WRITE_SCOPE = "cache_write"
# inside it too, on a layer that selects its positions: the indexer (its
# projections, the index scores, the choice) and the selected positions'
# attention (the gather of their latents and the core over them)
INDEXER_SCOPE = "indexer"
SELECT_SCOPE = "latent_select"
# the scope a multi-token-prediction module is traced under, whole: its
# next tokens' embedding, its block (whose attention, indexer and experts
# keep their own scopes inside it) and its pass through the head
MTP_SCOPE = "mtp"
# inside it too, in a configuration whose "sliding" and "full" layers
# differ in more than the mask (``GPTConfig.unlike_kinds``): the core and
# the cache's write of a layer of either kind, so that a capture tells the
# window layers' time from the full layers'
WINDOW_CORE_SCOPE = "window_core"
FULL_CORE_SCOPE = "full_core"


# the lanes of a TPU vector register: the extent the compiler tiles an
# array's minor-most dimension to
LANES = 128


def _write_rows(cache, new, index):
    """``cache`` (B, S, H, D) with ``new`` (B, s, H, D) written at
    positions ``index[r] .. index[r] + s - 1`` of each row ``r``: one
    ``dynamic_update_slice`` a row.  The TPU compiler runs those in place
    in whatever dimension order it keeps the cache in, where a ``scatter``
    wants its operand row-major and costs a copy of the whole cache into
    that order and one back (``tests/serve/test_decode_in_place.py``).

    The view the rows are written through follows the head width, and
    nothing else.  Heads of ``LANES`` channels or more fill the lanes, the
    compiler keeps the cache as it is named, and the rows are written into
    it as named.  Narrower heads (and heads of no whole number of lane
    tiles: 192 channels) would be padded to the lanes, so the
    compiler keeps such a cache with its POSITIONS minor-most
    (``{1,3,2,0}``), and the rows are written into the cache seen as it
    lies, ((B H D), S), through ``_write_latent_rows``: the transposes
    and reshapes there and back compile to bitcasts.  Written as named, a
    narrow-head cache whose new keys reach the write rotated (LFM2,
    CodeGen) or packed a head (Bloom) is moved whole into the keys' order
    for the writes and back for the attention, four copies of a cache a
    layer a tick (``test_lfm2_decode_moves_no_cache``,
    ``test_decode_moves_no_cache``); OPT's, which never was, writes its
    rows a little faster as it lies too (PERF.md section 6, PR 39).

    A row whose write does not fit (``index[r]`` outside ``[0, S - s]``)
    stays as it was: ``dynamic_update_slice`` clamps its start, so such a
    row writes back the ``s`` positions it read there.
    """
    b, seq_len, heads, dim = cache.shape
    s = new.shape[1]
    # (192 channels, a lane tile and a half, lie as 64 do: the compiler
    # keeps any head width that is no whole tiles positions-minor)
    narrow = dim % LANES != 0
    # at trace time: which view this cache's rows were written through
    tmetrics.get_registry().gauge(
        "alpa_cache_row_write_view",
        "caches whose per-row writes were traced through each view, by the "
        "cache's heads and head width", ("view", "heads", "head_dim")).labels(
            "positions_minor" if narrow else "as_named", heads, dim).inc()
    if narrow:
        def as_it_lies(x):
            return x.transpose(0, 2, 3, 1).reshape(b, heads * dim,
                                                   x.shape[1])
        written = _write_latent_rows(as_it_lies(cache), as_it_lies(new),
                                     index, 2)
        return written.reshape(b, heads, dim, seq_len).transpose(0, 3, 1, 2)
    fits = (index >= 0) & (index <= seq_len - s)
    start = jnp.clip(index, 0, seq_len - s)
    for r in range(b):
        at = (r, start[r], 0, 0)
        old = jax.lax.dynamic_slice(cache, at, (1,) + new.shape[1:])
        cache = jax.lax.dynamic_update_slice(
            cache, jnp.where(fits[r], new[r:r + 1], old), at)
    return cache


def update_kv_cache(kv_cache, k, v):
    """Write the step's keys and values into a resident cache: the
    mechanics shared by every decoder family (GPT/OPT, Bloom, CodeGen).

    ``kv_cache`` is (k_cache, v_cache, index) with a scalar index
    (uniform write position) or a (B,) vector (per-row positions for
    mixed-length continuous batching).  Returns the cache entry with the
    ``s`` new positions written at ``index``, and ``index + s``: its
    arrays, as they lie, are what the caller attends over.

    Nothing is zeroed.  The positions from a row's ``index + s`` on hold
    whatever was written there before (a fresh cache's zeros, a padded
    prefill's padding, a rejected draft, a block still being denoised).
    They are finite, since caches start as zeros (``fresh_kv_caches``) and
    only the model's own keys and values are written into them; and the
    caller's mask hides them, and nothing else: ``reference_attention(...,
    causal=True, offset=index)`` replaces the score of every key past a
    query's position, so the float32 softmax gives it a probability of
    exactly 0, and 0 times a finite value adds nothing.  (A block-causal
    mask shows a query its whole block: what such a step holds of a row
    ends on a block's edge, ``Generator`` sees to that.)

    Per-row indices: one ``dynamic_update_slice`` a row, through the view
    of the cache that its head width decides (``_write_rows``: as named
    for heads that fill the chip's lanes, as the cache lies, positions
    last, for narrower ones), so that no caller and no configuration
    chooses.  A row whose ``s`` positions do not all fit in the
    cache is not written at all (``_write_rows``); its index advances all
    the same.  Caches of three dimensions hold the heads folded into the
    channels, (B, S, Hkv D) (a layer whose keys are wider than its values:
    ``kv_cache_shapes``), and are written as a latent layer's are
    (``_write_latent_rows``).  No caller lets an active row get there
    (``generate``, the
    speculative rounds and the engine's ``submit`` refuse a request that
    would).  The rows that do are the engine's free rows, decoded along in
    every tick with an index that only grows: nothing reads them, and the
    next admission overwrites the whole row and its index.
    """
    k_cache, v_cache, index = kv_cache
    index = jnp.asarray(index, jnp.int32)
    k, v = k.astype(k_cache.dtype), v.astype(v_cache.dtype)
    write_rows = _write_rows
    if k_cache.ndim == 3:
        # caches with the heads folded into the channels
        # (``kv_cache_shapes``): the new keys and values fold for nothing
        k, v = (x.reshape(x.shape[0], x.shape[1], -1) for x in (k, v))
        write_rows = partial(_write_latent_rows, axis=1)
    with jax.named_scope(CACHE_WRITE_SCOPE):
        if index.ndim == 0:
            k_cache = jax.lax.dynamic_update_slice_in_dim(k_cache, k, index,
                                                          axis=1)
            v_cache = jax.lax.dynamic_update_slice_in_dim(v_cache, v, index,
                                                          axis=1)
        else:
            k_cache = write_rows(k_cache, k, index)
            v_cache = write_rows(v_cache, v, index)
    return k_cache, v_cache, index + k.shape[1]


def cached_attention(q, k_cache, v_cache, offset, *, block: int = 0,
                     sink=None):
    """The attention of a full-attention layer's ``s`` new queries ``q``
    (B, s, H, D) over its written caches (B, S, Hkv, D), as
    ``update_kv_cache`` returns them; ``offset``, ``block`` and ``sink`` are
    ``reference_attention``'s.  Caches with the heads folded into the
    channels, (B, S, Hkv D) and (B, S, Hkv Dv): ``folded_cached_attention``.

    One of three cores, by what the call's shapes say and nothing a caller
    or a configuration sets; the first two are Pallas kernels of
    ``ops/cached_attention.py`` where the program is lowered for a TPU and
    ``reference_attention`` on any other platform.  A FEW new queries a
    row at per-row offsets (the engine's decode tick and block step) in
    shapes its ``fits`` takes: the kernel over key blocks, which reads of
    every row's cache the key blocks the row's queries can see, through
    the view the rows were written in (``_write_rows``: heads of whole
    lanes as named, narrower ones with the positions in the lanes).  MANY
    new queries a row at a scalar or per-row offset (a prefill chunk, from
    a cached prefix too) in shapes its ``chunk_fits`` takes (heads of
    whole lanes, whole query and key blocks): the kernel over query blocks
    and key blocks, in which a block's scores stay in fast memory and a
    query block reads the key blocks up to its last query's reach.  Every
    other call (a sink; heads narrower than a lane at more than a few
    queries; ``generate`` and a verify step, a few queries at a scalar
    offset) is ``reference_attention`` over every position the cache can
    hold, the causal offset alone hiding what a row has not reached.  The
    gauge ``alpa_cached_attention_core`` says at trace time which one a
    program's layers took."""
    from alpa_tpu.ops import cached_attention as kernel
    if k_cache.ndim == 3:
        if block:
            raise ValueError("a block-causal mask goes with caches of "
                             "per-head keys and values as wide as each "
                             "other")
        return folded_cached_attention(q, k_cache, v_cache, offset, sink)
    offset = jnp.asarray(offset, jnp.int32)
    core = "reference"
    if sink is None and offset.ndim == 1 and kernel.fits(q, k_cache):
        core = "key_blocks"
    elif sink is None and kernel.chunk_fits(q, k_cache, v_cache):
        core = "query_key_blocks"
    _cached_core_gauge().labels(core, k_cache.shape[2], k_cache.shape[3],
                                q.shape[1]).inc()
    if core == "key_blocks":
        return _attention_over_key_blocks(q, k_cache, v_cache, offset, block)
    if core == "query_key_blocks":
        return _attention_over_query_blocks(q, k_cache, v_cache, offset,
                                            block)
    return reference_attention(q, k_cache, v_cache, causal=True,
                               offset=offset, block=block, sink=sink)


def _cached_core_gauge():
    return tmetrics.get_registry().gauge(
        "alpa_cached_attention_core",
        "full-attention layers whose attention over the written cache was "
        "traced with each core (key_blocks: where lowered for a TPU, the "
        "kernel that reads each row's cache as far as the row has written; "
        "query_key_blocks: where lowered for a TPU, the kernel over query "
        "blocks and key blocks of a prefill chunk, whose scores stay in "
        "fast memory and whose query blocks read the cache as far as "
        "their last query sees; "
        "key_block_walk: a loop in jax.numpy over the key blocks up to the "
        "last query's, of a cache with the heads folded into the channels; "
        "reference: every position the cache can hold), by the cache's "
        "heads, head width and the new queries a row",
        ("core", "heads", "head_dim", "queries"))


@partial(jax.jit, static_argnames="block")
def _attention_over_key_blocks(q, k_cache, v_cache, offset, block):
    """``cached_attention``'s core over key blocks: the kernel where the
    program is lowered for a TPU, ``reference_attention`` anywhere else.
    A ``jit`` of its own, so that a program of many layers traces and
    lowers the kernel once and not once a layer (24 times in OPT-1.3B's
    decode, a second of every set-up)."""
    from alpa_tpu.ops import cached_attention as kernel
    return jax.lax.platform_dependent(
        q, k_cache, v_cache, offset,
        tpu=partial(kernel.cached_attention, block=block),
        default=lambda q, k, v, offset: reference_attention(
            q, k, v, causal=True, offset=offset, block=block))


@partial(jax.jit, static_argnames="block")
def _attention_over_query_blocks(q, k_cache, v_cache, offset, block=0):
    """A chunk's core over query blocks and key blocks, for either layout
    of the caches: the kernel where the program is lowered for a TPU; on
    any other platform the core such a call took before there was one,
    ``reference_attention`` over per-head caches and the walk over key
    blocks over folded ones.  A ``jit`` of its own as
    ``_attention_over_key_blocks`` is."""
    from alpa_tpu.ops import cached_attention as kernel
    if k_cache.ndim == 3:
        twin = _attention_over_folded_blocks
    else:
        def twin(q, k, v, offset):
            return reference_attention(q, k, v, causal=True, offset=offset,
                                       block=block)
    return jax.lax.platform_dependent(
        q, k_cache, v_cache, offset,
        tpu=partial(kernel.chunk_attention, block=block), default=twin)


def folded_cached_attention(q, k_cache, v_cache, offset, sink=None):
    """``cached_attention`` over caches with the heads folded into the
    channels: ``q`` (B, s, H, D) over ``k_cache`` (B, S, Hkv D) and
    ``v_cache`` (B, S, Hkv Dv), as ``update_kv_cache`` returns them (a
    layer whose keys are wider than its values, ``kv_cache_shapes``);
    returns (B, s, H, Dv).

    No core here scores against every position the cache can hold (at the
    context such a layer serves, a chunk's scores over all of it would be
    gigabytes).  A few new queries a row at per-row offsets in shapes
    ``ops/cached_attention.py`` ``folded_fits`` takes (a decode tick): a
    program lowered for a TPU runs the kernel over key blocks, which reads
    of every row's cache the key blocks the row's queries can see, as the
    cache lies.  Many new queries a row in shapes its ``chunk_fits`` takes
    (a prefill chunk, at a scalar or per-row offset): a program lowered
    for a TPU runs the kernel over query blocks and key blocks, which
    fetches of a key block the whole lane tiles its head's keys lie in and
    keeps the scores in fast memory.  On any
    other platform both are ``_attention_over_folded_blocks``, their
    ``jax.numpy`` twin, a loop over key blocks that ends at the last
    query's; so is every other call (a sink; ``generate``, a few queries
    at a scalar offset).  The gauge ``alpa_cached_attention_core`` says
    which."""
    from alpa_tpu.ops import cached_attention as kernel
    offset = jnp.asarray(offset, jnp.int32)
    dim = q.shape[-1]
    core = "key_block_walk"
    if sink is None and offset.ndim == 1 and \
            kernel.folded_fits(q, k_cache, v_cache):
        core = "key_blocks"
    elif sink is None and kernel.chunk_fits(q, k_cache, v_cache):
        core = "query_key_blocks"
    _cached_core_gauge().labels(core, k_cache.shape[2] // dim, dim,
                                q.shape[1]).inc()
    if core == "key_blocks":
        return _folded_key_blocks(q, k_cache, v_cache, offset)
    if core == "query_key_blocks":
        return _attention_over_query_blocks(q, k_cache, v_cache, offset)
    return _attention_over_folded_blocks(q, k_cache, v_cache, offset, sink)


@jax.jit
def _folded_key_blocks(q, k_cache, v_cache, offset):
    """``folded_cached_attention``'s kernel where the program is lowered
    for a TPU, its twin anywhere else; a ``jit`` of its own as
    ``_attention_over_key_blocks`` is."""
    from alpa_tpu.ops import cached_attention as kernel
    return jax.lax.platform_dependent(
        q, k_cache, v_cache, offset, tpu=kernel.folded_cached_attention,
        default=_attention_over_folded_blocks)


# positions in a key block of ``_attention_over_folded_blocks`` at least
_WALK_BLOCK = 128


def _attention_over_folded_blocks(q, k_cache, v_cache, offset, sink=None):
    """``reference_attention(q, k, v, causal=True, offset=offset,
    sink=sink)`` over folded caches, a key block at a time: the block's
    keys and values are unfolded to their heads inside the loop, scored,
    and folded into a running maximum, sum and weighted values (float32),
    so that one block's scores exist at a time (``H x s x block``, the
    block ``max(s, _WALK_BLOCK)`` positions), and the loop ends at the
    block of the last query: positions no row holds yet are not read
    (``_latent_attention_blocks`` is the same walk over latents)."""
    b, sq, nh, dim = q.shape
    sk = k_cache.shape[1]
    nkv = k_cache.shape[2] // dim
    dv = v_cache.shape[2] // nkv
    block = min(max(sq, _WALK_BLOCK), sk)
    q_pos = _query_positions(offset, b, sq)
    n_blocks = jnp.minimum(jnp.max(q_pos) // block + 1, -(-sk // block))
    steps = jax.lax.broadcasted_iota(jnp.int32, (1, 1, block), 2)
    grouped = q.reshape(b, sq, nkv, nh // nkv, dim)
    scale = 1 / np.sqrt(dim)

    def one_block(j, carry):
        top, total, acc = carry
        # the last block of a cache that is no multiple of it overlaps
        # the one before: its first positions are then masked
        start = jnp.minimum(j * block, sk - block)
        keys = jax.lax.dynamic_slice_in_dim(k_cache, start, block, axis=1)
        values = jax.lax.dynamic_slice_in_dim(v_cache, start, block, axis=1)
        scores = scale * _einsum_f32(
            "bqhgd,bkhd->bhgqk", grouped, keys.reshape(b, block, nkv, dim))
        k_pos = start + steps
        seen = ((k_pos <= q_pos[:, :, None]) &
                (k_pos >= j * block))[:, None, None]       # (.,1,1,Sq,blk)
        top_new = jnp.maximum(
            top, jnp.where(seen, scores, -jnp.inf).max(-1))
        probs = jnp.where(seen, jnp.exp(scores - top_new[..., None]), 0.0)
        keep = jnp.exp(top - top_new)
        total = total * keep + probs.sum(-1)
        acc = acc * keep[..., None] + _einsum_f32(
            "bhgqk,bkhd->bhgqd", probs.astype(q.dtype),
            values.reshape(b, block, nkv, dv))
        return top_new, total, acc

    # a finite floor: a block that a query sees nothing of leaves its
    # maximum there, and exp(floor - floor) is 1 and not NaN
    heads = (b, nkv, nh // nkv, sq)
    init = (jnp.full(heads, -1e30, jnp.float32),
            jnp.zeros(heads, jnp.float32),
            jnp.zeros(heads + (dv,), jnp.float32))
    top, total, acc = jax.lax.fori_loop(0, n_blocks, one_block, init)
    if sink is not None:
        sink = sink.astype(jnp.float32).reshape(1, nkv, nh // nkv, 1)
        keep = jnp.exp(top - jnp.maximum(top, sink))
        total = total * keep + jnp.exp(sink - jnp.maximum(top, sink))
        acc = acc * keep[..., None]
    out = acc / jnp.maximum(total, 1e-30)[..., None]
    return out.transpose(0, 3, 1, 2, 4).reshape(b, sq, nh, dv).astype(
        q.dtype)


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
        with jax.named_scope(CACHE_WRITE_SCOPE):
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
    with jax.named_scope(CACHE_WRITE_SCOPE):
        take = (source >= 0)[:, :, None, None]
        at = jnp.clip(source, 0, s - 1)[:, :, None, None]
        k_new = jnp.where(take, jnp.take_along_axis(k, at, axis=1), k_cache)
        v_new = jnp.where(take, jnp.take_along_axis(v, at, axis=1), v_cache)
    return k_use, v_use, k_positions, (k_new, v_new, index + s)


def _write_latent_rows(cache, new, index, axis):
    """The per-row write of a cache that has no heads, or whose heads
    ``_write_rows`` has folded into the channels: ``cache`` (B, S, D) with
    ``new`` (B, s, D) written at positions ``index[r] ..`` of each row
    ``r`` along ``axis`` 1 (a latent layer's normed latents), or (B, D, S)
    with (B, D, s) along ``axis`` 2 (its rotated shared keys, and every
    narrow-head cache as it lies).  The rows are written into the cache
    seen as two dimensions, (B S, D) or (B D, S), one
    ``dynamic_update_slice`` a row, with ``_write_rows``' guard for a row
    that does not fit.  Seen as three the TPU compiler moves the whole
    cache into an order with the rows next to the channels for the writes
    and back for the attention, two copies of the cache a layer a tick
    (ten in an OPT decode of two layers, four in LFM2's attention layer);
    two dimensions leave it one order
    (``tests/serve/test_decode_in_place.py``:
    ``test_latent_decode_holds_no_per_head_cache``,
    ``test_decode_moves_no_cache``, ``test_lfm2_decode_moves_no_cache``).
    """
    b, seq_len, s = cache.shape[0], cache.shape[axis], new.shape[axis]
    fits = (index >= 0) & (index <= seq_len - s)
    start = jnp.clip(index, 0, seq_len - s)
    flat = cache.reshape(b * cache.shape[1], cache.shape[2])
    for r in range(b):
        at = (r * seq_len + start[r], 0) if axis == 1 else \
            (r * cache.shape[1], start[r])
        old = jax.lax.dynamic_slice(flat, at, new.shape[1:])
        flat = jax.lax.dynamic_update_slice(
            flat, jnp.where(fits[r], new[r], old), at)
    return flat.reshape(cache.shape)


def update_latent_cache(kv_cache, c, k_pe):
    """``update_kv_cache`` for a "latent" layer: ``kv_cache`` is ``(c_cache
    (B, S, kv_lora_rank), pe_cache (B, qk_rope_head_dim, S), index)``, the
    normed latents and the rotated shared keys of the positions so far,
    ``index`` a scalar or (B,) a row.  The keys lie with the positions
    last: 64 channels last would be padded to 128 on the chip and re-laid
    out for every product, and so they are what the scores' product takes
    as it is.  The ``s`` new positions ``c`` (B, s, r) and ``k_pe`` (B, s,
    dr) are written at ``index`` (a row whose write does not fit stays as
    it was: ``_write_latent_rows``); returns the cache entry with ``index +
    s``.  Nothing is zeroed: the attention masks by position."""
    c_cache, pe_cache, index = kv_cache
    index = jnp.asarray(index, jnp.int32)
    c = c.astype(c_cache.dtype)
    k_pe = k_pe.astype(pe_cache.dtype).swapaxes(1, 2)
    with jax.named_scope(CACHE_WRITE_SCOPE):
        if index.ndim == 0:
            c_cache = jax.lax.dynamic_update_slice_in_dim(c_cache, c, index,
                                                          axis=1)
            pe_cache = jax.lax.dynamic_update_slice_in_dim(pe_cache, k_pe,
                                                           index, axis=2)
        else:
            c_cache = _write_latent_rows(c_cache, c, index, 1)
            pe_cache = _write_latent_rows(pe_cache, k_pe, index, 2)
    return c_cache, pe_cache, index + c.shape[1]


def _einsum_f32(spec, a, b):
    """``einsum`` with a float32 result.  Operands in a lower precision are
    multiplied as they are and accumulated in float32 where the platform
    has such a product (the TPU's matrix unit); the CPU's runtime has none
    for bfloat16, and converts them first."""
    if a.dtype == jnp.float32:
        return jnp.einsum(spec, a, b)
    return jax.lax.platform_dependent(
        a, b,
        tpu=lambda a, b: jnp.einsum(spec, a, b,
                                    preferred_element_type=jnp.float32),
        default=lambda a, b: jnp.einsum(spec, a.astype(jnp.float32),
                                        b.astype(jnp.float32)))


def _query_positions(offset, b, s):
    """(1 or B, s) int32: the positions of ``s`` queries a row that start
    at ``offset`` (a scalar, or (B,) a row)."""
    offset = jnp.asarray(offset, jnp.int32)
    steps = jax.lax.broadcasted_iota(jnp.int32, (1, s), 1)
    return steps + (offset[:, None] if offset.ndim else offset)


def latent_attention_expanded(q_nope, q_pe, c, k_pe, w_kv_b, scale,
                              offset=None):
    """Latent attention in its published form (``_latent_attention_blocks``
    says what that is, and is it, in ``jax.numpy``).  Over a cache
    (``offset`` given) whose shapes the Pallas kernel takes
    (``ops/latent_attention.py``: whole lanes and whole key blocks, the
    published sizes) a program lowered for a TPU runs the kernel, in which
    a key block's scores never leave fast memory; the choice is made at
    lowering time from the platform compiled for."""
    from alpa_tpu.ops import latent_attention as kernel
    if offset is None or not kernel.fits(q_nope, c, w_kv_b):
        return _latent_attention_blocks(q_nope, q_pe, c, k_pe, w_kv_b,
                                        offset, scale=scale)
    offset = jnp.broadcast_to(jnp.asarray(offset, jnp.int32),
                              (q_nope.shape[0],))
    return jax.lax.platform_dependent(
        q_nope, q_pe, c, k_pe, w_kv_b, offset,
        tpu=partial(kernel.expanded, scale=scale),
        default=partial(_latent_attention_blocks, scale=scale))


def _latent_attention_blocks(q_nope, q_pe, c, k_pe, w_kv_b, offset=None, *,
                             scale):
    """Latent attention in its published form, over key blocks: the keys'
    ``k_nope`` and the values of a block of positions are EXPANDED from
    their latents ``c`` (B, Sk, r) by ``w_kv_b`` (r, H, dn + dv) inside
    the loop, scored against ``q_nope`` (B, Sq, H, dn) (and the block's
    shared rotated keys ``k_pe`` (B, dr, Sk) against ``q_pe`` (B, Sq, H,
    dr)), and folded into a running maximum, sum and weighted values
    (float32), so that only one block's per-head keys, values and scores
    exist at a time: ``H x Sq x block`` scores, the block ``min(Sq, Sk)``
    positions.  Causal: query i of a row sits at ``offset + i`` (a scalar,
    or (B,) a row) and sees the keys at or before it.  ``offset`` None:
    the keys ARE the queries' own positions (no cache).  With a cache the
    loop ends at the block of the last query, so positions no row holds
    yet are not read.  Returns (B, Sq, H, dv) in the queries' dtype."""
    b, sq, nh, dn = q_nope.shape
    sk = c.shape[1]
    block = min(sq, sk)
    q_pos = _query_positions(0 if offset is None else offset, b, sq)
    if offset is None:
        n_blocks = -(-sk // block)
    else:
        n_blocks = jnp.minimum(jnp.max(q_pos) // block + 1,
                               -(-sk // block))
    steps = jax.lax.broadcasted_iota(jnp.int32, (1, 1, block), 2)

    def one_block(j, carry):
        top, total, acc = carry
        # the last block of a cache that is no multiple of it overlaps
        # the one before: its first positions are then masked
        start = jnp.minimum(j * block, sk - block)
        kv = jnp.einsum(
            "bkr,rhd->bkhd",
            jax.lax.dynamic_slice_in_dim(c, start, block, axis=1), w_kv_b)
        scores = scale * (
            _einsum_f32("bqhd,bkhd->bhqk", q_nope, kv[..., :dn]) +
            _einsum_f32("bqhd,bdk->bhqk", q_pe,
                        jax.lax.dynamic_slice_in_dim(k_pe, start, block,
                                                     axis=2)))
        k_pos = start + steps
        seen = ((k_pos <= q_pos[:, :, None]) &
                (k_pos >= j * block))[:, None]               # (.,1,Sq,blk)
        top_new = jnp.maximum(
            top, jnp.where(seen, scores, -jnp.inf).max(-1))
        probs = jnp.where(seen, jnp.exp(scores - top_new[..., None]), 0.0)
        keep = jnp.exp(top - top_new)
        total = total * keep + probs.sum(-1)
        acc = acc * keep.transpose(0, 2, 1)[..., None] + _einsum_f32(
            "bhqk,bkhd->bqhd", probs.astype(q_nope.dtype), kv[..., dn:])
        return top_new, total, acc

    dv = w_kv_b.shape[-1] - dn
    # a finite floor: a block that a query sees nothing of leaves its
    # maximum there, and exp(floor - floor) is 1 and not NaN
    init = (jnp.full((b, nh, sq), -1e30, jnp.float32),
            jnp.zeros((b, nh, sq), jnp.float32),
            jnp.zeros((b, sq, nh, dv), jnp.float32))
    _, total, acc = jax.lax.fori_loop(0, n_blocks, one_block, init)
    total = jnp.maximum(total, 1e-30).transpose(0, 2, 1)[..., None]
    return (acc / total).astype(q_nope.dtype)


def latent_attention_absorbed(q_nope, q_pe, c, k_pe, w_kv_b, scale, offset,
                              seen=None):
    """The same function with the expansion ABSORBED into the query and
    the output (``w_kv_b`` a head split into ``W_uk`` (r, dn) and ``W_uv``
    (r, dv)): ``q_lat = q_nope W_uk^T`` scores against the latents
    themselves, the probabilities weigh the latents, and ``W_uv`` expands
    what comes out: the cache is read as it lies, for all heads at once,
    and no per-head key or value of any cached position exists.  What a
    decode wants (few queries, a long cache); for many queries the ``H x
    r`` wide products cost more than expanding does.

    One query a row over a cache whose shapes the Pallas kernel takes
    (``ops/latent_attention.py`` ``absorbed``): a program lowered for a
    TPU runs the kernel, which reads of each row's cache the positions
    the row holds; ``_absorbed_core`` is its ``jax.numpy`` twin, over every
    row's whole cache.  ``seen`` ((1 or B, Sq, Sk) bool): the positions
    each query sees where the causal rule over ``offset`` does not say it
    (a ring of latents, ``update_latent_ring``): always the twin."""
    from alpa_tpu.ops import latent_attention as kernel
    dn = q_nope.shape[-1]
    q_lat = jnp.einsum("bqhd,rhd->bqhr", q_nope, w_kv_b[..., :dn])
    offset = jnp.asarray(offset, jnp.int32)
    if seen is None and kernel.absorbed_fits(q_lat, c) and offset.ndim == 1:
        o_lat = jax.lax.platform_dependent(
            q_lat, q_pe, c, k_pe, offset,
            tpu=partial(kernel.absorbed, scale=scale),
            default=partial(_absorbed_core, scale=scale))
    else:
        o_lat = _absorbed_core(q_lat, q_pe, c, k_pe, offset, scale=scale,
                               seen=seen)
    return jnp.einsum("bqhr,rhd->bqhd", o_lat, w_kv_b[..., dn:])


def _absorbed_core(q_lat, q_pe, c, k_pe, offset, *, scale, seen=None):
    """Scores of ``[q_lat | q_pe]`` against ``[c | k_pe]``, a float32
    softmax over the positions at or before each query's (or over those
    ``seen`` (1 or B, Sq, Sk) says, a ring's), and the probabilities'
    weighted latents (B, Sq, H, r)."""
    b, sq = q_lat.shape[:2]
    scores = scale * (_einsum_f32("bqhr,bkr->bhqk", q_lat, c) +
                      _einsum_f32("bqhd,bdk->bhqk", q_pe, k_pe))
    if seen is None:
        k_pos = jax.lax.broadcasted_iota(jnp.int32, (1, 1, c.shape[1]), 2)
        seen = k_pos <= _query_positions(offset, b, sq)[:, :, None]
    probs = jax.nn.softmax(
        jnp.where(seen[:, None], scores, jnp.float32(-1e9)), axis=-1)
    return jnp.einsum("bhqk,bkr->bqhr", probs.astype(c.dtype), c)


def update_latent_ring(kv_cache, c, k_pe, lengths=None):
    """``update_ring_cache``'s rule on ``update_latent_cache``'s layout,
    for a "latent_sliding" layer: ``kv_cache`` is ``(c_ring (B, W, r),
    pe_ring (B, dr, W), index)``, position p lives in slot ``p % W``, and
    ``index`` (a scalar, or (B,) a row) is the position of the first of the
    ``s`` new ones ``c`` (B, s, r), ``k_pe`` (B, s, dr).  Returns ``(c_use,
    pe_use, k_positions, new_cache)``: the latents (B, Sk, r) and shared
    keys (B, dr, Sk) to attend over and the position each of them holds
    ((1 or B, Sk); negative: nothing yet).  One new position is written
    first and the ring attended over; several attend over the ring as it
    was with the new ones behind it, and the ring then takes, a slot, the
    LAST real new position that belongs in it (``lengths``: the rows'
    whole lengths, which say what of a right-padded chunk is real)."""
    c_ring, pe_ring, index = kv_cache
    w, s = c_ring.shape[1], c.shape[1]
    c = c.astype(c_ring.dtype)
    k_pe = k_pe.astype(pe_ring.dtype).swapaxes(1, 2)             # (B,dr,s)
    index = jnp.asarray(index, jnp.int32)
    first = index[:, None] if index.ndim else index[None, None]  # (.,1)
    slots = jax.lax.broadcasted_iota(jnp.int32, (1, w), 1)

    def held(last):
        """The position each slot holds when ``last`` (.,1) is the
        newest position written (negative: none)."""
        return last - (last - slots) % w

    if s == 1:
        with jax.named_scope(CACHE_WRITE_SCOPE):
            if index.ndim:
                c_new = _write_latent_rows(c_ring, c, index % w, 1)
                pe_new = _write_latent_rows(pe_ring, k_pe, index % w, 2)
            else:
                c_new = jax.lax.dynamic_update_slice_in_dim(
                    c_ring, c, index % w, axis=1)
                pe_new = jax.lax.dynamic_update_slice_in_dim(
                    pe_ring, k_pe, index % w, axis=2)
        return c_new, pe_new, held(first), (c_new, pe_new, index + 1)

    new_pos = first + jax.lax.broadcasted_iota(jnp.int32, (1, s), 1)
    k_positions = jnp.concatenate(
        [jnp.broadcast_to(held(first - 1), (first.shape[0], w)), new_pos],
        axis=1)
    c_use = jnp.concatenate([c_ring, c], axis=1)
    pe_use = jnp.concatenate([pe_ring, k_pe], axis=2)
    real = jnp.full_like(first, s) if lengths is None else \
        jnp.clip(lengths[:, None] - first, 0, s)
    # the new position slot j is to hold; before the chunk: the slot keeps
    # what it has
    source = jnp.broadcast_to(held(first + real - 1) - first,
                              (c.shape[0], w))
    with jax.named_scope(CACHE_WRITE_SCOPE):
        take, at = source >= 0, jnp.clip(source, 0, s - 1)
        c_new = jnp.where(take[:, :, None], jnp.take_along_axis(
            c, at[:, :, None], axis=1), c_ring)
        pe_new = jnp.where(take[:, None, :], jnp.take_along_axis(
            k_pe, at[:, None, :], axis=2), pe_ring)
    return c_use, pe_use, k_positions, (c_new, pe_new, index + s)


def latent_row_width(rank: int, rope_dim: int) -> int:
    """The channels of one row of a selecting layer's cache: the latent
    and the shared rotary key side by side, rounded up to whole lanes.  A
    row that ends inside a lane makes the TPU compiler keep the cache with
    its positions minor-most for the per-row writes and move it whole into
    row order for the gather, two copies of the cache a layer a tick (512
    + 64 channels: 604 MB each at 16 rows of 32,768 positions); rounded
    up, the writes, the gather and the kernel that reads the rows under a
    selection's mask share one order
    (``tests/serve/test_decode_in_place.py``).  The channels past the key
    are written as zeros and read only against zeros (that kernel's
    queries are ``[q_lat | q_pe | 0]``)."""
    return -(-(rank + rope_dim) // LANES) * LANES


def update_latent_index_cache(kv_cache, c, k_pe, k_index):
    """``update_latent_cache`` for a "latent" layer that selects its
    positions: ``kv_cache`` is ``(rows (B, S, latent_row_width), keys (B,
    S, di), index)``.  A position's normed latent and rotated shared key
    lie side by side in one row of ``rows`` (``[c | k_pe | unused]``), so
    that a decode fetches a selected position with one gather of one row,
    or scores a block of positions with one product against the rows as
    they lie (``latent_attention_over_selection``); its index key is a row
    of ``keys``.  The ``s`` new positions ``c`` (B,
    s, r), ``k_pe`` (B, s, dr), ``k_index`` (B, s, di) are written at
    ``index`` (a row whose write does not fit stays as it was); returns
    the entry with ``index + s``."""
    rows, keys, index = kv_cache
    index = jnp.asarray(index, jnp.int32)
    spare = rows.shape[2] - c.shape[2] - k_pe.shape[2]
    new = jnp.concatenate(
        [c, k_pe, jnp.zeros(c.shape[:2] + (spare,), c.dtype)],
        axis=-1).astype(rows.dtype)
    k_index = k_index.astype(keys.dtype)
    with jax.named_scope(CACHE_WRITE_SCOPE):
        if index.ndim == 0:
            rows = jax.lax.dynamic_update_slice_in_dim(rows, new, index,
                                                       axis=1)
            keys = jax.lax.dynamic_update_slice_in_dim(keys, k_index, index,
                                                       axis=1)
        else:
            rows = _write_latent_rows(rows, new, index, 1)
            keys = _write_latent_rows(keys, k_index, index, 1)
    return rows, keys, index + new.shape[1]


def index_scores(q_index, weights, keys, q_pos):
    """The indexer's score of every position for every query (DeepSeek-
    V3.2-Exp's ``Indexer``): ``I[t, s] = sum_j w[t, j] relu(q[t, j] .
    k[s])`` for ``s <= t``, ``-inf`` after.  ``q_index`` (B, Sq, J, di),
    ``weights`` (B, Sq, J) float32, ``keys`` (B, Sk, di), ``q_pos`` (1 or
    B, Sq) the queries' positions; returns (B, Sq, Sk) float32.  The
    products are reduced over the J heads a block of keys at a time: no
    (Sq, J, Sk) scores exist.  Shapes the kernels of
    ``ops/latent_attention.py`` take (``index_scores_fits``), in a
    program lowered for a TPU: those kernels, which compute of every row
    the key blocks its queries see and read no others."""
    from alpa_tpu.ops import latent_attention as kernel
    if not kernel.index_scores_fits(q_index, keys):
        return _index_scores_blocks(q_index, weights, keys, q_pos)
    q_pos = jnp.broadcast_to(q_pos, q_index.shape[:2])
    return jax.lax.platform_dependent(
        q_index, weights, keys, q_pos, tpu=kernel.index_scores,
        default=_index_scores_blocks)


def _index_scores_blocks(q_index, weights, keys, q_pos):
    """``index_scores`` in ``jax.numpy``, over every position the keys
    hold, a block of keys at a time."""
    b, sq = q_index.shape[:2]
    sk = keys.shape[1]
    block = 512 if sk % 512 == 0 else sk

    def one_block(at):
        k = jax.lax.dynamic_slice_in_dim(keys, at * block, block, axis=1)
        products = _einsum_f32("bqjd,bkd->bqjk", q_index, k)
        return (jax.nn.relu(products) * weights[..., None]).sum(2)

    scores = jax.lax.map(one_block, jnp.arange(sk // block))
    scores = scores.transpose(1, 2, 0, 3).reshape(b, sq, sk)
    k_pos = jax.lax.broadcasted_iota(jnp.int32, (1, 1, sk), 2)
    return jnp.where(k_pos <= q_pos[:, :, None], scores, -jnp.inf)


def _ordered_bits(x):
    """float32 -> uint32 that orders as the floats do."""
    bits = jax.lax.bitcast_convert_type(x, jnp.int32)
    bits = jnp.where(bits < 0, bits ^ jnp.int32(0x7fffffff), bits)
    return jax.lax.bitcast_convert_type(bits, jnp.uint32) ^ \
        jnp.uint32(0x80000000)


def selected_mask(scores, k: int):
    """(.., Sk) bool: the ``k`` largest of ``scores`` (.., Sk) float32 a
    row, ties to the lower position; ``-inf`` is never selected (a row
    with fewer than ``k`` finite scores selects those).  No sort: the
    k-th largest value a row is found bit by bit (32 counts of the scores
    at or above a candidate), and everything at or above it is selected;
    only where a tie straddles the k-th place is it broken, by a running
    count of the tied."""
    bits = _ordered_bits(scores)

    def one_bit(i, kth):
        candidate = kth | (jnp.uint32(1) << (31 - i).astype(jnp.uint32))
        enough = (bits >= candidate[..., None]).sum(
            -1, dtype=jnp.int32) >= k
        return jnp.where(enough, candidate, kth)

    kth = jax.lax.fori_loop(
        0, 32, one_bit, jnp.zeros(scores.shape[:-1], jnp.uint32))
    at_or_above = bits >= kth[..., None]

    def break_ties(_):
        above = bits > kth[..., None]
        tied = at_or_above & ~above
        room = k - above.sum(-1, dtype=jnp.int32)
        return above | (tied & (jnp.cumsum(tied, axis=-1, dtype=jnp.int32)
                                <= room[..., None]))

    exact = jax.lax.cond(
        (at_or_above.sum(-1, dtype=jnp.int32) > k).any(), break_ties,
        lambda _: at_or_above, None)
    return exact & (scores > -jnp.inf)


def selected_mask_upto(scores, k: int, upto):
    """``selected_mask`` where only the first ``upto`` positions (a traced
    scalar) can hold a finite score, as in a cache that the chunk's last
    query ends at: the counts go over a leading part of the positions that
    holds them all, the shortest of a few static lengths (powers of two up
    to the whole), and where that part is no longer than ``k`` nothing is
    counted at all: every finite score is selected."""
    sk = scores.shape[-1]
    lengths = [n for n in (k, 2 * k, 4 * k, 8 * k) if n < sk] + [sk]

    def within(n):
        def select(x):
            part = x[..., :n]
            chosen = part > -jnp.inf if n <= k else selected_mask(part, k)
            return jnp.pad(chosen,
                           [(0, 0)] * (x.ndim - 1) + [(0, sk - n)])
        return select

    return jax.lax.switch(
        sum((upto > n).astype(jnp.int32) for n in lengths[:-1]),
        [within(n) for n in lengths], scores)


def positions_of(mask, k: int):
    """(R, k) int32: the positions ``mask`` (R, n) bool holds, ascending,
    in a row's first ``mask.sum(-1)`` slots (at most ``k`` of them are
    taken); every later slot names position ``n - 1``.  A stable
    compaction with no sort, no scatter and no (k, n) array: the positions
    go in blocks of ``LANES``; a slot's block is the number of blocks that
    end at or before its count ((R, k, n / LANES) comparisons), and its
    place inside is read off the block's ``LANES`` running counts, which a
    product with the block's one-hot fetches (counts up to ``LANES`` and
    ones are exact in bfloat16, the sums in float32)."""
    r, n = mask.shape
    blocks = -(-n // LANES)
    held = jnp.pad(mask, ((0, 0), (0, blocks * LANES - n))).reshape(
        r, blocks, LANES).astype(jnp.bfloat16)
    at = jax.lax.broadcasted_iota(jnp.int32, (LANES, LANES), 0)
    upto = jax.lax.broadcasted_iota(jnp.int32, (LANES, LANES), 1)
    # how many of its block a position and those before it hold
    inside = jnp.einsum("rbi,ij->rbj", held, (at <= upto).astype(held.dtype),
                        preferred_element_type=jnp.float32)
    total = inside[..., -1]
    through = jnp.cumsum(total, axis=-1)
    slot = jax.lax.broadcasted_iota(jnp.float32, (1, k, 1), 1)
    ended = through[:, None, :] <= slot                       # (R, k, blocks)
    block = ended.sum(-1, dtype=jnp.int32)
    skipped = jnp.where(ended, total[:, None, :], 0).sum(-1)
    one_hot = block[..., None] == jax.lax.broadcasted_iota(
        jnp.int32, (1, 1, blocks), 2)
    counts = jnp.einsum("rkb,rbj->rkj", one_hot.astype(held.dtype),
                        inside.astype(held.dtype),
                        preferred_element_type=jnp.float32)  # (R, k, LANES)
    place = (counts <= slot - skipped[..., None]).sum(-1, dtype=jnp.int32)
    return jnp.minimum(block * LANES + place, n - 1)


def selected_positions(scores, k: int):
    """((.., k) int32, (..,) int32): the positions of the ``k`` largest of
    ``scores`` (.., Sk) a query, ties to the lower position (the set
    ``jax.lax.top_k`` names), and how many of them are real: the first
    ``min(k, finite scores)``, in ascending position; the rest name some
    position in range, which the caller masks.  No sort: the selection is
    ``selected_mask``'s (its threshold, its tie rule) with the queries
    folded into the rows, and the table is that mask compacted
    (``positions_of``)."""
    sk = scores.shape[-1]
    chosen = selected_mask(scores.reshape(-1, sk), k)
    return (positions_of(chosen, k).reshape(scores.shape[:-1] + (k,)),
            chosen.sum(-1, dtype=jnp.int32).reshape(scores.shape[:-1]))


def mask_of(positions, real, n: int):
    """(R, n) int8: ``positions_of`` inverted.  The positions the first
    ``real`` (R,) slots of the table ``positions`` (R, k) name are 1, every
    other of the ``n`` is 0, whatever the later slots name.  No scatter:
    a slot's two one-hots, its block of ``LANES`` positions ((R, k, n /
    LANES); a slot at or past ``real`` has none) and its place inside ((R,
    k, LANES)), and one product of them over the slots, the size of
    ``positions_of``'s own."""
    r, k = positions.shape
    blocks = -(-n // LANES)
    live = jax.lax.broadcasted_iota(jnp.int32, (1, k), 1) < real[:, None]
    block = jnp.where(live, positions // LANES, blocks)
    in_block = block[..., None] == jax.lax.broadcasted_iota(
        jnp.int32, (1, 1, blocks), 2)
    place = (positions % LANES)[..., None] == jax.lax.broadcasted_iota(
        jnp.int32, (1, 1, LANES), 2)
    held = jnp.einsum("rkb,rkj->rbj", in_block.astype(jnp.bfloat16),
                      place.astype(jnp.bfloat16),
                      preferred_element_type=jnp.float32)
    return (held > 0).reshape(r, blocks * LANES)[:, :n].astype(jnp.int8)


def _latent_attention_masked(q_nope, q_pe, c, k_pe, w_kv_b, seen, *, scale):
    """Latent attention in its published form over the keys ``seen`` ((1
    or B, Sq, Sk) bool) says, all keys at once: ``c`` (B, Sk, r) expanded
    by ``w_kv_b`` for all heads, ``k_pe`` (B, dr, Sk), a float32 softmax.
    What a window or a selection makes of ``_latent_attention_blocks``;
    (B, Sq, H, dv) in the queries' dtype."""
    dn = q_nope.shape[-1]
    kv = jnp.einsum("bkr,rhd->bkhd", c, w_kv_b)
    scores = scale * (_einsum_f32("bqhd,bkhd->bhqk", q_nope, kv[..., :dn]) +
                      _einsum_f32("bqhd,bdk->bhqk", q_pe, k_pe))
    probs = jax.nn.softmax(
        jnp.where(seen[:, None], scores, jnp.float32(-1e9)), axis=-1)
    return jnp.einsum("bhqk,bkhd->bqhd", probs.astype(q_nope.dtype),
                      kv[..., dn:])


def latent_attention_selected(q_nope, q_pe, rows, w_kv_b, scale, offset,
                              selected):
    """A chunk's queries over the cached positions ``selected`` ((B, Sq,
    Sk) bool, at or before each query) of a selecting layer's ``rows`` (B,
    Sk, ``latent_row_width``): the expanded core restricted by a mask.
    Over shapes the Pallas kernel takes, in a program lowered for a TPU:
    ``ops/latent_attention.py`` ``expanded`` with the mask as one more operand
    (a key block's scores never leave fast memory; it still walks every
    key block up to the chunk's last query); ``_latent_attention_masked``
    anywhere else."""
    from alpa_tpu.ops import latent_attention as kernel
    rank, dr = w_kv_b.shape[0], q_pe.shape[-1]
    k_pe = rows[..., rank:rank + dr].swapaxes(1, 2)

    def masked(q_nope, q_pe, rows, k_pe, w_kv_b, offset, selected):
        return _latent_attention_masked(
            q_nope, q_pe, rows[..., :rank], k_pe, w_kv_b, selected,
            scale=scale)

    offset = jnp.broadcast_to(jnp.asarray(offset, jnp.int32),
                              (q_nope.shape[0],))
    # the kernel splits a head's expansion at a whole lane: keys of 192
    # channels go in as keys of 256, the queries' and the expansion's new
    # channels zeros, which no score sees
    dn = q_nope.shape[-1]
    spare = -dn % LANES
    wide_q, wide_w = q_nope, w_kv_b
    if spare:
        wide_q = jnp.pad(q_nope, ((0, 0),) * 3 + ((0, spare),))
        wide_w = jnp.concatenate(
            [w_kv_b[..., :dn], jnp.zeros(w_kv_b.shape[:2] + (spare,),
                                         w_kv_b.dtype), w_kv_b[..., dn:]],
            -1)
    if not kernel.fits(wide_q, rows, wide_w, masked=True):
        return masked(q_nope, q_pe, rows, k_pe, w_kv_b, offset, selected)
    return jax.lax.platform_dependent(
        q_nope, q_pe, rows, k_pe, w_kv_b, offset, selected, wide_q, wide_w,
        tpu=lambda q_nope, q_pe, rows, k_pe, w_kv_b, offset, selected,
        wide_q, wide_w:
        kernel.expanded(wide_q, q_pe, rows, k_pe, wide_w, offset,
                        scale=scale, selected=selected.astype(jnp.int8)),
        default=lambda *args: masked(*args[:7]))


def few_queries(config, queries: int, index) -> bool:
    """Whether a cached call of ``queries`` new positions a row at the
    cache index ``index`` is a DECODE to a selecting layer, which makes
    each query a table of its selection and attends over that
    (``latent_attention_over_selection``): one query a row, or, at per-row
    indices, up to one more a multi-token-prediction module (the verify of
    a tick that drafts).  Anything else is a prefill's chunk, which walks
    the cache under the selection's mask with its many queries a block."""
    return queries == 1 or (
        queries <= 1 + config.num_nextn_predict_layers and
        jnp.ndim(index) == 1)


def latent_attention_gathered(q_nope, q_pe, rows, w_kv_b, scale, positions,
                              real):
    """A few queries a row, each over a selection of its own: ``positions``
    (B, s, k) int32 into the cached ``rows`` (B, Sk, ``latent_row_width``),
    the first ``real`` (B, s) of each real.  One gather of the selected
    rows, (B, s, k, row), and the absorbed core a query over its k rows
    alone, as one query a row runs it (the queries folded into the rows);
    (B, s, H, dv)."""
    b, s, k = positions.shape
    rank, dr = w_kv_b.shape[0], q_pe.shape[-1]
    taken = jnp.take_along_axis(
        rows, positions.reshape(b, s * k)[:, :, None], axis=1).reshape(
            b * s, k, rows.shape[-1])
    out = latent_attention_absorbed(
        q_nope.reshape((b * s, 1) + q_nope.shape[2:]),
        q_pe.reshape((b * s, 1) + q_pe.shape[2:]), taken[..., :rank],
        taken[..., rank:rank + dr].swapaxes(1, 2), w_kv_b, scale,
        real.reshape(b * s) - 1)
    return out.reshape((b, s) + out.shape[2:])


# What the gather of one (row, query)'s selected rows is worth, in key
# blocks of ``ops/latent_attention.py``'s ``DECODE_BLOCK_K`` positions read
# under the selection's mask instead.  ``scripts/time_dsa_parts.py --cores``
# on a v5e (PERF.md, PR 59), 16 rows all holding 2,048 positions up to the
# whole cache: under the mask 0.298 ms + 2.02 us a key block held at
# dots3-note's shape (one query of 128 heads, 32,768 positions) and 0.249
# ms + 2.01 us at GLM-5's (two queries of 64 heads, 24,576), the fixed part
# the grid's steps that skip (0.36 us each) and the table's mask; gathered
# 0.865 ms and 1.624 ms whatever the rows hold, 54 and 51 us a (row,
# query).  That is 17.6 and 21.4 key blocks a (row, query): the lower.
GATHER_WORTH_KEY_BLOCKS = 18


def latent_attention_over_selection(q_nope, q_pe, rows, w_kv_b, scale, index,
                                    positions, real, interpret=False):
    """A DECODE's queries (``few_queries``: (B, s, H, .), the first of a
    row at ``index`` (B,)) of a selecting layer, each over the selection
    ``selected_positions`` made for it: ``positions`` (B, s, k) into the
    cached ``rows`` (B, Sk, ``latent_row_width``), the first ``real`` (B,
    s) of each real; (B, s, H, dv).

    Two cores, one result but for the order of a sum.  ``gathered``
    (``latent_attention_gathered``) copies each query's selected rows out
    of the cache and runs the absorbed core over the copy: a fixed price a
    (row, query), whatever the row holds.  ``under_mask`` turns the table
    back into the mask it was compacted from (``mask_of``) and runs
    ``ops/latent_attention.py`` ``absorbed_under_mask`` over the cache as it
    lies: no copy, a row's key blocks read once for all its queries, as
    far as the row has written; its price is the key blocks the rows hold.
    A (row, query)'s gather is worth ``GATHER_WORTH_KEY_BLOCKS`` of them,
    so in a program lowered for a TPU, over shapes the kernel takes: a
    cache of no more than that many blocks a query goes ``under_mask``
    whatever it holds, and the program has no gather; a longer one
    ``by_held``, a ``lax.cond`` on the blocks this call's rows hold, summed,
    against that price for all rows and queries.  Anywhere else:
    ``gathered``.  The gauge ``alpa_selecting_decode_core`` says at trace
    time which.  ``interpret``: the kernel interpreted, whatever the
    platform (the tests)."""
    from alpa_tpu.ops import latent_attention as kernel
    b, s, nh, dn = q_nope.shape
    sk, rank = rows.shape[1], w_kv_b.shape[0]
    index = jnp.broadcast_to(jnp.asarray(index, jnp.int32), (b,))

    def gathered(q_nope, q_pe, rows, w_kv_b, index, positions, real):
        return latent_attention_gathered(q_nope, q_pe, rows, w_kv_b, scale,
                                         positions, real)

    def under_mask(q_nope, q_pe, rows, w_kv_b, index, positions, real):
        q_lat = jnp.einsum("bqhd,rhd->bqhr", q_nope, w_kv_b[..., :dn])
        chosen = mask_of(positions.reshape(b * s, -1), real.reshape(b * s),
                         sk).reshape(b, s, sk)
        o_lat = kernel.absorbed_under_mask(q_lat, q_pe, rows, chosen, index,
                                           scale=scale, interpret=interpret)
        return jnp.einsum("bqhr,rhd->bqhd", o_lat, w_kv_b[..., dn:])

    def by_held(q_nope, q_pe, rows, w_kv_b, index, positions, real):
        return jax.lax.cond(
            kernel.decode_blocks(index, s, sk).sum() <=
            b * s * GATHER_WORTH_KEY_BLOCKS, under_mask, gathered,
            q_nope, q_pe, rows, w_kv_b, index, positions, real)

    if not kernel.under_mask_fits(q_pe, rows, rank):
        core = gathered
    elif sk // kernel.DECODE_BLOCK_K <= s * GATHER_WORTH_KEY_BLOCKS:
        core = under_mask
    else:
        core = by_held
    _selecting_core_gauge().labels(core.__name__, nh, s, sk).inc()
    args = (q_nope, q_pe, rows, w_kv_b, index, positions, real)
    if core is gathered or interpret:
        return core(*args)
    return jax.lax.platform_dependent(*args, tpu=core, default=gathered)


def _selecting_core_gauge():
    return tmetrics.get_registry().gauge(
        "alpa_selecting_decode_core",
        "selecting latent layers whose decode was traced with each core "
        "(under_mask: where lowered for a TPU, the kernel that reads each "
        "row's cache as it lies under the selection's mask, no gather in "
        "the program; by_held: where lowered for a TPU, that kernel or the "
        "gather by the key blocks the call's rows hold; gathered: a copy "
        "of the selected rows and the absorbed core over it), by the "
        "heads, the queries a row and the cache's positions",
        ("core", "heads", "queries", "positions"))


def head_gates(logits):
    """The head-wise gates of ``LatentAttention``: a sigmoid a head."""
    return jax.nn.sigmoid(logits)


class LatentAttention(nn.Module):
    """Multi-head latent attention (DeepSeek-V2, 2024), the ``attention``
    kinds "latent" and "latent_sliding" (``GPTConfig``): ``c_q = norm(x
    Wq_a)``, ``q = c_q Wq_b`` a head ``[q_nope | q_pe]``; ``[c | k_pe] = x
    Wkv_a``, ``c = norm(c)``; rotary positions on ``q_pe`` and on ``k_pe``,
    ONE key for all heads; ``[k_nope | v] = c Wkv_b`` a head; scores
    ``(q_nope . k_nope + q_pe . k_pe) * attn_scale``; the heads' values
    (``v_head_dim`` each) through ``out``.  ``attention`` is the layer's
    kind (None: "latent"), and the widths are that kind's
    (``GPTConfig.latent_widths``): one module, whatever the widths.

    ``q_lora_scale`` and ``kv_lora_scale`` (LongCat-Flash) multiply the
    normed ``c_q`` and the normed ``c``, in float32 before the cast to
    ``dtype``; ``k_pe`` is not scaled.  The cache holds ``c`` SCALED, so
    that the expanded and the absorbed core read one cache as they do
    without the factor and no product downstream knows of it.

    The cache is ``(c, k_pe with the positions last, index)``
    (``update_latent_cache``).  Over it
    one new position a row (a decode) takes ``latent_attention_absorbed``,
    several (a prefill's chunk) ``latent_attention_expanded``: the choice
    is the static number of new positions, nothing else.

    ``attn_gate`` "head": the heads' outputs times ``sigmoid(x Wg)``, one
    gate a head, before ``out``.

    "latent_sliding": query p sees the keys with ``p - k <
    sliding_window``; the cache is a ring of the window's latents
    (``update_latent_ring``), a decode the absorbed core over the ring, a
    chunk the expanded form over the ring with the chunk behind it.

    A "latent" layer of a configuration with ``index_topk`` selects: an
    indexer (``index_q`` from the scaled ``c_q``, ``index_k`` and a
    LayerNorm from ``x``, rotary positions on the first
    ``qk_rope_head_dim`` channels of both, rotate-half; ``index_w`` from
    ``x``) scores every earlier position for every query (``index_scores``),
    and the softmax and the values go over the ``index_topk`` best alone.
    Its cache is ``(rows, index keys, index)``
    (``update_latent_index_cache``).  A decode names the selected
    positions (``selected_positions``: a table in ascending position, made
    with no sort) and runs the absorbed core over those alone
    (``latent_attention_over_selection``: over a copy of their rows, one
    gather, or over the cache as it lies under the mask the table came
    from, by what the cache can hold and the rows do hold); a chunk, and a
    call without a cache, the expanded form under the selection's mask
    (``latent_attention_selected``).  A decode of a FEW queries a row (a
    verify of a tick that drafts: ``few_queries``) scores and chooses for
    each query by itself and takes the same function, a row's queries
    together; one query a row is that at one.  With ``return_selected``
    the third result is what
    a decode selected: ``(positions (B, index_topk), how many of them are
    real (B,))``, of a few queries ``((B, s, index_topk), (B, s))``.  The
    indexer's rotary pairs are rotate-half, or interleaved where
    ``GPTConfig.index_rope_interleaved``."""
    config: GPTConfig
    attention: Optional[str] = None

    @nn.compact
    def __call__(self, x, kv_cache=None, deterministic=True,
                 position_ids=None, cache_lengths=None,
                 return_selected=False):
        cfg = self.config
        if cfg.block_length:
            raise ValueError("latent attention has no block-causal mask "
                             "(GPTConfig.block_length)")
        if not cfg.causal or position_ids is None:
            raise ValueError("latent attention is causal over rotary "
                             "positions")
        kind = self.attention or "latent"
        w = cfg.latent_widths(kind)
        window = cfg.sliding_window if kind == LATENT_SLIDING else 0
        selects = cfg.selects(kind)
        nh, rank = w.num_heads, w.kv_lora_rank
        dn, dr, dv = w.qk_nope_head_dim, w.qk_rope_head_dim, w.v_head_dim
        dense = partial(nn.Dense, dtype=cfg.dtype, use_bias=cfg.use_bias,
                        param_dtype=cfg.param_dtype)
        b, s = x.shape[0], x.shape[1]
        c_q = x
        if w.q_lora_rank:
            c_q = make_norm(cfg, "q_a_norm")(
                dense(w.q_lora_rank, name="q_a")(x))
            if w.q_lora_scale != 1.0:
                c_q = c_q * w.q_lora_scale
            c_q = c_q.astype(cfg.dtype)
        q = dense(nh * (dn + dr), name="q_b")(c_q).reshape(b, s, nh, dn + dr)
        kv_a = dense(rank + dr, name="kv_a")(x)
        c = make_norm(cfg, "kv_a_norm")(kv_a[..., :rank])
        if w.kv_lora_scale != 1.0:
            c = c * w.kv_lora_scale
        c = c.astype(cfg.dtype)
        w_kv_b = self.param(
            "kv_b", nn.initializers.lecun_normal(), (rank, nh * (dn + dv)),
            cfg.param_dtype).astype(cfg.dtype).reshape(rank, nh, dn + dv)
        rotate = partial(apply_rotary, position_ids=position_ids,
                         theta=w.rope_theta,
                         interleaved=cfg.rope_interleaved,
                         yarn=cfg.rope_yarn)
        q_nope, q_pe = q[..., :dn], rotate(q[..., dn:])
        k_pe = rotate(kv_a[:, :, None, rank:])[:, :, 0]
        scale = w.attn_scale or (dn + dr) ** -0.5
        q_pos = position_ids.astype(jnp.int32)

        new_cache = None
        selected = None
        # the scope of the attention core (the cache's write, the
        # expansion or the absorption, scores, softmax, values; not the
        # low-rank projections; of a selecting layer the indexer whole)
        with jax.named_scope(ATTENTION_SCOPE):
            if selects:
                with jax.named_scope(INDEXER_SCOPE):
                    q_index, k_index, w_index = self._indexer(
                        x, c_q, position_ids, dense, dr, w.rope_theta)
            if kv_cache is None and not (window or selects):
                out = latent_attention_expanded(
                    q_nope, q_pe, c, k_pe.swapaxes(1, 2), w_kv_b, scale)
            elif kv_cache is None:
                # every key at once under a mask: what the window or the
                # selection shows each query of its own sequence
                k_pos = jax.lax.broadcasted_iota(jnp.int32, (1, 1, s), 2)
                seen = k_pos <= q_pos[:, :, None]
                if window:
                    seen &= q_pos[:, :, None] - k_pos < window
                else:
                    with jax.named_scope(INDEXER_SCOPE):
                        seen = selected_mask(index_scores(
                            q_index, w_index, k_index, q_pos),
                            cfg.index_topk)
                out = _latent_attention_masked(
                    q_nope, q_pe, c, k_pe.swapaxes(1, 2), w_kv_b, seen,
                    scale=scale)
            elif window:
                index = jnp.asarray(kv_cache[2], jnp.int32)
                c_use, pe_use, k_positions, new_cache = update_latent_ring(
                    kv_cache, c, k_pe, cache_lengths)
                k_held = k_positions[:, None, :]
                seen = (k_held <= q_pos[:, :, None]) & (k_held >= 0) & \
                    (q_pos[:, :, None] - k_held < window)
                if s == 1:
                    out = latent_attention_absorbed(
                        q_nope, q_pe, c_use, pe_use, w_kv_b, scale, index,
                        seen=seen)
                else:
                    out = _latent_attention_masked(
                        q_nope, q_pe, c_use, pe_use, w_kv_b, seen,
                        scale=scale)
            elif selects:
                index = jnp.asarray(kv_cache[2], jnp.int32)
                new_cache = update_latent_index_cache(kv_cache, c, k_pe,
                                                      k_index)
                rows, keys = new_cache[:2]
                few = s > 1 and few_queries(cfg, s, index)
                with jax.named_scope(INDEXER_SCOPE):
                    scores = index_scores(q_index, w_index, keys, q_pos)
                    if s == 1:
                        selected = selected_positions(scores[:, 0],
                                                      cfg.index_topk)
                    elif few:
                        # (B, s, index_topk) and (B, s): a selection a
                        # query
                        selected = selected_positions(scores,
                                                      cfg.index_topk)
                    else:
                        chosen = selected_mask_upto(
                            scores, cfg.index_topk, jnp.max(index) + s)
                with jax.named_scope(SELECT_SCOPE):
                    if few or s == 1:
                        # a decode: over each query's table of positions,
                        # in ascending position, the first ``real`` real
                        positions, real = selected
                        if s == 1:
                            positions, real = positions[:, None], real[:, None]
                        out = latent_attention_over_selection(
                            q_nope, q_pe, rows, w_kv_b, scale, index,
                            positions, real)
                    else:
                        out = latent_attention_selected(
                            q_nope, q_pe, rows, w_kv_b, scale, index,
                            chosen)
            else:
                index = jnp.asarray(kv_cache[2], jnp.int32)
                new_cache = update_latent_cache(kv_cache, c, k_pe)
                core = latent_attention_absorbed if s == 1 else \
                    latent_attention_expanded
                out = core(q_nope, q_pe, new_cache[0], new_cache[1], w_kv_b,
                           scale, index)
        if cfg.attn_gate == "head":
            out = out * head_gates(dense(nh, name="gate")(x))[..., None]
        elif cfg.attn_gate:
            raise ValueError("latent attention takes attn_gate \"head\" "
                             f"or none, not {cfg.attn_gate!r}")
        out = dense(cfg.hidden_size, name="out")(out.reshape(b, s, nh * dv))
        if return_selected:
            return out, new_cache, selected
        return out, new_cache

    def _indexer(self, x, c_q, position_ids, dense, dr, theta):
        """The indexer's queries (B, S, J, di), keys (B, S, di) and head
        weights (B, S, J) float32 of the layer's normed input ``x`` and
        scaled query latent ``c_q``."""
        cfg = self.config
        heads, di = cfg.index_n_heads, cfg.index_head_dim
        b, s = x.shape[:2]
        rotate = partial(apply_rotary, position_ids=position_ids,
                         theta=theta,
                         interleaved=cfg.index_rope_interleaved)
        q = dense(heads * di, name="index_q")(c_q).reshape(b, s, heads, di)
        k = nn.LayerNorm(epsilon=1e-6, dtype=jnp.float32,
                         param_dtype=cfg.param_dtype, name="index_k_ln")(
                             dense(di, name="index_k")(x)).astype(cfg.dtype)
        q = jnp.concatenate([rotate(q[..., :dr]), q[..., dr:]], axis=-1)
        k = jnp.concatenate([rotate(k[:, :, None, :dr])[:, :, 0],
                             k[..., dr:]], axis=-1)
        weights = dense(heads, name="index_w")(x).astype(jnp.float32) * \
            (heads ** -0.5 * di ** -0.5)
        return q, k, weights


# the scope a gated short convolution is traced under, the whole mixer:
# both products, the gates, the taps, the state's update (a capture reads
# it: telemetry/device_time.py)
CONV_SCOPE = "short_conv"


def update_conv_state(kv_cache, g, lengths=None):
    """``update_kv_cache`` for a "conv" layer, whose state is the last
    ``taps - 1`` positions of the product ``g`` it convolves: ``kv_cache``
    is ``(state (B, taps - 1, h), an empty (B, 0) array, index)``, the
    entry a triple as every layer's is, ``index`` (a scalar, or (B,) a row)
    the position of the first of the ``s`` new ones ``g`` (B, s, h).
    Returns ``(full, new_cache)``: ``full`` (B, taps - 1 + s, h) is the
    state with ``g`` behind it, what the taps run over, and the new state
    is, a row, the ``taps - 1`` positions of ``full`` that end at the row's
    last REAL new position.

    ``lengths`` ((B,), the rows' whole lengths) says which new positions
    are real where the ids are right-padded: a row with ``r`` real ones
    keeps ``full[r : r + taps - 1]``: all of its old state if none is
    real, its old state's last position before ``g_0`` if one is.  No
    mask hides a wrong state afterwards, as one hides a cache's padding:
    every later token of the row reads it.  None: all ``s`` are real.

    A Mamba-2 mixer's convolution runs over the same kind of state, wider
    (``Mamba2``, which puts its ssm state where ``empty`` is).

    Nothing of ``update_kv_cache``'s rule for unwritten positions holds
    here: a fresh row's state has to BE zeros (position 0 sees two zero
    positions before it), which ``fresh_kv_caches`` gives; a free row's
    state is junk until an admission overwrites the whole row."""
    state, empty, index = kv_cache
    index = jnp.asarray(index, jnp.int32)
    keep, s = state.shape[1], g.shape[1]
    full = jnp.concatenate([state.astype(g.dtype), g], axis=1)
    if lengths is None:
        new_state = full[:, s:]
    else:
        first = index[:, None] if index.ndim else index[None, None]
        real = jnp.clip(lengths[:, None] - first, 0, s)          # (B, 1)
        at = real + jax.lax.broadcasted_iota(jnp.int32, (1, keep), 1)
        new_state = jnp.take_along_axis(full, at[:, :, None], axis=1)
    return full, (new_state.astype(state.dtype), empty, index + s)


class ShortConv(nn.Module):
    """The gated short convolution of LFM2 (LiquidAI 2025), the
    ``attention`` kind "conv" (``GPTConfig``), no bias anywhere: ``[B, C,
    X] = split3(in_proj(u))``; ``g = B * X``; ``c_t = sum_j kernel[j] *
    g_{t - (taps - 1) + j}`` with ``g_s = 0`` for ``s < 0`` (depthwise,
    causal, ``kernel`` (taps, h)); ``out_proj(C * c)``.

    Without a cache (the training call) the convolution runs over the
    whole sequence behind ``taps - 1`` zero positions.  With one, the
    layer's entry is ``(state, empty, index)`` (``update_conv_state``):
    one new position reads the state, computes its ``c`` and shifts; ``s``
    new positions run the taps over the state with ``g`` behind it and
    leave, a row, the state of its last real position
    (``cache_lengths``)."""
    config: GPTConfig

    @nn.compact
    def __call__(self, x, kv_cache=None, deterministic=True,
                 position_ids=None, cache_lengths=None):
        cfg = self.config
        if not cfg.causal or cfg.block_length:
            raise ValueError("a short convolution is causal over one "
                             "sequence a row and takes no block-causal "
                             "mask")
        h, taps = cfg.hidden_size, cfg.conv_taps
        if taps < 2:
            raise ValueError("a \"conv\" layer needs GPTConfig.conv_taps "
                             f">= 2, got {taps}")
        dense = partial(nn.Dense, dtype=cfg.dtype, use_bias=False,
                        param_dtype=cfg.param_dtype)
        s = x.shape[1]
        new_cache = None
        with jax.named_scope(CONV_SCOPE):
            b_gate, c_gate, xs = jnp.split(
                dense(3 * h, name="in_proj")(x), 3, axis=-1)
            g = b_gate * xs
            # lecun_normal over the taps: a tap's fan-in is ``taps``
            kernel = self.param("kernel", nn.initializers.lecun_normal(),
                                (taps, h), cfg.param_dtype)
            if kv_cache is None:
                full = jnp.pad(g, ((0, 0), (taps - 1, 0), (0, 0)))
            else:
                full, new_cache = update_conv_state(kv_cache, g,
                                                    cache_lengths)
            # three shifted copies, summed in float32
            conv = sum(kernel[j].astype(jnp.float32) *
                       full[:, j:j + s].astype(jnp.float32)
                       for j in range(taps)).astype(cfg.dtype)
            out = dense(h, name="out_proj")(c_gate * conv)
        return out, new_cache


# the scope a Mamba-2 mixer is traced under, the whole mixer: both
# projections, the convolution, the recurrence, the gated norm, both
# states' updates (a capture reads it: telemetry/device_time.py)
SSM_SCOPE = "ssm_mixer"


def _dt_bias_init(low: float = 1e-3, high: float = 0.1, floor: float = 1e-4):
    """``dt_bias`` as the family initialises it: the inverse softplus of a
    step drawn log-uniformly in [``low``, ``high``] and floored (the
    file's ``time_step_min``, ``time_step_max``, ``time_step_floor``), so
    that ``softplus(0 + dt_bias)`` is that step."""
    def init(key, shape, dtype=jnp.float32):
        dt = jnp.exp(jax.random.uniform(key, shape, jnp.float32) *
                     (np.log(high) - np.log(low)) + np.log(low))
        dt = jnp.maximum(dt, floor)
        return (dt + jnp.log(-jnp.expm1(-dt))).astype(dtype)
    return init


def real_steps(dt, index, lengths):
    """The steps ``dt`` (B, s, H) of ``s`` new positions from ``index`` on
    (a scalar, or (B,) a row), 0 at the positions past the rows' whole
    ``lengths`` (B,): right-padding, which is not real and has to pass the
    state on as it found it (a step of 0 neither decays nor adds)."""
    first = jnp.asarray(index, jnp.int32)
    first = first[:, None] if first.ndim else first[None, None]
    at = first + jax.lax.broadcasted_iota(jnp.int32, (1, dt.shape[1]), 1)
    return jnp.where((at < lengths[:, None])[..., None], dt, 0.0)


def gated_group_norm(y, z, weight, groups: int, eps: float):
    """``rmsnorm_groups(y * silu(z)) * weight`` in float32: the gate FIRST,
    then an RMSNorm over each of the ``groups`` groups of consecutive
    channels, one weight vector for all of them (Mamba-2's gated norm as
    Nemotron-H configures it)."""
    y = y.astype(jnp.float32) * nn.silu(z.astype(jnp.float32))
    grouped = y.reshape(y.shape[:-1] + (groups, -1))
    grouped = grouped * jax.lax.rsqrt(
        jnp.square(grouped).mean(-1, keepdims=True) + eps)
    return grouped.reshape(y.shape) * weight.astype(jnp.float32)


class Mamba2(nn.Module):
    """The Mamba-2 mixer of Nemotron-H (Dao & Gu 2024; the ``attention``
    kind "ssm" of ``GPTConfig``), no bias but the convolution's.  ``H``
    heads of ``P`` channels (``ssm_heads``, ``ssm_head_dim``; inner width
    ``H P``), a state of ``N`` (``ssm_state_size``), ``G`` groups
    (``ssm_groups``) of ``H / G`` consecutive heads:

    ``[z | xBC | dt] = in_proj(u)`` of widths ``H P``, ``H P + 2 G N``, ``H``;
    ``xBC = silu(conv(xBC) + bias)``, a depthwise causal convolution of
    ``conv_taps`` taps behind zeros; ``[x | B | C] = xBC``, ``x`` as (H, P),
    ``B`` and ``C`` as (G, N); ``dt = softplus(dt + dt_bias)`` and ``A =
    -exp(A_log)`` a head, float32; for head h of group g ``S_t = exp(dt_t
    A) S_{t-1} + dt_t x_t B_t^T`` and ``y_t = S_t C_t + D_h x_t``
    (``ops/ssm_scan.py``); ``y = rmsnorm_groups(y * silu(z)) * w``, the gate
    first, then an RMSNorm over each group's ``H P / G`` channels;
    ``out_proj(y)``.

    Without a cache (the training call) the sequence starts from zeros.
    With one, the layer's entry is ``(conv state (B, taps - 1, H P + 2 G
    N), ssm state (B, H, P, N) float32, index)``: one new position a row
    reads both states, steps and writes them (``ssm_step``); ``s`` new
    positions run the taps over the conv state with ``xBC`` behind it
    (``update_conv_state``) and the scan in sub-chunks of ``ssm_chunk``
    FROM the row's ssm state (``ssm_chunk_scan``), and leave, a row, both
    states of its last REAL position (``cache_lengths``: a padded position
    has ``dt`` 0, which neither decays the state nor adds to it; no mask
    hides a wrong state afterwards).

    A model made from a seed draws ``A_log = log(1 .. H)``, ``dt_bias``
    the inverse softplus of a log-uniform step in [0.001, 0.1], ``D`` ones,
    as the family does, so that its state neither dies in a position nor
    never forgets; the three are float32 whatever ``param_dtype``."""
    config: GPTConfig

    @nn.compact
    def __call__(self, x, kv_cache=None, deterministic=True,
                 position_ids=None, cache_lengths=None):
        from alpa_tpu.ops.ssm_scan import ssm_chunk_scan, ssm_step
        cfg = self.config
        if not cfg.causal or cfg.block_length:
            raise ValueError("a Mamba-2 mixer is causal over one sequence "
                             "a row and takes no block-causal mask")
        heads, p, n, g = (cfg.ssm_heads, cfg.ssm_head_dim,
                          cfg.ssm_state_size, cfg.ssm_groups)
        taps, inner, width = cfg.conv_taps, cfg.ssm_inner, cfg.ssm_conv_width
        if min(heads, p, n) < 1 or heads % g or inner % g or taps < 2:
            raise ValueError(
                "an \"ssm\" layer needs GPTConfig.ssm_heads (a multiple "
                "of ssm_groups), ssm_head_dim, ssm_state_size and "
                f"conv_taps >= 2, got {(heads, g, p, n, taps)}")
        dense = partial(nn.Dense, dtype=cfg.dtype, use_bias=False,
                        param_dtype=cfg.param_dtype)
        b, s = x.shape[:2]
        new_cache = None
        with jax.named_scope(SSM_SCOPE):
            z, xbc, dt = jnp.split(
                dense(inner + width + heads, name="in_proj")(x),
                [inner, inner + width], axis=-1)
            # lecun_normal over the taps: a tap's fan-in is ``taps``
            kernel = self.param("conv_kernel",
                                nn.initializers.lecun_normal(),
                                (taps, width), cfg.param_dtype)
            # (as torch's Conv1d draws it: a zero bias would hide its loss)
            bias = self.param(
                "conv_bias", lambda key, shape, dtype: jax.random.uniform(
                    key, shape, dtype, -0.5, 0.5), (width,), cfg.param_dtype)
            dt_bias = self.param("dt_bias", _dt_bias_init(), (heads,),
                                 jnp.float32)
            a_log = self.param(
                "A_log", lambda key, shape, dtype: jnp.log(
                    jnp.arange(1, shape[0] + 1, dtype=dtype)),
                (heads,), jnp.float32)
            skip = self.param("D", nn.initializers.ones, (heads,),
                              jnp.float32)
            if kv_cache is None:
                full = jnp.pad(xbc, ((0, 0), (taps - 1, 0), (0, 0)))
                state = jnp.zeros((b, heads, p, n), jnp.float32)
            else:
                conv_state, state, index = kv_cache
                full, (conv_state, _, index) = update_conv_state(
                    (conv_state, None, index), xbc, cache_lengths)
            # the shifted copies, summed in float32
            conv = sum(kernel[j].astype(jnp.float32) *
                       full[:, j:j + s].astype(jnp.float32)
                       for j in range(taps)) + bias.astype(jnp.float32)
            xs, bs, cs = jnp.split(nn.silu(conv).astype(cfg.dtype),
                                   [inner, inner + g * n], axis=-1)
            xs = xs.reshape(b, s, heads, p)
            bs, cs = bs.reshape(b, s, g, n), cs.reshape(b, s, g, n)
            dt = jax.nn.softplus(dt.astype(jnp.float32) + dt_bias)
            if kv_cache is not None and cache_lengths is not None:
                dt = real_steps(dt, kv_cache[2], cache_lengths)
            a = -jnp.exp(a_log)
            if s == 1 and kv_cache is not None:
                y, state = ssm_step(state, xs[:, 0], dt[:, 0], a, bs[:, 0],
                                    cs[:, 0])
                y = y[:, None]
            else:
                y, state = ssm_chunk_scan(state, xs, dt, a, bs, cs,
                                          cfg.ssm_chunk, cfg.dtype)
            y = y + skip[:, None] * xs.astype(jnp.float32)
            if kv_cache is not None:
                new_cache = (conv_state, state, index)
            weight = self.param("norm", nn.initializers.ones, (inner,),
                                cfg.param_dtype)
            y = gated_group_norm(y.reshape(b, s, inner), z, weight, g,
                                 cfg.layer_norm_eps)
            out = dense(cfg.hidden_size, name="out_proj")(
                y.astype(cfg.dtype))
        return out, new_cache


# the scope of a Mamba-1 mixer's recurrence ALONE, inside ``SSM_SCOPE`` (a
# capture reads it: telemetry/device_time.py)
S6_SCAN_SCOPE = "selective_scan"


def s6_rates(a_log):
    """``A = -exp(A_log)``, (N, D): a channel's AND a state value's own."""
    return -jnp.exp(a_log)


def s6_dt(low, kernel, bias):
    """``softplus(dt_proj(low))`` in float32, ``dt_proj`` WITH its bias and
    no clamp: ``low`` (B, s, R) in the kernel's dtype, ``kernel`` (R, D),
    ``bias`` (D,) float32."""
    return jax.nn.softplus(jnp.dot(
        low, kernel, preferred_element_type=jnp.float32) + bias)


def s6_gate(y, z):
    """``y * silu(z)`` in float32: the gate alone, no norm behind it."""
    return y * nn.silu(z.astype(jnp.float32))


class Mamba1(nn.Module):
    """The Mamba-1 mixer of Jamba (Gu & Dao 2023; the ``attention`` kind
    "s6" of ``GPTConfig``), no bias but the convolution's and
    ``dt_proj``'s.  ``D`` channels (``s6_inner``), ``N`` state values a
    channel (``ssm_state_size``), a step through rank ``R``
    (``s6_dt_rank``):

    ``[x | z] = in_proj(u)``, each ``D`` wide, ``x`` FIRST; ``x =
    silu(conv(x) + bias)``, a depthwise causal convolution of ``conv_taps``
    taps behind zeros; ``[dt | B | C] = x_proj(x)`` of widths ``R``, ``N``,
    ``N``, each through an RMSNorm of its own (the family's addition to
    Mamba); ``dt = softplus(dt_proj(dt))`` (``s6_dt``) and ``A =
    -exp(A_log)`` (N, D), float32; for channel d and state value n ``h_t =
    exp(dt_t[d] A[n, d]) h_{t-1} + dt_t[d] B_t[n] x_t[d]`` and ``y_t[d] =
    sum_n h_t[n, d] C_t[n] + D[d] x_t[d]`` (``ops/selective_scan.py``);
    ``y = y * silu(z)`` (``s6_gate``), no norm behind the gate;
    ``out_proj(y)``.

    Without a cache (the training call) the sequence starts from zeros.
    With one, the layer's entry is ``(conv state (B, taps - 1, D), ssm
    state (B, N, D) float32, index)``, the CHANNELS minor-most (16 state
    values in the chip's 128 lanes would lie at eight times their bytes):
    one new position a row reads both states, steps and writes them
    (``s6_step``); ``s`` new positions run the taps over the conv state
    with ``x`` behind it (``update_conv_state``) and the scan FROM the
    row's ssm state (``s6_chunk_scan``), and leave, a row, both states of
    its last REAL position (``cache_lengths``: a padded position has ``dt``
    0, ``real_steps``).

    A model made from a seed draws ``A_log[:, d] = log(1 .. N)``,
    ``dt_proj``'s bias the inverse softplus of a log-uniform step in
    [0.001, 0.1], ``D`` ones, as the Mamba reference does; the three are
    float32 whatever ``param_dtype``."""
    config: GPTConfig

    @nn.compact
    def __call__(self, x, kv_cache=None, deterministic=True,
                 position_ids=None, cache_lengths=None):
        from alpa_tpu.ops import selective_scan
        cfg = self.config
        if not cfg.causal or cfg.block_length:
            raise ValueError("a Mamba-1 mixer is causal over one sequence "
                             "a row and takes no block-causal mask")
        inner, n, rank, taps = (cfg.s6_inner, cfg.ssm_state_size,
                                cfg.s6_dt_rank, cfg.conv_taps)
        if min(inner, n, rank) < 1 or taps < 2:
            raise ValueError(
                "an \"s6\" layer needs GPTConfig.s6_inner, ssm_state_size, "
                f"s6_dt_rank and conv_taps >= 2, got {(inner, n, rank, taps)}")
        dense = partial(nn.Dense, dtype=cfg.dtype, use_bias=False,
                        param_dtype=cfg.param_dtype)
        b, s = x.shape[:2]
        new_cache = None
        with jax.named_scope(SSM_SCOPE):
            xs, z = jnp.split(dense(2 * inner, name="in_proj")(x), 2,
                              axis=-1)
            # lecun_normal over the taps: a tap's fan-in is ``taps``
            kernel = self.param("conv_kernel",
                                nn.initializers.lecun_normal(),
                                (taps, inner), cfg.param_dtype)
            # (as torch's Conv1d draws it: a zero bias would hide its loss)
            bias = self.param(
                "conv_bias", lambda key, shape, dtype: jax.random.uniform(
                    key, shape, dtype, -0.5, 0.5), (inner,), cfg.param_dtype)
            if kv_cache is None:
                full = jnp.pad(xs, ((0, 0), (taps - 1, 0), (0, 0)))
                state = jnp.zeros((b, n, inner), jnp.float32)
            else:
                conv_state, state, index = kv_cache
                full, (conv_state, _, index) = update_conv_state(
                    (conv_state, None, index), xs, cache_lengths)
            # the shifted copies, summed in float32
            conv = sum(kernel[j].astype(jnp.float32) *
                       full[:, j:j + s].astype(jnp.float32)
                       for j in range(taps)) + bias.astype(jnp.float32)
            xs = nn.silu(conv).astype(cfg.dtype)
            low, bs, cs = jnp.split(dense(rank + 2 * n, name="x_proj")(xs),
                                    [rank, rank + n], axis=-1)
            low, bs, cs = (
                make_norm(cfg, name)(v).astype(cfg.dtype) for name, v in
                (("dt_norm", low), ("b_norm", bs), ("c_norm", cs)))
            dt = s6_dt(low, self.param(
                "dt_proj", nn.initializers.lecun_normal(), (rank, inner),
                cfg.param_dtype), self.param(
                    "dt_bias", _dt_bias_init(), (inner,), jnp.float32))
            if kv_cache is not None and cache_lengths is not None:
                dt = real_steps(dt, kv_cache[2], cache_lengths)
            a = s6_rates(self.param(
                "A_log", lambda key, shape, dtype: jnp.broadcast_to(jnp.log(
                    jnp.arange(1, shape[0] + 1, dtype=dtype))[:, None],
                    shape), (n, inner), jnp.float32))
            skip = self.param("D", nn.initializers.ones, (inner,),
                              jnp.float32)
            with jax.named_scope(S6_SCAN_SCOPE):
                if s == 1 and kv_cache is not None:
                    y, state = selective_scan.s6_step(
                        state, xs[:, 0], dt[:, 0], a, bs[:, 0], cs[:, 0])
                    y = y[:, None]
                else:
                    y, state = selective_scan.s6_chunk_scan(
                        state, xs, dt, a, bs, cs)
            if kv_cache is not None:
                new_cache = (conv_state, state, index)
            y = s6_gate(y + skip * xs.astype(jnp.float32), z)
            out = dense(cfg.hidden_size, name="out_proj")(
                y.astype(cfg.dtype))
        return out, new_cache


# the scope an "eva" layer's summaries are traced under, inside
# ``ATTENTION_SCOPE``: the pooling of the chunks' keys and values and the
# write of the pooled rows (a capture reads it: telemetry/device_time.py)
EVA_SCOPE = "eva_summaries"


def eva_pool(k, v, mu, phi, scale: float):
    """The summaries of chunks of positions (``GPTConfig.attention``
    "eva"): ``k``, ``v`` (..., C, H, D), the ``C`` positions of a chunk,
    the keys rotated; ``mu``, ``phi`` (H, D) float32, a head's two learned
    vectors.  ``a_j = softmax_j(scale k_j . mu)``, ``k~ = sum_j a_j k_j``;
    ``b_j = softmax_j(scale k_j . phi)``, ``v~ = sum_j b_j v_j``: both
    softmaxes over the chunk's positions and off the KEYS, in float32.
    Returns ``(k~, v~)`` (..., H, D) float32."""
    k32, v32 = k.astype(jnp.float32), v.astype(jnp.float32)

    def weights(vector):
        return jax.nn.softmax(scale * jnp.einsum(
            "...chd,hd->...ch", k32, vector.astype(jnp.float32)), axis=-2)

    return (jnp.einsum("...ch,...chd->...hd", weights(mu), k32),
            jnp.einsum("...ch,...chd->...hd", weights(phi), v32))


def eva_seen(position, window: int, chunk: int, queries: int):
    """How many summaries a query at ``position`` sees: those of every
    chunk of every window before its own (the chunks ``0 ..`` under it);
    ``queries`` new positions a row in the step."""
    return position // window * (window // chunk)


def eva_exact_from(position, window: int):
    """The first position whose exact key a query at ``position`` sees:
    the start of its own aligned window."""
    return position // window * window


def eva_reach(position, window: int, queries: int):
    """The window's slot that holds the key AT ``position``, the last one
    its query sees (``queries`` new positions a row in the step)."""
    return position % window


def eva_keys_to_pool(unrotated, rotated):
    """The keys an "eva" layer's summaries are pooled from, of a step's new
    ones: the rotated ones, as the cache holds them."""
    return rotated


def eva_attention(q, k, v, mu, phi, window: int, chunk: int,
                  pooled_from=None):
    """The attention of an "eva" layer over whole sequences, without a
    cache (a forward pass, ``init``): ``q``, ``k``, ``v`` (B, S, H, D), the
    queries and keys rotated.  The query at ``t`` sees the keys ``j`` with
    ``eva_exact_from(t) <= j <= t`` and the summaries (``eva_pool``, cast
    to the keys' dtype as a cache holds them) of the full chunks ``c <
    eva_seen(t)``, under ONE float32 softmax; a sequence's last, unfinished
    chunk has no summary (and lies in the window of every query that could
    see it)."""
    b, s, nh, dim = q.shape
    scale = float(1 / np.sqrt(dim))
    chunks = s // chunk
    with jax.named_scope(EVA_SCOPE):
        k_pool = k if pooled_from is None else pooled_from
        k_sum, v_sum = eva_pool(
            k_pool[:, :chunks * chunk].reshape(b, chunks, chunk, nh, dim),
            v[:, :chunks * chunk].reshape(b, chunks, chunk, nh, dim),
            mu, phi, scale)
        k_sum, v_sum = k_sum.astype(k.dtype), v_sum.astype(v.dtype)
    t = jax.lax.broadcasted_iota(jnp.int32, (s, 1), 0)
    at = jax.lax.broadcasted_iota(jnp.int32, (1, s), 1)
    exact = (at <= t) & (at >= eva_exact_from(t, window))
    pooled = jax.lax.broadcasted_iota(jnp.int32, (1, chunks), 1) < \
        eva_seen(t, window, chunk, s)
    scores = scale * jnp.concatenate(
        [_einsum_f32("bqhd,bkhd->bhqk", q, k),
         _einsum_f32("bqhd,bkhd->bhqk", q, k_sum)], axis=-1)
    scores = jnp.where(jnp.concatenate([exact, pooled], axis=-1)[None, None],
                       scores, jnp.float32(-1e9))
    probs = jax.nn.softmax(scores, axis=-1).astype(q.dtype)
    return jnp.einsum("bhqk,bkhd->bqhd", probs[..., :s], v) + \
        jnp.einsum("bhqk,bkhd->bqhd", probs[..., s:], v_sum)


def eva_chunks_taken(first, lengths):
    """Which of a step's new full chunks (the positions of their first
    keys, ``first`` (B, n)) have their summaries written; ``lengths`` (B,)
    the rows' whole lengths or None.  None: all of them, the chunks of a
    padded prompt's padding too: a tick overwrites every summary of a
    window before the window moves on (``update_eva_cache``)."""
    return None


def eva_tick_writes(index, chunk: int, held_now):
    """Whether a tick at the positions ``index`` (B,) writes the summary of
    the chunk it is in over what the slot holds (``held_now()``: (B, H D),
    the pooled keys there); None: always (``update_eva_cache``)."""
    return None


def update_eva_cache(kv_cache, k, v, mu, phi, window: int, chunk: int,
                     lengths=None, pooled_from=None):
    """``update_kv_cache`` for an "eva" layer, whose cache is written
    TWICE.  ``kv_cache`` is (k_cache, v_cache, index), each array (B, N +
    W, H D) with the heads folded into the channels: the first ``N =
    seq_len / C`` slots hold the summaries, slot ``c`` the pooled key (or
    value) of chunk ``c`` of the row's positions (``eva_pool``), the last
    ``W`` the window's rows, position ``p`` at slot ``N + p % W``.  No
    ring: a window slot past ``index % W`` holds the window before's row
    (or padding), which the causal offset hides as ``update_kv_cache``'s
    does what a row has not reached.  ``index`` is the position of the
    first of the ``s`` new tokens (rotated keys ``k``, values ``v``, (B, s,
    H, D)).

    Several new positions (a prefill chunk, at a scalar index): ``s``
    divides the window and ``C`` divides ``s``, and the caller keeps
    ``index`` a multiple of ``s``, so the step never straddles a window and
    holds ``s / C`` whole chunks: their rows go to the window's slots and
    their summaries to the slots ``index / C ..``.  A right-padded prompt
    pools its padding too: into the summary of the chunk the prompt ends
    in and of those behind it.  None of these is ever read as it is: a
    query sees the summaries of the windows BEFORE its own, and before a
    row's window moves on the ticks have rewritten them.

    One new position (a tick, per-row indices): the row is written at slot
    ``N + index % W``, then the summary of the chunk it lies in is pooled
    once more from that chunk's ``C`` window slots and written at slot
    ``index / C``.  At the chunk's last position all ``C`` slots hold the
    row's own keys, whatever part of the chunk a prefill wrote; before it
    the later slots hold junk (finite: caches start as zeros and hold the
    model's own rows), and so does the summary, which nothing reads yet.
    A row whose positions ran out of the cache (an engine's free row)
    writes no summary.

    ``pooled_from``: the keys a prefill chunk's summaries are pooled from
    where they are not ``k`` (``eva_keys_to_pool``).  Returns ``(k_cache,
    v_cache, index + s)``."""
    k_cache, v_cache, index = kv_cache
    index = jnp.asarray(index, jnp.int32)
    b, s, nh, dim = k.shape
    held = k_cache.shape[1] - window              # the summaries' slots
    scale = float(1 / np.sqrt(dim))

    def folded(x, like):
        return x.reshape(x.shape[0], x.shape[1], nh * dim).astype(like.dtype)

    if s > 1:
        if index.ndim or window % s or s % chunk:
            raise ValueError(
                f"an \"eva\" layer's cached step of {s} positions must "
                f"start at one index for all rows, divide the window "
                f"({window}) and hold whole chunks of {chunk}: prefill in "
                "chunks (Generator(prefill_chunk=...))")
        with jax.named_scope(CACHE_WRITE_SCOPE):
            at = held + index % window
            k_cache = jax.lax.dynamic_update_slice_in_dim(
                k_cache, folded(k, k_cache), at, axis=1)
            v_cache = jax.lax.dynamic_update_slice_in_dim(
                v_cache, folded(v, v_cache), at, axis=1)
        with jax.named_scope(EVA_SCOPE):
            n = s // chunk
            k_pool = k if pooled_from is None else pooled_from
            k_sum, v_sum = eva_pool(k_pool.reshape(b, n, chunk, nh, dim),
                                    v.reshape(b, n, chunk, nh, dim),
                                    mu, phi, scale)
            k_sum, v_sum = folded(k_sum, k_cache), folded(v_sum, v_cache)
            slot = index // chunk
            taken = eva_chunks_taken(
                index + chunk * jax.lax.broadcasted_iota(
                    jnp.int32, (b, n), 1), lengths)
            if taken is not None:
                k_sum, v_sum = (
                    jnp.where(taken[:, :, None], new,
                              jax.lax.dynamic_slice_in_dim(old, slot, n, 1))
                    for new, old in ((k_sum, k_cache), (v_sum, v_cache)))
            k_cache = jax.lax.dynamic_update_slice_in_dim(k_cache, k_sum,
                                                          slot, axis=1)
            v_cache = jax.lax.dynamic_update_slice_in_dim(v_cache, v_sum,
                                                          slot, axis=1)
        return k_cache, v_cache, index + s

    index_b = jnp.broadcast_to(index, (b,))
    in_window = index_b % window
    with jax.named_scope(CACHE_WRITE_SCOPE):
        k_cache = _write_latent_rows(k_cache, folded(k, k_cache),
                                     held + in_window, 1)
        v_cache = _write_latent_rows(v_cache, folded(v, v_cache),
                                     held + in_window, 1)
    with jax.named_scope(EVA_SCOPE):
        # the window's slots of the chunk each row's new position lies in
        first = held + in_window // chunk * chunk

        def rows_of(cache, start, n):
            """(B, n, H D): ``n`` slots of every row from ``start[r]``, of
            the cache seen as two dimensions (``_write_latent_rows``)."""
            slots, width = cache.shape[1:]
            flat = cache.reshape(b * slots, width)
            return jnp.stack([jax.lax.dynamic_slice(
                flat, (r * slots + start[r], 0), (n, width))
                for r in range(b)])

        k_sum, v_sum = eva_pool(
            rows_of(k_cache, first, chunk).reshape(b, 1, chunk, nh, dim),
            rows_of(v_cache, first, chunk).reshape(b, 1, chunk, nh, dim),
            mu, phi, scale)
        slot = index_b // chunk
        writes = slot < held

        def held_now():
            """(B, H D): the pooled key each row's slot holds."""
            return rows_of(k_cache, jnp.minimum(slot, held - 1), 1)[:, 0]

        asked = eva_tick_writes(index_b, chunk, held_now)
        if asked is not None:
            writes &= asked
        # (a row that writes none: an index that does not fit)
        slot = jnp.where(writes, slot, -1)
        k_cache = _write_latent_rows(k_cache, folded(k_sum, k_cache), slot, 1)
        v_cache = _write_latent_rows(v_cache, folded(v_sum, v_cache), slot, 1)
    return k_cache, v_cache, index + 1


def eva_cached_attention(q, k_cache, v_cache, index, window: int, chunk: int):
    """The attention of an "eva" layer's ``s`` new queries ``q`` (B, s, H,
    D), the first at ``index`` (a scalar, or (B,) a row), over its written
    cache (``update_eva_cache``): slot ``j`` under ``eva_seen(index)`` (a
    summary of a window before the queries') is seen by every query, slot
    ``N + j`` (a row of the queries' own window) by the queries at or past
    it, ``j <= eva_reach(index) + i``, and the slots between by none.

    One of three cores, by what the call's shapes say: where the program
    is lowered for a TPU the Pallas kernels of ``ops/cached_attention.py``
    that ``cached_attention`` takes over folded caches, each told how many
    leading slots every query sees and where the causal part begins: the
    one over key blocks for a few new queries a row at per-row indices (a
    tick), the one over query blocks and key blocks for many (a prefill
    chunk); a skipped slot is not fetched.  On any other platform, and for
    shapes neither takes, one product over every slot under the same mask.
    The gauge ``alpa_cached_attention_core`` says which."""
    from alpa_tpu.ops import cached_attention as kernel
    index = jnp.asarray(index, jnp.int32)
    b, s, nh, dim = q.shape
    held = k_cache.shape[1] - window
    seen = jnp.broadcast_to(eva_seen(index, window, chunk, s), (b,))
    offset = jnp.broadcast_to(eva_reach(index, window, s), (b,))
    core = "reference"
    if index.ndim == 1 and kernel.eva_fits(q, k_cache, held):
        core = "key_blocks"
    elif kernel.chunk_fits(q, k_cache, v_cache) and \
            held % kernel.CHUNK_BLOCK_K == 0:
        core = "query_key_blocks"
    _cached_core_gauge().labels(core, nh, dim, s).inc()
    return _eva_core(q, k_cache, v_cache, offset, seen, held=held, core=core)


@partial(jax.jit, static_argnames=("held", "core"))
def _eva_core(q, k_cache, v_cache, offset, seen, held, core):
    """``eva_cached_attention``'s core: the kernel where the program is
    lowered for a TPU, the product over every slot anywhere else; a ``jit``
    of its own as ``_attention_over_key_blocks`` is."""
    from alpa_tpu.ops import cached_attention as kernel
    twin = partial(_eva_attention_over_slots, held=held)
    if core == "reference":
        return twin(q, k_cache, v_cache, offset, seen)
    take = kernel.folded_cached_attention if core == "key_blocks" \
        else kernel.chunk_attention

    def on_tpu(q, k_cache, v_cache, offset, seen):
        return take(q, k_cache, v_cache, offset, seen=seen, exact_from=held)

    return jax.lax.platform_dependent(q, k_cache, v_cache, offset, seen,
                                      tpu=on_tpu, default=twin)


def _eva_attention_over_slots(q, k_cache, v_cache, offset, seen, held: int):
    """``q`` (B, s, H, D) over every slot of folded caches (B, S, H D)
    under ``eva_cached_attention``'s mask (``offset``, ``seen`` (B,)): a
    float32 softmax over the products, the probabilities cast to the
    queries' dtype before the values' product."""
    b, s, nh, dim = q.shape
    slots = k_cache.shape[1]
    scores = _einsum_f32("bqhd,bkhd->bhqk", q,
                         k_cache.reshape(b, slots, nh, dim)) / np.sqrt(dim)
    at = jax.lax.broadcasted_iota(jnp.int32, (1, 1, slots), 2)
    reach = offset[:, None, None] + jax.lax.broadcasted_iota(
        jnp.int32, (1, s, 1), 1)
    visible = (at < seen[:, None, None]) | \
        ((at >= held) & (at - held <= reach))
    scores = jnp.where(visible[:, None], scores, jnp.float32(-1e9))
    probs = jax.nn.softmax(scores, axis=-1).astype(q.dtype)
    return jnp.einsum("bhqk,bkhd->bqhd", probs,
                      v_cache.reshape(b, slots, nh, dim))


def _sink_init(window: int):
    """Sinks drawn from N(ln(window), 1); N(0, 1) on a layer without a
    window."""
    def init(key, shape, dtype=jnp.float32):
        return np.log(max(window, 1)) + jax.random.normal(key, shape, dtype)
    return init


class SelfAttention(nn.Module):
    """``attention`` is the layer's kind (``GPTConfig.attention``; None:
    the configuration's, which must then be one kind for all layers).
    ``padding_bias`` (B, 1, 1, S), an encoder's padding mask added to the
    scores (``bert_model.attention_mask_to_bias``), goes with no cache."""
    config: GPTConfig
    attention: Optional[str] = None

    @nn.compact
    def __call__(self, x, kv_cache=None, deterministic=True,
                 position_ids=None, cache_lengths=None, padding_bias=None):
        cfg = self.config
        if padding_bias is not None and kv_cache is not None:
            raise ValueError("a padding bias goes with no cache: a cached "
                             "call masks by the rows' offsets")
        kind = self.attention or cfg.attention_kind(0)
        if kind not in ("full", "sliding", "eva"):
            raise ValueError(f"unknown attention kind {kind!r}")
        # keys (and queries) of ``hd`` channels, values of ``dv``
        h, nh, nkv, hd, dv = (cfg.hidden_size, cfg.num_heads,
                              cfg.kv_heads_of(kind), cfg.head_size,
                              cfg.value_size)
        window = cfg.sliding_window if kind == "sliding" else 0
        dense = partial(nn.Dense, dtype=cfg.dtype, use_bias=cfg.use_bias,
                        param_dtype=cfg.param_dtype)
        qkv = dense((nh + nkv) * hd + nkv * dv, name="qkv")(x)
        q, k, v = jnp.split(qkv, [nh * hd, (nh + nkv) * hd], axis=-1)
        b, s = x.shape[0], x.shape[1]
        if cfg.qk_norm is True:
            q = make_norm(cfg, "q_norm")(q).astype(cfg.dtype)
            k = make_norm(cfg, "k_norm")(k).astype(cfg.dtype)
        q = q.reshape(b, s, nh, hd)
        k = k.reshape(b, s, nkv, hd)
        v = v.reshape(b, s, nkv, dv)
        if cfg.qk_norm == "head":
            q = make_norm(cfg, "q_norm")(q).astype(cfg.dtype)
            k = make_norm(cfg, "k_norm")(k).astype(cfg.dtype)
        elif cfg.qk_norm not in (True, False):
            raise ValueError(f"unknown qk_norm {cfg.qk_norm!r}")
        if cfg.positions == "rotary" and (kind == "sliding" or
                                          cfg.rope_on_full_attention):
            turned, theta = cfg.rotary_dim, cfg.rope_theta_of(kind)

            def rotate(x):
                if not turned:
                    return apply_rotary(x, position_ids, theta)
                # the leading channels turn, the others pass
                return jnp.concatenate(
                    [apply_rotary(x[..., :turned], position_ids, theta),
                     x[..., turned:]], axis=-1)

            unrotated = k
            q, k = rotate(q), rotate(k)
        sink = None
        if kind in cfg.sink_kinds:
            # a logit a query head in the softmax's denominator.  A model
            # made from a seed draws it around ln(window), not around 0: a
            # sink then takes about as much as the window's keys together
            # (scores of random weights are unit normal), as a trained
            # sink does, where one at the scale of a single score takes
            # 1 / 129 of a head's mass and its absence hides in rounding
            sink = self.param("sink", _sink_init(window), (nh,),
                              jnp.float32)

        eva = ()
        if kind == "eva":
            if nkv != nh or dv != hd or cfg.positions != "rotary" or \
                    not cfg.rope_on_full_attention or not cfg.causal or \
                    not cfg.eva_chunk or cfg.eva_window % cfg.eva_chunk:
                raise ValueError(
                    "an \"eva\" layer is causal, has one key/value head a "
                    "query head, values as wide as the keys, rotary "
                    "positions and a window (GPTConfig.eva_window) of whole "
                    "chunks (eva_chunk)")
            # a head's two pooling vectors (``eva_pool``), float32
            eva = tuple(self.param(name, nn.initializers.normal(1.0),
                                   (nh, hd), jnp.float32)
                        for name in ("mu", "phi")) + \
                (cfg.eva_window, cfg.eva_chunk)

        new_cache = None
        # the scope of the attention core (the cache's update, scores,
        # softmax, values; not the projections): the benchmark finds its
        # device events by it (HLO metadata ``op_name``)
        block = cfg.block_length
        if block and window:
            raise ValueError("a block-causal mask (GPTConfig.block_length) "
                             "goes with full attention layers only")
        of_kind = jax.named_scope(
            WINDOW_CORE_SCOPE if window else FULL_CORE_SCOPE) \
            if cfg.unlike_kinds else contextlib.nullcontext()
        with jax.named_scope(ATTENTION_SCOPE), of_kind:
            if eva and kv_cache is not None:
                index = jnp.asarray(kv_cache[2], jnp.int32)
                new_cache = update_eva_cache(
                    kv_cache, k, v, *eva, lengths=cache_lengths,
                    pooled_from=eva_keys_to_pool(unrotated, k))
                out = eva_cached_attention(q, *new_cache[:2], index,
                                           *eva[2:])
            elif eva:
                out = eva_attention(
                    q, k, v, *eva, pooled_from=eva_keys_to_pool(unrotated, k))
            elif kv_cache is not None and window:
                index = jnp.asarray(kv_cache[2], jnp.int32)
                k_use, v_use, k_positions, new_cache = update_ring_cache(
                    kv_cache, k, v, cache_lengths)
                out = reference_attention(
                    q, k_use, v_use, causal=True, offset=index,
                    window=window, k_positions=k_positions, sink=sink)
            elif kv_cache is not None:
                index = jnp.asarray(kv_cache[2], jnp.int32)
                new_cache = update_kv_cache(kv_cache, k, v)
                # the written caches as they lie: the causal offset alone
                # hides what a row has not reached (``update_kv_cache``)
                out = cached_attention(q, *new_cache[:2], index,
                                       block=block, sink=sink)
            elif padding_bias is not None or window or nkv != nh or \
                    block or dv != hd or sink is not None:
                # additive padding bias: encoder path only (the ring and
                # ulysses cores take no bias operand, no window and no
                # grouped heads)
                if (window or nkv != nh or block or dv != hd or
                        sink is not None) and \
                        cfg.attention_impl != "reference":
                    raise ValueError(
                        "sliding-window, grouped-query and block-causal "
                        "attention, values narrower than the keys and a "
                        "sink need attention_impl 'reference'")
                out = reference_attention(q, k, v, causal=cfg.causal,
                                          bias=padding_bias, window=window,
                                          block=block, sink=sink)
            else:
                attn_fn = get_attention_fn(cfg)
                out = attn_fn(q, k, v, causal=cfg.causal)
        out = out.reshape(b, s, nh * dv)
        if cfg.value_scale != 1.0:
            out = out * jnp.asarray(cfg.value_scale, out.dtype)
        if cfg.attn_gate:
            out = out * jax.nn.sigmoid(dense(nh * dv, name="gate")(x))
        out = dense(h, name="out")(out)
        return out, new_cache


def activation_fn(name: str) -> Callable:
    if name == "relu":
        return nn.relu
    if name == "silu":
        return nn.silu
    if name == "relu2":
        # squared ReLU (Nemotron-H's ``mlp_hidden_act``)
        return lambda x: jnp.square(nn.relu(x))
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
    (``moe.DroplessExperts``).  ``padding_bias`` is ``SelfAttention``'s
    (``BertModel``'s padding mask; no decoder passes one).

    A block of the kind "gated+shortcut" (LongCat-Flash's shortcut-connected
    experts: the first half of a published layer) returns ``(x, new_cache,
    routing, shortcut)``: its gated MLP has joined ``x``; what its routed
    experts (module ``moe``) made of the same normed input has NOT, and is
    ``shortcut``.  The caller hands it to the next block as ``shortcut=``,
    which adds it to the stream with its own MLP's output, so that the
    experts' sum skips that block's attention.  A block handed none and of
    another kind adds and returns nothing more than it did.

    ``return_selected`` (a latent layer that selects its positions, one
    new position a row over a cache): what the layer selected comes third,
    before the routing (``LatentAttention``).

    A block whose ``attention`` or ``mlp`` is "none" (Nemotron-H: a layer
    is a mixer or a feed-forward part ALONE) has the other half only: one
    norm (``ln1`` before a mixer, ``ln2`` before an MLP), one sub-layer,
    one residual sum."""
    config: GPTConfig
    mlp: Optional[str] = None
    attention: Optional[str] = None

    @nn.compact
    def __call__(self, x, kv_cache=None, deterministic=True,
                 position_ids=None, cache_lengths=None, padding_bias=None,
                 shortcut=None, return_selected=False):
        cfg = self.config
        kind = self.mlp or cfg.mlp_kind(0)
        mixer = self.attention or cfg.attention_kind(0)
        if mixer == "none" and kind == "none":
            raise ValueError("a layer of neither a mixer nor an MLP")
        selection = ()
        if mixer == "none":
            # the layer is its MLP alone: its cache entry holds nothing and
            # goes back as it came, at the index the others are at
            new_cache = kv_cache if kv_cache is None else (
                kv_cache[0], kv_cache[1],
                jnp.asarray(kv_cache[2], jnp.int32) + x.shape[1])
        else:
            ln1 = make_norm(cfg, "ln1")(x)
            if mixer in ("latent", LATENT_SLIDING):
                attn = LatentAttention(cfg, attention=mixer, name="attn")
                if return_selected:
                    attn = partial(attn, return_selected=True)
            elif mixer == "conv":
                attn = ShortConv(cfg, name="conv")
            elif mixer == "ssm":
                attn = Mamba2(cfg, name="ssm")
            elif mixer == "s6":
                attn = Mamba1(cfg, name="ssm")
            else:
                attn = partial(
                    SelfAttention(cfg, attention=self.attention,
                                  name="attn"),
                    padding_bias=padding_bias)
            attn_out, new_cache, *selection = attn(
                ln1, kv_cache, deterministic, position_ids, cache_lengths)
            if cfg.post_norms:
                attn_out = make_norm(cfg, "ln1_post")(attn_out)
            x = x + attn_out.astype(x.dtype)
        if kind == "none":
            # the layer is its mixer alone
            if shortcut is not None:
                raise ValueError("a layer without an MLP joins no held-back "
                                 "experts to the stream")
            return (x, new_cache) + tuple(selection)
        ln2 = make_norm(cfg, "ln2")(x)
        routing = ()
        if routed_mlp(kind):
            from alpa_tpu.model.moe import DroplessExperts
        if kind == "experts":
            y, what = DroplessExperts(cfg, name="mlp")(ln2)
            routing = (what,)
        elif kind in ("dense", "gated", SHORTCUT_MLP):
            y = MLPBlock(cfg, gated=kind != "dense", name="mlp")(ln2)
        else:
            raise ValueError(f"unknown mlp kind {kind!r}")
        if cfg.post_norms:
            y = make_norm(cfg, "ln2_post")(y)
        x = x + y.astype(x.dtype)
        if shortcut is not None:
            x = x + shortcut.astype(x.dtype)
        if kind == SHORTCUT_MLP:
            # beside the MLP, on the same normed input, and held back
            held_back, what = DroplessExperts(cfg, name="moe")(ln2)
            routing = (what, held_back)
        return (x, new_cache) + tuple(selection) + routing


class MultiTokenPredictor(nn.Module):
    """One multi-token-prediction module (DeepSeek-V3's report, section
    2.2, with the tensor names of the family's public checkpoints): of a
    position ``i``, the embedding of its NEXT token ``t_{i+1}`` and the
    model's final-normed hidden state ``g_i`` (what the model's head
    reads) become ``h'_i = eh_proj [enorm(Emb(t_{i+1})) ; hnorm(g_i)]``
    (twice the hidden size to once, no bias), one block of the last
    layer's kinds runs over ``h'`` at the positions ``i`` themselves (a
    cache entry of its own: ``GPTConfig.cache_entries``), and
    ``shared_head_norm`` of what comes out goes through the model's own
    head, for ``t_{i+2}``.  Embedding and head are the model's, so the
    caller applies both: this takes ``embedded`` and returns the normed
    stream, then what its block returns beside ``x`` (the new cache entry,
    what it selected where asked, its routing)."""
    config: GPTConfig

    @nn.compact
    def __call__(self, hidden, embedded, kv_cache=None, position_ids=None,
                 cache_lengths=None, return_selected=False):
        cfg = self.config
        joined = jnp.concatenate(
            [make_norm(cfg, "enorm")(embedded),
             make_norm(cfg, "hnorm")(hidden)], axis=-1).astype(cfg.dtype)
        x = nn.Dense(cfg.hidden_size, dtype=cfg.dtype, use_bias=False,
                     param_dtype=cfg.param_dtype, name="eh_proj")(joined)
        layer = cfg.num_layers
        x, *rest = TransformerBlock(
            cfg, mlp=cfg.mlp_kind(layer),
            attention=cfg.attention_kind(layer), name=f"h{layer}")(
                x, kv_cache, True, position_ids, cache_lengths,
                return_selected=return_selected)
        return (make_norm(cfg, "shared_head_norm")(x),) + tuple(rest)


def keep_positions(x, logits_at):
    """The rows of the stream ``x`` (B, S, H) that a decoder's final norm
    and head have to see: all of them, or with ``logits_at`` ((B, K)
    int32, offsets into S) those K a row, (B, K, H).  Norm and head work a
    position at a time, so the logits of a kept position are what they
    were among all S; a prefill chunk keeps one position a row
    (``serve/generation.py``), and a head over S rows it throws away is
    the largest matmul of the step."""
    if logits_at is None:
        return x
    return jnp.take_along_axis(x, logits_at[:, :, None], axis=1,
                               mode="clip")


def _routers_said(routings) -> dict:
    """``return_routing``'s account of routed layers, from each one's
    ``routing`` (``moe.DroplessExperts``)."""
    if not routings:
        return {}
    said = {"experts": jnp.stack([r["experts"] for r in routings])}
    passes = [r["window_passes"] for r in routings if "window_passes" in r]
    if passes:
        said["window_passes"] = jnp.stack(passes)
    return said


def stream_dtype(config: GPTConfig):
    """What a float32 residual stream is kept in
    (``GPTConfig.fp32_residual``)."""
    return jnp.float32


class GPTModel(nn.Module):
    """Decoder-only LM.  Returns logits (and new kv caches if given).  A
    configuration with routed-expert layers returns ``(logits, routing)``
    from the training call: ``moe.routing_summary`` of its layers (the
    load-balancing term, each expert's rows, every token's experts)."""
    config: GPTConfig

    @nn.compact
    def __call__(self, input_ids, position_ids=None, kv_caches=None,
                 deterministic=True, return_hidden=False,
                 cache_lengths=None, return_routing=False, logits_at=None,
                 with_hidden=False, draft_from=None):
        """``return_hidden=True`` returns the final (B, S, H) hidden states
        instead of logits, for a fused/chunked lm-head + loss (see
        model_util.chunked_cross_entropy_loss).

        ``logits_at`` ((B, K) int32): the offsets into the call's S
        positions whose logits are wanted, where not all are.  The final
        norm and the head then run over those K rows of the stream alone
        and the logits are (B, K, V) (``keep_positions``).

        ``cache_lengths`` ((B,), with ``kv_caches``): the rows' whole
        lengths, where the ids are right-padded past them: a layer whose
        cache is a ring must not write the padding
        (``update_ring_cache``), and a short convolution keeps the state
        of a row's last real position (``update_conv_state``).
        ``return_routing`` (with ``kv_caches``, routed layers): a third
        result, what the routed layers' routers did: ``experts`` (expert
        layers, tokens, k) int32, every token's experts; where the expert
        layers walk the call's rows in windows (``moe.expert_window``),
        ``window_passes`` (expert layers,) int32, the passes each took; and,
        where layers
        select their positions and the call is one new position a row,
        ``selected`` (selecting layers, B, index_topk) int32, the positions
        each row's query attended over, in ascending position, and
        ``selected_real`` (selecting layers, B): how many of them are real
        (the first ones).  Of a few positions a row at per-row indices (a
        verify of a tick that drafts: ``few_queries``) both hold an entry a
        position, (selecting layers, B, s, index_topk) and (selecting
        layers, B, s).

        A configuration with a multi-token-prediction module
        (``GPTConfig.num_nextn_predict_layers``): ``kv_caches`` holds the
        module's entry behind the layers', which this call hands back as it
        came.  ``with_hidden``: the logits come as ``(logits, hidden)``,
        ``hidden`` (B, S, H) what the final norm made of ALL the call's
        positions (whatever ``logits_at`` keeps).  ``draft_from`` (such a
        ``hidden``): the MODULE's call and nothing else: ``input_ids`` are
        then the tokens that FOLLOW the positions ``position_ids`` whose
        hidden states ``draft_from`` holds; the module's block runs over
        its own entry of ``kv_caches`` (the layers' come back as they
        came), and the logits are its, for the token after next.
        ``return_routing`` then says of the module's block what it says
        of the layers.
        """
        cfg = self.config
        b, s = input_ids.shape
        if position_ids is None:
            position_ids = jax.lax.broadcasted_iota(jnp.int32, (b, s), 1)
        tok_emb = nn.Embed(cfg.vocab_size, cfg.hidden_size,
                           dtype=cfg.dtype, param_dtype=cfg.param_dtype,
                           name="wte")
        if cfg.tie_embeddings and (cfg.num_pred_heads > 1 or
                                   cfg.fp32_logits):
            raise ValueError("several prediction heads and float32 logits "
                             "go with an untied head")
        # float32 logits: products of ``dtype`` summed in float32
        exact = {"dot_general": partial(
            jax.lax.dot_general, preferred_element_type=jnp.float32)} \
            if cfg.fp32_logits else {}
        lm_head = None if cfg.tie_embeddings else nn.Dense(
            cfg.vocab_size * cfg.num_pred_heads, dtype=cfg.dtype,
            use_bias=False, param_dtype=cfg.param_dtype, name="lm_head",
            **exact)

        def head(x):
            if cfg.tie_embeddings:
                return tok_emb.attend(x.astype(cfg.dtype))
            return lm_head(x)

        layers = cfg.num_layers

        def says_selection(layer, cache):
            """Whether block ``layer``'s call is a selecting decode whose
            selection is asked for with the routing."""
            return return_routing and cache is not None and \
                cfg.selects(cfg.attention_kind(layer)) and \
                few_queries(cfg, s, cache[2])

        def draft(hidden, next_ids, cache, select=False):
            """The module over ``hidden``'s positions: its logits, and what
            its block returned beside the stream (``select``: with what it
            selected)."""
            with jax.named_scope(MTP_SCOPE):
                embedded = tok_emb(next_ids)
                if cfg.scale_embedding:
                    embedded = embedded * jnp.asarray(
                        np.sqrt(cfg.hidden_size), embedded.dtype)
            x, *rest = MultiTokenPredictor(cfg, name=MTP_SCOPE)(
                hidden, embedded, cache, position_ids, cache_lengths,
                return_selected=select)
            with jax.named_scope(MTP_SCOPE):
                return (head(keep_positions(x, logits_at)),) + tuple(rest)

        if draft_from is not None:
            if not cfg.num_nextn_predict_layers:
                raise ValueError("this configuration has no multi-token-"
                                 "prediction module to draft with")
            cache = None if kv_caches is None else kv_caches[layers]
            asked = says_selection(layers, cache)
            logits, new_cache, *said = draft(draft_from, input_ids, cache,
                                             asked)
            if kv_caches is None:
                return logits
            new_caches = list(kv_caches[:layers]) + [new_cache]
            if not return_routing:
                return logits, new_caches
            selection = said.pop(0) if asked else None
            said = _routers_said(said)
            if asked:
                said.update(selected=selection[0][None],
                            selected_real=selection[1][None])
            return logits, new_caches, said
        x = tok_emb(input_ids)
        if cfg.fp32_residual:
            # every block adds into the stream in its dtype and each norm
            # reads it (``TransformerBlock``)
            x = x.astype(stream_dtype(cfg))
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
            # (x, cache_i, deterministic, ...) puts kv_cache at 2 and
            # deterministic at 3 — mark BOTH static
            block_cls = nn.remat(TransformerBlock,
                                 static_argnums=(2, 3),
                                 policy=policy)
        new_caches = [] if kv_caches is not None else None
        routings, selections = [], []
        # what a "gated+shortcut" block's experts made, on its way to the
        # next block (``TransformerBlock``)
        carried = {}
        for i in range(cfg.num_layers):
            if (cfg.pipeline_boundary_every and i > 0 and
                    i % cfg.pipeline_boundary_every == 0):
                mark_pipeline_boundary()
            block = block_cls(cfg, mlp=cfg.mlp_kind(i),
                              attention=cfg.attention_kind(i), name=f"h{i}")
            cache_i = kv_caches[i] if kv_caches is not None else None
            # what a selecting layer's decode selected, with the routing
            asked = says_selection(i, cache_i)
            if asked:
                carried["return_selected"] = True
            x, new_cache, *routing = block(
                x, cache_i, deterministic, block_positions, cache_lengths,
                **carried)
            if asked:
                selections.append(routing.pop(0))
            carried = {}
            if cfg.mlp_kind(i) == SHORTCUT_MLP:
                carried = {"shortcut": routing.pop()}
            routings += routing
            if new_caches is not None:
                new_caches.append(new_cache)
        if carried:
            raise ValueError("the last block's experts are held back for a "
                             "next block (GPTConfig.mlp \"gated+shortcut\"), "
                             "and there is none")
        if with_hidden:
            # the norm works a position at a time: all of them, and the
            # head over those ``logits_at`` keeps
            hidden = make_norm(cfg, "ln_f")(x)
            x = keep_positions(hidden, logits_at)
        else:
            x = make_norm(cfg, "ln_f")(keep_positions(x, logits_at))
        if return_hidden:
            return x
        logits = head(x)
        if cfg.num_nextn_predict_layers and self.is_initializing():
            # the module's parameters: its call over the same positions
            draft(x, input_ids, None)
        if with_hidden:
            logits = (logits, hidden)
        if new_caches is not None:
            # a module's entry comes back as it came
            new_caches += list(kv_caches[layers:])
            if return_routing:
                said = _routers_said(routings)
                if selections:
                    said.update(
                        selected=jnp.stack([p for p, _ in selections]),
                        selected_real=jnp.stack(
                            [n for _, n in selections]))
                return logits, new_caches, said
            return logits, new_caches
        if routings:
            from alpa_tpu.model.moe import routing_summary
            return logits, routing_summary(routings)
        return logits


def kv_cache_shapes(config, batch_size: int) -> list:
    """The (B, positions, key/value heads, head size) of every layer's K
    and V cache: ``seq_len`` positions in a "full" layer, a ring of
    ``sliding_window`` (at most ``seq_len``) in a "sliding" one.  A
    "latent" layer's two arrays differ and have no heads: its entry is the
    pair ((B, seq_len, kv_lora_rank), (B, qk_rope_head_dim, seq_len)); one
    that selects its positions holds a row a position and an index key:
    ((B, seq_len, ``latent_row_width``), (B, seq_len, index_head_dim)); a
    "latent_sliding" layer's is a ring of its own widths:
    ((B, window, rank), (B, rope dim, window)).  A
    "conv" layer holds no positions but a state: its entry is the pair
    ((B, conv_taps - 1, hidden_size), (B, 0)), the second array empty so
    that the entry is a triple as every layer's is.  An "ssm" layer holds
    two states: ((B, conv_taps - 1, ``ssm_conv_width``), (B, ssm_heads,
    ssm_head_dim, ssm_state_size)), the second float32 whatever the
    caches' dtype (``init_kv_caches``); an "s6" layer likewise: ((B,
    conv_taps - 1, ``s6_inner``), (B, ssm_state_size, ``s6_inner``)), the
    channels minor-most.  A "none" layer (an MLP alone) holds nothing:
    ((B, 0), (B, 0)).  An "eva" layer holds rows of two kinds in one pair
    of arrays of one shape, (B, ``sum(eva_slots)``, H D), the heads folded
    into the channels: a pooled key (value) for every chunk of the context
    first, the rows of one window behind them (``update_eva_cache``).
    Takes any decoder family's configuration: what ``GPTConfig`` alone has
    reads as its default.

    A "full" or "sliding" layer whose values are narrower than its keys
    (``GPTConfig.v_head_dim``) holds a pair too, each kind with its own
    key/value heads (``GPTConfig.kv_heads_of``).  The ring keeps its
    heads: ((B, window, Hkv, D), (B, window, Hkv, Dv)).  The full layer's
    pair has them FOLDED into the channels: ((B, seq_len, Hkv D), (B,
    seq_len, Hkv Dv)).  Keys of 192 channels a head are a lane tile and a
    half: kept a head, the TPU compiler lays such a cache out with its
    positions minor-most (``_write_rows``), where a block of positions is
    no block of memory; four heads' 768 channels are six whole tiles, the
    cache lies as it is named, and the kernel that reads it takes it as
    it lies (``ops/cached_attention.py`` ``folded_cached_attention``).  A
    full layer of ONE key/value head lies folded too, (B, seq_len, D) each
    (``GPTConfig.folds_full_caches``)."""
    heads = getattr(config, "num_kv_heads", None) or config.num_heads
    hd = getattr(config, "head_dim", None) or \
        config.hidden_size // config.num_heads
    # values narrower than the keys, key/value heads a kind (``GPTConfig``
    # alone)
    dv = getattr(config, "value_size", hd)
    heads_of = getattr(config, "kv_heads_of", lambda kind: heads)
    kinds = getattr(config, "attention", "full")
    shapes = []
    # (behind the layers' entries a multi-token-prediction module's, of
    # the last layer's kind: ``GPTConfig.cache_entries``)
    for i in range(getattr(config, "cache_entries", config.num_layers)):
        kind = kinds if isinstance(kinds, str) else \
            kinds[min(i, config.num_layers - 1)]
        if kind == "latent" and getattr(config, "index_topk", 0):
            shapes.append((
                (batch_size, config.seq_len, latent_row_width(
                    config.kv_lora_rank, config.qk_rope_head_dim)),
                (batch_size, config.seq_len, config.index_head_dim)))
            continue
        if kind == "latent":
            shapes.append((
                (batch_size, config.seq_len, config.kv_lora_rank),
                (batch_size, config.qk_rope_head_dim, config.seq_len)))
            continue
        if kind == LATENT_SLIDING:
            ring = min(config.sliding_window, config.seq_len)
            widths = config.sliding_latent
            shapes.append((
                (batch_size, ring, widths.kv_lora_rank),
                (batch_size, widths.qk_rope_head_dim, ring)))
            continue
        if kind == "conv":
            shapes.append((
                (batch_size, config.conv_taps - 1, config.hidden_size),
                (batch_size, 0)))
            continue
        if kind == "ssm":
            shapes.append((
                (batch_size, config.conv_taps - 1, config.ssm_conv_width),
                (batch_size, config.ssm_heads, config.ssm_head_dim,
                 config.ssm_state_size)))
            continue
        if kind == "s6":
            shapes.append((
                (batch_size, config.conv_taps - 1, config.s6_inner),
                (batch_size, config.ssm_state_size, config.s6_inner)))
            continue
        if kind == "none":
            shapes.append(((batch_size, 0), (batch_size, 0)))
            continue
        if kind == "eva":
            shapes.append((batch_size, sum(eva_slots(config)), heads * hd))
            continue
        length = min(config.sliding_window, config.seq_len) \
            if kind == "sliding" else config.seq_len
        kv = heads_of(kind)
        if kind != "sliding" and dv == hd and \
                getattr(config, "folds_full_caches", False):
            # ONE key/value head, folded as the wider keys below are
            shapes.append((batch_size, length, kv * hd))
        elif dv == hd:
            shapes.append((batch_size, length, kv, hd))
        elif kind == "sliding":
            shapes.append(((batch_size, length, kv, hd),
                           (batch_size, length, kv, dv)))
        else:
            shapes.append(((batch_size, length, kv * hd),
                           (batch_size, length, kv * dv)))
    return shapes


def eva_slots(config) -> tuple:
    """(summaries, window rows): the slots of an "eva" layer's cache, the
    summaries' first: one for every ``eva_chunk`` positions of the served
    context (the last window's are written and never read: that many keep
    the window's rows at a whole number of the kernels' key blocks), and
    the window's ``eva_window`` (``update_eva_cache``)."""
    return -(-config.seq_len // config.eva_chunk), config.eva_window


def kv_cache_kinds(config) -> list:
    """The kind of every layer's cache entry, as the gauge
    ``alpa_serving_kv_cache_bytes`` labels them: "full" (``seq_len``
    positions of K and V), "window" (a "sliding" layer's ring), "latent",
    "latent_index" (a latent layer that selects its positions: a row and
    an index key a position), "latent_window" (a "latent_sliding" layer's
    ring of latents), "conv" (a state and no positions), "ssm" (a Mamba-2
    or a Mamba-1 mixer's two states and no positions), "none" (a layer
    that is its MLP alone: an empty entry)."""
    kinds = getattr(config, "attention", "full")

    def label(kind):
        if kind == "latent" and getattr(config, "index_topk", 0):
            return "latent_index"
        return {"sliding": "window", LATENT_SLIDING: "latent_window",
                "s6": "ssm"}.get(kind, kind)

    entries = getattr(config, "cache_entries", config.num_layers)
    return [label(kinds if isinstance(kinds, str) else
                  kinds[min(i, config.num_layers - 1)])
            for i in range(entries)]


def cached_key_block(config, queries: int) -> int:
    """The positions in one key block of the attention core that a tick
    of ``queries`` new positions a row, at per-row offsets, takes over the
    caches of ``config`` that hold the served context, where the program
    is lowered for a TPU: ``cached_attention``'s rule for "full" layers,
    ``latent_attention_absorbed``'s for "latent" ones.  A row then reads
    its positions rounded up to whole key blocks.  0: the core is the one
    over every position the cache can hold (another decoder family's
    configuration, whose layers call ``reference_attention`` themselves,
    too)."""
    if not isinstance(config, GPTConfig):
        return 0
    from alpa_tpu.ops import cached_attention, latent_attention

    def of(*shape):
        return jax.ShapeDtypeStruct(shape, config.dtype)

    kinds = kv_cache_kinds(config)
    if "latent_index" in kinds:
        # a selecting layer's core goes over the selection, not the cache
        return 0
    if "latent" in kinds:
        takes = latent_attention.absorbed_fits(
            of(1, queries, config.num_heads, config.kv_lora_rank),
            of(1, config.seq_len, config.kv_lora_rank))
        return latent_attention.DECODE_BLOCK_K if takes else 0
    if "full" not in kinds:
        return 0
    q = of(1, queries, config.num_heads, config.head_size)
    if config.folds_full_caches:
        # folded caches (``kv_cache_shapes``)
        caches = (of(1, config.seq_len, config.kv_heads * config.head_size),
                  of(1, config.seq_len, config.kv_heads * config.value_size))
        if "full" in config.sink_kinds or \
                not cached_attention.folded_fits(q, *caches):
            return 0
        return cached_attention.folded_block_k(*caches)
    takes = "full" not in config.sink_kinds and cached_attention.fits(
        q, of(1, config.seq_len, config.kv_heads, config.head_size))
    return cached_attention.block_k(config.kv_heads, config.head_size) \
        if takes else 0


def latent_kv_caches(config) -> bool:
    """Whether any layer's cache is a latent one (no per-head K and V)."""
    return any(kind.startswith("latent") for kind in kv_cache_kinds(config))


def selected_per_row(config) -> int:
    """The positions a decode's selecting layers fetch a row at most
    (``index_topk``); 0: no layer selects."""
    return config.index_topk if "latent_index" in kv_cache_kinds(config) \
        else 0


def conv_states(config) -> bool:
    """Whether any layer is a short convolution, whose cache entry is a
    state of fixed size and no cache of positions."""
    return "conv" in kv_cache_kinds(config)


def ssm_states(config) -> bool:
    """Whether any layer is a Mamba-2 or a Mamba-1 mixer
    (``GPTConfig.attention`` "ssm", "s6"), whose cache entry is two states
    of fixed size (the convolution's last positions; a matrix a head, or
    ``ssm_state_size`` values a channel) and no cache of positions."""
    return "ssm" in kv_cache_kinds(config)



def eva_caches(config) -> bool:
    """Whether any layer's cache holds rows of two kinds, summaries of
    chunks beside one window's own rows (``GPTConfig.attention`` "eva"): no
    array of the context's positions, and an index that rolls a row back
    only inside its current window."""
    return "eva" in kv_cache_kinds(config)


_EVA_ENTRY = (
    "layers whose softmax runs over the exact keys of the query's own "
    "aligned window beside pooled summaries of the chunks before it "
    "(GPTConfig.attention \"eva\"): a cache entry holds the summaries "
    "and ONE window's rows, a row at slot position % eva_window, so that "
    "no array holds the context's positions, a step that straddles a "
    "window's edge has overwritten the window before, and an index rolls "
    "a row back only inside its current window and only while the summary "
    "of its chunk is pooled again")


def _mamba_kind(config) -> tuple:
    """(name, ``GPTConfig.attention`` kind) of the mixers whose entry is
    labelled "ssm", for the refusals."""
    kinds = getattr(config, "attention", "full")
    if "s6" in ((kinds,) if isinstance(kinds, str) else kinds):
        return "Mamba-1", "s6"
    return "Mamba-2", "ssm"


def uniform_kv_caches(config) -> bool:
    """Whether every layer's cache holds positions and has one shape: what
    the block pool, the speculative verify step, beam search and the
    disaggregated prefill count on (one block table, one length and one
    index for all layers).  A short convolution's state holds no positions, so a
    configuration with one is not uniform whatever its shapes; its cached
    calls are handed the rows' lengths as a ring's are; so is one with a
    Mamba-2 mixer's states, or with a layer that holds nothing."""
    return not conv_states(config) and not ssm_states(config) and \
        not eva_caches(config) and \
        len(set(kv_cache_shapes(config, 1))) == 1


def require_uniform_kv_caches(config, what: str):
    """Raise unless every layer caches per-head K and V of one shape, as
    ``what`` indexes them: the KV block pool, ``generate_speculative``,
    ``generate_beam`` and the disaggregated ``PrefillEngine``.  The tick
    that drafts with a multi-token-prediction module inside an engine
    (``serve/generation.py`` ``_verify_draft``) is NOT among them: it
    indexes nothing a head, and asks only that the index roll a position
    back (``require_rollback_by_index``)."""
    if conv_states(config):
        raise ValueError(
            f"{what} indexes per-head K and V caches of one shape and "
            "rolls a row back by its index, and this configuration has "
            "short-convolution layers (GPTConfig.attention \"conv\"), "
            "whose entry is a state of the last conv_taps - 1 positions "
            "that every step overwrites: no index brings an earlier state "
            "back, and there are no positions to page or reorder: "
            f"{sorted(set(kv_cache_shapes(config, 1)), key=str)}")
    if ssm_states(config):
        name, kind = _mamba_kind(config)
        raise ValueError(
            f"{what} indexes per-head K and V caches of one shape and "
            "rolls a row back by its index, and this configuration has "
            f"{name} mixers (GPTConfig.attention \"{kind}\"), whose "
            "entry is two states (the convolution's last conv_taps - 1 "
            "positions, and a matrix a head or ssm_state_size values a "
            "channel) that every step overwrites: no index "
            "brings an earlier state back, and there are no positions to "
            "page or reorder: "
            f"{sorted(set(kv_cache_shapes(config, 1)), key=str)}")
    if eva_caches(config):
        raise ValueError(
            f"{what} indexes per-head K and V caches of one shape that hold "
            f"the context's positions, and this configuration has "
            f"{_EVA_ENTRY}: "
            f"{sorted(set(kv_cache_shapes(config, 1)), key=str)}")
    kinds = set(kv_cache_kinds(config))
    if kinds & {"latent_index", "latent_window"}:
        raise ValueError(
            f"{what} indexes per-head K and V caches of one shape, and "
            "this configuration's latent layers hold " + " and ".join(
                name for kind, name in (
                    ("latent_index", "a row of latent and shared rotary "
                     "key and an index key a position, of which a decode "
                     "reads a learned selection (GPTConfig.index_topk)"),
                    ("latent_window", "a ring of the window's latents "
                     "(GPTConfig.attention \"latent_sliding\")"))
                if kind in kinds) +
            f": {sorted(set(kv_cache_shapes(config, 1)))}")
    if latent_kv_caches(config):
        raise ValueError(
            f"{what} indexes per-head K and V caches of one shape, and "
            "this configuration's layers hold a latent cache (latent "
            "attention: a latent and a shared rotary key a position, in "
            "two arrays of unlike shapes, no heads): "
            f"{sorted(set(kv_cache_shapes(config, 1)))}")
    if getattr(config, "value_size", None) not in (
            None, getattr(config, "head_size", None)):
        shapes = sorted({shape for entry in kv_cache_shapes(config, 1)
                         for shape in entry}, key=str)
        raise ValueError(
            f"{what} indexes per-head K and V caches of one shape, and "
            "this configuration's layers hold keys wider than their values "
            f"(GPTConfig.v_head_dim: {config.head_size} and "
            f"{config.value_size} channels a head), a \"full\" layer's "
            "with the heads folded into the channels, a \"sliding\" "
            f"layer's a ring of {config.kv_heads_of('sliding')} key/value "
            f"heads where a full layer has {config.kv_heads}: "
            f"{len(shapes)} shapes in one model, K and V unlike in every "
            f"layer: {shapes}")
    if getattr(config, "folds_full_caches", False):
        raise ValueError(
            f"{what} indexes per-head K and V caches (B, positions, heads, "
            "channels), and this configuration's \"full\" layers have ONE "
            "key/value head, whose caches lie with the head folded into "
            "the channels (GPTConfig.folds_full_caches): "
            f"{sorted(set(kv_cache_shapes(config, 1)), key=str)}")
    if not uniform_kv_caches(config):
        raise ValueError(
            f"{what} indexes one cache shape for all layers, and this "
            "configuration's layers differ (sliding-window layers hold a "
            "ring of the window's positions, full layers the context): "
            f"{sorted(set(kv_cache_shapes(config, 1)))}")


def require_rollback_by_index(config, what: str):
    """Raise unless every cache entry of the configuration holds positions
    that the row's index alone rolls back: ``what`` (the tick that
    verifies a draft, ``serve/generation.py`` ``_verify_draft``) writes a
    position it may not keep and takes it back by not advancing the index
    over it, so that the next step overwrites it.  A ring that the
    rejected position was written into has lost the position it replaced,
    a short convolution's state and a Mamba-2 mixer's have moved on, and a
    configuration that
    generates by diffusion over blocks has no one token to verify: each
    is refused by name."""
    kinds = set(kv_cache_kinds(config))
    mamba, mamba_kind = _mamba_kind(config)
    for kind, name in (
            ("window", "a ring of the sliding window's positions "
             "(GPTConfig.attention \"sliding\")"),
            ("latent_window", "a ring of the window's latents "
             "(GPTConfig.attention \"latent_sliding\")"),
            ("conv", "a short convolution's state (GPTConfig.attention "
             "\"conv\")"),
            ("ssm", f"a {mamba} mixer's states (GPTConfig.attention "
             f"\"{mamba_kind}\")"),
            ("eva", "summaries of chunks beside ONE window's rows "
             "(GPTConfig.attention \"eva\": a rejected position at a "
             "window's first slot has overwritten the window before's row, "
             "and the summary of its chunk was pooled with it)")):
        if kind in kinds:
            raise ValueError(
                f"{what} rolls a rejected position back by the row's index "
                f"alone, and this configuration's layers hold {name}, "
                "which a written position has already changed for good")
    block = getattr(config, "block_length", 0)
    if block:
        raise ValueError(
            f"{what} verifies one drafted token a row, and this "
            f"configuration generates by diffusion over blocks of {block} "
            "positions (GPTConfig.block_length)")


def require_one_token_steps(config, what: str):
    """Raise if the configuration generates by diffusion over blocks:
    ``what`` is built on one token a row a step (a step there yields
    between none and ``block_length`` tokens a row, and writes a whole
    block's keys and values whether or not it keeps them): the four of
    ``require_uniform_kv_caches``, a static prefix
    (``Generator.cache_prefix``) and an engine's prefilled admission.  The
    tick that verifies a draft (``_verify_draft``) refuses such a
    configuration itself, with the rings and the states
    (``require_rollback_by_index``)."""
    if eva_caches(config):
        raise ValueError(
            f"{what} resumes a row at any position of a cache that holds "
            f"the context's positions, and this configuration has "
            f"{_EVA_ENTRY}: serve it through Generator(prefill_chunk=...) "
            "and a ContinuousBatchingEngine with chunked_admission, whose "
            "chunks divide the window, and nothing else")
    block = getattr(config, "block_length", 0)
    if block:
        raise ValueError(
            f"{what} is built on one token a row a step, and this "
            f"configuration generates by diffusion over blocks of {block} "
            "positions (GPTConfig.block_length): serve it through "
            "Generator.generate or a ContinuousBatchingEngine with "
            "chunked_admission and nothing else")


def init_kv_caches(config: GPTConfig, batch_size: int,
                   dtype=None) -> list:
    """KV caches as explicit arrays (ref opt_model.py:605 init_cache_aval):
    ``[(k, v, index)]`` a layer, each layer's of its own shape
    (``kv_cache_shapes``); a "latent" layer's ``(c, k_pe, index)``, a
    "conv" layer's ``(state, empty, index)``, an "ssm" layer's ``(conv
    state, ssm state, index)`` with the ssm state float32 whatever
    ``dtype`` (a state kept in bfloat16 would round once a position for
    thousands of positions).  All zeros, which a "conv" or "ssm" layer's
    states have to be for a row that starts (``update_conv_state``)."""
    dtype = dtype or config.dtype
    caches = []
    for kind, shape in zip(kv_cache_kinds(config),
                           kv_cache_shapes(config, batch_size)):
        k_shape, v_shape = shape if isinstance(shape[0], tuple) else \
            (shape, shape)
        caches.append((jnp.zeros(k_shape, dtype),
                       jnp.zeros(v_shape, jnp.float32 if kind == "ssm"
                                 else dtype), jnp.int32(0)))
    return caches


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
