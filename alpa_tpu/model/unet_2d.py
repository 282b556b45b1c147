"""2D UNets (diffusion-style).

Analog of ref ``alpa/model/unet_2d.py`` (1207 LoC diffusers-style
``FlaxUNet2DConditionModel`` used by ``benchmark/alpa/suite_unet.py``).

Two models live here:

* ``UNet2D`` — compact unconditioned UNet (kept for the conv-planner
  tests).
* ``UNet2DConditionModel`` — the reference-scale conditioned UNet:
  sinusoidal timestep embeddings + MLP, ResNet blocks with time-embedding
  injection, spatial transformers with cross-attention on encoder hidden
  states (GEGLU feed-forward), cross-attn down/mid/up blocks with skip
  connections and learned down/upsampling (ref unet_2d.py:81-1139).

TPU-first choices: channels-last (NHWC) convs so XLA tiles them onto the
MXU directly, fp32 GroupNorm/softmax with activations in ``dtype``
(bfloat16-ready), static shapes throughout, and attention written as
einsums over (B, HW, C) so the auto-sharding planner sees clean batch /
space / channel mesh targets.
"""
import dataclasses
from typing import Any, Optional, Sequence, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np


@dataclasses.dataclass(frozen=True)
class UNetConfig:
    in_channels: int = 3
    out_channels: int = 3
    block_channels: Tuple[int, ...] = (64, 128, 256)
    layers_per_block: int = 2
    attention_resolutions: Tuple[int, ...] = (2,)  # block indices w/ attn
    num_heads: int = 4
    time_embed_dim: int = 256
    dtype: Any = jnp.float32


@dataclasses.dataclass(frozen=True)
class UNetConditionConfig:
    """Reference-scale conditioned UNet (ref FlaxUNet2DConditionModel,
    unet_2d.py:900; defaults shrunk from the SD-class (320,640,1280,1280)
    so tests stay fast)."""
    sample_size: int = 32
    in_channels: int = 4
    out_channels: int = 4
    # "CrossAttnDownBlock2D" | "DownBlock2D" per stage (mirrored for up)
    down_block_types: Tuple[str, ...] = ("CrossAttnDownBlock2D",
                                         "CrossAttnDownBlock2D",
                                         "DownBlock2D")
    block_out_channels: Tuple[int, ...] = (64, 128, 256)
    layers_per_block: int = 2
    attention_head_dim: int = 8
    cross_attention_dim: int = 128
    freq_shift: float = 0.0
    dtype: Any = jnp.float32


def _num_groups(channels: int, max_groups: int = 32) -> int:
    """Largest divisor of ``channels`` not exceeding ``max_groups``."""
    g = min(max_groups, channels)
    while channels % g != 0:
        g -= 1
    return g


def timestep_embedding(t, dim, freq_shift: float = 0.0):
    """Sinusoidal timestep embeddings (ref get_sinusoidal_embeddings:65)."""
    half = dim // 2
    freqs = jnp.exp(-np.log(10000.0) *
                    jnp.arange(half, dtype=jnp.float32) /
                    (half - freq_shift))
    args = t.astype(jnp.float32)[:, None] * freqs[None]
    return jnp.concatenate([jnp.sin(args), jnp.cos(args)], axis=-1)


class TimestepEmbedding(nn.Module):
    """2-layer MLP over the sinusoid (ref FlaxTimestepEmbedding:81)."""
    dim: int
    dtype: Any

    @nn.compact
    def __call__(self, temb):
        temb = nn.Dense(self.dim, dtype=self.dtype, name="linear_1")(temb)
        temb = nn.swish(temb)
        return nn.Dense(self.dim, dtype=self.dtype, name="linear_2")(temb)


class ResnetBlock2D(nn.Module):
    """GN -> swish -> conv, time-emb injection, GN -> swish -> conv,
    learned shortcut on channel change (ref FlaxResnetBlock2D:165)."""
    channels: int
    dtype: Any

    @nn.compact
    def __call__(self, x, temb):
        h = nn.GroupNorm(num_groups=_num_groups(x.shape[-1]),
                         dtype=jnp.float32, name="norm1")(x)
        h = nn.swish(h).astype(self.dtype)
        h = nn.Conv(self.channels, (3, 3), dtype=self.dtype,
                    name="conv1")(h)
        t = nn.Dense(self.channels, dtype=self.dtype,
                     name="time_emb_proj")(nn.swish(temb))
        h = h + t[:, None, None, :]
        h = nn.GroupNorm(num_groups=_num_groups(self.channels),
                         dtype=jnp.float32, name="norm2")(h)
        h = nn.swish(h).astype(self.dtype)
        h = nn.Conv(self.channels, (3, 3), dtype=self.dtype,
                    name="conv2")(h)
        if x.shape[-1] != self.channels:
            x = nn.Conv(self.channels, (1, 1), dtype=self.dtype,
                        name="conv_shortcut")(x)
        return x + h


class Downsample2D(nn.Module):
    """Strided conv downsampling (ref FlaxDownsample2D:145)."""
    channels: int
    dtype: Any

    @nn.compact
    def __call__(self, x):
        return nn.Conv(self.channels, (3, 3), strides=(2, 2),
                       dtype=self.dtype, name="conv")(x)


class Upsample2D(nn.Module):
    """Nearest-resize + conv upsampling (ref FlaxUpsample2D:121)."""
    channels: int
    dtype: Any

    @nn.compact
    def __call__(self, x):
        b, h, w, c = x.shape
        x = jax.image.resize(x, (b, h * 2, w * 2, c), "nearest")
        return nn.Conv(self.channels, (3, 3), dtype=self.dtype,
                       name="conv")(x)


class CrossAttention(nn.Module):
    """Multi-head attention; self- when context is None, cross- otherwise.
    fp32 softmax, einsum-formulated (ref attention inside
    FlaxBasicTransformerBlock:323)."""
    query_dim: int
    heads: int
    head_dim: int
    dtype: Any

    @nn.compact
    def __call__(self, x, context=None):
        context = x if context is None else context
        inner = self.heads * self.head_dim
        q = nn.Dense(inner, use_bias=False, dtype=self.dtype,
                     name="to_q")(x)
        k = nn.Dense(inner, use_bias=False, dtype=self.dtype,
                     name="to_k")(context)
        v = nn.Dense(inner, use_bias=False, dtype=self.dtype,
                     name="to_v")(context)
        b, sq, _ = q.shape
        sk = k.shape[1]
        q = q.reshape(b, sq, self.heads, self.head_dim)
        k = k.reshape(b, sk, self.heads, self.head_dim)
        v = v.reshape(b, sk, self.heads, self.head_dim)
        scores = jnp.einsum("bqhd,bkhd->bhqk", q, k).astype(jnp.float32)
        scores = scores / np.sqrt(self.head_dim)
        probs = jax.nn.softmax(scores, axis=-1).astype(self.dtype)
        out = jnp.einsum("bhqk,bkhd->bqhd", probs, v).reshape(b, sq, inner)
        return nn.Dense(self.query_dim, dtype=self.dtype, name="to_out")(out)


class GEGLUFeedForward(nn.Module):
    """GEGLU-gated feed-forward (ref FlaxGluFeedForward:463 / FlaxGEGLU:491)."""
    dim: int
    dtype: Any

    @nn.compact
    def __call__(self, x):
        h = nn.Dense(self.dim * 8, dtype=self.dtype, name="proj_in")(x)
        h, gate = jnp.split(h, 2, axis=-1)
        h = h * nn.gelu(gate, approximate=True)
        return nn.Dense(self.dim, dtype=self.dtype, name="proj_out")(h)


class BasicTransformerBlock(nn.Module):
    """Self-attn -> cross-attn(context) -> GEGLU FF, pre-LN residuals
    (ref FlaxBasicTransformerBlock:323)."""
    dim: int
    heads: int
    head_dim: int
    dtype: Any

    @nn.compact
    def __call__(self, x, context):
        h = nn.LayerNorm(dtype=jnp.float32, name="norm1")(x)
        x = x + CrossAttention(self.dim, self.heads, self.head_dim,
                               self.dtype, name="attn1")(
                                   h.astype(self.dtype))
        h = nn.LayerNorm(dtype=jnp.float32, name="norm2")(x)
        x = x + CrossAttention(self.dim, self.heads, self.head_dim,
                               self.dtype, name="attn2")(
                                   h.astype(self.dtype), context)
        h = nn.LayerNorm(dtype=jnp.float32, name="norm3")(x)
        return x + GEGLUFeedForward(self.dim, self.dtype,
                                    name="ff")(h.astype(self.dtype))


class SpatialTransformer(nn.Module):
    """Flatten (H, W) -> tokens, run transformer blocks with cross-attention
    on the conditioning sequence, project back (ref FlaxSpatialTransformer:388)."""
    channels: int
    heads: int
    head_dim: int
    depth: int
    dtype: Any

    @nn.compact
    def __call__(self, x, context):
        b, h, w, c = x.shape
        residual = x
        y = nn.GroupNorm(num_groups=_num_groups(c), dtype=jnp.float32,
                         name="norm")(x)
        y = nn.Dense(self.channels, dtype=self.dtype,
                     name="proj_in")(y.astype(self.dtype))
        y = y.reshape(b, h * w, self.channels)
        for i in range(self.depth):
            y = BasicTransformerBlock(self.channels, self.heads,
                                      self.head_dim, self.dtype,
                                      name=f"block_{i}")(y, context)
        y = y.reshape(b, h, w, self.channels)
        y = nn.Dense(c, dtype=self.dtype, name="proj_out")(y)
        return y + residual


class CrossAttnDownBlock2D(nn.Module):
    """N x (resnet + spatial transformer) + downsample
    (ref FlaxCrossAttnDownBlock2D:518)."""
    channels: int
    num_layers: int
    heads: int
    head_dim: int
    add_downsample: bool
    dtype: Any

    @nn.compact
    def __call__(self, x, temb, context):
        skips = []
        for i in range(self.num_layers):
            x = ResnetBlock2D(self.channels, self.dtype,
                              name=f"resnet_{i}")(x, temb)
            x = SpatialTransformer(self.channels, self.heads, self.head_dim,
                                   1, self.dtype,
                                   name=f"attn_{i}")(x, context)
            skips.append(x)
        if self.add_downsample:
            x = Downsample2D(self.channels, self.dtype,
                             name="downsample")(x)
            skips.append(x)
        return x, skips


class DownBlock2D(nn.Module):
    """N x resnet + downsample (ref FlaxDownBlock2D:604)."""
    channels: int
    num_layers: int
    add_downsample: bool
    dtype: Any

    @nn.compact
    def __call__(self, x, temb):
        skips = []
        for i in range(self.num_layers):
            x = ResnetBlock2D(self.channels, self.dtype,
                              name=f"resnet_{i}")(x, temb)
            skips.append(x)
        if self.add_downsample:
            x = Downsample2D(self.channels, self.dtype,
                             name="downsample")(x)
            skips.append(x)
        return x, skips


class CrossAttnUpBlock2D(nn.Module):
    """N x (concat-skip + resnet + spatial transformer) + upsample
    (ref FlaxCrossAttnUpBlock2D:667)."""
    channels: int
    num_layers: int
    heads: int
    head_dim: int
    add_upsample: bool
    dtype: Any

    @nn.compact
    def __call__(self, x, skips, temb, context):
        for i in range(self.num_layers):
            x = jnp.concatenate([x, skips.pop()], axis=-1)
            x = ResnetBlock2D(self.channels, self.dtype,
                              name=f"resnet_{i}")(x, temb)
            x = SpatialTransformer(self.channels, self.heads, self.head_dim,
                                   1, self.dtype,
                                   name=f"attn_{i}")(x, context)
        if self.add_upsample:
            x = Upsample2D(self.channels, self.dtype, name="upsample")(x)
        return x


class UpBlock2D(nn.Module):
    """N x (concat-skip + resnet) + upsample (ref FlaxUpBlock2D:755)."""
    channels: int
    num_layers: int
    add_upsample: bool
    dtype: Any

    @nn.compact
    def __call__(self, x, skips, temb):
        for i in range(self.num_layers):
            x = jnp.concatenate([x, skips.pop()], axis=-1)
            x = ResnetBlock2D(self.channels, self.dtype,
                              name=f"resnet_{i}")(x, temb)
        if self.add_upsample:
            x = Upsample2D(self.channels, self.dtype, name="upsample")(x)
        return x


class UNetMidBlock2DCrossAttn(nn.Module):
    """resnet -> spatial transformer -> resnet
    (ref FlaxUNetMidBlock2DCrossAttn:826)."""
    channels: int
    heads: int
    head_dim: int
    dtype: Any

    @nn.compact
    def __call__(self, x, temb, context):
        x = ResnetBlock2D(self.channels, self.dtype,
                          name="resnet_0")(x, temb)
        x = SpatialTransformer(self.channels, self.heads, self.head_dim, 1,
                               self.dtype, name="attn")(x, context)
        return ResnetBlock2D(self.channels, self.dtype,
                             name="resnet_1")(x, temb)


class UNet2DConditionModel(nn.Module):
    """Conditioned UNet: (sample NHWC, timesteps, encoder_hidden_states)
    -> predicted noise (ref FlaxUNet2DConditionModel:900)."""
    config: UNetConditionConfig

    @nn.compact
    def __call__(self, sample, timesteps, encoder_hidden_states):
        cfg = self.config
        chans = cfg.block_out_channels
        heads = [max(1, c // cfg.attention_head_dim) for c in chans]
        temb_dim = chans[0] * 4
        temb = timestep_embedding(timesteps, chans[0], cfg.freq_shift)
        temb = TimestepEmbedding(temb_dim, cfg.dtype,
                                 name="time_embedding")(temb)
        context = encoder_hidden_states.astype(cfg.dtype)

        x = nn.Conv(chans[0], (3, 3), dtype=cfg.dtype,
                    name="conv_in")(sample.astype(cfg.dtype))
        skips = [x]
        for bi, (btype, ch) in enumerate(zip(cfg.down_block_types, chans)):
            last = bi == len(chans) - 1
            if btype == "CrossAttnDownBlock2D":
                x, s = CrossAttnDownBlock2D(
                    ch, cfg.layers_per_block, heads[bi],
                    cfg.attention_head_dim, not last, cfg.dtype,
                    name=f"down_{bi}")(x, temb, context)
            else:
                x, s = DownBlock2D(ch, cfg.layers_per_block, not last,
                                   cfg.dtype, name=f"down_{bi}")(x, temb)
            skips.extend(s)

        x = UNetMidBlock2DCrossAttn(chans[-1], heads[-1],
                                    cfg.attention_head_dim, cfg.dtype,
                                    name="mid")(x, temb, context)

        up_types = tuple(reversed(cfg.down_block_types))
        up_chans = tuple(reversed(chans))
        for bi, (btype, ch) in enumerate(zip(up_types, up_chans)):
            last = bi == len(chans) - 1
            blk_skips = [skips.pop() for _ in range(cfg.layers_per_block + 1)]
            blk_skips.reverse()
            if btype == "CrossAttnDownBlock2D":
                x = CrossAttnUpBlock2D(
                    ch, cfg.layers_per_block + 1, heads[len(chans) - 1 - bi],
                    cfg.attention_head_dim, not last, cfg.dtype,
                    name=f"up_{bi}")(x, blk_skips, temb, context)
            else:
                x = UpBlock2D(ch, cfg.layers_per_block + 1, not last,
                              cfg.dtype, name=f"up_{bi}")(x, blk_skips, temb)

        x = nn.GroupNorm(num_groups=_num_groups(x.shape[-1]),
                         dtype=jnp.float32, name="norm_out")(x)
        x = nn.swish(x).astype(cfg.dtype)
        return nn.Conv(cfg.out_channels, (3, 3), dtype=cfg.dtype,
                       name="conv_out")(x)


class ResBlock(nn.Module):
    channels: int
    dtype: Any

    @nn.compact
    def __call__(self, x, temb):
        h = nn.GroupNorm(num_groups=_num_groups(x.shape[-1]),
                         dtype=jnp.float32)(x)
        h = nn.swish(h)
        h = nn.Conv(self.channels, (3, 3), dtype=self.dtype)(h)
        h = h + nn.Dense(self.channels, dtype=self.dtype)(
            nn.swish(temb))[:, None, None, :]
        h = nn.GroupNorm(num_groups=_num_groups(self.channels),
                         dtype=jnp.float32)(h)
        h = nn.swish(h)
        h = nn.Conv(self.channels, (3, 3), dtype=self.dtype)(h)
        if x.shape[-1] != self.channels:
            x = nn.Conv(self.channels, (1, 1), dtype=self.dtype)(x)
        return x + h


class AttnBlock2D(nn.Module):
    num_heads: int
    dtype: Any

    @nn.compact
    def __call__(self, x):
        b, h, w, c = x.shape
        y = nn.GroupNorm(num_groups=_num_groups(c), dtype=jnp.float32)(x)
        y = y.reshape(b, h * w, c)
        y = nn.MultiHeadDotProductAttention(
            num_heads=self.num_heads, dtype=self.dtype)(y, y)
        return x + y.reshape(b, h, w, c)


class UNet2D(nn.Module):
    """Compact unconditioned UNet (conv-planner tests)."""
    config: UNetConfig

    @nn.compact
    def __call__(self, x, timesteps):
        cfg = self.config
        temb = timestep_embedding(timesteps, cfg.time_embed_dim)
        temb = nn.Dense(cfg.time_embed_dim, dtype=cfg.dtype)(temb)
        temb = nn.Dense(cfg.time_embed_dim, dtype=cfg.dtype)(
            nn.swish(temb))

        h = nn.Conv(cfg.block_channels[0], (3, 3), dtype=cfg.dtype,
                    name="conv_in")(x)
        skips = [h]
        # down
        for bi, ch in enumerate(cfg.block_channels):
            for _ in range(cfg.layers_per_block):
                h = ResBlock(ch, cfg.dtype)(h, temb)
                if bi in cfg.attention_resolutions:
                    h = AttnBlock2D(cfg.num_heads, cfg.dtype)(h)
                skips.append(h)
            if bi < len(cfg.block_channels) - 1:
                h = nn.Conv(ch, (3, 3), (2, 2), dtype=cfg.dtype)(h)
                skips.append(h)
        # mid
        mid_ch = cfg.block_channels[-1]
        h = ResBlock(mid_ch, cfg.dtype)(h, temb)
        h = AttnBlock2D(cfg.num_heads, cfg.dtype)(h)
        h = ResBlock(mid_ch, cfg.dtype)(h, temb)
        # up
        for bi, ch in reversed(list(enumerate(cfg.block_channels))):
            for _ in range(cfg.layers_per_block + 1):
                h = jnp.concatenate([h, skips.pop()], axis=-1)
                h = ResBlock(ch, cfg.dtype)(h, temb)
                if bi in cfg.attention_resolutions:
                    h = AttnBlock2D(cfg.num_heads, cfg.dtype)(h)
            if bi > 0:
                b, hh, ww, c = h.shape
                h = jax.image.resize(h, (b, hh * 2, ww * 2, c), "nearest")
                h = nn.Conv(c, (3, 3), dtype=cfg.dtype)(h)
        h = nn.GroupNorm(num_groups=_num_groups(h.shape[-1]),
                         dtype=jnp.float32)(h)
        h = nn.swish(h)
        return nn.Conv(cfg.out_channels, (3, 3), dtype=cfg.dtype,
                       name="conv_out")(h)
