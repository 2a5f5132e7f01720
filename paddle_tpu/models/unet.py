# lint-tpu: disable-file=L004 -- grandfathered direct jax use; new backend code belongs under core/ ops/ kernels/ static/ distributed/ (README: Repo lint)
"""Diffusion UNet with cross-attention (the SDXL UNet shape, served via
the inference predictor).

Compact UNet2DConditionModel: timestep sinusoidal embedding + MLP, ResNet
blocks (GroupNorm/SiLU), down/up sampling, and transformer blocks with
self + cross attention over text context — the ppdiffusers UNet structure,
sized by config.  Serving path: jit.save → inference.Predictor.
"""
from __future__ import annotations

import math
from dataclasses import dataclass, field
from typing import List, Tuple

import jax
import jax.numpy as jnp

from .. import nn
from ..core.dispatch import apply
from ..core.tensor import Tensor
from ..nn import functional as F


@dataclass
class UNetConfig:
    in_channels: int = 4
    out_channels: int = 4
    block_out_channels: Tuple[int, ...] = (320, 640, 1280)
    layers_per_block: int = 2
    cross_attention_dim: int = 2048
    attention_head_dim: int = 64
    transformer_layers_per_block: Tuple[int, ...] = (1, 2, 10)
    norm_num_groups: int = 32
    dtype: str = "float32"

    @staticmethod
    def tiny(**overrides):
        cfg = UNetConfig(
            in_channels=4, out_channels=4, block_out_channels=(32, 64),
            layers_per_block=1, cross_attention_dim=32, attention_head_dim=8,
            transformer_layers_per_block=(1, 1), norm_num_groups=8)
        for k, v in overrides.items():
            setattr(cfg, k, v)
        return cfg


def timestep_embedding(timesteps, dim, max_period=10000.0):
    half = dim // 2
    freqs = jnp.exp(-math.log(max_period)
                    * jnp.arange(half, dtype=jnp.float32) / half)
    args = timesteps.astype(jnp.float32)[:, None] * freqs[None]
    return jnp.concatenate([jnp.cos(args), jnp.sin(args)], axis=-1)


class ResnetBlock(nn.Layer):
    def __init__(self, in_c, out_c, temb_dim, groups):
        super().__init__()
        self.norm1 = nn.GroupNorm(min(groups, in_c), in_c)
        self.conv1 = nn.Conv2D(in_c, out_c, 3, padding=1)
        self.time_emb_proj = nn.Linear(temb_dim, out_c)
        self.norm2 = nn.GroupNorm(min(groups, out_c), out_c)
        self.conv2 = nn.Conv2D(out_c, out_c, 3, padding=1)
        self.skip = nn.Conv2D(in_c, out_c, 1) if in_c != out_c else None

    def forward(self, x, temb):
        h = self.conv1(F.silu(self.norm1(x)))
        t = self.time_emb_proj(F.silu(temb))
        h = h + t.unsqueeze(-1).unsqueeze(-1)
        h = self.conv2(F.silu(self.norm2(h)))
        if self.skip is not None:
            x = self.skip(x)
        return x + h


class CrossAttnBlock(nn.Layer):
    """Spatial transformer: self-attn + cross-attn + geglu FFN."""

    def __init__(self, channels, n_layers, ctx_dim, head_dim, groups):
        super().__init__()
        self.norm = nn.GroupNorm(min(groups, channels), channels)
        self.proj_in = nn.Linear(channels, channels)
        heads = max(channels // head_dim, 1)
        self.blocks = nn.LayerList()
        for _ in range(n_layers):
            blk = nn.LayerDict({
                "norm1": nn.LayerNorm(channels),
                "attn1": nn.MultiHeadAttention(channels, heads),
                "norm2": nn.LayerNorm(channels),
                "attn2": nn.MultiHeadAttention(channels, heads,
                                               kdim=ctx_dim, vdim=ctx_dim),
                "norm3": nn.LayerNorm(channels),
                "ff1": nn.Linear(channels, channels * 4),
                "ff2": nn.Linear(channels * 4, channels),
            })
            self.blocks.append(blk)
        self.proj_out = nn.Linear(channels, channels)

    def forward(self, x, context):
        B, C, H, W = x.shape
        residual = x
        h = self.norm(x)
        from ..ops.manipulation import reshape, transpose

        h = transpose(reshape(h, [B, C, H * W]), [0, 2, 1])  # [B, HW, C]
        h = self.proj_in(h)
        for blk in self.blocks:
            h = h + blk["attn1"](blk["norm1"](h))
            h = h + blk["attn2"](blk["norm2"](h), context, context)
            h = h + blk["ff2"](F.gelu(blk["ff1"](blk["norm3"](h))))
        h = self.proj_out(h)
        h = reshape(transpose(h, [0, 2, 1]), [B, C, H, W])
        return h + residual


class UNet2DConditionModel(nn.Layer):
    def __init__(self, config: UNetConfig):
        super().__init__()
        self.config = config
        ch = config.block_out_channels
        temb_dim = ch[0] * 4
        g = config.norm_num_groups
        self.time_embed = nn.Sequential(
            nn.Linear(ch[0], temb_dim), nn.Silu(), nn.Linear(temb_dim,
                                                             temb_dim))
        self.conv_in = nn.Conv2D(config.in_channels, ch[0], 3, padding=1)

        self.down_res = nn.LayerList()
        self.down_attn = nn.LayerList()
        self.downsamplers = nn.LayerList()
        in_c = ch[0]
        skip_chs = [ch[0]]  # conv_in output
        for i, out_c in enumerate(ch):
            for j in range(config.layers_per_block):
                self.down_res.append(ResnetBlock(in_c, out_c, temb_dim, g))
                self.down_attn.append(CrossAttnBlock(
                    out_c, config.transformer_layers_per_block[i],
                    config.cross_attention_dim, config.attention_head_dim, g)
                    if i > 0 else nn.Identity())
                in_c = out_c
                skip_chs.append(out_c)
            if i < len(ch) - 1:
                self.downsamplers.append(
                    nn.Conv2D(out_c, out_c, 3, stride=2, padding=1))
                skip_chs.append(out_c)

        self.mid_res1 = ResnetBlock(in_c, in_c, temb_dim, g)
        self.mid_attn = CrossAttnBlock(
            in_c, config.transformer_layers_per_block[-1],
            config.cross_attention_dim, config.attention_head_dim, g)
        self.mid_res2 = ResnetBlock(in_c, in_c, temb_dim, g)

        self.up_res = nn.LayerList()
        self.up_attn = nn.LayerList()
        self.upsamplers = nn.LayerList()
        rev = list(reversed(ch))
        for i, out_c in enumerate(rev):
            for j in range(config.layers_per_block + 1):
                skip_c = skip_chs.pop()
                self.up_res.append(ResnetBlock(in_c + skip_c, out_c, temb_dim,
                                               g))
                self.up_attn.append(CrossAttnBlock(
                    out_c, config.transformer_layers_per_block[
                        len(ch) - 1 - i],
                    config.cross_attention_dim, config.attention_head_dim, g)
                    if (len(ch) - 1 - i) > 0 else nn.Identity())
                in_c = out_c
            if i < len(rev) - 1:
                self.upsamplers.append(nn.Conv2D(out_c, out_c, 3, padding=1))

        self.conv_norm_out = nn.GroupNorm(min(g, ch[0]), ch[0])
        self.conv_out = nn.Conv2D(ch[0], config.out_channels, 3, padding=1)
        if config.dtype != "float32":
            self.astype(config.dtype)

    def forward(self, sample, timestep, encoder_hidden_states):
        cfg = self.config
        # sinusoid computed in f32 for precision, then cast to whatever
        # dtype the weights actually hold (cfg.dtype, a later .bfloat16()
        # or .half() — all routes change the parameter dtype)
        wdt = self.time_embed[0].weight._value.dtype
        temb = apply("timestep_embed",
                     lambda t: timestep_embedding(
                         t, cfg.block_out_channels[0]).astype(wdt),
                     timestep, _differentiable=False)
        temb = self.time_embed(temb)

        h = self.conv_in(sample)
        skips = [h]
        idx = 0
        for i, out_c in enumerate(cfg.block_out_channels):
            for j in range(cfg.layers_per_block):
                h = self.down_res[idx](h, temb)
                attn = self.down_attn[idx]
                if not isinstance(attn, nn.Identity):
                    h = attn(h, encoder_hidden_states)
                skips.append(h)
                idx += 1
            if i < len(cfg.block_out_channels) - 1:
                h = self.downsamplers[i](h)
                skips.append(h)

        h = self.mid_res1(h, temb)
        h = self.mid_attn(h, encoder_hidden_states)
        h = self.mid_res2(h, temb)

        from ..ops.manipulation import concat

        idx = 0
        for i in range(len(cfg.block_out_channels)):
            for j in range(cfg.layers_per_block + 1):
                skip = skips.pop()
                h = concat([h, skip], axis=1)
                h = self.up_res[idx](h, temb)
                attn = self.up_attn[idx]
                if not isinstance(attn, nn.Identity):
                    h = attn(h, encoder_hidden_states)
                idx += 1
            if i < len(cfg.block_out_channels) - 1:
                h = F.interpolate(h, scale_factor=2, mode="nearest")
                h = self.upsamplers[i](h)

        h = F.silu(self.conv_norm_out(h))
        return self.conv_out(h)
