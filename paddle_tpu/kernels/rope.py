"""Fused rotary position embedding (RoPE) Pallas kernel.

TPU-native replacement for the rotary step of the reference fused
attention ops (/root/reference/paddle/fluid/operators/fused/
fused_multi_transformer_op.cu applies rotary inline in its QKV kernel):
one VMEM pass applies the rotate-half formula to a [T_block, H*D] tile
with the cos/sin tables streamed per T block — no separate concat/mul/add
HLOs or doubled activation traffic.

Backward is RoPE with the angle negated (rotation matrices are
orthogonal), so the same kernel serves both directions.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

KERNEL_NAME = "fused_rope"
DEFAULT_BLOCK_T = 256
# elements per [block_t, H*D] tile: the in and out tiles are
# double-buffered and the body holds several f32 temporaries of the same
# extent, all inside 16 MiB of scoped VMEM — 256 rows of 8 KV heads, 128
# rows of 32 heads at head 128
_MAX_BLOCK_ELEMS = 1 << 19


def _rope_kernel(x_ref, cos_ref, sin_ref, o_ref, *, H, D):
    bt = x_ref.shape[1]
    x = x_ref[0].astype(jnp.float32).reshape(bt, H, D)
    c = cos_ref[:].astype(jnp.float32)[:, None, :]  # [bt, 1, D/2]
    s = sin_ref[:].astype(jnp.float32)[:, None, :]
    x1 = x[..., : D // 2]
    x2 = x[..., D // 2:]
    out = jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s], axis=-1)
    o_ref[0] = out.reshape(bt, H * D).astype(o_ref.dtype)


def _rope_fwd(x, cos, sin, block_t, interpret):
    B, T, H, D = x.shape
    bt = min(block_t, T, max(8, _MAX_BLOCK_ELEMS // (H * D) // 8 * 8))
    if T % bt or (H * D) % 128 or D % 2:
        # untileable: plain XLA formula
        c = cos[None, :, None, :]
        s = sin[None, :, None, :]
        x1, x2 = jnp.split(x, 2, axis=-1)
        return jnp.concatenate(
            [x1 * c - x2 * s, x2 * c + x1 * s], axis=-1).astype(x.dtype)
    xr = x.reshape(B, T, H * D)
    out = pl.pallas_call(
        functools.partial(_rope_kernel, H=H, D=D),
        grid=(B, T // bt),
        in_specs=[
            pl.BlockSpec((1, bt, H * D), lambda b, i: (b, i, 0)),
            pl.BlockSpec((bt, D // 2), lambda b, i: (i, 0)),
            pl.BlockSpec((bt, D // 2), lambda b, i: (i, 0)),
        ],
        out_specs=pl.BlockSpec((1, bt, H * D), lambda b, i: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct((B, T, H * D), x.dtype),
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel"))
        if not interpret else None,
        name=KERNEL_NAME,
    )(xr, cos, sin)
    return out.reshape(B, T, H, D)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def _rope(x, cos, sin, block_t, interpret):
    return _rope_fwd(x, cos, sin, block_t, interpret)


def _rope_vjp_fwd(x, cos, sin, block_t, interpret):
    return _rope_fwd(x, cos, sin, block_t, interpret), (cos, sin)


def _rope_vjp_bwd(block_t, interpret, res, g):
    cos, sin = res
    # inverse rotation: transpose of an orthogonal block-rotation
    return _rope_fwd(g, cos, -sin, block_t, interpret), None, None


_rope.defvjp(_rope_vjp_fwd, _rope_vjp_bwd)


def fused_rope(x, cos, sin, position_offset=0, block_t=DEFAULT_BLOCK_T,
               interpret=None):
    """Apply rotary embeddings to x: [B, T, H, D]; cos/sin: [maxT, D/2].

    Matches models/llama.py apply_rope (rotate-half convention)."""
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    T = x.shape[1]
    c = jax.lax.dynamic_slice_in_dim(cos, position_offset, T)
    s = jax.lax.dynamic_slice_in_dim(sin, position_offset, T)
    return _rope(x, c, s, block_t, interpret)
