# lint-tpu: disable-file=L004 -- grandfathered direct jax use; new backend code belongs under core/ ops/ kernels/ static/ distributed/ (README: Repo lint)
"""Attention functionals.

The reference implements fused attention as hand-written CUDA
(/root/reference/paddle/fluid/operators/fused/fused_attention_op.cu,
fmha_ref.h).  Here the TPU-native path is a Pallas flash-attention kernel
(paddle_tpu/kernels/flash_attention.py) on TPU, with an XLA-fused jnp
reference path everywhere else.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from ...core.dispatch import apply
from ...core.tensor import Tensor, to_tensor


def _t(x):
    return x if isinstance(x, Tensor) else to_tensor(x)


def _sdpa_reference(q, k, v, mask, dropout_p, causal, scale):
    """[B, T, H, D] layout (paddle flash_attention layout)."""
    if scale is None:
        scale = 1.0 / math.sqrt(q.shape[-1])
    logits = jnp.einsum("bthd,bshd->bhts", q, k) * scale
    if causal:
        t, s = logits.shape[-2], logits.shape[-1]
        causal_mask = jnp.tril(jnp.ones((t, s), bool), k=s - t)
        logits = jnp.where(causal_mask, logits, -jnp.inf)
    if mask is not None:
        if mask.dtype == jnp.bool_:
            logits = jnp.where(mask, logits, -jnp.inf)
        else:
            logits = logits + mask
    probs = jax.nn.softmax(logits.astype(jnp.float32), axis=-1).astype(q.dtype)
    return jnp.einsum("bhts,bshd->bthd", probs, v)


def scaled_dot_product_attention(query, key, value, attn_mask=None,
                                 dropout_p=0.0, is_causal=False, training=True,
                                 name=None):
    """paddle.nn.functional.scaled_dot_product_attention: [B, T, H, D]."""
    def _sdpa(q, k, v, *maybe_mask):
        from ...kernels.fusion import pallas_lowering

        mask = maybe_mask[0] if maybe_mask else None
        if pallas_lowering()[0] and mask is None and dropout_p == 0.0:
            from ...kernels.flash_attention import flash_attention_bthd

            return flash_attention_bthd(q, k, v, causal=is_causal)
        return _sdpa_reference(q, k, v, mask, dropout_p, is_causal, None)
    args = [_t(query), _t(key), _t(value)]
    if attn_mask is not None:
        args.append(_t(attn_mask))
    return apply("scaled_dot_product_attention", _sdpa, *args)


def flash_attention(query, key, value, dropout=0.0, causal=False,
                    return_softmax=False, name=None):
    out = scaled_dot_product_attention(query, key, value, None, dropout, causal)
    if return_softmax:
        return out, None
    return out, None
