"""RMSNorm folded into the projections that follow it.

The Llama block enters attention and the MLP through the same shape of
boundary: RMSNorm, then one or more matmuls over the SAME normalized
activation.  Here only the [M] row-scale vector ``rsqrt(mean(x^2) +
eps)`` is computed once (``rms_scale`` — a few KiB) and shared; each
projection applies the scale and the norm weight to x on its way into
the product, with the optional activation behind it, and XLA fuses the
whole expression around its own matmul.

Math contract (must mirror models/llama.py LlamaRMSNorm + Linear):

    normed = (x_f32 * rsqrt(mean(x_f32^2) + eps)).astype(x.dtype) * nw
    out    = act(normed @ w)

the product accumulated in float32 with the operands in the dtype they
have: bf16 times bf16 is exact in float32, so widening both first (as
this module did until PR 37) buys nothing.

One form on every backend.  Until PR 37 a Pallas kernel ran this on the
chip; measured there against XLA's lowering of the same expression
(PERF.md section 6, PR 37) the kernel at its best tiles read within 3 %
of XLA's form alone and 5-8 % behind it inside the Mistral step
programs, so it went.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from .fused_linear import _ACTS


def rms_scale(x, eps):
    """Per-row RMSNorm scale in f32: rsqrt(mean(x^2) + eps), shape
    [..., 1].  The ONLY intermediate the fused path materializes."""
    var = jnp.mean(jnp.square(x.astype(jnp.float32)), axis=-1,
                   keepdims=True)
    return jax.lax.rsqrt(var + eps)


def fused_norm_linear(x, row_scale, norm_weight, w, activation="none"):
    """act(((x * row_scale).astype(x.dtype) * norm_weight) @ w).

    x: [..., K]; row_scale: [..., 1] f32 from ``rms_scale`` (computed
    ONCE and shared by every projection off the same normalized
    activation); norm_weight: [K]; w: [K, N].  Where the normed
    activation and ``w`` differ in dtype the narrower is widened to the
    other's.
    """
    if activation not in _ACTS:
        raise ValueError(f"unsupported activation {activation!r}")
    K = x.shape[-1]
    x2d = x.reshape(-1, K)
    normed = (x2d.astype(jnp.float32) * row_scale.reshape(-1, 1)).astype(
        x2d.dtype) * norm_weight
    dt = jnp.promote_types(normed.dtype, w.dtype)
    z = jnp.dot(normed.astype(dt), w.astype(dt),
                preferred_element_type=jnp.float32)
    out = _ACTS[activation](z).astype(x.dtype)
    return out.reshape(*x.shape[:-1], w.shape[1])


def fused_rmsnorm_linear(x, norm_weight, w, eps, activation="none"):
    """Single-projection convenience: rms_scale + fused_norm_linear."""
    return fused_norm_linear(x, rms_scale(x, eps), norm_weight, w,
                             activation)
