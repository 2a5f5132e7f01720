"""RMSNorm->matmul prologue fusion (Pallas) with an XLA fallback.

The Llama block enters attention and the MLP through the same shape of
boundary: RMSNorm, then one or more matmuls over the SAME normalized
activation.  Unfused, the normalized [M, K] matrix round-trips HBM
between the norm and every projection.  Fused, only the [M] row-scale
vector ``rsqrt(mean(x^2) + eps)`` is materialized (``rms_scale`` — a
few KiB); each projection then applies the scale and the norm weight
to the x tile IN VMEM as the matmul's prologue, with the optional
activation as its epilogue (kernels/fused_linear.py's epilogue idiom,
extended upward into the producer).

Math contract (must mirror models/llama.py LlamaRMSNorm + Linear):

    normed = (x_f32 * rsqrt(mean(x_f32^2) + eps)).astype(x.dtype) * nw
    out    = act(normed @ w)

The XLA fallback composes exactly this expression, so CPU tier-1 and
the jaxpr audits cover the fused math without a pallas_call.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .costs import KernelCost, register_kernel_cost
from .fused_linear import _ACTS, DEFAULT_BK, DEFAULT_BM, DEFAULT_BN

KERNEL_NAME = "fused_norm_linear"
_LANES = 128


def rms_scale(x, eps):
    """Per-row RMSNorm scale in f32: rsqrt(mean(x^2) + eps), shape
    [..., 1].  The ONLY intermediate the fused path materializes."""
    var = jnp.mean(jnp.square(x.astype(jnp.float32)), axis=-1,
                   keepdims=True)
    return jax.lax.rsqrt(var + eps)


def _norm_linear_ref(x2d, rs, nw, w, act):
    normed = (x2d.astype(jnp.float32) * rs).astype(x2d.dtype) * nw
    z = jnp.dot(normed.astype(jnp.float32), w.astype(jnp.float32),
                preferred_element_type=jnp.float32)
    return _ACTS[act](z).astype(x2d.dtype)


def _kernel(x_ref, rs_ref, nw_ref, w_ref, o_ref, acc_ref, *, act,
            x_dtype):
    ki = pl.program_id(2)
    nk = pl.num_programs(2)

    @pl.when(ki == 0)
    def _init():
        acc_ref[:] = jnp.zeros_like(acc_ref)

    # prologue: norm the x tile in VMEM — scale rows by rs, columns by
    # the norm weight, with the unfused path's exact cast points
    xb = x_ref[:].astype(jnp.float32) * rs_ref[:, 0:1]
    normed = xb.astype(x_dtype) * nw_ref[0]
    acc_ref[:] += jax.lax.dot_general(
        normed.astype(jnp.float32), w_ref[:].astype(jnp.float32),
        (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)

    @pl.when(ki == nk - 1)
    def _epilogue():
        o_ref[:] = _ACTS[act](acc_ref[:]).astype(o_ref.dtype)


def _norm_linear_pallas(x2d, rs, nw, w, act, bm, bn, bk, interpret):
    M, K = x2d.shape
    N = w.shape[1]
    bm_, bn_, bk_ = min(bm, M), min(bn, N), min(bk, K)
    if M % bm_ or N % bn_ or K % bk_:
        return _norm_linear_ref(x2d, rs, nw, w, act)
    # row scale travels lane-broadcast (a 1-wide trailing dim is not a
    # legal TPU tile); norm weight as a [1, K] row (fused_linear's bias
    # idiom)
    rs_b = jnp.broadcast_to(rs.astype(jnp.float32), (M, _LANES))
    nw_row = nw.reshape(1, K)
    return pl.pallas_call(
        functools.partial(_kernel, act=act, x_dtype=x2d.dtype),
        grid=(M // bm_, N // bn_, K // bk_),
        in_specs=[
            pl.BlockSpec((bm_, bk_), lambda i, j, k: (i, k)),
            pl.BlockSpec((bm_, _LANES), lambda i, j, k: (i, 0)),
            pl.BlockSpec((1, bk_), lambda i, j, k: (0, k)),
            pl.BlockSpec((bk_, bn_), lambda i, j, k: (k, j)),
        ],
        out_specs=pl.BlockSpec((bm_, bn_), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((M, N), x2d.dtype),
        scratch_shapes=[pltpu.VMEM((bm_, bn_), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"))
        if not interpret else None,
        cost_estimate=pl.CostEstimate(
            flops=2 * M * N * K,
            bytes_accessed=(M * K + K * N + M * N)
            * jnp.dtype(x2d.dtype).itemsize,
            transcendentals=0),
        interpret=interpret,
        name=KERNEL_NAME,
    )(x2d, rs_b, nw_row, w)


def _autotuned_tiles(x2d, w, act, interpret):
    """(bm, bn, bk) via the autotune cache (FLAGS_use_autotune)."""
    from ..core.flags import flag
    from . import autotune as at

    defaults = (DEFAULT_BM, DEFAULT_BN, DEFAULT_BK)
    if interpret or not flag("use_autotune"):
        return defaults
    M, K = x2d.shape
    N = w.shape[1]
    key = (M, K, N, str(x2d.dtype), act)
    if isinstance(x2d, jax.core.Tracer):
        return at.lookup("fused_norm_linear", key) or defaults
    cands = [(bm, bn, bk)
             for bm in (128, 256, 512) for bn in (128, 256, 512)
             for bk in (256, 512)
             if M % min(bm, M) == 0 and N % min(bn, N) == 0
             and K % min(bk, K) == 0]
    if not cands:
        return defaults
    rs = rms_scale(x2d, 1e-5)
    nw = jnp.ones((K,), x2d.dtype)
    jitted = {}

    def run(cfg):
        fn = jitted.get(cfg)
        if fn is None:
            fn = jax.jit(functools.partial(
                _norm_linear_pallas, act=act, bm=cfg[0], bn=cfg[1],
                bk=cfg[2], interpret=False))
            jitted[cfg] = fn
        jax.block_until_ready(fn(x2d, rs, nw, w))

    best = at.autotune("fused_norm_linear", key, cands, run)
    return best or defaults


def fused_norm_linear(x, row_scale, norm_weight, w, activation="none",
                      bm=None, bn=None, bk=None, use_pallas=None,
                      interpret=None):
    """act(((x * row_scale).astype(x.dtype) * norm_weight) @ w) with the
    norm applied as the matmul's VMEM prologue.

    x: [..., K]; row_scale: [..., 1] f32 from ``rms_scale`` (computed
    ONCE and shared by every projection off the same normalized
    activation); norm_weight: [K]; w: [K, N].
    """
    from .fusion import pallas_lowering

    if activation not in _ACTS:
        raise ValueError(f"unsupported activation {activation!r}")
    use_pallas, interpret = pallas_lowering(use_pallas, interpret)
    lead = x.shape[:-1]
    K = x.shape[-1]
    x2d = x.reshape(-1, K)
    rs = row_scale.reshape(-1, 1)
    if use_pallas:
        if bm is None or bn is None or bk is None:
            abm, abn, abk = _autotuned_tiles(x2d, w, activation, interpret)
            bm, bn, bk = bm or abm, bn or abn, bk or abk
        out = _norm_linear_pallas(x2d, rs, norm_weight, w, activation,
                                  bm, bn, bk, interpret)
    else:
        out = _norm_linear_ref(x2d, rs, norm_weight, w, activation)
    return out.reshape(*lead, w.shape[1])


def fused_rmsnorm_linear(x, norm_weight, w, eps, activation="none",
                         **kwargs):
    """Single-projection convenience: rms_scale + fused_norm_linear."""
    return fused_norm_linear(x, rms_scale(x, eps), norm_weight, w,
                             activation, **kwargs)


def _norm_linear_cost(in_avals, out_avals):
    # operand order fixed by _norm_linear_pallas: (x, rs, nw, w)
    (x_shape, x_dtype), _, _, (w_shape, w_dtype) = in_avals
    M, K = int(x_shape[0]), int(x_shape[1])
    N = int(w_shape[1])
    xe = np.dtype(x_dtype).itemsize
    we = np.dtype(w_dtype).itemsize
    out_bytes = sum(
        float(np.prod(shape, dtype=np.int64)) * np.dtype(dt).itemsize
        for shape, dt in out_avals)
    return KernelCost(
        flops=2.0 * M * N * K + 2.0 * M * K,            # matmul + norm
        bytes_accessed=float(M * K * xe + K * N * we + M * (_LANES * 4)
                             + K * xe) + out_bytes,
        transcendentals=0.0, dtype=str(x_dtype))


register_kernel_cost(
    KERNEL_NAME, _norm_linear_cost,
    sample_in=[((64, 64), "float32"), ((64, _LANES), "float32"),
               ((1, 64), "float32"), ((64, 128), "float32")],
    sample_out=[((64, 128), "float32")])
