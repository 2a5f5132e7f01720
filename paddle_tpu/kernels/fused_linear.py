"""Blocked matmul with fused bias/activation epilogue (Pallas).

TPU-native replacement for the reference fused GEMM-epilogue ops
(/root/reference/paddle/fluid/operators/fused/fused_gemm_epilogue_op.cu —
cublasLt matmul with BIAS/GELU epilogues): the epilogue runs in VMEM on
the final K step of a (M, N, K)-blocked matmul, so the pre-activation
matrix never round-trips through HBM.

Backward recomputes z = x @ w + b (one extra GEMM) and applies the
activation derivative, matching the reference's fused_gemm_epilogue_grad
with auxiliary-output disabled.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

DEFAULT_BM, DEFAULT_BN, DEFAULT_BK = 256, 256, 512

_ACTS = {
    "none": lambda z: z,
    "relu": jax.nn.relu,
    # exact (erf) gelu: paddle's F.gelu default and the reference
    # fused_gemm_epilogue's cublasLt GELU are both erf-based
    "gelu": functools.partial(jax.nn.gelu, approximate=False),
    "gelu_tanh": jax.nn.gelu,
    "silu": jax.nn.silu,
}


def _kernel(x_ref, w_ref, b_ref, o_ref, acc_ref, *, act, has_bias):
    ki = pl.program_id(2)
    nk = pl.num_programs(2)

    @pl.when(ki == 0)
    def _init():
        acc_ref[:] = jnp.zeros_like(acc_ref)

    acc_ref[:] += jax.lax.dot_general(
        x_ref[:].astype(jnp.float32), w_ref[:].astype(jnp.float32),
        (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)

    @pl.when(ki == nk - 1)
    def _epilogue():
        z = acc_ref[:]
        if has_bias:
            z = z + b_ref[0].astype(jnp.float32)  # [bn] row broadcast
        o_ref[:] = _ACTS[act](z).astype(o_ref.dtype)


def _fused_linear_fwd(x, w, b, act, bm, bn, bk, interpret):
    M, K = x.shape
    N = w.shape[1]
    bm_, bn_, bk_ = min(bm, M), min(bn, N), min(bk, K)
    if M % bm_ or N % bn_ or K % bk_:
        z = x @ w
        if b is not None:
            z = z + b
        return _ACTS[act](z).astype(x.dtype)
    has_bias = b is not None
    # bias travels as [1, N] — 1-D operands hit XLA/Mosaic layout mismatches
    b_in = (b if has_bias else jnp.zeros((N,), x.dtype)).reshape(1, N)
    out = pl.pallas_call(
        functools.partial(_kernel, act=act, has_bias=has_bias),
        grid=(M // bm_, N // bn_, K // bk_),
        in_specs=[
            pl.BlockSpec((bm_, bk_), lambda i, j, k: (i, k)),
            pl.BlockSpec((bk_, bn_), lambda i, j, k: (k, j)),
            pl.BlockSpec((1, bn_), lambda i, j, k: (0, j)),
        ],
        out_specs=pl.BlockSpec((bm_, bn_), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((M, N), x.dtype),
        scratch_shapes=[pltpu.VMEM((bm_, bn_), jnp.float32)],
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"))
        if not interpret else None,
    )(x, w, b_in)
    return out


def _act_grad(act, z):
    if act == "none":
        return jnp.ones_like(z)
    return jax.grad(lambda t: jnp.sum(_ACTS[act](t)))(z)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7))
def _fused_linear(x, w, b, act, bm, bn, bk, interpret):
    return _fused_linear_fwd(x, w, b, act, bm, bn, bk, interpret)


def _vjp_fwd(x, w, b, act, bm, bn, bk, interpret):
    return _fused_linear_fwd(x, w, b, act, bm, bn, bk, interpret), (x, w, b)


def _vjp_bwd(act, bm, bn, bk, interpret, res, g):
    x, w, b = res
    z = (x @ w).astype(jnp.float32)  # recompute pre-activation
    if b is not None:
        z = z + b.astype(jnp.float32)
    dz = (g.astype(jnp.float32) * _act_grad(act, z))
    dx = (dz @ w.astype(jnp.float32).T).astype(x.dtype)
    dw = (x.astype(jnp.float32).T @ dz).astype(w.dtype)
    db = dz.sum(axis=0).astype(b.dtype) if b is not None else None
    return dx, dw, db


_fused_linear.defvjp(_vjp_fwd, _vjp_bwd)


def fused_linear(x, w, bias=None, activation="none",
                 bm=DEFAULT_BM, bn=DEFAULT_BN, bk=DEFAULT_BK,
                 interpret=None):
    """activation(x @ w + bias) with the epilogue fused into the matmul.

    x: [..., K]; w: [K, N]; bias: [N] or None.
    activation: none | relu | gelu | silu."""
    if activation not in _ACTS:
        raise ValueError(f"unsupported activation {activation!r}")
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    lead = x.shape[:-1]
    K = x.shape[-1]
    x2 = x.reshape(-1, K)
    out = _fused_linear(x2, w, bias, activation, bm, bn, bk, interpret)
    return out.reshape(*lead, w.shape[1])
