"""Pallas flash attention for TPU.

TPU-native replacement for the reference fused attention CUDA stack
(/root/reference/paddle/fluid/operators/fused/fused_attention_op.cu,
fmha_ref.h): online-softmax tiling over the KV sequence so logits never
materialize in HBM.  Grid = (batch*heads, q_blocks, k_blocks) with the KV
axis innermost; m/l/acc accumulate in VMEM scratch across k steps and the
output block is written on the last k step.

Backward (round 2) = Pallas kernels too (FlashAttention-2 style): the
forward saves only O and the per-row logsumexp L; backward recomputes
P = exp(S - L) blockwise and runs two kernels — dQ (grid over q blocks,
kv innermost) and dK/dV (grid over kv blocks, q innermost) — so no O(T^2)
tensor ever lives in HBM in either direction.  XLA-recompute backward
remains the fallback for untileable shapes.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# pallas_call names: what a compiled program's HLO and a profiler trace
# show for the forward, dQ and dK/dV kernels
KERNEL_NAME = "flash_attention"
DEFAULT_BLOCK_Q = 256
DEFAULT_BLOCK_K = 512
NEG_INF = -1e30
# TPU vector lanes: per-row scalars (lse, delta) are stored broadcast over a
# trailing lane dim so their blocks satisfy the (8, 128) tiling rule.
NUM_LANES = 128


def _attn_reference(q, k, v, causal, scale):
    """[B, H, T, D] reference; also used for the recompute backward."""
    logits = jnp.einsum(
        "bhtd,bhsd->bhts", q, k, preferred_element_type=jnp.float32) * scale
    if causal:
        t, s = logits.shape[-2], logits.shape[-1]
        mask = jnp.tril(jnp.ones((t, s), bool), k=s - t)
        logits = jnp.where(mask, logits, NEG_INF)
    probs = jax.nn.softmax(logits, axis=-1).astype(q.dtype)
    return jnp.einsum("bhts,bhsd->bhtd", probs, v)


def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, acc_ref, m_ref, l_ref, *,
                scale, causal, block_q, block_k, offset):
    qi = pl.program_id(1)
    ki = pl.program_id(2)
    nk = pl.num_programs(2)

    @pl.when(ki == 0)
    def _init():
        acc_ref[:] = jnp.zeros_like(acc_ref)
        m_ref[:] = jnp.full_like(m_ref, NEG_INF)
        l_ref[:] = jnp.zeros_like(l_ref)

    q = q_ref[0].astype(jnp.float32)  # [block_q, d]
    k = k_ref[0].astype(jnp.float32)  # [block_k, d]
    s = jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32) * scale  # [block_q, block_k]

    if causal:
        q_pos = qi * block_q + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, block_k), 0)
        k_pos = ki * block_k + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, block_k), 1)
        s = jnp.where(q_pos + offset >= k_pos, s, NEG_INF)

    m_prev = m_ref[:]  # [block_q, 1]
    m_cur = jnp.max(s, axis=1, keepdims=True)
    m_new = jnp.maximum(m_prev, m_cur)
    p = jnp.exp(s - m_new)
    alpha = jnp.exp(m_prev - m_new)
    l_new = alpha * l_ref[:] + jnp.sum(p, axis=1, keepdims=True)
    v = v_ref[0].astype(jnp.float32)
    acc_ref[:] = acc_ref[:] * alpha + jax.lax.dot_general(
        p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
    m_ref[:] = m_new
    l_ref[:] = l_new

    @pl.when(ki == nk - 1)
    def _finalize():
        o_ref[0] = (acc_ref[:] / jnp.maximum(l_ref[:], 1e-30)).astype(
            o_ref.dtype)


def _fwd_kernel_lse(q_ref, k_ref, v_ref, o_ref, lse_ref, acc_ref, m_ref,
                    l_ref, *, scale, causal, block_q, block_k, offset):
    """Forward that also writes L = m + log(l) for the Pallas backward."""
    _fwd_kernel(q_ref, k_ref, v_ref, o_ref, acc_ref, m_ref, l_ref,
                scale=scale, causal=causal, block_q=block_q,
                block_k=block_k, offset=offset)
    ki = pl.program_id(2)
    nk = pl.num_programs(2)

    @pl.when(ki == nk - 1)
    def _write_lse():
        lse = m_ref[:] + jnp.log(jnp.maximum(l_ref[:], 1e-30))  # [bq, 1]
        lse_ref[0] = jax.lax.broadcast_in_dim(
            lse[:, 0], lse_ref.shape[1:], (0,))


def _bwd_dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dq_ref,
                   acc_ref, *, scale, causal, block_q, block_k, offset):
    """dQ = sum_k dS @ K * scale, dS = P * (dO V^T - D)."""
    qi = pl.program_id(1)
    ki = pl.program_id(2)
    nk = pl.num_programs(2)

    @pl.when(ki == 0)
    def _init():
        acc_ref[:] = jnp.zeros_like(acc_ref)

    q = q_ref[0].astype(jnp.float32)
    k = k_ref[0].astype(jnp.float32)
    v = v_ref[0].astype(jnp.float32)
    do = do_ref[0].astype(jnp.float32)
    lse = lse_ref[0][:, :1]         # [bq, 1] (lanes are identical)
    delta = delta_ref[0][:, :1]     # [bq, 1]

    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale
    if causal:
        q_pos = qi * block_q + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, block_k), 0)
        k_pos = ki * block_k + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, block_k), 1)
        s = jnp.where(q_pos + offset >= k_pos, s, NEG_INF)
    p = jnp.exp(s - lse)  # masked entries: exp(NEG_INF - lse) = 0
    dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())),
                             preferred_element_type=jnp.float32)
    ds = p * (dp - delta) * scale
    acc_ref[:] += jax.lax.dot_general(
        ds, k, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)

    @pl.when(ki == nk - 1)
    def _finalize():
        dq_ref[0] = acc_ref[:].astype(dq_ref.dtype)


def _bwd_dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                    dk_ref, dv_ref, dk_acc, dv_acc, *, scale, causal,
                    block_q, block_k, offset):
    """dV = P^T dO ; dK = dS^T Q * scale — grid over kv blocks, q inner."""
    ki = pl.program_id(1)
    qi = pl.program_id(2)
    nq = pl.num_programs(2)

    @pl.when(qi == 0)
    def _init():
        dk_acc[:] = jnp.zeros_like(dk_acc)
        dv_acc[:] = jnp.zeros_like(dv_acc)

    q = q_ref[0].astype(jnp.float32)
    k = k_ref[0].astype(jnp.float32)
    v = v_ref[0].astype(jnp.float32)
    do = do_ref[0].astype(jnp.float32)
    lse = lse_ref[0][:, :1]
    delta = delta_ref[0][:, :1]

    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale
    if causal:
        q_pos = qi * block_q + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, block_k), 0)
        k_pos = ki * block_k + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, block_k), 1)
        s = jnp.where(q_pos + offset >= k_pos, s, NEG_INF)
    p = jnp.exp(s - lse)  # [bq, bk]
    dv_acc[:] += jax.lax.dot_general(
        p, do, (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32)
    dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())),
                             preferred_element_type=jnp.float32)
    ds = p * (dp - delta) * scale  # [bq, bk]
    dk_acc[:] += jax.lax.dot_general(
        ds, q, (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32)

    @pl.when(qi == nq - 1)
    def _finalize():
        dk_ref[0] = dk_acc[:].astype(dk_ref.dtype)
        dv_ref[0] = dv_acc[:].astype(dv_ref.dtype)


def _flash_fwd_bhtd(q, k, v, causal, scale, block_q, block_k, interpret):
    B, H, Tq, D = q.shape
    Tk = k.shape[2]
    bq = min(block_q, Tq)
    bk = min(block_k, Tk)
    if Tq % bq or Tk % bk:
        # shape not tileable: fall back
        return _attn_reference(q, k, v, causal, scale)
    qr = q.reshape(B * H, Tq, D)
    kr = k.reshape(B * H, Tk, D)
    vr = v.reshape(B * H, Tk, D)

    grid = (B * H, Tq // bq, Tk // bk)
    kernel = functools.partial(
        _fwd_kernel, scale=scale, causal=causal, block_q=bq, block_k=bk,
        offset=Tk - Tq)
    scratch = [
        pltpu.VMEM((bq, D), jnp.float32),
        pltpu.VMEM((bq, 1), jnp.float32),
        pltpu.VMEM((bq, 1), jnp.float32),
    ]
    out = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, bq, D), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, bk, D), lambda b, i, j: (b, j, 0)),
            pl.BlockSpec((1, bk, D), lambda b, i, j: (b, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, bq, D), lambda b, i, j: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct((B * H, Tq, D), q.dtype),
        scratch_shapes=scratch,
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"))
        if not interpret else None,
        name=KERNEL_NAME + "_fwd",
    )(qr, kr, vr)
    return out.reshape(B, H, Tq, D)


def _tileable(Tq, Tk, block_q, block_k):
    bq = min(block_q, Tq)
    bk = min(block_k, Tk)
    return (Tq % bq == 0 and Tk % bk == 0), bq, bk


def _flash_fwd_lse_bhtd(q, k, v, causal, scale, block_q, block_k, interpret):
    """Forward returning (out, lse) via the Pallas kernel."""
    B, H, Tq, D = q.shape
    Tk = k.shape[2]
    ok, bq, bk = _tileable(Tq, Tk, block_q, block_k)
    assert ok
    qr = q.reshape(B * H, Tq, D)
    kr = k.reshape(B * H, Tk, D)
    vr = v.reshape(B * H, Tk, D)
    grid = (B * H, Tq // bq, Tk // bk)
    kernel = functools.partial(
        _fwd_kernel_lse, scale=scale, causal=causal, block_q=bq, block_k=bk,
        offset=Tk - Tq)
    out, lse = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, bq, D), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, bk, D), lambda b, i, j: (b, j, 0)),
            pl.BlockSpec((1, bk, D), lambda b, i, j: (b, j, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, bq, D), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, bq, NUM_LANES), lambda b, i, j: (b, i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B * H, Tq, D), q.dtype),
            jax.ShapeDtypeStruct((B * H, Tq, NUM_LANES), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((bq, D), jnp.float32),
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, 1), jnp.float32),
        ],
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"))
        if not interpret else None,
        name=KERNEL_NAME + "_fwd",
    )(qr, kr, vr)
    return out.reshape(B, H, Tq, D), lse[:, :, 0]


def _flash_bwd_bhtd(q, k, v, out, lse, g, causal, scale, block_q, block_k,
                    interpret):
    """FlashAttention-2 backward: dq kernel + dkv kernel."""
    B, H, Tq, D = q.shape
    Tk = k.shape[2]
    ok, bq, bk = _tileable(Tq, Tk, block_q, block_k)
    assert ok
    qr = q.reshape(B * H, Tq, D)
    kr = k.reshape(B * H, Tk, D)
    vr = v.reshape(B * H, Tk, D)
    gr = g.reshape(B * H, Tq, D)
    # delta = rowsum(dO * O) — the 'D' vector of FlashAttention-2
    delta = jnp.sum(gr.astype(jnp.float32)
                    * out.reshape(B * H, Tq, D).astype(jnp.float32), axis=-1)
    # broadcast per-row scalars over lanes so blocks obey the (8,128) tiling
    lse_l = jnp.broadcast_to(lse[..., None], (*lse.shape, NUM_LANES))
    delta_l = jnp.broadcast_to(delta[..., None], (*delta.shape, NUM_LANES))

    common = dict(scale=scale, causal=causal, block_q=bq, block_k=bk,
                  offset=Tk - Tq)
    q_spec = pl.BlockSpec((1, bq, D), lambda b, i, j: (b, i, 0))
    kv_spec_dq = pl.BlockSpec((1, bk, D), lambda b, i, j: (b, j, 0))
    row_spec = pl.BlockSpec((1, bq, NUM_LANES), lambda b, i, j: (b, i, 0))
    dq = pl.pallas_call(
        functools.partial(_bwd_dq_kernel, **common),
        grid=(B * H, Tq // bq, Tk // bk),
        in_specs=[q_spec, kv_spec_dq, kv_spec_dq, q_spec, row_spec,
                  row_spec],
        out_specs=q_spec,
        out_shape=jax.ShapeDtypeStruct((B * H, Tq, D), q.dtype),
        scratch_shapes=[pltpu.VMEM((bq, D), jnp.float32)],
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"))
        if not interpret else None,
        name=KERNEL_NAME + "_bwd_dq",
    )(qr, kr, vr, gr, lse_l, delta_l)

    # dkv: grid over kv blocks, q innermost
    q_spec2 = pl.BlockSpec((1, bq, D), lambda b, j, i: (b, i, 0))
    kv_spec2 = pl.BlockSpec((1, bk, D), lambda b, j, i: (b, j, 0))
    row_spec2 = pl.BlockSpec((1, bq, NUM_LANES), lambda b, j, i: (b, i, 0))
    dk, dv = pl.pallas_call(
        functools.partial(_bwd_dkv_kernel, **common),
        grid=(B * H, Tk // bk, Tq // bq),
        in_specs=[q_spec2, kv_spec2, kv_spec2, q_spec2, row_spec2,
                  row_spec2],
        out_specs=[kv_spec2, kv_spec2],
        out_shape=[jax.ShapeDtypeStruct((B * H, Tk, D), k.dtype),
                   jax.ShapeDtypeStruct((B * H, Tk, D), v.dtype)],
        scratch_shapes=[pltpu.VMEM((bk, D), jnp.float32),
                        pltpu.VMEM((bk, D), jnp.float32)],
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"))
        if not interpret else None,
        name=KERNEL_NAME + "_bwd_dkv",
    )(qr, kr, vr, gr, lse_l, delta_l)
    return (dq.reshape(B, H, Tq, D), dk.reshape(B, H, Tk, D),
            dv.reshape(B, H, Tk, D))


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7))
def _flash_attention_bhtd(q, k, v, causal, scale, block_q, block_k, interpret):
    return _flash_fwd_bhtd(q, k, v, causal, scale, block_q, block_k, interpret)


def _flash_vjp_fwd(q, k, v, causal, scale, block_q, block_k, interpret):
    ok, _, _ = _tileable(q.shape[2], k.shape[2], block_q, block_k)
    if not ok:
        out = _attn_reference(q, k, v, causal, scale)
        return out, (q, k, v, None, None)
    out, lse = _flash_fwd_lse_bhtd(q, k, v, causal, scale, block_q, block_k,
                                   interpret)
    return out, (q, k, v, out, lse)


def _flash_vjp_bwd(causal, scale, block_q, block_k, interpret, res, g):
    q, k, v, out, lse = res
    if lse is None:
        # untileable shape: XLA recompute fallback
        _, vjp_fn = jax.vjp(
            lambda q_, k_, v_: _attn_reference(q_, k_, v_, causal, scale),
            q, k, v)
        return vjp_fn(g)
    return _flash_bwd_bhtd(q, k, v, out, lse, g, causal, scale, block_q,
                           block_k, interpret)


_flash_attention_bhtd.defvjp(_flash_vjp_fwd, _flash_vjp_bwd)


_AUTOTUNE_BLOCKS = [(128, 128), (128, 256), (256, 256), (256, 512),
                    (512, 512), (512, 1024)]


def _autotuned_blocks(q, k, causal, scale, interpret):
    """(block_q, block_k) via the autotune cache (FLAGS_use_autotune)."""
    from ..core.flags import flag
    from . import autotune as at

    if interpret or not flag("use_autotune"):
        return DEFAULT_BLOCK_Q, DEFAULT_BLOCK_K
    B, H, Tq, D = q.shape
    Tk = k.shape[2]
    key = (B, H, Tq, Tk, D, str(q.dtype), causal)
    if isinstance(q, jax.core.Tracer):
        # under a trace: timing is impossible; use a cached winner if one
        # exists for these (static) shapes, else the defaults
        return at.lookup("flash_attention", key) or (DEFAULT_BLOCK_Q,
                                                     DEFAULT_BLOCK_K)
    cands = [(bq, bk) for bq, bk in _AUTOTUNE_BLOCKS
             if Tq % min(bq, Tq) == 0 and Tk % min(bk, Tk) == 0]
    if not cands:
        return DEFAULT_BLOCK_Q, DEFAULT_BLOCK_K

    v_probe = k  # same shape/dtype as v
    jitted = {}  # one compiled fn per cfg: the timed iters must hit the
    # jit cache, else the search measures XLA compile time, not kernels

    def run(cfg):
        fn = jitted.get(cfg)
        if fn is None:
            fn = jax.jit(functools.partial(
                _flash_fwd_bhtd, causal=causal, scale=scale,
                block_q=cfg[0], block_k=cfg[1], interpret=False))
            jitted[cfg] = fn
        fn(q, k, v_probe).block_until_ready()

    best = at.autotune("flash_attention", key, cands, run)
    return best or (DEFAULT_BLOCK_Q, DEFAULT_BLOCK_K)


def flash_attention_bhtd(q, k, v, causal=False, scale=None,
                         block_q=None, block_k=None, interpret=None):
    """[B, H, T, D] flash attention."""
    if scale is None:
        scale = 1.0 / math.sqrt(q.shape[-1])
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    if block_q is None or block_k is None:
        # explicit flag override (perf experiments: FLAGS_flash_block_q=…
        # env or set_flags) beats autotune/defaults
        from ..core.flags import flag

        block_q = block_q or (int(flag("flash_block_q")) or None)
        block_k = block_k or (int(flag("flash_block_k")) or None)
    if block_q is None or block_k is None:
        abq, abk = _autotuned_blocks(q, k, causal, scale, interpret)
        block_q = block_q or abq
        block_k = block_k or abk
    return _flash_attention_bhtd(q, k, v, causal, scale, block_q, block_k,
                                 interpret)


def flash_attention_bthd(q, k, v, causal=False, scale=None, **kwargs):
    """[B, T, H, D] layout (paddle flash_attention layout).  Supports GQA by
    repeating KV heads when q heads are a multiple of kv heads."""
    qh = q.shape[2]
    kh = k.shape[2]
    if qh != kh:
        rep = qh // kh
        k = jnp.repeat(k, rep, axis=2)
        v = jnp.repeat(v, rep, axis=2)
    qt = jnp.swapaxes(q, 1, 2)
    kt = jnp.swapaxes(k, 1, 2)
    vt = jnp.swapaxes(v, 1, 2)
    out = flash_attention_bhtd(qt, kt, vt, causal=causal, scale=scale, **kwargs)
    return jnp.swapaxes(out, 1, 2)
