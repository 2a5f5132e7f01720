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

Causal calls compute only the block pairs that causality keeps
(``causal_pairs``): a wholly masked pair's step does nothing, and its
operands' index map names the block already held, so nothing is copied
for it; only pairs that straddle the diagonal build the mask.  The MXU
takes q, k, v, dO and the bf16-rounded P and dS as stored, with float32
accumulation: on a v5e that is what the float32 products of one bf16
pass gave, bit for bit.
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


def _last_k_block(qi, block_q, block_k, offset):
    """The last k block that q block ``qi``'s last row sees (causal)."""
    return (qi * block_q + block_q - 1 + offset) // block_k


def _skips(causal, offset):
    """Whether causality leaves whole block pairs out.  With Tk < Tq
    (``offset`` = Tk - Tq < 0) the first rows see no key, and their answer
    (every value averaged, as the reference's softmax over a row of NEG_INF
    gives) needs every pair: such calls mask every pair and skip none."""
    return causal and offset >= 0


def causal_pairs(Tq, Tk, block_q, block_k, causal):
    """(block pairs a kernel computes, all block pairs) of one batch-head.

    A causal pair is computed when its q block's last row sees its k
    block's first key (bottom-right alignment: row i sees keys up to
    i + Tk - Tq); the grids' ``pl.when`` conditions are this arithmetic.
    """
    bq, bk = min(block_q, Tq), min(block_k, Tk)
    nq, nk = Tq // bq, Tk // bk
    if not _skips(causal, Tk - Tq):
        return nq * nk, nq * nk
    return (sum(_last_k_block(i, bq, bk, Tk - Tq) + 1 for i in range(nq)),
            nq * nk)


def _each_pair(body, *, causal, qi, ki, block_q, block_k, offset):
    """Run ``body(masked)`` for block pair (qi, ki): not at all where
    causality masks the pair whole, with the mask only where the pair
    straddles the diagonal."""
    if not _skips(causal, offset):
        body(causal)
    else:
        seen = ki <= _last_k_block(qi, block_q, block_k, offset)
        straddles = ki * block_k + block_k - 1 > qi * block_q + offset
        pl.when(jnp.logical_and(seen, straddles))(lambda: body(True))
        pl.when(jnp.logical_and(seen, jnp.logical_not(straddles)))(
            lambda: body(False))


def _causal_mask(s, qi, ki, block_q, block_k, offset):
    q_pos = qi * block_q + jax.lax.broadcasted_iota(
        jnp.int32, (block_q, block_k), 0)
    k_pos = ki * block_k + jax.lax.broadcasted_iota(
        jnp.int32, (block_q, block_k), 1)
    return jnp.where(q_pos + offset >= k_pos, s, NEG_INF)


# contracting dimensions of the kernels' products: a b^T, a b, a^T b
_NT, _NN, _TN = ((1,), (1,)), ((1,), (0,)), ((0,), (0,))


def _dot(a, b, contract):
    """a · b in float32 from the operands as stored.  A bf16 product is
    one MXU pass, stated so that a process-wide ``highest`` default cannot
    ask Mosaic for several (it refuses); float32 operands keep the
    process's default."""
    precision = None if a.dtype == jnp.float32 else jax.lax.Precision.DEFAULT
    return jax.lax.dot_general(a, b, (contract, ((), ())),
                               precision=precision,
                               preferred_element_type=jnp.float32)


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

    def pair(masked):
        s = _dot(q_ref[0], k_ref[0], _NT) * scale  # [block_q, block_k]
        if masked:
            s = _causal_mask(s, qi, ki, block_q, block_k, offset)
        m_prev = m_ref[:]  # [block_q, 1]
        m_cur = jnp.max(s, axis=1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m_prev - m_new)
        l_new = alpha * l_ref[:] + jnp.sum(p, axis=1, keepdims=True)
        v = v_ref[0]
        acc_ref[:] = acc_ref[:] * alpha + _dot(p.astype(v.dtype), v, _NN)
        m_ref[:] = m_new
        l_ref[:] = l_new

    _each_pair(pair, causal=causal, qi=qi, ki=ki, block_q=block_q,
               block_k=block_k, offset=offset)

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

    def pair(masked):
        k = k_ref[0]
        s = _dot(q_ref[0], k, _NT) * scale
        if masked:
            s = _causal_mask(s, qi, ki, block_q, block_k, offset)
        # masked entries: exp(NEG_INF - lse) = 0; lanes of lse are identical
        p = jnp.exp(s - lse_ref[0][:, :1])
        dp = _dot(do_ref[0], v_ref[0], _NT)
        ds = p * (dp - delta_ref[0][:, :1]) * scale
        acc_ref[:] += _dot(ds.astype(k.dtype), k, _NN)

    _each_pair(pair, causal=causal, qi=qi, ki=ki, block_q=block_q,
               block_k=block_k, offset=offset)

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

    def pair(masked):
        q = q_ref[0]
        do = do_ref[0]
        s = _dot(q, k_ref[0], _NT) * scale
        if masked:
            s = _causal_mask(s, qi, ki, block_q, block_k, offset)
        p = jnp.exp(s - lse_ref[0][:, :1])  # [bq, bk]
        dv_acc[:] += _dot(p.astype(do.dtype), do, _TN)
        dp = _dot(do, v_ref[0], _NT)
        ds = p * (dp - delta_ref[0][:, :1]) * scale  # [bq, bk]
        dk_acc[:] += _dot(ds.astype(q.dtype), q, _TN)

    _each_pair(pair, causal=causal, qi=qi, ki=ki, block_q=block_q,
               block_k=block_k, offset=offset)

    @pl.when(qi == nq - 1)
    def _finalize():
        dk_ref[0] = dk_acc[:].astype(dk_ref.dtype)
        dv_ref[0] = dv_acc[:].astype(dv_ref.dtype)


def _params(interpret):
    if interpret:
        return None
    return pltpu.CompilerParams(
        dimension_semantics=("parallel", "parallel", "arbitrary"))


def _kv_index(causal, Tq, Tk, bq, bk):
    """Index map of K and V in the forward and dQ grids (b, q block, k
    block): a step past the q block's last needed k block names that block
    again, so the pipeline fetches nothing for it."""
    offset = Tk - Tq
    if not _skips(causal, offset):
        return lambda b, i, j: (b, j, 0)
    return lambda b, i, j: (
        b, jnp.minimum(j, _last_k_block(i, bq, bk, offset)), 0)


def _q_index(causal, Tq, Tk, bq, bk):
    """Index map of q, dO, L and D in the dK/dV grid (b, k block, q block):
    a step before the k block's first needed q block names that block."""
    offset = Tk - Tq
    if not _skips(causal, offset):
        return lambda b, j, i: (b, i, 0)
    return lambda b, j, i: (
        b, jnp.maximum(i, jnp.maximum(j * bk - offset, 0) // bq), 0)


def _tileable(Tq, Tk, block_q, block_k):
    bq = min(block_q, Tq)
    bk = min(block_k, Tk)
    return (Tq % bq == 0 and Tk % bk == 0), bq, bk


def _forward(q, k, v, causal, scale, block_q, block_k, interpret, with_lse):
    """Pallas forward: out, and with ``with_lse`` also L = m + log(l)."""
    B, H, Tq, D = q.shape
    Tk = k.shape[2]
    _, bq, bk = _tileable(Tq, Tk, block_q, block_k)
    q_spec = pl.BlockSpec((1, bq, D), lambda b, i, j: (b, i, 0))
    kv_spec = pl.BlockSpec((1, bk, D), _kv_index(causal, Tq, Tk, bq, bk))
    out_specs = [q_spec]
    out_shape = [jax.ShapeDtypeStruct((B * H, Tq, D), q.dtype)]
    if with_lse:
        out_specs.append(
            pl.BlockSpec((1, bq, NUM_LANES), lambda b, i, j: (b, i, 0)))
        out_shape.append(
            jax.ShapeDtypeStruct((B * H, Tq, NUM_LANES), jnp.float32))
    outs = pl.pallas_call(
        functools.partial(
            _fwd_kernel_lse if with_lse else _fwd_kernel, scale=scale,
            causal=causal, block_q=bq, block_k=bk, offset=Tk - Tq),
        grid=(B * H, Tq // bq, Tk // bk),
        in_specs=[q_spec, kv_spec, kv_spec],
        out_specs=out_specs,
        out_shape=out_shape,
        scratch_shapes=[
            pltpu.VMEM((bq, D), jnp.float32),
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, 1), jnp.float32),
        ],
        interpret=interpret,
        compiler_params=_params(interpret),
        name=KERNEL_NAME + "_fwd",
    )(q.reshape(B * H, Tq, D), k.reshape(B * H, Tk, D),
      v.reshape(B * H, Tk, D))
    out = outs[0].reshape(B, H, Tq, D)
    return (out, outs[1][:, :, 0]) if with_lse else out


def _flash_fwd_bhtd(q, k, v, causal, scale, block_q, block_k, interpret):
    if not _tileable(q.shape[2], k.shape[2], block_q, block_k)[0]:
        # shape not tileable: fall back
        return _attn_reference(q, k, v, causal, scale)
    return _forward(q, k, v, causal, scale, block_q, block_k, interpret,
                    with_lse=False)


def _flash_bwd_bhtd(q, k, v, out, lse, g, causal, scale, block_q, block_k,
                    interpret):
    """FlashAttention-2 backward: dq kernel + dkv kernel."""
    B, H, Tq, D = q.shape
    Tk = k.shape[2]
    _, bq, bk = _tileable(Tq, Tk, block_q, block_k)
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

    q_spec = pl.BlockSpec((1, bq, D), lambda b, i, j: (b, i, 0))
    kv_spec = pl.BlockSpec((1, bk, D), _kv_index(causal, Tq, Tk, bq, bk))
    row_spec = pl.BlockSpec((1, bq, NUM_LANES), lambda b, i, j: (b, i, 0))
    dq = pl.pallas_call(
        functools.partial(_bwd_dq_kernel, scale=scale, causal=causal,
                          block_q=bq, block_k=bk, offset=Tk - Tq),
        grid=(B * H, Tq // bq, Tk // bk),
        in_specs=[q_spec, kv_spec, kv_spec, q_spec, row_spec, row_spec],
        out_specs=q_spec,
        out_shape=jax.ShapeDtypeStruct((B * H, Tq, D), q.dtype),
        scratch_shapes=[pltpu.VMEM((bq, D), jnp.float32)],
        interpret=interpret,
        compiler_params=_params(interpret),
        name=KERNEL_NAME + "_bwd_dq",
    )(qr, kr, vr, gr, lse_l, delta_l)

    # dkv: grid over kv blocks, q innermost
    q_index = _q_index(causal, Tq, Tk, bq, bk)
    q_spec = pl.BlockSpec((1, bq, D), q_index)
    kv_spec = pl.BlockSpec((1, bk, D), lambda b, j, i: (b, j, 0))
    row_spec = pl.BlockSpec((1, bq, NUM_LANES), q_index)
    dk, dv = pl.pallas_call(
        functools.partial(_bwd_dkv_kernel, scale=scale, causal=causal,
                          block_q=bq, block_k=bk, offset=Tk - Tq),
        grid=(B * H, Tk // bk, Tq // bq),
        in_specs=[q_spec, kv_spec, kv_spec, q_spec, row_spec, row_spec],
        out_specs=[kv_spec, kv_spec],
        out_shape=[jax.ShapeDtypeStruct((B * H, Tk, D), k.dtype),
                   jax.ShapeDtypeStruct((B * H, Tk, D), v.dtype)],
        scratch_shapes=[pltpu.VMEM((bk, D), jnp.float32),
                        pltpu.VMEM((bk, D), jnp.float32)],
        interpret=interpret,
        compiler_params=_params(interpret),
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
    out, lse = _forward(q, k, v, causal, scale, block_q, block_k, interpret,
                        with_lse=True)
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


def _block(T, target):
    """The whole of T where it is no longer than ``target``, else the
    largest of target, target / 2, ... 128 that divides it."""
    if T <= target:
        return T
    block = target
    while block > NUM_LANES and T % block:
        block //= 2
    return block


def default_blocks(Tq, Tk, D):
    """(block_q, block_k) of a call that names none.  Fewer, larger grid
    steps win although a diagonal pair of 1024 x 1024 computes its masked
    half: at T 2048, head 128, bf16 (the training benchmark's shape) it was
    the fastest of ten pairs on a v5e for each of the three kernels, alone
    and in the training step (PERF.md).  A wider head halves the target,
    keeping a step's scoped VMEM near that shape's."""
    target = 1024 if D <= 128 else 512
    return _block(Tq, target), _block(Tk, target)


_AUTOTUNE_BLOCKS = [(128, 128), (128, 256), (256, 256), (256, 512),
                    (512, 512), (512, 1024)]


def _autotuned_blocks(q, k, causal, scale, interpret):
    """(block_q, block_k) via the autotune cache (FLAGS_use_autotune)."""
    from ..core.flags import flag
    from . import autotune as at

    B, H, Tq, D = q.shape
    Tk = k.shape[2]
    defaults = default_blocks(Tq, Tk, D)
    if interpret or not flag("use_autotune"):
        return defaults
    key = (B, H, Tq, Tk, D, str(q.dtype), causal)
    if isinstance(q, jax.core.Tracer):
        # under a trace: timing is impossible; use a cached winner if one
        # exists for these (static) shapes, else the defaults
        return at.lookup("flash_attention", key) or defaults
    cands = [(bq, bk) for bq, bk in _AUTOTUNE_BLOCKS
             if Tq % min(bq, Tq) == 0 and Tk % min(bk, Tk) == 0]
    if not cands:
        return defaults

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
    return best or defaults


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
