"""Fused chunked-prefill attention kernel (Pallas) with an XLA fallback.

This is the first kernel MINED rather than hand-picked: on the fused
prefill trace, analysis/fusionminer ranks the chunked-prefill attention
inner loop as the #1 remaining candidate — the gathered [B, L, KVH, D]
context copy, the [B, H, T, L] score/probability tensors and the
repeat-to-H KV expansion all round-trip HBM between the two attention
matmuls, while only the projection epilogues around them fuse.

The kernel attends one query CHUNK (T tokens per sequence, already
RoPE-rotated and scattered into the pools by the caller) over each
sequence's paged KV context in one pass: the block table rides in as a
scalar-prefetch operand, each grid step DMAs exactly one KV block from
the pool, and an online (flash) softmax keeps the running max/sum and
accumulator for all T queries in VMEM.  GQA never materializes the
repeat: queries are grouped [B, KVH, rep*T, D] so every q row of a
group shares the group's KV block.

Numerics contract: ``_xla_chunked`` is the same grouped-query math in
plain XLA ops (identical masking, f32 accumulation, full softmax in
place of the online rescale).  On CPU the fused path lowers through
it, so tier-1 and the jaxpr audits cover the exact fused-step math
with no pallas_call in the program.  models/llama.py's ``_paged_attn``
gather path stays the unfused parity oracle.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .costs import KernelCost, register_kernel_cost
from .kv_quant import decode_codes

KERNEL_NAME = "fused_chunked_prefill"
NEG_INF = -1e30


def _pick_head(page, h):
    """Head ``h`` of one [bs, KVH, D] f32 page -> [bs, D].  ``h`` is a
    grid index, and Mosaic takes no dynamic index on a tiled (sublane)
    dim, so the pick is a mask and a sum over KVH — exact, since every
    other term is zero."""
    heads = jax.lax.broadcasted_iota(jnp.int32, page.shape, 1)
    return jnp.sum(jnp.where(heads == h, page, 0.0), axis=1)


def _visible_upto(q_pos, mask_block):
    """The last key position a query at ``q_pos`` sees: itself under the
    causal mask (``mask_block`` 1), the END of its own block of
    ``mask_block`` positions under a block-causal one (blocks aligned at
    multiples of ``mask_block``)."""
    if mask_block == 1:
        return q_pos
    return (q_pos // mask_block + 1) * mask_block - 1


def _chunk_kernel(bt_ref, pos_ref, q_ref, k_ref, v_ref, *rest,
                  bs, chunk, n_pages, kv_dtype=None, mask_block=1):
    if kv_dtype is not None:
        ks_ref, vs_ref, o_ref, acc_ref, m_ref, l_ref = rest
    else:
        o_ref, acc_ref, m_ref, l_ref = rest
        ks_ref = vs_ref = None
    b = pl.program_id(0)
    h = pl.program_id(1)
    p = pl.program_id(2)

    @pl.when(p == 0)
    def _init():
        acc_ref[:] = jnp.zeros_like(acc_ref)
        m_ref[:] = jnp.full_like(m_ref, NEG_INF)
        l_ref[:] = jnp.zeros_like(l_ref)

    # q rows are [rep * chunk, D] with row r * chunk + t; scale is
    # already folded into q by the caller, so the score math is a bare
    # dot against this page's gathered block.  Quantized pools dequant
    # right at the DMA boundary: the int8 block just landed in VMEM and
    # the per-row scale multiply rides the same f32 upcast.
    qv = q_ref[0, 0].astype(jnp.float32)                # [RT, D]
    if kv_dtype is not None:
        kb = _pick_head(decode_codes(k_ref[0], kv_dtype), h) * \
            ks_ref[0].T                                 # [bs, D]
        vb = _pick_head(decode_codes(v_ref[0], kv_dtype), h) * \
            vs_ref[0].T
    else:
        kb = _pick_head(k_ref[0].astype(jnp.float32), h)    # [bs, D]
        vb = _pick_head(v_ref[0].astype(jnp.float32), h)

    scores = jax.lax.dot_general(
        qv, kb, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32)             # [RT, bs]

    # causal chunk mask: key position vs this row's query position
    # pos_ref[b] + t.  Page 0 always holds key position 0, so m stays
    # anchored to a real score and masked lanes underflow to exp(-inf).
    k_pos = p * bs + jax.lax.broadcasted_iota(jnp.int32, scores.shape, 1)
    q_pos = pos_ref[b] + \
        jax.lax.broadcasted_iota(jnp.int32, scores.shape, 0) % chunk
    scores = jnp.where(k_pos <= _visible_upto(q_pos, mask_block), scores,
                       NEG_INF)

    m_cur = jnp.max(scores, axis=-1, keepdims=True)     # [RT, 1]
    m_new = jnp.maximum(m_ref[:], m_cur)
    alpha = jnp.exp(m_ref[:] - m_new)
    pexp = jnp.exp(scores - m_new)
    acc_ref[:] = acc_ref[:] * alpha + jax.lax.dot_general(
        pexp, vb, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)             # [RT, D]
    l_ref[:] = l_ref[:] * alpha + jnp.sum(pexp, axis=-1, keepdims=True)
    m_ref[:] = m_new

    @pl.when(p == n_pages - 1)
    def _emit():
        o_ref[0, 0] = (acc_ref[:] /
                       jnp.maximum(l_ref[:], 1e-30)).astype(o_ref.dtype)


def _pallas_chunked(q_g, k_pool, v_pool, block_table, positions, chunk,
                    interpret, k_scale=None, v_scale=None, kv_dtype=None,
                    mask_block=1):
    """q_g: grouped, ROTATED, pre-scaled [B, KVH, RT, D] f32 queries;
    returns the normalized context [B, KVH, RT, D] f32."""
    B, KVH, RT, D = q_g.shape
    bs = k_pool.shape[1]
    nbs = block_table.shape[1]

    in_specs = [
        pl.BlockSpec((1, 1, RT, D),
                     lambda b, h, p, bt, pos: (b, h, 0, 0)),
        # the page rides in with ALL its KV heads and the kernel picks
        # head h: a one-head (1, bs, 1, D) block of the [nb, bs, KVH, D]
        # pool is not (8, 128)-tileable on its (KVH, D) minor dims
        pl.BlockSpec((1, bs, KVH, D),
                     lambda b, h, p, bt, pos: (bt[b, p], 0, 0, 0)),
        pl.BlockSpec((1, bs, KVH, D),
                     lambda b, h, p, bt, pos: (bt[b, p], 0, 0, 0)),
    ]
    operands = [q_g, k_pool, v_pool]
    if kv_dtype is not None:
        # per-row scale sidecars ride the same block-table indexing as
        # the pools they describe ([nb, bs] -> one (1, bs) row strip)
        in_specs += [
            pl.BlockSpec((1, 1, bs),
                         lambda b, h, p, bt, pos: (bt[b, p], 0, 0)),
            pl.BlockSpec((1, 1, bs),
                         lambda b, h, p, bt, pos: (bt[b, p], 0, 0)),
        ]
        operands += [k_scale[:, None, :], v_scale[:, None, :]]

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(B, KVH, nbs),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, 1, RT, D),
                               lambda b, h, p, bt, pos: (b, h, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((RT, D), jnp.float32),
            pltpu.VMEM((RT, 1), jnp.float32),
            pltpu.VMEM((RT, 1), jnp.float32),
        ],
    )
    L = nbs * bs
    esize = jnp.dtype(k_pool.dtype).itemsize
    scale_bytes = 2 * B * KVH * L * 4 if kv_dtype is not None else 0
    # every one of the KVH head steps DMAs the whole page (see in_specs)
    kv_bytes = 2 * B * L * KVH * D * esize * KVH
    return pl.pallas_call(
        functools.partial(_chunk_kernel, bs=bs, chunk=chunk,
                          n_pages=nbs, kv_dtype=kv_dtype,
                          mask_block=mask_block),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, KVH, RT, D), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"))
        if not interpret else None,
        cost_estimate=pl.CostEstimate(
            flops=4 * B * KVH * RT * D * L,
            bytes_accessed=kv_bytes + scale_bytes,
            transcendentals=B * KVH * RT * L),
        interpret=interpret,
        name=KERNEL_NAME,
    )(block_table, positions, *operands)


def _xla_chunked(q_g, k_pool, v_pool, block_table, positions, chunk,
                 k_scale=None, v_scale=None, kv_dtype=None, mask_block=1):
    """Same grouped-query chunk attention in plain XLA: q_g is the
    ROTATED and pre-scaled [B, KVH, RT, D] f32 query (scale folded in,
    exactly as the caller hands the kernel)."""
    B, KVH, RT, D = q_g.shape
    bs = k_pool.shape[1]
    nbs = block_table.shape[1]
    L = nbs * bs
    if kv_dtype is not None:
        # same decode_codes * per-row-scale multiply as the kernel's
        # DMA boundary, just on the gathered [B,nbs,bs,KVH,D] copy
        kb = decode_codes(k_pool[block_table], kv_dtype) * \
            k_scale[block_table][..., None, None]
        vb = decode_codes(v_pool[block_table], kv_dtype) * \
            v_scale[block_table][..., None, None]
    else:
        kb = k_pool[block_table].astype(jnp.float32)    # [B,nbs,bs,KVH,D]
        vb = v_pool[block_table].astype(jnp.float32)
    kb = kb.reshape(B, L, KVH, D)
    vb = vb.reshape(B, L, KVH, D)
    scores = jnp.einsum("bkrd,blkd->bkrl", q_g, kb,
                        preferred_element_type=jnp.float32)
    k_pos = jnp.arange(L)
    q_pos = positions[:, None] + jnp.arange(RT) % chunk  # [B, RT]
    valid = k_pos[None, None, None, :] <= \
        _visible_upto(q_pos, mask_block)[:, None, :, None]
    scores = jnp.where(valid, scores, NEG_INF)
    m = jnp.max(scores, axis=-1, keepdims=True)
    pexp = jnp.exp(scores - m)
    l = jnp.sum(pexp, axis=-1, keepdims=True)
    acc = jnp.einsum("bkrl,blkd->bkrd", pexp, vb,
                     preferred_element_type=jnp.float32)
    return acc / jnp.maximum(l, 1e-30)


def fused_chunked_attention(q, k_pool, v_pool, block_table, positions,
                            *, use_pallas=None, interpret=None,
                            k_scale=None, v_scale=None,
                            kv_cache_dtype=None, mask_block=1):
    """Paged attention for one prefill chunk, fused end to end.

    q: [B, T, H, D] ROTATED queries for the chunk; k_pool/v_pool:
    [nb, bs, KVH, D] block pools ALREADY holding the chunk's scattered
    k/v; block_table: [B, max_blocks] int32; positions: [B] int32
    per-sequence chunk-start frontiers (query t of sequence b sits at
    ``positions[b] + t``).  Returns the attention context [B, T, H, D]
    in q's dtype — the drop-in replacement for models/llama.py's
    ``_paged_attn`` gather path (identical causal masking, so padded
    chunk tails produce the same discarded garbage rows).

    Quantized pools (``kv_cache_dtype`` of ``"int8"``/``"fp8"``) hand
    in int8 code pools plus per-row ``k_scale``/``v_scale`` [nb, bs]
    f32 sidecars; dequant happens at the kernel's block-DMA boundary
    (and identically in the XLA fallback).  The caller has already
    scatter-quantized the chunk's k/v into the pools.

    ``mask_block`` > 1 makes the mask BLOCK-causal: a query sees the
    keys up to the end of its own block of ``mask_block`` positions
    (a model that denoises a block of positions together), so the chunk
    must end on a block boundary; 1 is the causal mask.

    On TPU the gather + mask + softmax + context is one Pallas kernel
    with an online softmax; elsewhere the numerically-identical XLA
    lowering runs instead.
    """
    from ..core.flags import flag
    from .fusion import pallas_interpret_forced

    B, T, H, D = q.shape
    KVH = k_pool.shape[2]
    rep = H // KVH
    positions = jnp.asarray(positions, jnp.int32)
    scale = 1.0 / math.sqrt(D)

    if use_pallas is None:
        if pallas_interpret_forced():
            use_pallas, interpret = True, True
        else:
            use_pallas = bool(flag("use_pallas_kernels")) and \
                jax.default_backend() == "tpu"
    if interpret is None:
        interpret = jax.default_backend() != "tpu"

    # GQA grouping: head h = kvh * rep + r, so the grouped row index is
    # r * T + t and every row of group kvh reads KV head kvh
    q_g = q.reshape(B, T, KVH, rep, D).transpose(0, 2, 3, 1, 4) \
        .reshape(B, KVH, rep * T, D).astype(jnp.float32) * scale
    if use_pallas:
        out = _pallas_chunked(q_g, k_pool, v_pool, block_table,
                              positions, T, interpret,
                              k_scale=k_scale, v_scale=v_scale,
                              kv_dtype=kv_cache_dtype,
                              mask_block=mask_block)
    else:
        out = _xla_chunked(q_g, k_pool, v_pool, block_table, positions,
                           T, k_scale=k_scale, v_scale=v_scale,
                           kv_dtype=kv_cache_dtype, mask_block=mask_block)
    return out.reshape(B, KVH, rep, T, D).transpose(0, 3, 1, 2, 4) \
        .reshape(B, T, H, D).astype(q.dtype)


# ---------------------------------------------------------------------------
# cost annotation (xray/shardplan price the pallas_call through this)
# ---------------------------------------------------------------------------

def _chunked_prefill_cost(in_avals, out_avals):
    # operand order fixed by _pallas_chunked:
    # (block_table, positions, q_g, k_pool, v_pool[, k_scale, v_scale])
    bt_shape = in_avals[0][0]
    q_shape, q_dtype = in_avals[2][0], in_avals[2][1]
    pool_shape, pool_dtype = in_avals[3][0], in_avals[3][1]
    B, nbs = int(bt_shape[0]), int(bt_shape[1])
    KVH, RT, D = int(q_shape[1]), int(q_shape[2]), int(q_shape[3])
    bs = int(pool_shape[1])
    L = nbs * bs
    flops = 4.0 * B * KVH * RT * D * L                  # qk^T + pv MACs
    trans = float(B * KVH * RT * L)                     # exp per score
    esize = np.dtype(pool_dtype).itemsize
    in_bytes = sum(
        float(np.prod(shape, dtype=np.int64)) * np.dtype(dt).itemsize
        for shape, dt in in_avals[:3])                  # table/pos/q
    # the pools are read THROUGH the block table: B*L rows each, not
    # the whole pool allocation (esize already reflects int8 when the
    # pool is quantized) — but once per kv-head grid step, since a page
    # rides in with all its heads; per-row f32 scale sidecars ride
    # along per kv-head grid step when present
    kv_bytes = 2.0 * B * L * KVH * D * esize * KVH
    if len(in_avals) > 5:
        kv_bytes += 2.0 * B * KVH * L * \
            np.dtype(in_avals[5][1]).itemsize
    out_bytes = sum(
        float(np.prod(shape, dtype=np.int64)) * np.dtype(dt).itemsize
        for shape, dt in out_avals)
    return KernelCost(flops=flops, bytes_accessed=in_bytes + kv_bytes
                      + out_bytes, transcendentals=trans,
                      dtype=str(q_dtype))


register_kernel_cost(
    KERNEL_NAME, _chunked_prefill_cost,
    sample_in=[((2, 4), "int32"), ((2,), "int32"),
               ((2, 2, 8, 16), "float32"), ((8, 4, 2, 16), "float32"),
               ((8, 4, 2, 16), "float32")],
    sample_out=[((2, 2, 8, 16), "float32")])
