"""Fused chunked-prefill attention kernel (Pallas) with an XLA fallback.

This is the first kernel MINED rather than hand-picked: on the fused
prefill trace, analysis/fusionminer ranks the chunked-prefill attention
inner loop as the #1 remaining candidate — the gathered [B, L, KVH, D]
context copy, the [B, H, T, L] score/probability tensors and the
repeat-to-H KV expansion all round-trip HBM between the two attention
matmuls, while only the projection epilogues around them fuse.

The kernel attends one query CHUNK (T tokens per sequence, already
RoPE-rotated and scattered into the pools by the caller) over each
sequence's paged KV context in one pass.  The walk follows the chunk's
own context, not the table's width: sequence ``b`` has ``ceil((
positions[b] + T) / bs)`` live pages (no query of the chunk sees a key
past ``positions[b] + T - 1``, under the causal and the block-causal
mask alike), and no table entry and no page past them is read.  It is
the decode kernel's walk (``paged_attention._PageWalk``): the pools
stay in HBM, the block table and the chunk starts ride in as
scalar-prefetch operands, and a COMPUTE BLOCK of ``G = max(1, 128 //
bs)`` pages at a time is copied into a double-buffered VMEM scratch,
one online (flash) softmax update a compute block with the next
block's copies in flight under it.  Compute blocks that end before
``positions[b]`` are wholly visible and skip the mask's compare.

GQA never materializes the repeat: queries are grouped [B, KVH, rep*T,
D] so every q row of a group shares the group's keys.  A grid cell is
one sequence and one TILE of those rows with all their KV heads (the
running max/sum and accumulator of a tile stay in VMEM), so a page is
read once a row tile.

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
from .paged_attention import (_PageWalk, _online_softmax, _pool_streams,
                              _split_walk_refs)

KERNEL_NAME = "fused_chunked_prefill"
NEG_INF = -1e30


def _visible_upto(q_pos, mask_block):
    """The last key position a query at ``q_pos`` sees: itself under the
    causal mask (``mask_block`` 1), the END of its own block of
    ``mask_block`` positions under a block-causal one (blocks aligned at
    multiples of ``mask_block``)."""
    if mask_block == 1:
        return q_pos
    return (q_pos // mask_block + 1) * mask_block - 1


def _row_tile(RT, KVH):
    """Query rows of one grid cell: all ``RT`` where a ``[KVH, rows,
    128]`` f32 tile (the scores of one compute block; q, the accumulator
    and the output are as large) stays within 1 MiB, else the largest
    divisor of ``RT`` that does and is a whole number of sublanes."""
    cap = max(8, 2048 // KVH)
    if RT <= cap:
        return RT
    fits = [r for r in range(8, cap + 1, 8) if RT % r == 0]
    return fits[-1] if fits else RT


def _chunk_kernel(bt_ref, pos_ref, q_ref, *rest, chunk, kv_dtype=None,
                  mask_block=1, window=None):
    hbm_refs, (o_ref, acc_ref, m_ref, l_ref), bufs, sems = \
        _split_walk_refs(rest, kv_dtype)
    b = pl.program_id(0)
    rows = acc_ref.shape[1]
    bs = bufs[0].shape[2]

    # the chunk's context: the pages that hold a key some query of the
    # chunk may see (k_pos <= pos + chunk - 1), clamped to the table.
    # The walk never reads a table entry, or fetches a page, past them.
    pos = pos_ref[b]
    live = jnp.minimum((pos + chunk + bs - 1) // bs, bt_ref.shape[1])
    lo = 0
    if window is not None:
        # a window layer: query ``q`` sees the keys at ``k_pos > q -
        # window``, so nothing before the first key of the chunk's FIRST
        # query is seen by any.  The walk starts at that key's page; no
        # table entry before it is read
        lo = jnp.maximum(pos - window + 1, 0) // bs
    walk = _PageWalk(bt_ref, b, lo, live, hbm_refs, bufs, sems, kv_dtype)
    K = walk.G * bs                                     # keys a block

    acc_ref[:] = jnp.zeros_like(acc_ref)
    m_ref[:] = jnp.full_like(m_ref, NEG_INF)
    l_ref[:] = jnp.zeros_like(l_ref)

    # q rows are [rep * chunk, D] with row r * chunk + t, tiled over the
    # grid's second axis; scale is already folded into q by the caller,
    # so the score math is a bare dot against the block's keys
    row = pl.program_id(1) * rows + \
        jax.lax.broadcasted_iota(jnp.int32, (1, rows, K), 1)
    visible = _visible_upto(pos + row % chunk, mask_block)
    key = jax.lax.broadcasted_iota(jnp.int32, (1, rows, K), 2)
    if window is not None:
        key = key + lo * bs
        hidden = pos + row % chunk - window     # the last key NOT seen

    walk.start()

    def compute_block(j, _, masked):
        slot = walk.arrive(j)
        scores = jax.lax.dot_general(
            q_ref[0], walk.keys(slot), (((2,), (2,)), ((0,), (0,))),
            preferred_element_type=jnp.float32)         # [KVH, rows, K]
        if masked:
            # causal chunk mask: key position vs this row's query
            # position.  Block 0 always holds key position 0, so m stays
            # anchored to a real score and masked lanes underflow to
            # exp(-inf); a dead page's keys lie past every query.
            seen = j * K + key <= visible
            if window is not None:
                # (a row whose window starts past this block finds no
                # key in it: the next block's real scores rescale what
                # the all-masked update left to nothing)
                seen = seen & (j * K + key > hidden)
            scores = jnp.where(seen, scores, NEG_INF)
        m_ref[:], l_ref[:], acc_ref[:] = _online_softmax(
            scores, walk.values(slot), m_ref[:], l_ref[:], acc_ref[:])

    # compute blocks that end before ``pos`` are seen whole by every query
    # of the chunk: only the ones that reach it pay for the compare
    if window is None:
        clear = jnp.minimum(pos // K, walk.num_blocks)
    else:
        # ... and that begin inside the LAST query's window: the blocks
        # before those pay for the window's compare
        clear = jnp.clip((pos - lo * bs) // K, 0, walk.num_blocks)
        head = jnp.clip((pos + chunk - window - lo * bs + K - 1) // K,
                        0, clear)
        jax.lax.fori_loop(0, head,
                          functools.partial(compute_block, masked=True),
                          None)
    jax.lax.fori_loop(0 if window is None else head, clear,
                      functools.partial(compute_block, masked=False), None)
    jax.lax.fori_loop(clear, walk.num_blocks,
                      functools.partial(compute_block, masked=True), None)
    o_ref[0] = (acc_ref[:] /
                jnp.maximum(l_ref[:], 1e-30)).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("chunk", "interpret",
                                             "kv_dtype", "mask_block",
                                             "window"))
def _pallas_chunked(q_g, k_pool, v_pool, block_table, positions, chunk,
                    interpret, k_scale=None, v_scale=None, kv_dtype=None,
                    mask_block=1, window=None):
    """q_g: grouped, ROTATED, pre-scaled [B, KVH, RT, D] f32 queries;
    returns the normalized context [B, KVH, RT, D] f32.

    Jitted so that a model's layers share ONE trace and ONE lowering of
    the kernel (see ``paged_attention._pallas_partials``)."""
    B, KVH, RT, D = q_g.shape
    bs = k_pool.shape[1]
    nbs = block_table.shape[1]
    rows = _row_tile(RT, KVH)
    pool_specs, pool_operands, pool_scratch = _pool_streams(
        k_pool, v_pool, k_scale, v_scale, kv_dtype)

    def q_tile(b, t, bt, pos):
        return (b, 0, t, 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(B, RT // rows),
        in_specs=[pl.BlockSpec((1, KVH, rows, D), q_tile), *pool_specs],
        out_specs=pl.BlockSpec((1, KVH, rows, D), q_tile),
        scratch_shapes=[
            pltpu.VMEM((KVH, rows, D), jnp.float32),
            pltpu.VMEM((KVH, rows, 1), jnp.float32),
            pltpu.VMEM((KVH, rows, 1), jnp.float32),
            *pool_scratch,
        ],
    )
    # priced for the whole table, the worst case: shapes cannot see the
    # chunk starts that bound the walk
    L = nbs * bs
    esize = jnp.dtype(k_pool.dtype).itemsize
    # every row tile walks the pages again; a quantized pool also
    # streams one f32 scale per (pool, token) row
    kv_bytes = 2 * B * L * KVH * D * esize * (RT // rows)
    scale_bytes = 2 * B * L * 4 * (RT // rows) if kv_dtype is not None \
        else 0
    return pl.pallas_call(
        functools.partial(_chunk_kernel, chunk=chunk, kv_dtype=kv_dtype,
                          mask_block=mask_block, window=window),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, KVH, RT, D), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel"))
        if not interpret else None,
        cost_estimate=pl.CostEstimate(
            flops=4 * B * KVH * RT * D * L,
            bytes_accessed=kv_bytes + scale_bytes,
            transcendentals=B * KVH * RT * L),
        interpret=interpret,
        name=KERNEL_NAME,
    )(block_table, positions, q_g, *pool_operands)


def _xla_chunked(q_g, k_pool, v_pool, block_table, positions, chunk,
                 k_scale=None, v_scale=None, kv_dtype=None, mask_block=1,
                 window=None):
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
    if window is not None:
        valid = valid & (k_pos[None, None, None, :] >
                         (q_pos - window)[:, None, :, None])
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
                            kv_cache_dtype=None, mask_block=1,
                            window=None):
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

    ``window`` (static) makes the layer a WINDOW layer under the causal
    mask: query ``q`` sees the keys at ``q - window < k_pos <= q``, the
    walk starts at the page of the chunk's first query's first key, and
    no table entry before that page is read.

    On TPU the gather + mask + softmax + context is one Pallas kernel
    with an online softmax; elsewhere the numerically-identical XLA
    lowering runs instead.
    """
    from .fusion import pallas_lowering

    B, T, H, D = q.shape
    KVH = k_pool.shape[2]
    rep = H // KVH
    positions = jnp.asarray(positions, jnp.int32)
    scale = 1.0 / math.sqrt(D)

    use_pallas, interpret = pallas_lowering(use_pallas, interpret)
    if window is not None and mask_block != 1:
        raise ValueError("a window layer attends under the causal mask "
                         "(mask_block 1)")

    # GQA grouping: head h = kvh * rep + r, so the grouped row index is
    # r * T + t and every row of group kvh reads KV head kvh
    q_g = q.reshape(B, T, KVH, rep, D).transpose(0, 2, 3, 1, 4) \
        .reshape(B, KVH, rep * T, D).astype(jnp.float32) * scale
    if use_pallas:
        out = _pallas_chunked(q_g, k_pool, v_pool, block_table,
                              positions, T, interpret,
                              k_scale=k_scale, v_scale=v_scale,
                              kv_dtype=kv_cache_dtype,
                              mask_block=mask_block, window=window)
    else:
        out = _xla_chunked(q_g, k_pool, v_pool, block_table, positions,
                           T, k_scale=k_scale, v_scale=v_scale,
                           kv_dtype=kv_cache_dtype, mask_block=mask_block,
                           window=window)
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
    # the pools are read THROUGH the block table: at most B*L rows each,
    # not the whole pool allocation (esize already reflects int8 when the
    # pool is quantized), once per tile of query rows.  The kernel walks
    # only the chunk's live pages, but shapes cannot see where a chunk
    # starts: this is the worst case, the table's last chunk.  Per-row
    # f32 scale sidecars ride along when present
    tiles = RT // _row_tile(RT, KVH)
    kv_bytes = 2.0 * B * L * KVH * D * esize * tiles
    if len(in_avals) > 5:
        kv_bytes += 2.0 * B * L * tiles * \
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
