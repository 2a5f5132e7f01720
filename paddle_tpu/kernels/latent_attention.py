"""Absorbed latent (MLA) attention over paged LATENT pages: a decode
walk and a chunk kernel (Pallas), each with its XLA lowering.

A latent layer caches ONE array a position and no heads: ``e(j) =
[c_kv(j) | k_rope(j)]`` (the normed compressed key/value of
``value_lanes`` numbers, then the one rotary key every head shares),
laid out ``[num_blocks, block_size, W]`` with the entry's lanes padded
with zeros to ``W``, a whole number of 128-lane registers
(``latent_pool_lanes``).  In the ABSORBED form every head's query is
carried into the latent space before the kernel (``q~_h = [q_nope_h .
W_UK_h^T | q_rope_h]``, rotated and scaled by the caller, padded with
zeros to ``W``), so that

    score_h(i, j) = q~_h(i) . e(j)          o~_h(i) = sum_j p . e(j)[:value_lanes]

and the value is the key's first ``value_lanes`` lanes **of the same
VMEM buffer**: a page is fetched ONCE, and per-head keys or values of
the context never exist.  ``W_UV`` and the output projection follow in
XLA.

Both kernels walk the live context only, through the block table, with
``paged_attention._PageWalk``'s double-buffered copies (one stream here:
``_LatentWalk``): the decode kernel a split of the table's pages a grid
cell, split-K and ``_combine_splits`` as ``fused_paged_decode``; the
chunk kernel the pages at or before the chunk's end, causal inside the
chunk, a tile of the chunk's ``heads * chunk`` query rows a grid cell.

Products take their operands in the POOL's type with float32
accumulation: a bfloat16 pool gives the MXU bfloat16 queries, pages and
softmax weights (what a bfloat16 deployment computes: one pass a
product); a float32 pool computes in float32.  The running max and sum
are float32 always.

Numerics contract: ``_xla_decode_partials`` / ``_xla_chunk`` are the same
mathematics in plain XLA (identical masking and operand types, the full
softmax in place of the online rescale).  On the CPU the fused path
lowers through them, so tier-1 covers the served math with no
``pallas_call`` in the program.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .chunked_prefill import _row_tile
from .costs import KernelCost, register_kernel_cost
from .paged_attention import (NEG_INF, _LANES, _PageWalk, _combine_splits,
                              _default_splits)

DECODE_KERNEL_NAME = "fused_latent_decode"
CHUNK_KERNEL_NAME = "fused_latent_chunk"
# bf16 packs 16 rows a register: the decode kernel's query rows (the
# heads) are padded with zeros to a whole number of them
_ROW_PAD = 16
# the chunk kernel's row tile, as a divisor of ``_row_tile``'s 2,048 rows
_TILE_HEADS = 2


def latent_pool_lanes(entry_lanes: int) -> int:
    """Lanes a cached position takes in the pool: ``entry_lanes`` up to
    the next multiple of 128.  (The device stores an array's minor
    dimension in whole 128-lane tiles whatever its logical width, so a
    ``[.., 576]`` pool costs 640 lanes too, and Mosaic slices it only at
    tile boundaries; padding in the open makes the bytes the pool
    reports the bytes it takes.)"""
    return -(-int(entry_lanes) // _LANES) * _LANES


class _LatentWalk(_PageWalk):
    """``_PageWalk`` over ONE stream of pages ``[bs, W]``: a compute
    block's rows are its keys and, their first lanes, its values."""

    def __init__(self, bt_ref, b, lo, hi, hbm_ref, buf, sems):
        self.bt_ref, self.b, self.lo, self.hi = bt_ref, b, lo, hi
        self.streams = ((hbm_ref, buf),)
        self.sems, self.kv_dtype = sems, None
        self.buf = buf
        self.G, self.bs = buf.shape[1], buf.shape[2]
        self.num_blocks = jnp.maximum(hi - lo + self.G - 1, 0) // self.G

    def _fetch(self, j, slot):
        buf = self.buf

        def start(g, _):
            block = self.bt_ref[self.b, self.lo + j * self.G + g]
            for copy in self._page_copies(slot, g, block):
                copy.start()

        # a dead page of the last compute block: its keys are masked by
        # the caller, and as values zeros keep 0 * stale from being a NaN
        def zero(g, _):
            buf[slot, g] = jnp.zeros(buf.shape[2:], buf.dtype)

        n = self._live_in_block(j)
        jax.lax.fori_loop(0, n, start, None)
        jax.lax.fori_loop(n, self.G, zero, None)

    def rows(self, slot):
        """One compute block as ``[G * bs, W]`` in the pool's type."""
        return self.buf[slot].reshape(self.G * self.bs, self.buf.shape[3])


def _page_stream(pool):
    """``(in_spec, scratch_shapes)`` of the one stream: the pool stays in
    HBM; a ``[2, G, bs, W]`` VMEM buffer and a DMA semaphore a slot."""
    bs, W = pool.shape[1:]
    G = max(1, _LANES // bs)
    return (pl.BlockSpec(memory_space=pl.ANY),
            [pltpu.VMEM((2, G, bs, W), pool.dtype),
             pltpu.SemaphoreType.DMA((1, 2))])


def _precision(dtype):
    """Stated on every product, so that a process-wide default (the
    tests pin ``highest``) cannot ask the MXU for a bf16 product in
    several passes, which Mosaic refuses: one pass for bf16 operands,
    full precision for float32 ones."""
    return jax.lax.Precision.HIGHEST if dtype == jnp.float32 \
        else jax.lax.Precision.DEFAULT


def _softmax_update(scores, rows, value_lanes, m, l, acc):
    """One online-softmax update: ``scores [R, K]`` float32 (masked keys
    at NEG_INF) against the block's ``rows [K, W]``, whose first
    ``value_lanes`` lanes are the values; the weights meet the values in
    the rows' type, the sums are float32."""
    m_new = jnp.maximum(m, jnp.max(scores, axis=-1, keepdims=True))
    alpha = jnp.exp(m - m_new)
    pexp = jnp.exp(scores - m_new)
    acc = acc * alpha + jnp.dot(pexp.astype(rows.dtype),
                                rows[:, :value_lanes],
                                precision=_precision(rows.dtype),
                                preferred_element_type=jnp.float32)
    l = l * alpha + jnp.sum(pexp, axis=-1, keepdims=True)
    return m_new, l, acc


def _scores(q, rows):
    """``q [R, W] . rows [K, W]^T`` in float32."""
    return jax.lax.dot_general(q, rows, (((1,), (1,)), ((), ())),
                               precision=_precision(rows.dtype),
                               preferred_element_type=jnp.float32)


# ---------------------------------------------------------------------------
# decode: split-K partials over the live pages
# ---------------------------------------------------------------------------

def _decode_kernel(bt_ref, pos_ref, q_ref, pool_ref, o_ref, m_out_ref,
                   l_out_ref, buf, sems, *, bs, pages_per_split,
                   value_lanes):
    b = pl.program_id(0)
    s = pl.program_id(1)
    # this cell's pages: its split of the table, cut at the sequence's
    # live pages (keys at k_pos <= pos, the new token included)
    pos = pos_ref[b]
    live = jnp.minimum(pos // bs + 1, bt_ref.shape[1])
    lo = s * pages_per_split
    hi = jnp.minimum(lo + pages_per_split, live)
    walk = _LatentWalk(bt_ref, b, lo, hi, pool_ref, buf, sems)
    G = walk.G
    key_limit = jnp.minimum(pos + 1, (lo + pages_per_split) * bs)
    q = q_ref[0]                                        # [R, W]
    walk.start()

    def compute_block(j, carry):
        rows = walk.rows(walk.arrive(j))
        scores = _scores(q, rows)                       # [R, G * bs]
        k_pos = (lo + j * G) * bs + jax.lax.broadcasted_iota(
            jnp.int32, scores.shape, 1)
        scores = jnp.where(k_pos < key_limit, scores, NEG_INF)
        return _softmax_update(scores, rows, value_lanes, *carry)

    R = q.shape[0]
    # a split with no live page emits (NEG_INF, 0, 0): the combine
    # weighs it to zero
    m, l, acc = jax.lax.fori_loop(
        0, walk.num_blocks, compute_block,
        (jnp.full((R, 1), NEG_INF, jnp.float32),
         jnp.zeros((R, 1), jnp.float32),
         jnp.zeros((R, value_lanes), jnp.float32)))
    o_ref[0, 0] = acc
    m_out_ref[0, 0] = jnp.broadcast_to(m, m_out_ref.shape[2:])
    l_out_ref[0, 0] = jnp.broadcast_to(l, l_out_ref.shape[2:])


@functools.partial(jax.jit, static_argnames=("num_splits", "value_lanes",
                                             "interpret"))
def _pallas_decode_partials(q, pool, block_table, positions, num_splits,
                            value_lanes, interpret):
    """``q [B, R, W]`` (absorbed, rotated, scaled, the pool's type);
    returns ``(acc [B, S, R, value_lanes], m [B, S, R], l [B, S, R])`` in
    float32.  Jitted so that a model's layers share one trace of the
    kernel (``paged_attention._pallas_partials``)."""
    B, R, W = q.shape
    bs = pool.shape[1]
    nbs = block_table.shape[1]
    pool_spec, scratch = _page_stream(pool)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(B, num_splits),
        in_specs=[pl.BlockSpec((1, R, W), lambda b, s, bt, pos: (b, 0, 0)),
                  pool_spec],
        out_specs=[
            pl.BlockSpec((1, 1, R, value_lanes),
                         lambda b, s, bt, pos: (b, s, 0, 0)),
            pl.BlockSpec((1, 1, R, _LANES),
                         lambda b, s, bt, pos: (b, s, 0, 0)),
            pl.BlockSpec((1, 1, R, _LANES),
                         lambda b, s, bt, pos: (b, s, 0, 0))],
        scratch_shapes=scratch)
    # priced for the whole table, the worst case: shapes cannot see the
    # lengths that bound the walk
    L = nbs * bs
    acc, m_b, l_b = pl.pallas_call(
        functools.partial(_decode_kernel, bs=bs,
                          pages_per_split=nbs // num_splits,
                          value_lanes=value_lanes),
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((B, num_splits, R, value_lanes),
                                 jnp.float32),
            jax.ShapeDtypeStruct((B, num_splits, R, _LANES), jnp.float32),
            jax.ShapeDtypeStruct((B, num_splits, R, _LANES), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel"))
        if not interpret else None,
        cost_estimate=pl.CostEstimate(
            flops=2 * B * R * L * (W + value_lanes),
            bytes_accessed=B * L * W * jnp.dtype(pool.dtype).itemsize,
            transcendentals=B * R * L),
        interpret=interpret,
        name=DECODE_KERNEL_NAME,
    )(block_table, positions, q, pool)
    return acc, m_b[..., 0], l_b[..., 0]


def _xla_decode_partials(q, pool, block_table, positions, num_splits,
                         value_lanes):
    """The same split-K partials in plain XLA."""
    B = q.shape[0]
    bs = pool.shape[1]
    nbs = block_table.shape[1]
    Lp = (nbs // num_splits) * bs                       # keys per split
    pages = pool[block_table].reshape(B, num_splits, Lp, pool.shape[2])
    scores = jnp.einsum("brw,bslw->bsrl", q, pages,
                        preferred_element_type=jnp.float32)
    k_pos = jnp.arange(nbs * bs).reshape(num_splits, Lp)
    seen = k_pos[None, :, None, :] <= positions[:, None, None, None]
    scores = jnp.where(seen, scores, NEG_INF)
    m = jnp.max(scores, axis=-1)                        # [B, S, R]
    pexp = jnp.exp(scores - m[..., None])
    l = jnp.sum(pexp, axis=-1)
    acc = jnp.einsum("bsrl,bslv->bsrv", pexp.astype(pool.dtype),
                     pages[..., :value_lanes],
                     preferred_element_type=jnp.float32)
    return acc, m, l


def fused_latent_decode(q, entry, pool, block_table, positions, *,
                        value_lanes, num_splits=None, use_pallas=None,
                        interpret=None):
    """One decode step of absorbed latent attention.

    ``q [B, H, W]``: every head's absorbed query, rotated and scaled,
    zeros past the entry's lanes; ``entry [B, W]``: the new token's
    cache entry (``[c_kv | rotated k_rope | zeros]``); ``pool [nb, bs,
    W]``; ``block_table [B, max_blocks]``; ``positions [B]`` the write
    frontiers.  The entry is written at ``positions[b]`` and sequence
    ``b`` attends to the keys at ``k_pos <= positions[b]`` through its
    table, reading each live page once.  Returns ``(o~ [B, H,
    value_lanes] float32, new_pool)``."""
    from .fusion import pallas_lowering

    B, H, W = q.shape
    nbs, bs = block_table.shape[1], pool.shape[1]
    positions = jnp.asarray(positions, jnp.int32)
    use_pallas, interpret = pallas_lowering(use_pallas, interpret)
    if num_splits is None or nbs % num_splits:
        num_splits = _default_splits(nbs)
    with jax.named_scope("kv_write"):
        col = jnp.minimum(positions // bs, nbs - 1)
        idx = block_table[jnp.arange(B), col] * bs + positions % bs
        pool = pool.reshape(-1, W).at[idx].set(
            entry.astype(pool.dtype)).reshape(pool.shape)
    q = q.astype(pool.dtype)
    if use_pallas:
        R = -(-H // _ROW_PAD) * _ROW_PAD
        acc, m, l = _pallas_decode_partials(
            jnp.pad(q, ((0, 0), (0, R - H), (0, 0))), pool, block_table,
            positions, num_splits, value_lanes, interpret)
        acc, m, l = acc[:, :, :H], m[:, :, :H], l[:, :, :H]
    else:
        acc, m, l = _xla_decode_partials(q, pool, block_table, positions,
                                         num_splits, value_lanes)
    # (``_combine_splits`` takes a KV-head axis: one here)
    out = _combine_splits(acc[:, :, None], m[:, :, None], l[:, :, None])
    return out[:, 0], pool


# ---------------------------------------------------------------------------
# chunk: a tile of the chunk's query rows against the chunk's context
# ---------------------------------------------------------------------------

def _chunk_kernel(bt_ref, pos_ref, q_ref, pool_ref, o_ref, acc_ref, m_ref,
                  l_ref, buf, sems, *, chunk, value_lanes):
    b = pl.program_id(0)
    rows = acc_ref.shape[0]
    bs = buf.shape[2]
    # the chunk's context: the pages that hold a key some query of the
    # chunk may see (k_pos <= pos + chunk - 1), clamped to the table
    pos = pos_ref[b]
    live = jnp.minimum((pos + chunk + bs - 1) // bs, bt_ref.shape[1])
    walk = _LatentWalk(bt_ref, b, 0, live, pool_ref, buf, sems)
    K = walk.G * bs                                     # keys a block

    acc_ref[:] = jnp.zeros_like(acc_ref)
    m_ref[:] = jnp.full_like(m_ref, NEG_INF)
    l_ref[:] = jnp.zeros_like(l_ref)
    # q rows are ``h * chunk + t``, tiled over the grid's second axis
    row = pl.program_id(1) * rows + \
        jax.lax.broadcasted_iota(jnp.int32, (rows, K), 0)
    visible = pos + row % chunk
    key = jax.lax.broadcasted_iota(jnp.int32, (rows, K), 1)
    walk.start()

    def compute_block(j, _, masked):
        block = walk.rows(walk.arrive(j))
        scores = _scores(q_ref[0], block)               # [rows, K]
        if masked:
            # block 0 always holds key 0, so m stays anchored to a real
            # score; a dead page's keys lie past every query
            scores = jnp.where(j * K + key <= visible, scores, NEG_INF)
        m_ref[:], l_ref[:], acc_ref[:] = _softmax_update(
            scores, block, value_lanes, m_ref[:], l_ref[:], acc_ref[:])

    # compute blocks that end before ``pos`` are seen whole by every
    # query of the chunk: only the ones that reach it pay for the compare
    clear = jnp.minimum(pos // K, walk.num_blocks)
    jax.lax.fori_loop(0, clear,
                      functools.partial(compute_block, masked=False), None)
    jax.lax.fori_loop(clear, walk.num_blocks,
                      functools.partial(compute_block, masked=True), None)
    o_ref[0] = acc_ref[:] / jnp.maximum(l_ref[:], 1e-30)


def _chunk_row_tile(RT):
    """Query rows of one grid cell: what ``_row_tile`` gives a walk of
    ``_TILE_HEADS`` KV heads, since a row here is 640 lanes of query and
    512 of accumulator where a row there is 128 of each."""
    return _row_tile(RT, _TILE_HEADS)


@functools.partial(jax.jit, static_argnames=("chunk", "value_lanes",
                                             "interpret"))
def _pallas_chunk(q, pool, block_table, positions, chunk, value_lanes,
                  interpret):
    """``q [B, RT, W]`` (rows ``h * chunk + t``; absorbed, rotated,
    scaled, the pool's type); returns the normalised ``o~ [B, RT,
    value_lanes]`` float32."""
    B, RT, W = q.shape
    bs = pool.shape[1]
    nbs = block_table.shape[1]
    rows = _chunk_row_tile(RT)
    pool_spec, pool_scratch = _page_stream(pool)

    def q_tile(b, t, bt, pos):
        return (b, t, 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(B, RT // rows),
        in_specs=[pl.BlockSpec((1, rows, W), q_tile), pool_spec],
        out_specs=pl.BlockSpec((1, rows, value_lanes), q_tile),
        scratch_shapes=[pltpu.VMEM((rows, value_lanes), jnp.float32),
                        pltpu.VMEM((rows, 1), jnp.float32),
                        pltpu.VMEM((rows, 1), jnp.float32),
                        *pool_scratch])
    # priced for the whole table, the worst case; every row tile walks
    # the pages again
    L = nbs * bs
    return pl.pallas_call(
        functools.partial(_chunk_kernel, chunk=chunk,
                          value_lanes=value_lanes),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, RT, value_lanes), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel"))
        if not interpret else None,
        cost_estimate=pl.CostEstimate(
            flops=2 * B * RT * L * (W + value_lanes),
            bytes_accessed=B * L * W * jnp.dtype(pool.dtype).itemsize
            * (RT // rows),
            transcendentals=B * RT * L),
        interpret=interpret,
        name=CHUNK_KERNEL_NAME,
    )(block_table, positions, q, pool)


def _xla_chunk(q, pool, block_table, positions, chunk, value_lanes):
    """The same chunk attention in plain XLA."""
    B, RT, W = q.shape
    L = block_table.shape[1] * pool.shape[1]
    pages = pool[block_table].reshape(B, L, W)
    scores = jnp.einsum("brw,blw->brl", q, pages,
                        preferred_element_type=jnp.float32)
    q_pos = positions[:, None] + jnp.arange(RT) % chunk  # [B, RT]
    seen = jnp.arange(L)[None, None, :] <= q_pos[:, :, None]
    scores = jnp.where(seen, scores, NEG_INF)
    m = jnp.max(scores, axis=-1, keepdims=True)
    pexp = jnp.exp(scores - m)
    l = jnp.sum(pexp, axis=-1, keepdims=True)
    acc = jnp.einsum("brl,blv->brv", pexp.astype(pool.dtype),
                     pages[..., :value_lanes],
                     preferred_element_type=jnp.float32)
    return acc / jnp.maximum(l, 1e-30)


def fused_latent_chunk(q, pool, block_table, positions, *, value_lanes,
                       use_pallas=None, interpret=None):
    """Absorbed latent attention of one prefill chunk.

    ``q [B, T, H, W]``: the chunk's absorbed queries, rotated and
    scaled, zeros past the entry's lanes; ``pool [nb, bs, W]`` ALREADY
    holds the chunk's entries; ``positions [B]`` the chunk starts (query
    ``t`` of sequence ``b`` sits at ``positions[b] + t`` and sees the
    keys up to itself).  Returns ``o~ [B, T, H, value_lanes]``
    float32."""
    from .fusion import pallas_lowering

    B, T, H, W = q.shape
    positions = jnp.asarray(positions, jnp.int32)
    use_pallas, interpret = pallas_lowering(use_pallas, interpret)
    # every head reads the one stream: row ``h * T + t``
    rows = q.astype(pool.dtype).transpose(0, 2, 1, 3).reshape(B, H * T, W)
    if use_pallas:
        out = _pallas_chunk(rows, pool, block_table, positions, T,
                            value_lanes, interpret)
    else:
        out = _xla_chunk(rows, pool, block_table, positions, T,
                         value_lanes)
    return out.reshape(B, H, T, value_lanes).transpose(0, 2, 1, 3)


# ---------------------------------------------------------------------------
# cost annotations (xray/shardplan price the pallas_calls through these)
# ---------------------------------------------------------------------------

def _avals_bytes(avals):
    return sum(float(np.prod(shape, dtype=np.int64)) * np.dtype(dt).itemsize
               for shape, dt in avals)


def _latent_cost(in_avals, out_avals, tiles):
    # operand order of both calls: (block_table, positions, q, pool)
    (bt_shape, _), _, (q_shape, q_dtype), (pool_shape, pool_dtype) = \
        in_avals[:4]
    B, nbs = int(bt_shape[0]), int(bt_shape[1])
    R, W = int(q_shape[1]), int(q_shape[2])
    L = nbs * int(pool_shape[1])
    value_lanes = int(out_avals[0][0][-1])
    # the pool is read THROUGH the block table (at most B * L rows, once
    # a tile of query rows): the worst case, every walk at the table's end
    page_bytes = float(B) * L * W * np.dtype(pool_dtype).itemsize * tiles(R)
    return KernelCost(
        flops=2.0 * B * R * L * (W + value_lanes),
        bytes_accessed=_avals_bytes(in_avals[:3]) + page_bytes
        + _avals_bytes(out_avals),
        transcendentals=float(B * R * L), dtype=str(q_dtype))


register_kernel_cost(
    DECODE_KERNEL_NAME,
    functools.partial(_latent_cost, tiles=lambda R: 1),
    sample_in=[((4, 8), "int32"), ((4,), "int32"),
               ((4, 16, 128), "float32"), ((32, 8, 128), "float32")],
    sample_out=[((4, 2, 16, 64), "float32"), ((4, 2, 16, 128), "float32"),
                ((4, 2, 16, 128), "float32")])
register_kernel_cost(
    CHUNK_KERNEL_NAME,
    functools.partial(_latent_cost,
                      tiles=lambda R: R // _chunk_row_tile(R)),
    sample_in=[((2, 4), "int32"), ((2,), "int32"),
               ((2, 16, 128), "float32"), ((8, 4, 128), "float32")],
    sample_out=[((2, 16, 64), "float32")])
