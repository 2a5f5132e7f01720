"""Fused paged-attention decode kernel (Pallas) with an XLA fallback.

The serving decode hot path was four separate HBM round trips per
layer: rotate q/k (RoPE), scatter the new k/v into the block pool,
gather every sequence's blocks back out, then run masked softmax
attention over the gathered copy.  This module fuses the gather + q
RoPE + attention into ONE Pallas kernel that reads the pages straight
from the pool — the gathered [B, L, H, D] context copy never exists in
HBM.

The walk follows the live context, not the table's width.  The pools
stay in HBM (``memory_space=pl.ANY``); the block table and the lengths
ride in as scalar-prefetch operands.  Sequence ``b`` has
``positions[b] // bs + 1`` live pages (keys at ``k_pos <=
positions[b]``, the new token included; an idle slot has one), and
nothing past them is read: not the table entry, not the page.  Pages
are fetched with ``pltpu.make_async_copy``, a COMPUTE BLOCK at a time
(``_decode_block_pages``: whole 128-key units of pages, about 1 MiB of
K and V) into a double-buffered VMEM scratch, and reach the MXU as they
are stored: a block's ``[G * bs, KVH, D]`` rows are ONE ``[G * bs *
KVH, D]`` operand in the pool's dtype, every query row against every
head's keys, its own head's kept by the mask; one online-softmax update
a block, the next block's copies in flight under it.

Split-K (``_decode_splits``): on a chip of one TensorCore each sequence
is ONE grid cell, the cells run in order, and a cell's last block has
the NEXT sequence's first block in flight under it.  On a chip of
several cores the table's pages are divided into ``_default_splits``
independent chunks whose (batch, split) cells the cores share.  A cell
emits an UNNORMALIZED partial (running max ``m``, exp-sum ``l``,
accumulator ``acc``), merged after by the log-sum-exp combine; a chunk
with no live page emits ``(NEG_INF, 0, 0)``, which it weighs to zero.

Numerics contract: ``_xla_partials`` + ``_combine_splits`` is the SAME
split-K math in plain XLA (identical masking, products in an unquantized
pool's dtype, f32 accumulation, the same combine); on CPU the fused path
lowers through it.  ``paged_decode_reference`` reproduces models/llama.py's
scatter/gather path for parity tests.  A quantized pool's block is
dequantized to f32 at the DMA boundary and read head by head.

Heads of other shapes, each a static choice that changes nothing at its
default: a value narrower than the key (the value width is V's own), a
key wider than one 128-lane register stored in whole registers (the
pool's lanes past the key width are zeros; the query is padded to match,
the scale is the key's own), a rotation of the first ``rotary`` lanes
only, and a SINK: a learned logit a head that joins the softmax's
denominator and weighs no value, counted once, in the split-K combine.
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
from .kv_quant import decode_codes, quantize_kv

KERNEL_NAME = "fused_paged_decode"
NEG_INF = -1e30
_LANES = 128


def _rotate_half(x, c, s):
    """Rotate-half RoPE, matching models/llama.py apply_rope: c/s carry
    the per-position cos/sin rows broadcast against x's last dim."""
    half = x.shape[-1] // 2
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s], axis=-1)


def _rotate_lanes(x, c, s, rotary):
    """Rotate-half RoPE on the first ``rotary`` lanes of ``x`` (pairs
    ``i, i + rotary / 2``; ``c``/``s`` are ``rotary / 2`` wide), the
    other lanes as they are."""
    return jnp.concatenate([_rotate_half(x[..., :rotary], c, s),
                            x[..., rotary:]], axis=-1)


def pad_lanes(x, lanes):
    """``x [.., D]`` with zeros up to ``lanes`` (``x`` itself where
    ``D`` is already that wide)."""
    if x.shape[-1] == lanes:
        return x
    return jnp.pad(x, [(0, 0)] * (x.ndim - 1) + [(0, lanes - x.shape[-1])])


def _scatter_token(pool, new, block_table, positions):
    """Write one token per sequence into its pool slot — the T == 1
    case of models/llama.py's ``_scatter`` (same index math, same
    column clamp)."""
    nb, bs = pool.shape[0], pool.shape[1]
    nbs = block_table.shape[1]
    rows = jnp.arange(block_table.shape[0])
    col = jnp.minimum(positions // bs, nbs - 1)
    idx = block_table[rows, col] * bs + positions % bs          # [B]
    flat = pool.reshape(nb * bs, pool.shape[2], pool.shape[3])
    flat = flat.at[idx].set(new.astype(pool.dtype))
    return flat.reshape(pool.shape)


def _scatter_token_quant(pool, scales, new, block_table, positions,
                         scheme):
    """Quantize-at-write T == 1 scatter (kernels/kv_quant): int8 codes
    into the pool row, the row's absmax scale into the [nb, bs] f32
    sidecar — same index math and column clamp as ``_scatter_token``,
    all inside the traced step (no host sync, H106)."""
    nb, bs = pool.shape[0], pool.shape[1]
    nbs = block_table.shape[1]
    rows = jnp.arange(block_table.shape[0])
    col = jnp.minimum(positions // bs, nbs - 1)
    idx = block_table[rows, col] * bs + positions % bs          # [B]
    codes, sc = quantize_kv(new, scheme)            # [B,KVH,D], [B]
    flat = pool.reshape(nb * bs, pool.shape[2], pool.shape[3])
    flat = flat.at[idx].set(codes)
    sflat = scales.reshape(nb * bs).at[idx].set(sc)
    return flat.reshape(pool.shape), sflat.reshape(nb, bs)


# ---------------------------------------------------------------------------
# split-K partials: Pallas kernel
# ---------------------------------------------------------------------------

class _PageWalk:
    """The walk over one sequence's live pages that the serving kernels
    share: pages ``[lo, hi)`` of row ``b`` of the block table, a compute
    block of ``G`` pages at a time, copied from the pools in HBM into
    the double-buffered VMEM scratch that ``_pool_streams`` laid out
    (one ``[2, G, page]`` buffer and one DMA semaphore a slot for each
    stream: K, V, and a quantized pool's two scale rows).  No table
    entry and no page outside ``[lo, hi)`` is read.  A kernel calls
    ``start()``, then in a ``fori_loop`` over ``num_blocks`` takes each
    block's ``slot = arrive(j)`` and reads ``keys(slot)`` and
    ``values(slot)``: block ``j + 1`` is in flight meanwhile."""

    def __init__(self, bt_ref, b, lo, hi, hbm_refs, bufs, sems, kv_dtype):
        self.bt_ref, self.b, self.lo, self.hi = bt_ref, b, lo, hi
        self.streams = tuple(zip(hbm_refs, bufs))
        self.sems, self.kv_dtype = sems, kv_dtype
        self.k_buf, self.v_buf = bufs[:2]
        self.ks_buf, self.vs_buf = bufs[2:] if kv_dtype is not None \
            else (None, None)
        self.G, self.bs = self.k_buf.shape[1], self.k_buf.shape[2]
        self.num_blocks = jnp.maximum(hi - lo + self.G - 1, 0) // self.G

    def _page_copies(self, slot, g, block):
        return [pltpu.make_async_copy(hbm.at[block], buf.at[slot, g],
                                      self.sems.at[i, slot])
                for i, (hbm, buf) in enumerate(self.streams)]

    def _live_in_block(self, j):
        return jnp.minimum(self.hi - (self.lo + j * self.G), self.G)

    def _fetch(self, j, slot):
        """Start the copies of compute block ``j`` into ``slot``."""
        G, v_buf, vs_buf = self.G, self.v_buf, self.vs_buf

        def start(g, _):
            block = self.bt_ref[self.b, self.lo + j * G + g]
            for copy in self._page_copies(slot, g, block):
                copy.start()

        # a dead page of the last compute block: its keys are masked
        # by the caller, and zeroed values keep 0 * stale from being a NaN
        def zero(g, _):
            v_buf[slot, g] = jnp.zeros(v_buf.shape[2:], v_buf.dtype)
            if vs_buf is not None:
                vs_buf[slot, g] = jnp.zeros(vs_buf.shape[2:], vs_buf.dtype)

        n = self._live_in_block(j)
        jax.lax.fori_loop(0, n, start, None)
        jax.lax.fori_loop(n, G, zero, None)

    def _wait(self, j, slot):
        def wait_page(g, _):
            # a wait needs the copy's shape only, not its source
            for copy in self._page_copies(slot, g, 0):
                copy.wait()

        jax.lax.fori_loop(0, self._live_in_block(j), wait_page, None)

    def start(self):
        """Before the loop over compute blocks: the first block's copies."""
        @pl.when(self.num_blocks > 0)
        def _prologue():
            self._fetch(0, 0)

    def arrive(self, j):
        """The slot that holds compute block ``j``, its copies landed."""
        slot = jax.lax.rem(j, 2)

        # the next block's copies fly under this block's arithmetic
        @pl.when(j + 1 < self.num_blocks)
        def _prefetch():
            self._fetch(j + 1, 1 - slot)

        self._wait(j, slot)
        return slot

    def _keys_major(self, buf, scale_buf, slot):
        """One compute block as f32 [KVH, G * bs, D].  A quantized pool
        dequantizes HERE, at the DMA boundary: codes * per-row scale, so
        the wide KV copy never exists in HBM (ISSUE 20)."""
        G, bs = self.G, self.bs
        if scale_buf is None:
            x = buf[slot].reshape(G * bs, *buf.shape[3:])
            return jnp.swapaxes(x.astype(jnp.float32), 0, 1)
        # a page's [1, bs] scale row turns into a [bs, 1] column (Mosaic
        # has no layout for [bs] -> [bs, 1, 1]) and meets the codes
        # AFTER the swap: the same products, elementwise
        return jnp.concatenate(
            [jnp.swapaxes(decode_codes(buf[slot, g], self.kv_dtype), 0, 1)
             * scale_buf[slot, g][:, :bs].T[None] for g in range(G)],
            axis=1)

    def keys(self, slot):
        return self._keys_major(self.k_buf, self.ks_buf, slot)

    def values(self, slot):
        return self._keys_major(self.v_buf, self.vs_buf, slot)


def _split_walk_refs(rest, kv_dtype):
    """A walking kernel's refs after its own inputs, in ``pallas_call``
    order: the pools in HBM (``_pool_streams``' operands), the kernel's
    outputs and own scratch, then ``_pool_streams``' scratch: the pools'
    VMEM buffers and the DMA semaphores.  Returns ``(hbm_refs, own,
    bufs, sems)``."""
    n = 4 if kv_dtype is not None else 2
    return rest[:n], rest[n:-n - 1], rest[-n - 1:-1], rest[-1]


def _pool_streams(k_pool, v_pool, k_scale, v_scale, kv_dtype, G=None):
    """What a walking kernel's ``pallas_call`` needs for its pools:
    ``(in_specs, operands, scratch_shapes)``.  The pools (and a
    quantized pool's scale rows) stay in HBM; each stream has a
    ``[2, G, page]`` VMEM buffer and a DMA semaphore a slot."""
    bs, KVH, D = k_pool.shape[1:]
    # ``G`` pages a compute block; by default about 128 keys, whatever the
    # pool's page size: 8 pages at the served ``block_size`` 16
    G = G or max(1, _LANES // bs)
    in_specs = [pl.BlockSpec(memory_space=pl.ANY)] * 2
    operands = [k_pool, v_pool]
    scratch = [pltpu.VMEM((2, G, bs, KVH, D), k_pool.dtype),
               pltpu.VMEM((2, G) + v_pool.shape[1:], v_pool.dtype)]
    if kv_dtype is not None:
        # a page's [bs] f32 scale row rides with the page, one more copy;
        # Mosaic slices an HBM array only in whole 128-lane rows
        lanes = -(-bs // _LANES) * _LANES
        in_specs += [pl.BlockSpec(memory_space=pl.ANY)] * 2
        operands += [jnp.pad(sc, ((0, 0), (0, lanes - bs)))[:, None, :]
                     for sc in (k_scale, v_scale)]
        scratch += [pltpu.VMEM((2, G, 1, lanes), jnp.float32)] * 2
    # one DMA semaphore a stream and slot
    scratch.append(pltpu.SemaphoreType.DMA((len(scratch), 2)))
    return in_specs, operands, scratch


def _online_softmax(scores, values, m, l, acc):
    """One online-softmax update: ``scores [KVH, R, K]`` (masked keys at
    NEG_INF) against ``values [KVH, K, D]``, folded into the running max
    ``m``, exp-sum ``l`` (``[KVH, R, 1]``) and accumulator ``acc``."""
    m_new = jnp.maximum(m, jnp.max(scores, axis=-1, keepdims=True))
    alpha = jnp.exp(m - m_new)
    pexp = jnp.exp(scores - m_new)
    acc = acc * alpha + jax.lax.dot_general(
        pexp, values, (((2,), (1,)), ((0,), (0,))),
        preferred_element_type=jnp.float32)             # [KVH, R, D]
    l = l * alpha + jnp.sum(pexp, axis=-1, keepdims=True)
    return m_new, l, acc


# ---------------------------------------------------------------------------
# the decode walk: its own block read, block size and split-K width
# ---------------------------------------------------------------------------

# a compute block's keys and values together, about: the decode walk takes
# as many whole 128-key units of pages as come nearest to it
_DECODE_BLOCK_BYTES = 1 << 20


def _decode_block_pages(bs, page_bytes, pages):
    """Pages of one compute block of the decode walk: whole units of
    ``max(1, 128 // bs)`` pages (128 keys: a lane row of scores), as many
    as come nearest to ``_DECODE_BLOCK_BYTES`` of K and V at
    ``page_bytes`` a page of both pools, and no more units than cover
    ``pages`` (a split's share of the table)."""
    unit = max(1, _LANES // bs)
    units = max(1, round(_DECODE_BLOCK_BYTES / (unit * page_bytes)))
    return unit * min(units, -(-pages // unit))


def _tensor_cores():
    """TensorCores of the device the program runs on: 1 off a TPU."""
    return getattr(jax.devices()[0], "num_cores", 1)


def _decode_splits(nbs, cores):
    """Split-K width of the decode walk: ONE cell a sequence where the
    chip has one core (its cells run in order, and a split would only add
    cells with nothing to walk); the table's split where it has more, its
    cells shared out among the cores."""
    return 1 if cores == 1 else _default_splits(nbs)


class _DecodeWalk(_PageWalk):
    """``_PageWalk`` as the decode kernel walks it.  Compute block ``j``
    sits in slot ``(first_slot + j) % 2``, so a grid cell can start where
    the cell before it left the double buffer; ``arrive(j, after_last)``
    calls ``after_last(free_slot)`` at the cell's last block, the other
    slot free, which is where the next cell's first block is fetched.  An
    unquantized pool's block reaches the products as it is stored: its
    pages' ``[bs, KVH, D]`` rows, every head's, as ONE ``[G * bs * KVH,
    D]`` operand in the pool's dtype (row ``(t, h)`` is key ``t`` of head
    ``h``).  A quantized pool's is dequantized as ``_PageWalk`` does."""

    def __init__(self, *args, first_slot=0):
        super().__init__(*args)
        self.first_slot = first_slot

    def start(self, fetched=False):
        """The first block's copies, unless the cell before started them."""
        @pl.when((self.num_blocks > 0) & jnp.logical_not(fetched))
        def _prologue():
            self._fetch(0, self.first_slot)

    def fetch_first(self, slot):
        """Start the first block's copies into ``slot``: the cell before
        this one's, under its last block."""
        self._fetch(0, slot)

    def arrive(self, j, after_last=None):
        slot = jax.lax.rem(self.first_slot + j, 2)

        # the next block's copies fly under this block's arithmetic
        @pl.when(j + 1 < self.num_blocks)
        def _prefetch():
            self._fetch(j + 1, 1 - slot)

        if after_last is not None:
            @pl.when(j + 1 == self.num_blocks)
            def _next_cell():
                after_last(1 - slot)

        self._wait(j, slot)
        return slot

    def _block(self, buf, scale_buf, slot):
        if self.kv_dtype is not None:
            return self._keys_major(buf, scale_buf, slot)
        return buf[slot].reshape(-1, buf.shape[-1])

    def keys(self, slot):
        return self._block(self.k_buf, self.ks_buf, slot)

    def values(self, slot):
        return self._block(self.v_buf, self.vs_buf, slot)


def _decode_update(scores, values, carry, dims, precision):
    """``_online_softmax`` for either product of the decode kernel
    (``dims`` its dimension numbers), the exponentials meeting the values
    in the values' dtype."""
    m, l, acc = carry
    m_new = jnp.maximum(m, jnp.max(scores, axis=-1, keepdims=True))
    alpha = jnp.exp(m - m_new)
    pexp = jnp.exp(scores - m_new)
    acc = acc * alpha + jax.lax.dot_general(
        pexp.astype(values.dtype), values, dims, precision=precision,
        preferred_element_type=jnp.float32)
    l = l * alpha + jnp.sum(pexp, axis=-1, keepdims=True)
    return m_new, l, acc


def _decode_kernel(bt_ref, pos_ref, q_ref, cos_ref, sin_ref, *rest,
                   bs, pages_per_split, scale, kv_dtype=None, window=None,
                   chained=False):
    hbm_refs, own, bufs, sems = _split_walk_refs(rest, kv_dtype)
    o_ref, m_out_ref, l_out_ref = own[:3]
    b = pl.program_id(0)
    s = pl.program_id(1)
    nbs = bt_ref.shape[1]

    def cell_pages(b):
        # sequence b's pages in this split, cut at its live pages (keys at
        # k_pos <= pos, the new token included): the walk never reads a
        # table entry, or fetches a page, past them.  A window layer sees
        # the last ``window`` keys (k_pos > pos - window): the walk starts
        # at the page of the first of them, and that page's earlier keys
        # are masked.  No page before it is read: its table entry may name
        # a page given back long ago
        pos = pos_ref[b]
        lo = s * pages_per_split
        hi = jnp.minimum(lo + pages_per_split,
                         jnp.minimum(pos // bs + 1, nbs))
        first_key = None
        if window is not None:
            first_key = jnp.maximum(pos - window + 1, 0)
            lo = jnp.maximum(lo, first_key // bs)
        return pos, lo, hi, first_key

    def walk_of(b, lo, hi, first_slot=0):
        return _DecodeWalk(bt_ref, b, lo, hi, hbm_refs, bufs, sems,
                           kv_dtype, first_slot=first_slot)

    pos, lo, hi, first_key = cell_pages(b)
    key_limit = jnp.minimum(pos + 1, (s + 1) * pages_per_split * bs)
    after_last, fetched = None, False
    if chained:
        # one cell a sequence, run in order: ``state`` hands the double
        # buffer on from cell to cell (the slot of the cell's first block;
        # whether the cell before started its copies)
        state = own[3]

        @pl.when(b == 0)
        def _first_cell():
            state[0] = 0
            state[1] = 0

        first_slot, fetched = state[0], state[1] == 1
        state[1] = 0
        walk = walk_of(b, lo, hi, first_slot)

        def after_last(free_slot):
            # the next sequence's first block flies under this one's last
            @pl.when(b + 1 < pl.num_programs(0))
            def _next_sequence():
                _, n_lo, n_hi, _ = cell_pages(b + 1)
                nxt = walk_of(b + 1, n_lo, n_hi)

                @pl.when(nxt.num_blocks > 0)
                def _fetch_first_block():
                    nxt.fetch_first(free_slot)
                    state[1] = 1
    else:
        walk = walk_of(b, lo, hi)
    G, num_blocks = walk.G, walk.num_blocks

    # rotate + pre-scale q once per cell: RoPE lives inside the kernel,
    # and folding 1/sqrt(D) into q here keeps the score math a bare dot
    q_rot = _rotate_half(q_ref[0].astype(jnp.float32),      # [KVH,rep,D]
                         cos_ref[0].astype(jnp.float32),    # [1, half]
                         sin_ref[0].astype(jnp.float32)) * scale
    KVH, rep, D = q_rot.shape
    precision = None
    if kv_dtype is None:
        # every head's rows against every head's keys, in the pool's
        # dtype (a float32 product is one bf16 pass on the chip anyway):
        # [H, D] x [G*bs*KVH, D], each row's own head kept by the mask.
        # Operands narrower than float32 are exact in one pass, whatever
        # precision a caller asks of float32 products
        q_rot = q_rot.reshape(KVH * rep, D).astype(bufs[0].dtype)
        if bufs[0].dtype != jnp.float32:
            precision = jax.lax.Precision.DEFAULT
        col = jax.lax.broadcasted_iota(jnp.int32,
                                       (KVH * rep, G * bs * KVH), 1)
        own_head = col % KVH == jax.lax.broadcasted_iota(
            jnp.int32, col.shape, 0) // rep
        col_pos = col // KVH
        dims = ((((1,), (1,)), ((), ())), (((1,), (0,)), ((), ())))
    else:
        col_pos = jax.lax.broadcasted_iota(jnp.int32, (KVH, rep, G * bs), 2)
        dims = ((((2,), (2,)), ((0,), (0,))), (((2,), (1,)), ((0,), (0,))))

    walk.start(fetched)

    def compute_block(j, carry):
        slot = walk.arrive(j, after_last)
        scores = jax.lax.dot_general(
            q_rot, walk.keys(slot), dims[0], precision=precision,
            preferred_element_type=jnp.float32)
        k_pos = (lo + j * G) * bs + col_pos
        seen = k_pos < key_limit
        if kv_dtype is None:
            seen = seen & own_head
        if window is not None:
            seen = seen & (k_pos >= first_key)
        scores = jnp.where(seen, scores, NEG_INF)
        return _decode_update(scores, walk.values(slot), carry, dims[1],
                              precision)

    rows = q_rot.shape[:-1]
    # a split with no live page emits (NEG_INF, 0, 0): the combine
    # weighs it to zero
    m, l, acc = jax.lax.fori_loop(
        0, num_blocks, compute_block,
        (jnp.full(rows + (1,), NEG_INF, jnp.float32),
         jnp.zeros(rows + (1,), jnp.float32),
         jnp.zeros(rows + (o_ref.shape[-1],), jnp.float32)))
    if chained:
        state[0] = jax.lax.rem(first_slot + num_blocks, 2)
    o_ref[0, 0] = acc.reshape(o_ref.shape[2:])
    # per-row scalars broadcast over the lane dim (flash kernel lse
    # idiom: a 1-wide trailing dim is not a legal TPU output tile)
    m_out_ref[0, 0] = jnp.broadcast_to(m.reshape(-1, 1),
                                       m_out_ref.shape[2:])
    l_out_ref[0, 0] = jnp.broadcast_to(l.reshape(-1, 1),
                                       l_out_ref.shape[2:])


@functools.partial(jax.jit, static_argnames=("num_splits", "scale",
                                             "interpret", "kv_dtype",
                                             "window"))
def _pallas_partials(q, cos_b, sin_b, k_pool, v_pool, block_table,
                     positions, num_splits, scale, interpret,
                     k_scale=None, v_scale=None, kv_dtype=None,
                     window=None):
    """q: UNROTATED [B, KVH, rep, D] (``D`` the pools' key lanes);
    returns (acc [B,S,KVH,rep,Dv] f32, m [B,S,KVH,rep] f32, l
    [B,S,KVH,rep] f32), ``Dv`` the value pool's width.

    Jitted so that a model's layers share ONE trace and ONE lowering of
    the kernel: ``pallas_call`` traces its kernel at every call, and a
    step program's first call is part of a server's start."""
    B, KVH, rep, D = q.shape
    H = KVH * rep
    bs = k_pool.shape[1]
    Dv = v_pool.shape[-1]
    nbs = block_table.shape[1]
    P = nbs // num_splits
    half = D // 2
    page_bytes = bs * KVH * (D * k_pool.dtype.itemsize
                             + Dv * v_pool.dtype.itemsize)
    pool_specs, pool_operands, pool_scratch = _pool_streams(
        k_pool, v_pool, k_scale, v_scale, kv_dtype,
        _decode_block_pages(bs, page_bytes, P))
    # one split: the cells run in order and hand the double buffer on
    chained = num_splits == 1

    in_specs = [
        pl.BlockSpec((1, KVH, rep, D), lambda b, s, bt, pos: (b, 0, 0, 0)),
        # one row per sequence, as a full-extent (1, half) tile: a bare
        # (1, half) block of a [B, half] array is not (8, 128)-tileable
        pl.BlockSpec((1, 1, half), lambda b, s, bt, pos: (b, 0, 0)),
        pl.BlockSpec((1, 1, half), lambda b, s, bt, pos: (b, 0, 0)),
        *pool_specs,
    ]
    operands = [q, cos_b[:, None, :], sin_b[:, None, :], *pool_operands]

    def out(width):
        return pl.BlockSpec((1, 1, H, width),
                            lambda b, s, bt, pos: (b, s, 0, 0))

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(B, num_splits),
        in_specs=in_specs,
        out_specs=[out(Dv), out(_LANES), out(_LANES)],
        scratch_shapes=([pltpu.SMEM((2,), jnp.int32)] if chained else [])
        + pool_scratch,
    )
    # priced for the whole table, the worst case: shapes cannot see the
    # lengths that bound the walk
    L = nbs * bs
    esize = jnp.dtype(k_pool.dtype).itemsize
    # quantized pools also stream one f32 scale per (pool, token) row
    scale_bytes = 2 * B * L * 4 if kv_dtype is not None else 0
    acc, m_b, l_b = pl.pallas_call(
        functools.partial(_decode_kernel, bs=bs, pages_per_split=P,
                          scale=scale, kv_dtype=kv_dtype, window=window,
                          chained=chained),
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((B, num_splits, H, width), jnp.float32)
            for width in (Dv, _LANES, _LANES)],
        # chained cells must run in order; split cells are independent and
        # shared out among a chip's cores
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",) * 2 if chained
            else ("parallel",) * 2)
        if not interpret else None,
        cost_estimate=pl.CostEstimate(
            flops=2 * B * H * (D + Dv) * L,
            bytes_accessed=B * L * KVH * (D + Dv) * esize + scale_bytes,
            transcendentals=B * H * L),
        interpret=interpret,
        name=KERNEL_NAME,
    )(block_table, positions, *operands)
    lead = (B, num_splits, KVH, rep)
    return (acc.reshape(lead + (Dv,)), m_b[..., 0].reshape(lead),
            l_b[..., 0].reshape(lead))


# ---------------------------------------------------------------------------
# split-K partials: numerically-identical XLA lowering
# ---------------------------------------------------------------------------

def _xla_partials(q_rot, k_pool, v_pool, block_table, positions,
                  num_splits, k_scale=None, v_scale=None, kv_dtype=None,
                  window=None):
    """Same split-K partials in plain XLA: q_rot is the ROTATED and
    pre-scaled [B, KVH, rep, D] f32 query (scale folded in, exactly as
    the kernel does once a grid cell).  Quantized pools dequant at the gather
    with the IDENTICAL codes * per-row-scale f32 multiply the kernel
    fuses into its block DMA, so CPU covers the exact served math."""
    B = q_rot.shape[0]
    bs = k_pool.shape[1]
    nbs = block_table.shape[1]
    Lp = (nbs // num_splits) * bs                       # keys per split
    if kv_dtype is not None:
        kb = decode_codes(k_pool[block_table], kv_dtype) \
            * k_scale[block_table][..., None, None]     # [B,nbs,bs,KVH,D]
        vb = decode_codes(v_pool[block_table], kv_dtype) \
            * v_scale[block_table][..., None, None]
    else:
        # the kernel's products take an unquantized pool's dtype: q and
        # the exponentials are rounded to it, here as there
        kb = k_pool[block_table]                        # [B,nbs,bs,KVH,D]
        vb = v_pool[block_table]
        q_rot = q_rot.astype(kb.dtype)
    kb = kb.reshape(B, num_splits, Lp, kb.shape[3], kb.shape[4])
    vb = vb.reshape(B, num_splits, Lp, vb.shape[3], vb.shape[4])
    scores = jnp.einsum("bkrd,bslkd->bskrl", q_rot, kb,
                        preferred_element_type=jnp.float32)
    k_pos = jnp.arange(nbs * bs).reshape(num_splits, Lp)
    valid = k_pos[None, :, None, None, :] <= \
        positions[:, None, None, None, None]
    if window is not None:
        valid = valid & (k_pos[None, :, None, None, :] >
                         positions[:, None, None, None, None] - window)
    scores = jnp.where(valid, scores, NEG_INF)
    m = jnp.max(scores, axis=-1)                        # [B,S,KVH,rep]
    pexp = jnp.exp(scores - m[..., None])
    l = jnp.sum(pexp, axis=-1)
    acc = jnp.einsum("bskrl,bslkd->bskrd", pexp.astype(vb.dtype), vb,
                     preferred_element_type=jnp.float32)
    return acc, m, l


def _combine_splits(acc, m, l, sink=None):
    """Log-sum-exp merge of the per-split partials — shared verbatim by
    both lowerings, so the combine rounding is identical.  ``sink
    [KVH, rep]`` (a logit a head, or None) joins the merge once, as one
    more partial of exp-sum 1 and no value."""
    m_g = jnp.max(m, axis=1)                            # [B,KVH,rep]
    if sink is not None:
        m_g = jnp.maximum(m_g, sink)
    w = jnp.exp(m - m_g[:, None])                       # [B,S,KVH,rep]
    l_g = jnp.sum(w * l, axis=1)
    if sink is not None:
        l_g = l_g + jnp.exp(sink - m_g)
    out = jnp.sum(w[..., None] * acc, axis=1)
    return out / jnp.maximum(l_g, 1e-30)[..., None]     # [B,KVH,rep,Dv]


# ---------------------------------------------------------------------------
# split-K width
# ---------------------------------------------------------------------------

def _split_candidates(nbs):
    return [s for s in (1, 2, 4, 8, 16) if s <= nbs and nbs % s == 0]


def _default_splits(nbs):
    """Static heuristic: ~4-way split-K once the table is deep enough
    to amortize the combine, else fewer."""
    best = 1
    for s in _split_candidates(nbs):
        if s <= max(1, nbs // 2) and s <= 4:
            best = s
    return best


# ---------------------------------------------------------------------------
# public entry points
# ---------------------------------------------------------------------------

def fused_paged_decode(q, k_new, v_new, k_pool, v_pool, block_table,
                       positions, cos, sin, *, num_splits=None,
                       use_pallas=None, interpret=None,
                       k_scale=None, v_scale=None, kv_cache_dtype=None,
                       window=None, sink=None, rotary=None):
    """One fused decode step of paged attention.

    q: [B, 1, H, D] UNROTATED queries; k_new/v_new: [B, 1, KVH, D]
    unrotated new-token key/value; k_pool/v_pool: [nb, bs, KVH, D]
    block pools; block_table: [B, max_blocks] int32; positions: [B]
    int32 per-sequence write frontiers; cos/sin: [max_pos, D/2] RoPE
    tables.  Returns (attn_out [B, 1, H, D], new_k_pool, new_v_pool).

    RoPE is applied to q and k_new at ``positions[b]``, the rotated
    k/v are scattered into the pools, and attention runs over the
    updated pools through the block table with causal masking
    ``k_pos <= positions[b]`` (garbage-block-0 rows sit past the
    frontier and are masked off).  On TPU the gather + q-RoPE +
    attention is one Pallas kernel; elsewhere the numerically-identical
    XLA split-K lowering runs instead.

    Quantized pools (``kv_cache_dtype`` = ``"int8"``/``"fp8"``,
    kernels/kv_quant): ``k_pool``/``v_pool`` hold int8 codes and
    ``k_scale``/``v_scale`` the [nb, bs] per-row f32 absmax scales.
    The new token quantizes at write and dequant fuses into the block
    DMA; the return grows to (attn_out, new_k_pool, new_v_pool,
    new_k_scale, new_v_scale).

    ``window`` (static) makes the layer a WINDOW layer: sequence ``b``
    sees the keys at ``positions[b] - window < k_pos <= positions[b]``,
    the walk starts at the page of the first of them, and no table
    entry before that page is read.  ``cos=None`` (with ``sin``) is the
    identity rotation: a layer with no position encoding.

    Heads of other shapes: ``v_new``/``v_pool`` may be narrower than the
    key (``Dv``; the output is ``[B, 1, H, Dv]``); ``k_pool`` may hold
    more lanes than the key's ``D`` (zeros past it: the new key and the
    query are padded to them, the scale stays ``1/sqrt(D)``); ``rotary``
    (static) turns the first ``rotary`` lanes of q and the new key only,
    under ``cos``/``sin`` tables ``rotary / 2`` wide; ``sink [H]`` f32 is
    a logit a head in the softmax's denominator (``exp(sink) + sum_j
    exp(s_j)``) that weighs no value.
    """
    from .fusion import pallas_lowering

    B, T, H, D = q.shape
    if T != 1:
        raise ValueError(f"fused_paged_decode is single-token (T == 1), "
                         f"got T == {T}")
    KVH = k_new.shape[2]
    rep = H // KVH
    out_dtype = q.dtype
    Dv = v_new.shape[-1]
    lanes = k_pool.shape[-1]
    nbs = block_table.shape[1]
    positions = jnp.asarray(positions, jnp.int32)
    scale = 1.0 / math.sqrt(D)

    use_pallas, interpret = pallas_lowering(use_pallas, interpret)
    if num_splits is None or nbs % num_splits:
        num_splits = _decode_splits(nbs, _tensor_cores())

    # per-sequence RoPE rows + scatter of the rotated new token (tiny:
    # B rows — XLA prologue shared verbatim by both lowerings).  A
    # quantized pool quantizes the token's row here, at write time,
    # inside the traced step.
    if rotary is not None:
        # the first ``rotary`` lanes turn here, q's too; the kernel is
        # handed the identity rotation over the pool's lanes
        c, s = cos[positions], sin[positions]           # [B, rotary/2]
        k_rot = _rotate_lanes(k_new[:, 0].astype(jnp.float32),
                              c[:, None, :], s[:, None, :],
                              rotary).astype(k_new.dtype)
        q = _rotate_lanes(q.astype(jnp.float32), c[:, None, None, :],
                          s[:, None, None, :], rotary)
        c = jnp.ones((B, lanes // 2), jnp.float32)
        s = jnp.zeros((B, lanes // 2), jnp.float32)
    elif cos is None:
        c = jnp.ones((B, lanes // 2), jnp.float32)
        s = jnp.zeros((B, lanes // 2), jnp.float32)
        k_rot = k_new[:, 0]
    else:
        c = cos[positions]                              # [B, half] f32
        s = sin[positions]
        k_rot = _rotate_half(
            k_new[:, 0].astype(jnp.float32),
            c[:, None, :], s[:, None, :]).astype(k_new.dtype)
    with jax.named_scope("kv_write"):
        if kv_cache_dtype is not None:
            new_k_pool, new_k_scale = _scatter_token_quant(
                k_pool, k_scale, k_rot, block_table, positions,
                kv_cache_dtype)
            new_v_pool, new_v_scale = _scatter_token_quant(
                v_pool, v_scale, v_new[:, 0], block_table, positions,
                kv_cache_dtype)
        else:
            new_k_pool = _scatter_token(k_pool, pad_lanes(k_rot, lanes),
                                        block_table, positions)
            new_v_pool = _scatter_token(v_pool, v_new[:, 0], block_table,
                                        positions)
            new_k_scale = new_v_scale = None

    q_g = pad_lanes(q[:, 0].reshape(B, KVH, rep, D),    # GQA grouping
                    lanes)
    if use_pallas:
        acc, m, l = _pallas_partials(
            q_g, c, s, new_k_pool, new_v_pool, block_table,
            positions, num_splits, scale, interpret,
            k_scale=new_k_scale, v_scale=new_v_scale,
            kv_dtype=kv_cache_dtype, window=window)
    else:
        q_rot = _rotate_half(q_g.astype(jnp.float32),
                             c[:, None, None, :],
                             s[:, None, None, :]) * scale
        acc, m, l = _xla_partials(q_rot, new_k_pool, new_v_pool,
                                  block_table, positions, num_splits,
                                  k_scale=new_k_scale,
                                  v_scale=new_v_scale,
                                  kv_dtype=kv_cache_dtype, window=window)
    out = _combine_splits(acc, m, l, None if sink is None else
                          sink.astype(jnp.float32).reshape(KVH, rep))
    out = out.reshape(B, 1, H, Dv).astype(out_dtype)
    if kv_cache_dtype is not None:
        return out, new_k_pool, new_v_pool, new_k_scale, new_v_scale
    return out, new_k_pool, new_v_pool


def paged_context_partials(q_rot, k_pool, v_pool, block_table, last_pos,
                           *, num_splits=None, use_pallas=None,
                           interpret=None):
    """Unnormalized attention partials of a step whose queries share ONE
    cached context per sequence (a block of positions denoised together,
    a verify window): ``q_rot [B, KVH, R, D]`` are the ROTATED, unscaled
    queries, ``R`` rows a KV head (the GQA group times the step's
    positions); every row of sequence ``b`` sees the cached keys at
    ``k_pos <= last_pos[b]`` and nothing of the step itself.  Returns
    ``(acc [B, S, KVH, R, D], m [B, S, KVH, R], l [B, S, KVH, R])`` in
    float32 for :func:`_combine_splits`; the caller appends the step's
    own keys as one more split.

    The walk is the decode kernel's (live pages only, a compute block of
    pages at a time under the next block's copies): its in-kernel
    rotation is given the identity."""
    from .fusion import pallas_lowering

    B, KVH, R, D = q_rot.shape
    nbs = block_table.shape[1]
    last_pos = jnp.asarray(last_pos, jnp.int32)
    scale = 1.0 / math.sqrt(D)
    use_pallas, interpret = pallas_lowering(use_pallas, interpret)
    if num_splits is None or nbs % num_splits:
        num_splits = _decode_splits(nbs, _tensor_cores())
    if use_pallas:
        return _pallas_partials(
            q_rot, jnp.ones((B, D // 2), jnp.float32),
            jnp.zeros((B, D // 2), jnp.float32), k_pool, v_pool,
            block_table, last_pos, num_splits, scale, interpret)
    return _xla_partials(q_rot.astype(jnp.float32) * scale, k_pool,
                         v_pool, block_table, last_pos, num_splits)


def paged_decode_reference(q, k_new, v_new, k_pool, v_pool, block_table,
                           positions, cos, sin, *, k_scale=None,
                           v_scale=None, kv_cache_dtype=None):
    """The UNFUSED scatter/gather decode math of models/llama.py's
    paged branch (rope gather path, full-buffer masked softmax) — the
    parity oracle for both fused lowerings.  With a quantized pool it
    quantizes the write and dequantizes the WHOLE gathered view up
    front (the naive two-pass the fused path avoids)."""
    B, T, H, D = q.shape
    positions = jnp.asarray(positions, jnp.int32)
    pos = positions[:, None] + jnp.arange(T)            # [B, 1]
    c = cos[pos][:, :, None, :]
    s = sin[pos][:, :, None, :]
    q_r = _rotate_half(q.astype(jnp.float32), c, s).astype(q.dtype)
    k_r = _rotate_half(k_new.astype(jnp.float32), c, s).astype(k_new.dtype)
    if kv_cache_dtype is not None:
        kp, ks = _scatter_token_quant(k_pool, k_scale, k_r[:, 0],
                                      block_table, positions,
                                      kv_cache_dtype)
        vp, vs = _scatter_token_quant(v_pool, v_scale, v_new[:, 0],
                                      block_table, positions,
                                      kv_cache_dtype)
        kd = decode_codes(kp, kv_cache_dtype) * ks[:, :, None, None]
        vd = decode_codes(vp, kv_cache_dtype) * vs[:, :, None, None]
    else:
        kp = _scatter_token(k_pool, k_r[:, 0], block_table, positions)
        vp = _scatter_token(v_pool, v_new[:, 0], block_table, positions)
        kd, vd = kp, vp
    kb = kd[block_table].reshape(B, -1, kp.shape[2], kp.shape[3])
    vb = vd[block_table].reshape(B, -1, vp.shape[2], vp.shape[3])
    rep = H // kb.shape[2]
    if rep > 1:
        kb = jnp.repeat(kb, rep, axis=2)
        vb = jnp.repeat(vb, rep, axis=2)
    scores = jnp.einsum("bthd,bshd->bhts", q_r, kb,
                        preferred_element_type=jnp.float32)
    scores = scores / math.sqrt(D)
    k_pos = jnp.arange(kb.shape[1])
    valid = k_pos[None, None, :] <= pos[:, :, None]
    scores = jnp.where(valid[:, None], scores, NEG_INF)
    probs = jax.nn.softmax(scores, axis=-1).astype(q.dtype)
    out = jnp.einsum("bhts,bshd->bthd", probs, vb)
    return out, kp, vp


# ---------------------------------------------------------------------------
# cost annotation (xray/shardplan price the pallas_call through this)
# ---------------------------------------------------------------------------

def _paged_decode_cost(in_avals, out_avals):
    # operand order fixed by _pallas_partials:
    # (block_table, positions, q, cos, sin, k_pool, v_pool
    #  [, k_scale, v_scale])  — the two trailing scale operands mark a
    # QUANTIZED pool (kernels/kv_quant), whose int8 element size flows
    # through ``esize`` below so the roofline prices quantized bytes
    bt_shape = in_avals[0][0]
    q_shape, q_dtype = in_avals[2][0], in_avals[2][1]
    pool_shape, pool_dtype = in_avals[5][0], in_avals[5][1]
    B, nbs = int(bt_shape[0]), int(bt_shape[1])
    KVH, rep, D = int(q_shape[1]), int(q_shape[2]), int(q_shape[3])
    Dv = int(in_avals[6][0][-1])                        # the value's width
    bs = int(pool_shape[1])
    H, L = KVH * rep, nbs * bs
    flops = 2.0 * B * H * (D + Dv) * L                  # qk^T + pv MACs
    trans = float(B * H * L)                            # exp per score
    esize = np.dtype(pool_dtype).itemsize
    in_bytes = sum(
        float(np.prod(shape, dtype=np.int64)) * np.dtype(dt).itemsize
        for shape, dt in in_avals[:5])                  # q/rope/tables
    # the pools are read THROUGH the block table: at most B*L rows each,
    # not the whole pool allocation.  The kernel walks only the live
    # pages, but shapes cannot see lengths: this is the worst case, every
    # sequence at the table's end
    kv_bytes = 1.0 * B * L * KVH * (D + Dv) * esize
    if len(in_avals) > 7:                               # quantized pool
        # one f32 absmax per (pool, token) row streams with its block
        kv_bytes += 2.0 * B * L * np.dtype(in_avals[7][1]).itemsize
    out_bytes = sum(
        float(np.prod(shape, dtype=np.int64)) * np.dtype(dt).itemsize
        for shape, dt in out_avals)
    # compute dtype stays q's (the kernel dequantizes to f32 for the
    # dots); the QUANTIZED width is already priced into kv_bytes
    return KernelCost(flops=flops, bytes_accessed=in_bytes + kv_bytes
                      + out_bytes, transcendentals=trans,
                      dtype=str(q_dtype))


register_kernel_cost(
    KERNEL_NAME, _paged_decode_cost,
    sample_in=[((4, 8), "int32"), ((4,), "int32"),
               ((4, 2, 2, 16), "float32"), ((4, 8), "float32"),
               ((4, 8), "float32"), ((32, 8, 2, 16), "float32"),
               ((32, 8, 2, 16), "float32")],
    sample_out=[((4, 2, 2, 2, 16), "float32"),
                ((4, 2, 2, 2, 128), "float32"),
                ((4, 2, 2, 2, 128), "float32")])
