"""Dropless expert computation: tokens grouped by expert, one fused
kernel for ``silu(x.Wg) * (x.Wu) . Wd`` over every group (Pallas), with
an XLA lowering of the same grouping.

No capacity and no dropped token: an expert gets exactly the rows that
were routed to it.  The ``A = T * K`` assignments of a step are sorted
by expert and laid out in ROW TILES of ``tm`` rows, every expert's
group padded up to whole tiles, so that a tile belongs to one expert.
The kernel's grid is ``(tiles, M / block_m)``: the tile's expert id
rides in as a scalar-prefetch operand and picks the expert's weight
blocks, so only experts that hold a tile are read from HBM, each
``[block_m, H]`` block of the three matrices once a tile (a group
larger than a tile reads its expert again: ``tm`` follows the mean
group size).  Weights are ``[E, M, H]`` for all three matrices (gate
and up as the published ``[out, in]``, down transposed), so a block is
one contiguous piece of HBM.  Products are bf16 with float32
accumulation; the activation is rounded to the weights' type between
the two products, as a bf16 model does.

The number of tiles is static (the worst case: every expert's group
one row over a tile), tiles past the last real one are skipped: they
keep the last real tile's weight block, so nothing is fetched for
them, and write zeros.

``route_topk`` is the router every caller shares: softmax (or sigmoid)
scores in float32 over ALL experts, the ``k`` largest (by score, or by
score plus a selection-only bias), renormalised and scaled.
``grouped_experts`` takes the routing and the held experts' weights
and returns the combined output and what the step read
(``RouteStats``), counted on the device.
"""
from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .costs import KernelCost, register_kernel_cost

KERNEL_NAME = "moe_grouped_experts"


class RouteStats(NamedTuple):
    """What one expert layer's call read and computed, as int32
    scalars: experts with at least one row, rows in all, and the rows
    of the busiest expert."""
    experts_read: jax.Array
    assignments: jax.Array
    assignments_max: jax.Array

    def as_vector(self):
        return jnp.stack([self.experts_read, self.assignments,
                          self.assignments_max]).astype(jnp.int32)


def route_topk(x, router_w, k, *, normalize=True, scores="softmax",
               bias=None, scale=None, norm_eps=None):
    """``x [T, H]``, ``router_w [H, E]`` -> ``(chosen [T, k] int32,
    gates [T, k] f32)``: scores over all ``E`` experts in float32
    (``"softmax"`` over them, or a ``"sigmoid"`` each), the ``k``
    largest, divided by their sum (plus ``norm_eps``) when
    ``normalize``, times ``scale``.  ``bias [E]`` moves the SELECTION
    only: the ``k`` largest of ``score + bias`` are chosen, and each
    keeps its own score as its gate."""
    logits = jnp.dot(x.astype(jnp.float32), router_w.astype(jnp.float32),
                     preferred_element_type=jnp.float32)
    if scores == "softmax":
        scores = jax.nn.softmax(logits, axis=-1)
    elif scores == "sigmoid":
        scores = jax.nn.sigmoid(logits)
    else:
        raise ValueError(f"scores must be softmax or sigmoid, got {scores!r}")
    if bias is None:
        gates, chosen = jax.lax.top_k(scores, k)
    else:
        _, chosen = jax.lax.top_k(scores + bias.astype(jnp.float32), k)
        gates = jnp.take_along_axis(scores, chosen, axis=-1)
    if normalize:
        total = jnp.sum(gates, axis=-1, keepdims=True)
        gates = gates / (total if norm_eps is None else total + norm_eps)
    if scale is not None:
        gates = gates * scale
    return chosen.astype(jnp.int32), gates


def tile_rows(assignments: int, experts: int) -> int:
    """Rows of a tile: about twice the mean group, a power of two in
    ``[16, 128]`` (16: a bf16 sublane tile)."""
    mean = max(1, -(-assignments // max(experts, 1)))
    tm = 16
    while tm < 2 * mean and tm < 128:
        tm *= 2
    return tm


def _layout(slot_of, valid, n_slots, tm):
    """Rows of the tiled layout for assignments ``slot_of [A]`` (the
    weight slot of each assignment's expert; ``valid [A]`` False for an
    assignment that is not computed here).

    Returns ``(dest [A], row_src [R], tile_slot [NT], tile_valid [NT],
    group_sizes [n_slots])``: ``dest`` the row of each assignment (R
    for one not computed: a zero row is appended by the caller),
    ``row_src`` the assignment a row holds (A for padding)."""
    A = slot_of.shape[0]
    n_tiles = (A + n_slots * (tm - 1)) // tm
    R = n_tiles * tm
    key = jnp.where(valid, slot_of, n_slots)
    sizes = jnp.zeros((n_slots + 1,), jnp.int32).at[key].add(1)[:n_slots]
    padded = (sizes + tm - 1) // tm * tm
    pad_end = jnp.cumsum(padded)
    pad_start = pad_end - padded
    grp_start = jnp.cumsum(sizes) - sizes
    order = jnp.argsort(key, stable=True)
    sorted_key = key[order]
    safe = jnp.minimum(sorted_key, n_slots - 1)
    rank = jnp.arange(A, dtype=jnp.int32) - grp_start[safe]
    dest_sorted = jnp.where(sorted_key < n_slots,
                            pad_start[safe] + rank, R)
    dest = jnp.zeros((A,), jnp.int32).at[order].set(dest_sorted)
    row_src = jnp.full((R + 1,), A, jnp.int32).at[dest_sorted].set(
        order.astype(jnp.int32))[:R]
    tile_first = jnp.arange(n_tiles, dtype=jnp.int32) * tm
    total = pad_end[-1]
    tile_valid = tile_first < total
    # the group a row lies in: how many groups end at or before it (by
    # comparison with every end: a search would be a loop on the device)
    slot = jnp.sum(pad_end[None, :] <= tile_first[:, None], axis=1)
    last = jnp.sum(pad_end <= jnp.maximum(total - 1, 0))
    tile_slot = jnp.where(tile_valid, slot, last)
    tile_slot = jnp.minimum(tile_slot, n_slots - 1).astype(jnp.int32)
    return dest, row_src, tile_slot, tile_valid.astype(jnp.int32), sizes


def _experts_kernel(slot_ref, valid_ref, x_ref, wg_ref, wu_ref, wd_ref,
                    o_ref, acc_ref, *, n_m):
    i = pl.program_id(0)
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        acc_ref[:] = jnp.zeros_like(acc_ref)

    @pl.when(valid_ref[i] != 0)
    def _compute():
        x = x_ref[...]                                      # [tm, H]
        # (the precision is spelled out: a process-wide "highest" would
        # ask Mosaic for a float32 product of bf16 operands)
        dot = functools.partial(
            jax.lax.dot_general, precision=jax.lax.Precision.DEFAULT,
            preferred_element_type=jnp.float32)
        contract_h = (((1,), (1,)), ((), ()))
        g = dot(x, wg_ref[0], contract_h)
        u = dot(x, wu_ref[0], contract_h)
        act = (g * jax.nn.sigmoid(g) * u).astype(x.dtype)   # [tm, bm]
        acc_ref[:] += dot(act, wd_ref[0], (((1,), (0,)), ((), ())))

    @pl.when(j == n_m - 1)
    def _emit():
        o_ref[...] = acc_ref[:]


@functools.partial(jax.jit, static_argnames=("tm", "block_m", "interpret"))
def _pallas_experts(x_rows, tile_slot, tile_valid, w_gate, w_up, w_down,
                    tm, block_m, interpret):
    """``x_rows [R, H]`` (tiled layout) -> ``[R, H]`` float32.  Jitted
    so that a model's layers share one trace and one lowering."""
    R, H = x_rows.shape
    M = w_gate.shape[1]
    n_tiles, n_m = R // tm, M // block_m

    def w_index(i, j, slot, valid):
        # a skipped tile keeps the block the last real tile ended on
        return slot[i], jnp.where(valid[i] != 0, j, n_m - 1), 0

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(n_tiles, n_m),
        in_specs=[
            pl.BlockSpec((tm, H), lambda i, j, slot, valid: (i, 0)),
            pl.BlockSpec((1, block_m, H), w_index),
            pl.BlockSpec((1, block_m, H), w_index),
            pl.BlockSpec((1, block_m, H), w_index),
        ],
        out_specs=pl.BlockSpec((tm, H), lambda i, j, slot, valid: (i, 0)),
        scratch_shapes=[pltpu.VMEM((tm, H), jnp.float32)],
    )
    esize = jnp.dtype(w_gate.dtype).itemsize
    return pl.pallas_call(
        functools.partial(_experts_kernel, n_m=n_m),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((R, H), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"))
        if not interpret else None,
        cost_estimate=pl.CostEstimate(
            flops=6 * R * H * M,
            bytes_accessed=3 * n_tiles * M * H * esize
            + R * H * (jnp.dtype(x_rows.dtype).itemsize + 4),
            transcendentals=R * M),
        interpret=interpret,
        name=KERNEL_NAME,
    )(tile_slot, tile_valid, x_rows, w_gate, w_up, w_down)


def _xla_experts(x_rows, tile_slot, tile_valid, w_gate, w_up, w_down, tm):
    """The kernel's arithmetic in plain XLA on the same tiled layout:
    each tile against its expert's matrices, gathered."""
    R, H = x_rows.shape
    xt = x_rows.reshape(R // tm, tm, H)
    g = jnp.einsum("nth,nmh->ntm", xt, w_gate[tile_slot],
                   preferred_element_type=jnp.float32)
    u = jnp.einsum("nth,nmh->ntm", xt, w_up[tile_slot],
                   preferred_element_type=jnp.float32)
    act = (g * jax.nn.sigmoid(g) * u).astype(x_rows.dtype)
    out = jnp.einsum("ntm,nmh->nth", act, w_down[tile_slot],
                     preferred_element_type=jnp.float32)
    out = out * (tile_valid != 0)[:, None, None]
    return out.reshape(R, H)


def grouped_experts(x, chosen, gates, w_gate, w_up, w_down, *,
                    held=None, num_experts=None, token_valid=None,
                    use_pallas=None, interpret=None, block_m=None):
    """``x [T, H]`` through the experts ``chosen [T, K]`` with weights
    ``gates [T, K]`` -> ``(out [T, H] in x's type, RouteStats)``.

    ``w_gate``/``w_up``/``w_down`` are ``[E_held, M, H]``: the matrices
    of the experts this caller HOLDS.  ``held [E_held]`` gives their
    expert ids among ``num_experts`` (``None``: all of them, in order);
    an assignment to an expert that is not held adds nothing here (its
    holder adds it).  ``token_valid [T]`` False leaves a token out: it
    reads no expert and its output row is zero."""
    from .fusion import pallas_lowering

    T, H = x.shape
    K = chosen.shape[1]
    n_slots, M = w_gate.shape[0], w_gate.shape[1]
    num_experts = n_slots if num_experts is None else num_experts
    flat = chosen.reshape(-1)
    if held is None:
        slot_of, valid = flat, jnp.ones(flat.shape, bool)
    else:
        slot_by_expert = jnp.full((num_experts,), n_slots, jnp.int32).at[
            jnp.asarray(held, jnp.int32)].set(
                jnp.arange(n_slots, dtype=jnp.int32))
        slot_of = slot_by_expert[flat]
        valid = slot_of < n_slots
    if token_valid is not None:
        valid = valid & jnp.repeat(token_valid.astype(bool), K)
    tm = tile_rows(T * K, num_experts)
    dest, row_src, tile_slot, tile_valid, sizes = _layout(
        slot_of, valid, n_slots, tm)
    x_ext = jnp.concatenate([x, jnp.zeros((1, H), x.dtype)])
    # (a row of padding names assignment T * K: the zero row)
    x_rows = x_ext[row_src // K]

    use_pallas, interpret = pallas_lowering(use_pallas, interpret)
    if use_pallas:
        if block_m is None:
            block_m = next(b for b in (256, 128, M) if M % b == 0)
        rows = _pallas_experts(x_rows, tile_slot, tile_valid, w_gate,
                               w_up, w_down, tm, block_m, interpret)
    else:
        rows = _xla_experts(x_rows, tile_slot, tile_valid, w_gate, w_up,
                            w_down, tm)
    rows = jnp.concatenate([rows, jnp.zeros((1, H), rows.dtype)])
    picked = rows[dest].reshape(T, K, H)
    out = jnp.sum(picked * gates.astype(jnp.float32)[..., None], axis=1)
    stats = RouteStats(jnp.sum(sizes > 0).astype(jnp.int32),
                       jnp.sum(sizes).astype(jnp.int32),
                       jnp.max(sizes).astype(jnp.int32))
    return out.astype(x.dtype), stats


def _experts_cost(in_avals, out_avals):
    # operands: (tile_slot, tile_valid, x_rows, w_gate, w_up, w_down);
    # priced for the worst case, every tile real: shapes cannot see the
    # routing
    (n_tiles,), _ = in_avals[0]
    (R, H), x_dtype = in_avals[2]
    (_, M, _), w_dtype = in_avals[3]
    from .costs import dtype_element_bytes

    w_bytes = 3.0 * int(n_tiles) * int(M) * int(H) \
        * dtype_element_bytes(w_dtype)
    io = float(R) * H * (dtype_element_bytes(x_dtype) + 4.0)
    return KernelCost(flops=6.0 * R * H * M, bytes_accessed=w_bytes + io,
                      transcendentals=float(R) * M, dtype="float32")


register_kernel_cost(
    KERNEL_NAME, _experts_cost,
    sample_in=[((4,), "int32"), ((4,), "int32"), ((64, 32), "float32"),
               ((4, 16, 32), "float32"), ((4, 16, 32), "float32"),
               ((4, 16, 32), "float32")],
    sample_out=[((64, 32), "float32")])
