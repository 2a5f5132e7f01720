"""The decode walk of ``kernels/paged_attention.py``: one grid cell a
sequence on a chip of one TensorCore with the next sequence's first block
in flight under the last one's products, compute blocks sized by the
page's bytes, and pages that reach the products as they are stored (every
head's keys one operand in the pool's dtype, each query row's own head
kept by the mask).

Interpret mode against the XLA lowering (``_xla_partials`` +
``_combine_splits``, which rounds q and the exponentials to an
unquantized pool's dtype as the kernel does) and against the plain
references, at the served cells' head shapes: 8 KV heads x 4 rows x 128
lanes (Mistral); keys of 192 in 256 lanes beside values of 128 at 4 and 8
KV heads with a partial rotation and a sink (MiMo); windows of 128 and
2,048 keys that start in the middle of the table; idle slots at position
0; a cell with no page to walk between two that have some."""
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu.kernels import paged_attention as pa
from paddle_tpu.kernels.paged_attention import (fused_paged_decode,
                                                paged_context_partials,
                                                paged_decode_reference)
from paddle_tpu.models.llama import apply_rope, precompute_rope

BS = 16


def _bf16(rng, *shape):
    """Normal values that a bf16 pool holds exactly."""
    return np.array(jnp.asarray(rng.standard_normal(shape), jnp.bfloat16)
                    .astype(jnp.float32))


def _walk_operands(kvh, rep, d, lanes, dv, positions, nbs, window=None,
                   seed=0, poison=-1e3):
    """A batch of ``len(positions)`` sequences over tables of ``nbs``
    pages.  Block 0 is the garbage block an idle slot's row names (1e3
    keys, -1e3 values); the last block is the poison (1e3 keys, values
    ``poison``) that every table entry names which the walk must never
    read: past a sequence's live pages, and before a window's first page.
    The XLA lowering gathers the whole table and masks, so it is given a
    finite poison; the kernel alone is also given NaN."""
    rng = np.random.default_rng(seed)
    positions = np.asarray(positions, np.int32)
    B, H = len(positions), kvh * rep
    nb = 2 + B * nbs
    k_pool = _bf16(rng, nb, BS, kvh, lanes)
    k_pool[..., d:] = 0.0               # zeros past the key, as stored
    v_pool = _bf16(rng, nb, BS, kvh, dv)
    k_pool[0, ..., :d], v_pool[0] = 1e3, -1e3
    k_pool[-1, ..., :d], v_pool[-1] = 1e3, poison
    table = (1 + np.arange(B * nbs)).reshape(B, nbs).astype(np.int32)
    page = np.arange(nbs)[None, :]
    dead = page > positions[:, None] // BS
    if window is not None:
        first = np.maximum(positions - window + 1, 0) // BS
        dead |= page < first[:, None]
    table[dead] = nb - 1
    table[positions == 0] = 0           # an idle slot
    q = _bf16(rng, B, 1, H, d)
    k_new = _bf16(rng, B, 1, kvh, d)
    v_new = _bf16(rng, B, 1, kvh, dv)
    return q, k_new, v_new, k_pool, v_pool, table, positions


def _dense(q, keys, values, at, window, sink, scale):
    """``q [H, D]`` at position ``at`` over ``keys [L, KVH, D]`` and
    ``values [L, KVH, Dv]`` in float64; ``sink [H]`` or None."""
    lo = 0 if window is None else max(0, at - window + 1)
    rep = q.shape[0] // keys.shape[1]
    out = np.zeros((q.shape[0], values.shape[-1]))
    for h in range(q.shape[0]):
        s = keys[lo:at + 1, h // rep] @ q[h] * scale
        m = s.max() if sink is None else max(s.max(), sink[h])
        w = np.exp(s - m)
        total = w.sum() + (0.0 if sink is None else np.exp(sink[h] - m))
        out[h] = (w / total) @ values[lo:at + 1, h // rep]
    return out


# the served cells' head shapes: (KV heads, rows a KV head, key width,
# key lanes, value width, rotated lanes, window, sink)
SHAPES = {
    "mistral": (8, 4, 128, 128, 128, None, None, False),
    "mimo-full": (4, 16, 192, 256, 128, 64, None, False),
    "mimo-window": (8, 8, 192, 256, 128, 64, 128, True),
    "trinity-window": (4, 8, 128, 128, 128, None, 2048, False),
}
# an idle slot, a length that ends mid-page, mid-block and at a block's
# last key, a sequence at the table's end, one of two pages, and an idle
# slot last: the grid's last cell has nothing to prefetch behind it
POSITIONS = {48: [0, 24, 300, 511, 767, 31, 0],
             160: [0, 700, 2100, 2559, 1500, 0]}


def _decode(shape, dtype, use_pallas, seed=0):
    kvh, rep, d, lanes, dv, rotary, window, sink = SHAPES[shape]
    nbs = 160 if window == 2048 else 48
    q, k_new, v_new, k_pool, v_pool, table, pos = _walk_operands(
        kvh, rep, d, lanes, dv, POSITIONS[nbs], nbs, window, seed)
    cos, sin = precompute_rope(rotary or d, nbs * BS + 1, 10000.0)
    sinks = jnp.asarray(np.linspace(-1.0, 3.0, kvh * rep), jnp.float32) \
        if sink else None
    args = [jnp.asarray(a, dtype) for a in (q, k_new, v_new, k_pool, v_pool)]
    out = fused_paged_decode(
        *args, jnp.asarray(table), jnp.asarray(pos), cos, sin,
        window=window, sink=sinks, rotary=rotary, use_pallas=use_pallas,
        interpret=True)
    return out, (q, k_new, cos, sin, sinks, table, pos, rotary, window, d)


@pytest.mark.parametrize("shape", sorted(SHAPES))
def test_the_walk_against_the_xla_lowering_and_the_dense_softmax(shape):
    """A float32 pool of bf16 values: the kernel and the XLA lowering to
    float32 rounding, both against the dense softmax over the gathered
    pages (which must hold the new token where the kernel wrote it)."""
    (got, new_k, new_v), (q, k_new, cos, sin, sinks, table, pos, rotary,
                          window, d) = _decode(shape, jnp.float32, True)
    want, _, _ = _decode(shape, jnp.float32, False)[0]
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)
    assert np.isfinite(np.asarray(got)).all()
    for b, p in enumerate(pos):
        if p == 0:
            continue                    # an idle slot's output is unread
        at = jnp.asarray([p])
        qr, kr = (np.concatenate([np.asarray(apply_rope(
            t[b:b + 1, ..., :rotary or d], cos, sin, at)),
            t[b:b + 1, ..., rotary or d:]], -1)[0, 0] for t in (q, k_new))
        keys, values = (np.concatenate([np.asarray(pool)[i]
                                        for i in table[b]])
                        for pool in (new_k, new_v))
        np.testing.assert_allclose(keys[p, :, :d], kr, atol=1e-6)
        lo = 0 if window is None else max(0, p - window + 1)
        ref = _dense(qr, keys[:, :, :d].astype(np.float64), values, p,
                     window, None if sinks is None else np.asarray(sinks),
                     1 / math.sqrt(d))
        assert lo <= p
        np.testing.assert_allclose(np.asarray(got)[b, 0], ref, atol=2e-5)


@pytest.mark.parametrize("shape", sorted(SHAPES))
def test_a_bf16_pool_meets_the_products_in_bf16(shape):
    """A bf16 pool: the kernel rounds q and the exponentials to bf16 as
    the XLA lowering does (the block boundaries of the online softmax
    round other exponentials, so the two meet to bf16's grain), and both
    stay within bf16's grain of the float32 pool's answer."""
    got = np.asarray(_decode(shape, jnp.bfloat16, True)[0][0], np.float32)
    want = np.asarray(_decode(shape, jnp.bfloat16, False)[0][0],
                      np.float32)
    exact = np.asarray(_decode(shape, jnp.float32, False)[0][0])
    live = np.asarray(POSITIONS[160 if SHAPES[shape][6] == 2048 else 48]) > 0
    # one bf16 step of the output apart at most; about a third of the
    # room to the float32 pool's answer is used at these shapes
    np.testing.assert_allclose(got[live], want[live], rtol=2 ** -7,
                               atol=2 ** -8)
    np.testing.assert_allclose(got[live], exact[live], atol=1.5e-2)
    assert np.isfinite(got).all()


@pytest.mark.parametrize("shape", sorted(SHAPES))
def test_no_dead_page_is_read(shape):
    """A NaN value in a page the walk fetched would survive its zero
    weight (0 * NaN): with NaN in every page past a sequence's live ones
    and before a window's first, the kernel's answer is the one it gives
    over finite data, to the bit."""
    kvh, rep, d, lanes, dv, rotary, window, sink = SHAPES[shape]
    nbs = 160 if window == 2048 else 48
    cos, sin = precompute_rope(rotary or d, nbs * BS + 1, 10000.0)
    outs = []
    for poison in (-1e3, np.nan):
        ops = _walk_operands(kvh, rep, d, lanes, dv, POSITIONS[nbs], nbs,
                             window, poison=poison)
        outs.append(np.asarray(fused_paged_decode(
            *[jnp.asarray(a, jnp.bfloat16) for a in ops[:5]],
            *[jnp.asarray(a) for a in ops[5:]], cos, sin, window=window,
            rotary=rotary, use_pallas=True, interpret=True)[0], np.float32))
    assert np.isfinite(outs[1]).all()
    np.testing.assert_array_equal(outs[1], outs[0])


def test_the_mistral_shape_against_the_unfused_reference():
    kvh, rep, d, lanes, dv = SHAPES["mistral"][:5]
    q, k_new, v_new, k_pool, v_pool, table, pos = _walk_operands(
        kvh, rep, d, lanes, dv, POSITIONS[48], 48)
    k_pool[-1], v_pool[-1] = 0.0, 0.0   # the reference gathers every page
    cos, sin = precompute_rope(d, 48 * BS + 1, 10000.0)
    args = [jnp.asarray(a) for a in (q, k_new, v_new, k_pool, v_pool,
                                     table, pos)] + [cos, sin]
    ref, ref_k, ref_v = paged_decode_reference(*args)
    got, new_k, new_v = fused_paged_decode(*args, use_pallas=True,
                                           interpret=True)
    np.testing.assert_array_equal(np.asarray(new_k), np.asarray(ref_k))
    np.testing.assert_array_equal(np.asarray(new_v), np.asarray(ref_v))
    live = pos > 0
    np.testing.assert_allclose(np.asarray(got)[live], np.asarray(ref)[live],
                               rtol=2e-5, atol=2e-5)


def test_a_cell_with_nothing_to_walk_between_two_that_have_pages():
    """``paged_context_partials`` at ``last_pos`` -1 walks no page: the
    cell before it must not start copies for it, and the cell after it
    fetches its own first block."""
    kvh, rep, d = 4, 8, 128
    q, _, _, k_pool, v_pool, table, _ = _walk_operands(
        kvh, rep, d, d, d, [0, 24, 300, 511], 48)
    table[2] = 1 + np.arange(2 * 48, 3 * 48)    # no poison: one whole row
    last = jnp.asarray([300, -1, 700, 5], jnp.int32)
    q_rot = jnp.asarray(q.reshape(4, kvh, rep, d))
    got = pa._combine_splits(*paged_context_partials(
        q_rot, jnp.asarray(k_pool), jnp.asarray(v_pool), jnp.asarray(table),
        last, use_pallas=True, interpret=True))
    want = pa._combine_splits(*paged_context_partials(
        q_rot, jnp.asarray(k_pool), jnp.asarray(v_pool), jnp.asarray(table),
        last, use_pallas=False))
    for b in (0, 2, 3):
        np.testing.assert_allclose(np.asarray(got)[b], np.asarray(want)[b],
                                   rtol=2e-5, atol=2e-5)
    acc, m, l = paged_context_partials(
        q_rot, jnp.asarray(k_pool), jnp.asarray(v_pool), jnp.asarray(table),
        last, use_pallas=True, interpret=True)
    assert not np.asarray(l[1]).any()
    assert (np.asarray(m[1]) == np.float32(pa.NEG_INF)).all()


@pytest.mark.parametrize("nbs,cores,want", [
    (256, 1, 1), (1792, 1, 1), (256, 2, 4), (1792, 2, 4), (2, 2, 1),
    (8, 2, 4)])
def test_one_cell_a_sequence_on_one_core_and_the_split_on_two(nbs, cores,
                                                              want):
    assert pa._decode_splits(nbs, cores) == want


def test_this_backend_has_one_core():
    assert pa._tensor_cores() == 1


@pytest.mark.parametrize("cores,splits", [(1, 1), (2, 4)])
def test_the_split_follows_the_core_count(monkeypatch, cores, splits):
    """On two cores the table keeps its split of independent cells; on
    one the cells chain.  Both give the XLA lowering's answer."""
    seen = []
    real = pa._pallas_partials

    def spy(*args, **kw):
        seen.append(args[7])
        return real(*args, **kw)

    monkeypatch.setattr(pa, "_tensor_cores", lambda: cores)
    monkeypatch.setattr(pa, "_pallas_partials", spy)
    q, k_new, v_new, k_pool, v_pool, table, pos = _walk_operands(
        8, 4, 128, 128, 128, POSITIONS[48], 48)
    cos, sin = precompute_rope(128, 48 * BS + 1, 10000.0)
    args = [jnp.asarray(a) for a in (q, k_new, v_new, k_pool, v_pool,
                                     table, pos)] + [cos, sin]
    got = fused_paged_decode(*args, use_pallas=True, interpret=True)[0]
    want = fused_paged_decode(*args, use_pallas=False)[0]
    assert seen == [splits]
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


# a page of both pools in bytes -> pages a compute block, at block 16 over
# a 256-page table: about 1 MiB of K and V in whole 128-key units
@pytest.mark.parametrize("kvh,d,dv,itemsize,pages,want", [
    (8, 128, 128, 2, 256, 16),      # Mistral: 64 KiB a page
    (4, 256, 128, 2, 1792, 24),     # MiMo full layers: 48 KiB
    (8, 256, 128, 2, 1792, 8),      # MiMo window layers: 96 KiB
    (4, 128, 128, 2, 1024, 32),     # Trinity, SDAR: 32 KiB
    (8, 128, 128, 4, 256, 8),       # a float32 pool at Mistral's heads
    (32, 128, 128, 2, 256, 8),      # 32 KV heads: 256 KiB a page
    (8, 128, 128, 2, 10, 16),       # a short table: whole units still
    (8, 128, 128, 2, 4, 8),
])
def test_the_block_size_follows_the_page_s_bytes(kvh, d, dv, itemsize,
                                                 pages, want):
    page_bytes = BS * kvh * (d + dv) * itemsize
    assert pa._decode_block_pages(BS, page_bytes, pages) == want


def test_a_small_page_takes_more_pages_a_block():
    # 4-token pages: a unit is 32 pages (128 keys), capped by the table
    assert pa._decode_block_pages(4, 4 * 2 * 16 * 4, 24) == 32
    assert pa._decode_block_pages(4, 4 * 2 * 16 * 4, 4096) == 32 * 64


def test_the_kernel_s_buffers_hold_the_chosen_block(monkeypatch):
    """The scratch the walk double-buffers is ``[2, G, page]`` with ``G``
    from the page's bytes: 16 pages of Mistral's heads."""
    shapes = []
    real = pa._pool_streams

    def spy(*args):
        out = real(*args)
        shapes.extend(s.shape for s in out[2][:2])
        return out

    monkeypatch.setattr(pa, "_pool_streams", spy)
    q, k_new, v_new, k_pool, v_pool, table, pos = _walk_operands(
        8, 4, 128, 128, 128, [24, 300], 48)
    cos, sin = precompute_rope(128, 48 * BS + 1, 10000.0)
    jax.eval_shape(lambda *a: fused_paged_decode(
        *a, use_pallas=True, interpret=True), *[jnp.asarray(
            a, jnp.bfloat16 if i < 5 else None) for i, a in enumerate(
            (q, k_new, v_new, k_pool, v_pool, table, pos))], cos, sin)
    assert shapes == [(2, 16, BS, 8, 128)] * 2
