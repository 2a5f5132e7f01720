"""Fused serving hot path (ISSUE 13): the fused paged-attention decode
kernel, the RMSNorm->matmul epilogue fusion, and their wiring through
the engine and the analysis layer.

The done bar: the Pallas kernel (interpret mode), the XLA fallback and
the unfused scatter/gather reference are numerically interchangeable;
the fused engine is token-exact with the unfused engine AND with
``generate()`` at zero retraces; ``xray`` prices the pallas_call
through the kernel-cost registry; ``shardplan`` treats it as a priced
leaf (no S210); bad cost annotations fail loudly at registration.
"""
import functools
import json
import math
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.kernels import autotune as at
from paddle_tpu.kernels.costs import (KernelCost, register_kernel_cost,
                                      registered_kernels)
from paddle_tpu.kernels.fused_norm_linear import (fused_norm_linear,
                                                  fused_rmsnorm_linear,
                                                  rms_scale)
from paddle_tpu.kernels.chunked_prefill import fused_chunked_attention
from paddle_tpu.kernels.kv_quant import decode_codes, quantize_kv
from paddle_tpu.kernels.paged_attention import (fused_paged_decode,
                                                paged_decode_reference)
from paddle_tpu.models import LlamaConfig, LlamaForCausalLM


# ---------------------------------------------------------------------------
# decode-kernel operands: GQA heads, garbage block 0, varied frontiers
# ---------------------------------------------------------------------------

def _rope_tables(max_pos, D):
    inv = 1.0 / (10000.0 ** (np.arange(0, D, 2) / D))
    t = np.arange(max_pos)[:, None] * inv[None, :]
    return np.cos(t).astype(np.float32), np.sin(t).astype(np.float32)


def _decode_operands(B=2, KVH=2, rep=2, D=8, bs=4, nbs=4, seed=0,
                     dtype=np.float32):
    """Pools with a poisoned block 0 (never owned by any sequence) and
    per-sequence context frontiers that straddle block boundaries."""
    rng = np.random.RandomState(seed)
    H = KVH * rep
    nb = 1 + B * nbs
    max_pos = nbs * bs + 1

    q = rng.randn(B, 1, H, D).astype(dtype)
    k_new = rng.randn(B, 1, KVH, D).astype(dtype)
    v_new = rng.randn(B, 1, KVH, D).astype(dtype)
    k_pool = rng.randn(nb, bs, KVH, D).astype(dtype)
    v_pool = rng.randn(nb, bs, KVH, D).astype(dtype)
    # block 0 is the classic paged-KV trap: garbage rows that MUST be
    # masked off, never attended to
    k_pool[0] = 1e3
    v_pool[0] = -1e3
    block_table = (1 + np.arange(B * nbs)).reshape(B, nbs).astype(np.int32)
    positions = np.array([bs + 1, (nbs - 1) * bs + 2][:B],
                         dtype=np.int32)
    cos, sin = _rope_tables(max_pos, D)
    return (jnp.asarray(q), jnp.asarray(k_new), jnp.asarray(v_new),
            jnp.asarray(k_pool), jnp.asarray(v_pool),
            jnp.asarray(block_table), jnp.asarray(positions),
            jnp.asarray(cos), jnp.asarray(sin))


class TestFusedPagedDecodeParity:
    @pytest.mark.parametrize("num_splits", [1, 2, 4])
    def test_pallas_interpret_vs_xla_vs_reference(self, num_splits):
        args = _decode_operands()
        ref_out, ref_kp, ref_vp = paged_decode_reference(*args)
        for use_pallas in (True, False):
            out, kp, vp = fused_paged_decode(
                *args, num_splits=num_splits, use_pallas=use_pallas,
                interpret=True)
            np.testing.assert_allclose(np.asarray(out),
                                       np.asarray(ref_out),
                                       rtol=2e-5, atol=2e-5)
            np.testing.assert_array_equal(np.asarray(kp),
                                          np.asarray(ref_kp))
            np.testing.assert_array_equal(np.asarray(vp),
                                          np.asarray(ref_vp))

    def test_pallas_vs_xla_bitwise_close(self):
        # the two fused lowerings share the combine code object; they
        # must agree far tighter than either does with the reference
        args = _decode_operands(seed=3)
        p_out, _, _ = fused_paged_decode(*args, num_splits=2,
                                         use_pallas=True, interpret=True)
        x_out, _, _ = fused_paged_decode(*args, num_splits=2,
                                         use_pallas=False, interpret=True)
        np.testing.assert_allclose(np.asarray(p_out), np.asarray(x_out),
                                   rtol=1e-6, atol=1e-6)

    def test_garbage_block_zero_never_leaks(self):
        # if block 0 leaked into attention, its 1e3 keys would dominate
        # the softmax and the outputs would be ~-1e3
        args = _decode_operands(seed=1)
        out, _, _ = fused_paged_decode(*args, num_splits=2,
                                       use_pallas=False)
        assert float(jnp.max(jnp.abs(out))) < 50.0

    def test_split_k_long_context(self):
        # deep table, frontier near the end: every split contributes,
        # and fully-masked splits (frontier near the START) are benign
        args = list(_decode_operands(B=2, nbs=8, bs=4, seed=2))
        for positions in ([30, 29], [1, 2]):
            args[6] = jnp.asarray(np.array(positions, np.int32))
            ref, _, _ = paged_decode_reference(*args)
            for s in (1, 2, 4, 8):
                out, _, _ = fused_paged_decode(*args, num_splits=s,
                                               use_pallas=True,
                                               interpret=True)
                np.testing.assert_allclose(np.asarray(out),
                                           np.asarray(ref),
                                           rtol=2e-5, atol=2e-5)

    def test_mha_no_gqa(self):
        args = _decode_operands(KVH=4, rep=1, seed=4)
        ref, _, _ = paged_decode_reference(*args)
        out, _, _ = fused_paged_decode(*args, num_splits=2,
                                       use_pallas=True, interpret=True)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=2e-5, atol=2e-5)

    def test_multi_token_rejected(self):
        args = list(_decode_operands())
        args[0] = jnp.zeros((2, 2, 4, 8), jnp.float32)  # T == 2
        with pytest.raises(ValueError, match="single-token"):
            fused_paged_decode(*args)


# ---------------------------------------------------------------------------
# ragged batches: the Pallas walk is bounded by each sequence's length
# ---------------------------------------------------------------------------

_POOL_KINDS = [None, "int8", "fp8"]     # None: bf16 values in f32 pools
_POOL_IDS = ["bf16", "int8", "fp8"]


def _bf16_values(rng, *shape):
    x = jnp.asarray(rng.randn(*shape), jnp.bfloat16)
    return np.array(x.astype(jnp.float32))


def _trapped_pools(rng, shape, kv_dtype):
    """K and V pools ``[nb, bs, KVH, D]`` whose block 0 is the garbage
    block (1e3 keys, -1e3 values) and whose last block is the poison (1e3
    keys, NaN values); quantized to ``kv_dtype`` where given, the poison
    then in the scale rows.  Returns ``(k_pool, v_pool, kw)``."""
    nb, bs, KVH, D = shape
    k_pool, v_pool = _bf16_values(rng, *shape), _bf16_values(rng, *shape)
    k_pool[0], v_pool[0] = 1e3, -1e3
    k_pool[-1], v_pool[-1] = 1e3, np.nan
    if kv_dtype is None:
        return k_pool, v_pool, {}
    pools = []
    for pool in (k_pool, v_pool):
        pool[-1] = 1e3                  # the poison rides in the scale
        codes, scale = quantize_kv(
            jnp.asarray(pool).reshape(nb * bs, KVH, D), kv_dtype)
        scale = scale.reshape(nb, bs).at[-1].set(jnp.nan)
        pools.append((codes.reshape(pool.shape), scale))
    (k_pool, k_scale), (v_pool, v_scale) = pools
    return k_pool, v_pool, dict(k_scale=k_scale, v_scale=v_scale,
                                kv_cache_dtype=kv_dtype)


def _ragged_operands(bs, kv_dtype=None, dead="own", seed=0, KVH=2, rep=2,
                     D=8):
    """One batch with every edge of the walk in it: an idle slot (length
    0, a table row of zeros), a length that ends in the middle of a page,
    in the middle of a compute block and on a compute block's last
    token, a sequence at the table's end, and one of two pages.

    ``dead`` says what the table holds PAST each sequence's live pages:
    ``"own"`` blocks of benign data, ``"poison"`` (one block of 1e3 keys
    and NaN values) or ``"outside"`` (an id beyond the pool).  Block 0
    is the garbage block, as in ``_decode_operands``."""
    G = max(1, 128 // bs)               # pages of one compute block
    nbs = 2 * G + 8
    positions = np.array([0, bs + bs // 2, (G + G // 2) * bs + 1,
                          2 * G * bs - 1, nbs * bs - 1, 2 * bs - 1],
                         np.int32)
    B, H = len(positions), KVH * rep
    nb = 2 + B * nbs                    # garbage block, B rows, poison
    rng = np.random.RandomState(seed)
    q, k_new, v_new = (_bf16_values(rng, B, 1, n, D) for n in (H, KVH, KVH))
    table = (1 + np.arange(B * nbs)).reshape(B, nbs).astype(np.int32)
    if dead != "own":
        past = np.arange(nbs)[None, :] > positions[:, None] // bs
        table[past] = {"poison": nb - 1, "outside": nb + 3}[dead]
    table[0] = 0                        # the idle slot
    k_pool, v_pool, kw = _trapped_pools(rng, (nb, bs, KVH, D), kv_dtype)
    args = (q, k_new, v_new, k_pool, v_pool, table, positions,
            *_rope_tables(nbs * bs + 1, D))
    return tuple(jnp.asarray(a) for a in args), kw


class TestPagedDecodeRaggedWalk:
    @pytest.mark.parametrize("kv_dtype", _POOL_KINDS, ids=_POOL_IDS)
    @pytest.mark.parametrize("num_splits", [1, 2, 4, 8])
    @pytest.mark.parametrize("bs", [4, 16])
    def test_three_way_parity(self, bs, num_splits, kv_dtype):
        args, kw = _ragged_operands(bs, kv_dtype)
        ref = paged_decode_reference(*args, **kw)
        for use_pallas in (True, False):
            got = fused_paged_decode(*args, num_splits=num_splits,
                                     use_pallas=use_pallas, interpret=True,
                                     **kw)
            np.testing.assert_allclose(np.asarray(got[0]),
                                       np.asarray(ref[0]),
                                       rtol=2e-5, atol=2e-5)
            for pool, ref_pool in zip(got[1:3], ref[1:3]):
                np.testing.assert_array_equal(np.asarray(pool),
                                              np.asarray(ref_pool))

    @pytest.mark.parametrize("kv_dtype", _POOL_KINDS, ids=_POOL_IDS)
    @pytest.mark.parametrize("dead", ["poison", "outside"])
    def test_walk_ends_at_the_length(self, dead, kv_dtype):
        # table entries past a sequence's live pages are never read: a
        # fetched NaN value would survive its zero weight (0 * NaN), and
        # the interpreter clamps an id beyond the pool onto the NaN block
        outs = []
        for past in ("own", dead):
            args, kw = _ragged_operands(16, kv_dtype, dead=past)
            outs.append(np.asarray(fused_paged_decode(
                *args, num_splits=2, use_pallas=True, interpret=True,
                **kw)[0]))
        assert np.isfinite(outs[0]).all()
        np.testing.assert_array_equal(outs[1], outs[0])


# ---------------------------------------------------------------------------
# prefill chunks: the Pallas walk is bounded by the chunk's own context
# ---------------------------------------------------------------------------

_CHUNK = 24                             # query tokens a sequence


def _chunk_walk_operands(bs, kv_dtype=None, dead="own", mask_block=1,
                         KVH=2, rep=4, D=8, seed=0):
    """One batch of chunks with every edge of the walk in it: a chunk
    that starts at 0, contexts that end in the middle of a page, in the
    middle of a compute block, on a compute block's last key and one key
    into the next, the table's last chunk, and one whose padded tail
    runs a page past the table's end (the clamp).

    ``dead`` says what the table holds PAST each chunk's live pages
    (``ceil((start + T) / bs)`` of them), as in ``_ragged_operands``.
    Block 0 is the garbage block; the last is the poison."""
    G = max(1, 128 // bs)
    K, T = G * bs, _CHUNK
    nbs = 3 * G
    ends = [T, K + bs + bs // 2, K + K // 2, 2 * K, K + 1, nbs * bs,
            nbs * bs + bs]
    starts = np.array([e - T for e in ends], np.int32)
    starts -= starts % mask_block       # a chunk holds whole mask blocks
    B, H = len(starts), KVH * rep
    nb = 2 + B * nbs
    rng = np.random.RandomState(seed)
    q = _bf16_values(rng, B, T, H, D)
    table = (1 + np.arange(B * nbs)).reshape(B, nbs).astype(np.int32)
    if dead != "own":
        past = np.arange(nbs)[None, :] >= -(-(starts + T) // bs)[:, None]
        table[past] = {"poison": nb - 1, "outside": nb + 3}[dead]
    k_pool, v_pool, kw = _trapped_pools(rng, (nb, bs, KVH, D), kv_dtype)
    args = (q, k_pool, v_pool, table, starts)
    return tuple(jnp.asarray(a) for a in args), kw


def _chunk_gather_reference(q, k_pool, v_pool, table, starts, *,
                            mask_block=1, k_scale=None, v_scale=None,
                            kv_cache_dtype=None):
    """models/llama.py's unfused ``_paged_attn`` gather path (a quantized
    pool dequantized whole, the KV heads repeated to H, a full softmax),
    with the block-causal mask of models/sdar_moe.py where asked."""
    B, T, H, D = q.shape
    if kv_cache_dtype is not None:
        k_pool = decode_codes(k_pool, kv_cache_dtype) \
            * k_scale[:, :, None, None]
        v_pool = decode_codes(v_pool, kv_cache_dtype) \
            * v_scale[:, :, None, None]
    rep = H // k_pool.shape[2]
    kb = jnp.repeat(k_pool[table].reshape(B, -1, *k_pool.shape[2:]), rep, 2)
    vb = jnp.repeat(v_pool[table].reshape(B, -1, *v_pool.shape[2:]), rep, 2)
    scores = jnp.einsum("bthd,bshd->bhts", q, kb) / math.sqrt(D)
    q_pos = starts[:, None] + jnp.arange(T)
    last_seen = q_pos - q_pos % mask_block + mask_block - 1
    seen = jnp.arange(kb.shape[1])[None, None, :] <= last_seen[:, :, None]
    probs = jax.nn.softmax(jnp.where(seen[:, None], scores, -1e30), axis=-1)
    return jnp.einsum("bhts,bshd->bthd", probs, vb)


class TestChunkedPrefillLiveWalk:
    def _three_way(self, args, kw, mask_block):
        ref = _chunk_gather_reference(*args, mask_block=mask_block, **kw)
        for use_pallas in (True, False):
            got = fused_chunked_attention(*args, use_pallas=use_pallas,
                                          interpret=True,
                                          mask_block=mask_block, **kw)
            np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                                       rtol=2e-5, atol=2e-5)

    @pytest.mark.parametrize("kv_dtype", _POOL_KINDS, ids=_POOL_IDS)
    @pytest.mark.parametrize("mask_block", [1, 4])
    @pytest.mark.parametrize("bs", [4, 16])
    def test_three_way_parity(self, bs, mask_block, kv_dtype):
        args, kw = _chunk_walk_operands(bs, kv_dtype, mask_block=mask_block)
        self._three_way(args, kw, mask_block)

    @pytest.mark.parametrize("mask_block", [1, 4])
    @pytest.mark.parametrize("KVH,rep", [(1, 8), (4, 1)],
                             ids=["gqa8", "mha"])
    def test_three_way_parity_by_heads(self, KVH, rep, mask_block):
        args, kw = _chunk_walk_operands(16, mask_block=mask_block, KVH=KVH,
                                        rep=rep)
        self._three_way(args, kw, mask_block)

    @pytest.mark.parametrize("kv_dtype", _POOL_KINDS, ids=_POOL_IDS)
    @pytest.mark.parametrize("mask_block", [1, 4])
    @pytest.mark.parametrize("dead", ["poison", "outside"])
    def test_walk_ends_at_the_context(self, dead, mask_block, kv_dtype):
        # table entries past a chunk's live pages are never read: a
        # fetched NaN value would survive its zero weight (0 * NaN), and
        # the interpreter clamps an id beyond the pool onto the NaN block
        outs = []
        for past in ("own", dead):
            args, kw = _chunk_walk_operands(16, kv_dtype, dead=past,
                                            mask_block=mask_block)
            outs.append(np.asarray(fused_chunked_attention(
                *args, use_pallas=True, interpret=True,
                mask_block=mask_block, **kw)))
        assert np.isfinite(outs[0]).all()
        np.testing.assert_array_equal(outs[1], outs[0])

    @pytest.mark.parametrize("mask_block", [1, 4])
    def test_padded_tail_over_unallocated_pages(self, mask_block):
        # a prompt's last chunk: the pages past its real tokens were
        # never allocated (the table says 0, the garbage block, where the
        # padding's K/V went); the real rows see none of it
        bs, real = 16, 8
        args, kw = _chunk_walk_operands(bs, mask_block=mask_block)
        q, k_pool, v_pool, table, starts = args
        past = np.arange(table.shape[1])[None, :] \
            >= -(-(np.asarray(starts) + real) // bs)[:, None]
        padded = jnp.where(past, 0, table)
        outs = [np.asarray(fused_chunked_attention(
            q, k_pool, v_pool, t, starts, use_pallas=True, interpret=True,
            mask_block=mask_block)) for t in (table, padded)]
        ref = _chunk_gather_reference(q, k_pool, v_pool, padded, starts,
                                      mask_block=mask_block)
        np.testing.assert_array_equal(outs[1][:, :real], outs[0][:, :real])
        # the padding's rows average the garbage block's 1e3s, the same
        # discarded garbage on every path
        np.testing.assert_allclose(outs[1], np.asarray(ref), rtol=2e-4,
                                   atol=2e-5)

    @pytest.mark.parametrize("KVH,rep,tiles", [(8, 4, 2), (32, 1, 3)])
    def test_query_rows_tiled_over_the_grid(self, KVH, rep, tiles):
        # more rows than one cell holds: each row tile walks the pages
        # itself, and a tile's rows keep their own positions
        from paddle_tpu.kernels.chunked_prefill import _row_tile

        T, bs, D = tiles * 2048 // (KVH * rep), 16, 8
        assert (rep * T) // _row_tile(rep * T, KVH) == tiles
        rng = np.random.RandomState(1)
        q = jnp.asarray(rng.randn(2, T, KVH * rep, D), jnp.float32)
        k_pool, v_pool = (jnp.asarray(rng.randn(40, bs, KVH, D), jnp.float32)
                          for _ in range(2))
        table = jnp.asarray(rng.permutation(39)[:2 * 16].reshape(2, 16) + 1,
                            jnp.int32)
        starts = jnp.asarray([0, 16 * bs - T], jnp.int32)
        self._three_way((q, k_pool, v_pool, table, starts), {}, 1)


# ---------------------------------------------------------------------------
# RMSNorm -> matmul epilogue fusion
# ---------------------------------------------------------------------------

def _norm_linear_oracle(x, nw, w, eps, act):
    """Independent numpy oracle for the module's math contract."""
    xf = np.asarray(x, np.float64).astype(np.float32)
    rs = 1.0 / np.sqrt(np.mean(xf * xf, axis=-1, keepdims=True) + eps)
    normed = (xf * rs).astype(np.asarray(x).dtype) * np.asarray(nw)
    z = normed.astype(np.float32) @ np.asarray(w, np.float32)
    if act == "silu":
        z = z / (1.0 + np.exp(-z))
    return z.astype(np.asarray(x).dtype)


class TestFusedNormLinear:
    @pytest.mark.parametrize("act", ["none", "silu"])
    @pytest.mark.parametrize("jitted", [False, True])
    def test_parity_vs_oracle(self, act, jitted):
        # one form on every backend (PR 37 took the Pallas kernel out):
        # op by op and as XLA fuses it under jit
        rng = np.random.RandomState(0)
        x = jnp.asarray(rng.randn(8, 16).astype(np.float32))
        nw = jnp.asarray(rng.randn(16).astype(np.float32))
        w = jnp.asarray(rng.randn(16, 32).astype(np.float32))
        eps = 1e-5
        fn = functools.partial(fused_rmsnorm_linear, eps=eps, activation=act)
        got = (jax.jit(fn) if jitted else fn)(x, nw, w)
        want = _norm_linear_oracle(x, nw, w, eps, act)
        np.testing.assert_allclose(np.asarray(got), want,
                                   rtol=2e-5, atol=2e-5)

    def test_shared_row_scale_matches_per_projection(self):
        # one rms_scale reused by several projections (the llama fused
        # attention-in boundary) == recomputing it per projection
        rng = np.random.RandomState(1)
        x = jnp.asarray(rng.randn(4, 16).astype(np.float32))
        nw = jnp.asarray(rng.randn(16).astype(np.float32))
        eps = 1e-6
        rs = rms_scale(x, eps)
        for n in (8, 24):
            w = jnp.asarray(rng.randn(16, n).astype(np.float32))
            shared = fused_norm_linear(x, rs, nw, w)
            solo = fused_rmsnorm_linear(x, nw, w, eps)
            np.testing.assert_array_equal(np.asarray(shared),
                                          np.asarray(solo))

    def test_bad_activation_rejected(self):
        x = jnp.zeros((4, 8))
        with pytest.raises(ValueError, match="activation"):
            fused_rmsnorm_linear(x, jnp.ones((8,)), jnp.zeros((8, 8)),
                                 1e-5, activation="tanhh")

    @pytest.mark.parametrize("act", ["none", "silu"])
    @pytest.mark.parametrize("K", [512, 768])
    @pytest.mark.parametrize("rows", [8, 16, 32, 256])
    def test_bf16_against_float64_oracle(self, rows, K, act):
        # bf16 operands into the product, float32 accumulation: against
        # the contract in float64 with the contract's own roundings,
        # within one bf16 rounding of the output; compiled and op by op
        rng = np.random.RandomState(rows + K)
        N = 384
        x = jnp.asarray(rng.randn(rows, K), jnp.bfloat16)
        nw = jnp.asarray(1.0 + 0.1 * rng.randn(K), jnp.bfloat16)
        w = jnp.asarray(rng.randn(K, N) * K ** -0.5, jnp.bfloat16)
        rs = rms_scale(x, 1e-5)

        def bf16(a):
            return np.asarray(jnp.asarray(a, jnp.bfloat16), np.float64)

        f64 = functools.partial(np.asarray, dtype=np.float64)
        normed = bf16(bf16(f64(x) * f64(rs)) * f64(nw))
        z = normed @ f64(w)
        if act == "silu":
            z = z / (1.0 + np.exp(-z))
        fn = functools.partial(fused_norm_linear, activation=act)
        for form in (fn, jax.jit(fn)):
            got = form(x, rs, nw, w)
            assert got.dtype == jnp.bfloat16 and got.shape == (rows, N)
            # a rounding to bf16 is at most 2^-9 of the value; the
            # float32 sum's own error is what the absolute term allows
            np.testing.assert_allclose(np.asarray(got, np.float64), z,
                                       rtol=2.0 ** -8, atol=1e-4)

    @staticmethod
    def _the_product(x, nw, w):
        jaxpr = jax.make_jaxpr(fused_norm_linear)(
            x, jax.ShapeDtypeStruct(x.shape[:-1] + (1,), jnp.float32), nw, w)
        dots = [e for e in jaxpr.jaxpr.eqns
                if e.primitive.name == "dot_general"]
        assert len(dots) == 1
        assert not any(e.primitive.name == "pallas_call"
                       for e in jaxpr.jaxpr.eqns)
        return ([v.aval.dtype for v in dots[0].invars],
                dots[0].outvars[0].aval.dtype, jaxpr.out_avals[0])

    @pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32])
    def test_product_takes_its_operands_in_their_own_dtype(self, dtype):
        # what the MXU is handed: bf16 x bf16 -> float32 in every cell,
        # float32 operands for a float32 caller (tier-1, tiny configs)
        spec = functools.partial(jax.ShapeDtypeStruct, dtype=dtype)
        operands, result, _ = self._the_product(
            spec((32, 256)), spec((256,)), spec((256, 128)))
        assert operands == [dtype, dtype] and result == jnp.float32

    def test_the_narrower_operand_is_widened_to_the_other(self):
        operands, result, out = self._the_product(
            jax.ShapeDtypeStruct((4, 256), jnp.float32),
            jax.ShapeDtypeStruct((256,), jnp.float32),
            jax.ShapeDtypeStruct((256, 128), jnp.bfloat16))
        assert operands == [jnp.float32] * 2 and result == jnp.float32
        assert out.dtype == jnp.float32

    def test_every_served_shape_feeds_bf16_to_one_product(self):
        # every (rows, K, N) a served configuration calls it with: a
        # decode run's rows and a chunk's 256; traced only, nothing this
        # size runs here
        spec = functools.partial(jax.ShapeDtypeStruct, dtype=jnp.bfloat16)
        for rows, K, widths in (
                ((32, 256), 4096, (4096, 1024, 14336)),            # Mistral
                ((128, 256), 2048, (4096, 512)),                   # SDAR
                ((16, 256), 2048, (4096, 512, 6144, 1024)),        # Trinity
                ((8, 256), 2048, (768, 576, 10240, 1536)),         # GLM
                ((8, 256), 768, (5120,))):
            for M in rows:
                for N in widths:
                    operands, result, out = self._the_product(
                        spec((1, M, K)), spec((K,)), spec((K, N)))
                    assert operands == [jnp.bfloat16] * 2
                    assert result == jnp.float32
                    assert out.shape == (1, M, N)
                    assert out.dtype == jnp.bfloat16


# ---------------------------------------------------------------------------
# engine integration: token parity + zero retraces + distinct caches
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def model():
    paddle.seed(0)
    m = LlamaForCausalLM(LlamaConfig.tiny())
    m.eval()
    return m


class TestFusedEngine:
    def test_token_parity_and_zero_retraces(self, model):
        from paddle_tpu.serving import Engine, ServingConfig

        rng = np.random.RandomState(0)
        prompts = [rng.randint(1, 256, size=(L,)).astype(np.int32)
                   for L in (3, 9, 6)]
        max_new = 8
        outs = {}
        for fused in (True, False):
            eng = Engine(model, ServingConfig(
                max_batch_size=4, block_size=8, num_blocks=64,
                fused_kernels=fused))
            reqs = [eng.submit(p, max_new_tokens=max_new)
                    for p in prompts]
            eng.run_until_complete()
            outs[fused] = [r.output_ids()[r.prompt_len:].tolist()
                           for r in reqs]
            assert eng._decode_step.retraces == 0
            assert eng._prefill_step.retraces == 0
            eng.pool.check_leaks()
        assert outs[True] == outs[False]

        # ... and both agree with the whole-sequence generate() oracle
        for prompt, got in zip(prompts, outs[True]):
            ref = model.generate(paddle.to_tensor(prompt[None, :]),
                                 max_new_tokens=max_new, temperature=0.0)
            ref_new = np.asarray(ref.numpy())[0, len(prompt):].tolist()
            assert got == ref_new

    def test_fused_and_unfused_steps_cached_separately(self, model):
        from paddle_tpu.models.generation import (make_chunked_prefill_step,
                                                  make_paged_decode_step)

        dec_f = make_paged_decode_step(model, fused=True)
        dec_u = make_paged_decode_step(model, fused=False)
        assert dec_f is not dec_u
        # same mode -> same cached step (no rebuild, no retrace risk)
        assert make_paged_decode_step(model, fused=True) is dec_f
        assert make_paged_decode_step(model, fused=False) is dec_u
        pre_f = make_chunked_prefill_step(model, fused=True)
        pre_u = make_chunked_prefill_step(model, fused=False)
        assert pre_f is not pre_u
        assert make_chunked_prefill_step(model, fused=True) is pre_f


# ---------------------------------------------------------------------------
# kernel-cost registry: validated at registration
# ---------------------------------------------------------------------------

class TestKernelCostValidation:
    def test_zero_bytes_rejected(self):
        with pytest.raises(ValueError,
                           match="every kernel touches memory"):
            KernelCost(flops=1.0, bytes_accessed=0.0)

    def test_negative_bytes_rejected(self):
        with pytest.raises(ValueError, match="bytes_accessed"):
            KernelCost(flops=1.0, bytes_accessed=-4.0)

    def test_negative_flops_rejected(self):
        with pytest.raises(ValueError, match="flops"):
            KernelCost(flops=-1.0, bytes_accessed=8.0)

    def test_nan_flops_rejected(self):
        with pytest.raises(ValueError, match="flops"):
            KernelCost(flops=float("nan"), bytes_accessed=8.0)

    def test_unknown_dtype_rejected(self):
        with pytest.raises(ValueError, match="dtype"):
            KernelCost(flops=1.0, bytes_accessed=8.0,
                       dtype="float17")

    def test_non_kernelcost_return_fails_registration(self):
        with pytest.raises(TypeError, match="expected KernelCost"):
            register_kernel_cost(
                "bogus_kernel", lambda i, o: 42.0,
                sample_in=[((4, 4), "float32")],
                sample_out=[((4, 4), "float32")])
        assert "bogus_kernel" not in registered_kernels()

    def test_raising_cost_fn_fails_registration(self):
        def bad(i, o):
            raise KeyError("missing operand")

        with pytest.raises(KeyError):
            register_kernel_cost("bogus_kernel2", bad,
                                 sample_in=[((4,), "float32")],
                                 sample_out=[((4,), "float32")])
        assert "bogus_kernel2" not in registered_kernels()

    def test_empty_name_rejected(self):
        with pytest.raises(ValueError, match="non-empty"):
            register_kernel_cost(
                "", lambda i, o: KernelCost(flops=1.0, bytes_accessed=1.0),
                sample_in=[], sample_out=[])

    def test_serving_kernels_registered(self):
        assert "fused_paged_decode" in registered_kernels()
        assert "fused_chunked_prefill" in registered_kernels()


# ---------------------------------------------------------------------------
# analysis layer: pallas_call priced (xray) and planned (shardplan)
# ---------------------------------------------------------------------------

class TestAnalysisPricesPallas:
    def _closed_fused_jaxpr(self):
        args = _decode_operands()
        fn = functools.partial(fused_paged_decode, num_splits=2,
                               use_pallas=True, interpret=True)
        return jax.make_jaxpr(fn)(*args), args

    def test_xray_prices_pallas_call_from_registry(self):
        from paddle_tpu.analysis import xray
        from paddle_tpu.kernels.costs import price_eqn_avals

        args = _decode_operands()
        fn = functools.partial(fused_paged_decode, num_splits=2,
                               use_pallas=True, interpret=True)
        report = xray.analyze(fn, list(args), chip="cpu",
                              name="kernel::fused_paged_decode")
        ops = {o.primitive: o for o in report.ops}
        assert "pallas_call:fused_paged_decode" in ops
        op = ops["pallas_call:fused_paged_decode"]
        assert op.count == 1
        # the price must be the REGISTRY's, not a generic guess: B=2,
        # H=4, D=8, L=16 -> flops = 4*B*H*D*L
        assert op.flops == 4.0 * 2 * 4 * 8 * 16
        assert op.bytes > 0
        assert not report.errors()

    def test_xray_does_not_recurse_into_block_jaxpr(self):
        # the kernel body is written in BLOCK shapes; recursing would
        # multiply every inner eqn by the grid.  The eqn count must stay
        # flat whether the kernel runs 2 or 4 splits.
        from paddle_tpu.analysis import xray

        args = _decode_operands()
        reports = [
            xray.analyze(functools.partial(fused_paged_decode,
                                           num_splits=s, use_pallas=True,
                                           interpret=True),
                         list(args), chip="cpu")
            for s in (2, 4)]
        assert reports[0].n_eqns == reports[1].n_eqns

    def test_shardplan_pallas_is_priced_leaf_no_s210(self):
        from paddle_tpu.analysis import shardplan

        closed, _ = self._closed_fused_jaxpr()
        r = shardplan.plan_jaxpr(
            closed, [None] * len(closed.jaxpr.invars),
            mesh={"data": 2, "tp": 2}, name="fused_decode_kernel")
        codes = [d.code for d in r.diagnostics]
        assert "S210" not in codes
        assert not r.errors()
        assert all(c.planned for c in r.collectives)

    def test_audit_default_steps_fused(self):
        from paddle_tpu.analysis import xray

        reports = xray.audit_default_steps(chip="cpu", fused=True)
        names = [r.name for r in reports]
        assert "serving::decode_step[fused]" in names
        assert "serving::prefill_step[fused]" in names
        assert "kernel::fused_paged_decode" in names
        assert not any(r.errors() for r in reports)
        kernel = reports[names.index("kernel::fused_paged_decode")]
        assert any(o.primitive == "pallas_call:fused_paged_decode"
                   for o in kernel.ops)


# ---------------------------------------------------------------------------
# autotune cache: chip-qualified keys, the retune escape hatch
# ---------------------------------------------------------------------------

@pytest.fixture
def clean_autotune(tmp_path, monkeypatch):
    monkeypatch.setenv("PADDLE_TPU_CACHE_DIR", str(tmp_path))
    monkeypatch.delenv("PADDLE_TPU_RETUNE", raising=False)
    saved = dict(at._mem_cache)
    at._mem_cache.clear()
    at.set_retune(False)
    yield tmp_path
    at.set_retune(False)
    at._mem_cache.clear()
    at._mem_cache.update(saved)


class TestAutotuneCache:
    def test_cache_key_is_chip_qualified(self):
        key = at.cache_key("paged_attn_decode", 64, 16, "float32")
        assert key.startswith(f"{at._chip()}|paged_attn_decode|")
        assert key.endswith("64|16|float32")

    def test_winner_cached_and_persisted(self, clean_autotune):
        calls = []

        def run(cfg):
            calls.append(cfg)

        best = at.autotune("op_x", (1, 2), [(1,), (2,)], run,
                           warmup=1, iters=1)
        assert best in ((1,), (2,))
        n_search = len(calls)
        assert n_search == 4                      # 2 cfgs x (1 warm + 1)
        # second call: pure cache hit, zero measurements
        again = at.autotune("op_x", (1, 2), [(1,), (2,)], run,
                            warmup=1, iters=1)
        assert again == best and len(calls) == n_search
        # ... and the winner survived to the JSON cache on disk
        disk = json.load(open(os.path.join(str(clean_autotune),
                                           "autotune.json")))
        assert disk[at.cache_key("op_x", 1, 2)] == list(best)

    def test_set_retune_remeasures(self, clean_autotune):
        calls = []
        at.autotune("op_y", ("k",), [(8,)], calls.append,
                    warmup=0, iters=1)
        n = len(calls)
        at.set_retune(True)
        assert at.retune_enabled()
        at.autotune("op_y", ("k",), [(8,)], calls.append,
                    warmup=0, iters=1)
        assert len(calls) > n
        at.set_retune(False)

    def test_retune_env_var(self, clean_autotune, monkeypatch):
        assert not at.retune_enabled()
        monkeypatch.setenv("PADDLE_TPU_RETUNE", "1")
        assert at.retune_enabled()

    def test_failing_candidates_skipped(self, clean_autotune):
        def run(cfg):
            if cfg == (1,):
                raise RuntimeError("unsupported tile")

        best = at.autotune("op_z", (), [(1,), (2,)], run,
                           warmup=0, iters=1)
        assert best == (2,)
