# lint-tpu: disable-file=L004 -- grandfathered direct jax use; new backend code belongs under core/ ops/ kernels/ static/ distributed/ (README: Repo lint)
"""Llama model family (Llama-2/3 architecture) — the flagship pretrain and
serving model.

The 2022 reference snapshot predates Llama; its closest analogs are the
fused transformer ops (/root/reference/paddle/fluid/operators/fused/
fused_multi_transformer_op.cu) and the Fleet mp_layers the model composes
with.  TPU-native design:
  - weights bf16, attention via the Pallas flash kernel (paddle_tpu/kernels)
  - RMSNorm via the fused Pallas kernel
  - tensor parallel through GSPMD-annotated Column/RowParallel layers
  - sequence axis shardable ("sp") for context parallelism
  - rotary embeddings precomputed once per max_position
"""
from __future__ import annotations

import math
from dataclasses import dataclass, field
from typing import Optional

import jax
import jax.numpy as jnp

from .. import nn
from ..core.dispatch import apply
from ..core.tensor import Tensor
from ..distributed.parallel_layers import (ColumnParallelLinear,
                                           RowParallelLinear,
                                           VocabParallelEmbedding)
from ..nn import functional as F


@dataclass
class LlamaConfig:
    vocab_size: int = 32000
    hidden_size: int = 4096
    intermediate_size: int = 11008
    num_hidden_layers: int = 32
    num_attention_heads: int = 32
    num_key_value_heads: int = 32
    max_position_embeddings: int = 4096
    rms_norm_eps: float = 1e-5
    rope_theta: float = 10000.0
    tie_word_embeddings: bool = False
    use_flash_attention: bool = True
    sequence_parallel: bool = False
    # Chunked fused lm-head + cross-entropy: the [B,T,V] logits are never
    # materialized in HBM (computed per token-chunk under remat).  Saves
    # ~4x vocab*tokens bytes of activation memory on the pretrain path;
    # forward(labels=...) then returns (loss, None).  Opt-in (off by
    # default) because callers that consume logits — token accuracy,
    # per-token ppl, distillation — would silently get None.
    fused_lm_loss: bool = False
    lm_loss_chunk: int = 2048
    # Per-decoder-layer activation rematerialization (reference:
    # fleet/utils/recompute.py) — XLA recomputes the layer in backward,
    # cutting live activations to ~one layer's worth.
    recompute: bool = False
    # Mixture-of-experts MLP (GShard-style top-k routing through
    # kernels/moe_dispatch; reference analog: incubate moe_layer over
    # global_scatter/global_gather).  0 experts = dense LlamaMLP.
    moe_num_experts: int = 0
    moe_top_k: int = 2
    moe_capacity_factor: float = 2.0
    # Sequence/context parallelism for the no-cache attention path:
    # "" (dense), "ring" (kernels/ring_attention) or "ulysses".  Falls
    # back to dense attention when the active mesh has no `sp` axis.
    context_parallel: str = ""
    dtype: str = "bfloat16"

    @staticmethod
    def llama3_8b(**overrides):
        cfg = LlamaConfig(
            vocab_size=128256, hidden_size=4096, intermediate_size=14336,
            num_hidden_layers=32, num_attention_heads=32,
            num_key_value_heads=8, max_position_embeddings=8192,
            rope_theta=500000.0)
        for k, v in overrides.items():
            setattr(cfg, k, v)
        return cfg

    @staticmethod
    def tiny(**overrides):
        cfg = LlamaConfig(
            vocab_size=256, hidden_size=64, intermediate_size=128,
            num_hidden_layers=2, num_attention_heads=4, num_key_value_heads=2,
            max_position_embeddings=128, dtype="float32")
        for k, v in overrides.items():
            setattr(cfg, k, v)
        return cfg


def _pallas_kernels_on() -> bool:
    """The training-path kernels (rms_norm, fused_rope, flash
    attention): on a TPU, outside a mesh."""
    from ..distributed.mesh import mesh_live
    from ..kernels.fusion import pallas_lowering

    return pallas_lowering()[0] and not mesh_live()


def precompute_rope(head_dim, max_pos, theta):
    inv_freq = 1.0 / (theta ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32)
                                / head_dim))
    t = jnp.arange(max_pos, dtype=jnp.float32)
    freqs = jnp.outer(t, inv_freq)  # [T, D/2]
    return jnp.cos(freqs), jnp.sin(freqs)


def apply_rope(x, cos, sin, position_offset=0):
    """x: [B, T, H, D].  Rotate-half convention.  position_offset may be
    a traced scalar (static-cache decode compiles ONE step program) or a
    traced [B] vector of per-sequence positions (continuous-batching
    decode: every sequence in the bucket sits at its own frontier)."""
    T = x.shape[1]
    if jnp.ndim(position_offset):
        pos = jnp.asarray(position_offset)[:, None] + jnp.arange(T)
        c = cos[pos][:, :, None, :]     # [B, T, 1, D/2]
        s = sin[pos][:, :, None, :]
        x1, x2 = jnp.split(x, 2, axis=-1)
        out = jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s], axis=-1)
        return out.astype(x.dtype)
    c = jax.lax.dynamic_slice_in_dim(cos, position_offset, T)[
        None, :, None, :]
    s = jax.lax.dynamic_slice_in_dim(sin, position_offset, T)[
        None, :, None, :]
    x1, x2 = jnp.split(x, 2, axis=-1)
    out = jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s], axis=-1)
    return out.astype(x.dtype)


class StaticKVCache:
    """Preallocated decode cache (TPU-native: a concat-growing cache
    changes shape every token, forcing an XLA recompile per step; a
    fixed-size buffer + dynamic_update_slice keeps ONE compiled decode
    program for the whole generation).  The reference's analog is the
    ring buffer inside fused_multi_transformer_op.cu's CacheKV."""

    __slots__ = ("k", "v")

    def __init__(self, k, v):
        self.k = k  # [B, max_len, kv_heads, head_dim]
        self.v = v

    @staticmethod
    def empty(batch, max_len, kv_heads, head_dim, dtype):
        z = jnp.zeros((batch, max_len, kv_heads, head_dim), dtype)
        return StaticKVCache(z, z)

    def tree_flatten(self):
        return (self.k, self.v), None

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(*children)


jax.tree_util.register_pytree_node(
    StaticKVCache, lambda c: c.tree_flatten(),
    StaticKVCache.tree_unflatten)


class PagedKVCache:
    """Block-pool cache view for continuous-batching decode (the serving
    engine's substrate; PAPERS.md: vLLM's PagedAttention over Orca's
    iteration-level scheduling).  ``k``/``v`` are SHARED physical pools of
    shape [num_blocks, block_size, kv_heads, head_dim]; ``block_table``
    [B, max_blocks] maps each sequence's logical block i to a pool block
    id.  Per-sequence write frontiers ride in as the (vector)
    ``position_offset`` of the forward call, exactly as the scalar offset
    does for :class:`StaticKVCache` — every shape is fixed, so ONE
    compiled decode step serves every mix of sequences forever.

    Unallocated/retired table entries may point anywhere (the engine uses
    a reserved garbage block): attention masks keys past each sequence's
    frontier, so stale pool contents are never observable.

    Quantized pools (``kv_dtype`` of ``"int8"``/``"fp8"``) carry int8
    CODE pools plus per-(block, token)-row f32 absmax scales
    (``k_scale``/``v_scale`` [num_blocks, block_size]); writes quantize
    in-trace and reads dequantize at the kernel DMA boundary
    (kernels/kv_quant.py).  ``kv_dtype`` is pytree aux data, so fp32
    and quantized caches trace as DIFFERENT treedefs and can never
    silently share a compiled step.
    """

    __slots__ = ("k", "v", "block_table", "k_scale", "v_scale",
                 "kv_dtype")

    def __init__(self, k, v, block_table, k_scale=None, v_scale=None,
                 kv_dtype=None):
        self.k = k              # [num_blocks, block_size, kv_heads, head_dim]
        self.v = v
        self.block_table = block_table      # [B, max_blocks] int32
        self.k_scale = k_scale  # [num_blocks, block_size] f32 or None
        self.v_scale = v_scale
        self.kv_dtype = kv_dtype            # None / "int8" / "fp8"

    @property
    def block_size(self):
        return self.k.shape[1]

    def tree_flatten(self):
        return (self.k, self.v, self.block_table, self.k_scale,
                self.v_scale), self.kv_dtype

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(*children, kv_dtype=aux)


jax.tree_util.register_pytree_node(
    PagedKVCache, lambda c: c.tree_flatten(),
    PagedKVCache.tree_unflatten)


def paged_scatter(pool, new, block_table, pos, wmask=None):
    """Write per-position rows into a block pool through the table:
    pool [nb, bs, ...]; new [B, T, ...] → flat row index
    block_table[b, pos//bs]*bs + pos%bs per position ``pos [B, T]``.
    The column clamp keeps padded positions past the table width in
    range (their write is already redirected to garbage by ``wmask``
    before it could land anywhere real); where ``wmask [B, T]`` is
    False the row lands in the reserved garbage block 0."""
    nb, bs = pool.shape[0], pool.shape[1]
    rows = jnp.arange(block_table.shape[0])[:, None]
    col = jnp.minimum(pos // bs, block_table.shape[1] - 1)
    idx = block_table[rows, col] * bs + pos % bs            # [B, T]
    if wmask is not None:
        idx = jnp.where(wmask, idx, 0)
    flat = pool.reshape(nb * bs, *pool.shape[2:])
    flat = flat.at[idx.reshape(-1)].set(
        new.reshape(-1, *new.shape[2:]).astype(pool.dtype))
    return flat.reshape(pool.shape)


class LlamaRMSNorm(nn.Layer):
    def __init__(self, hidden_size, eps=1e-5):
        super().__init__()
        from ..nn import initializer as I

        self._epsilon = eps
        self.weight = self.create_parameter(
            [hidden_size], default_initializer=I.Constant(1.0))

    def forward(self, x):
        def _rms(v, w):
            if _pallas_kernels_on():
                from ..kernels.rms_norm import rms_norm as pallas_rms

                return pallas_rms(v, w, self._epsilon)
            var = jnp.mean(jnp.square(v.astype(jnp.float32)), axis=-1,
                           keepdims=True)
            return (v.astype(jnp.float32) * jax.lax.rsqrt(
                var + self._epsilon)).astype(v.dtype) * w
        return apply("rms_norm", _rms, x, self.weight)


class LlamaAttention(nn.Layer):
    def __init__(self, config: LlamaConfig):
        super().__init__()
        self.config = config
        self.num_heads = config.num_attention_heads
        self.num_kv_heads = config.num_key_value_heads
        self.head_dim = config.hidden_size // config.num_attention_heads
        h = config.hidden_size
        self.q_proj = ColumnParallelLinear(
            h, self.num_heads * self.head_dim, has_bias=False,
            gather_output=False)
        self.k_proj = ColumnParallelLinear(
            h, self.num_kv_heads * self.head_dim, has_bias=False,
            gather_output=False)
        self.v_proj = ColumnParallelLinear(
            h, self.num_kv_heads * self.head_dim, has_bias=False,
            gather_output=False)
        self.o_proj = RowParallelLinear(
            self.num_heads * self.head_dim, h, has_bias=False,
            input_is_parallel=True)

    def _out(self, out):
        with jax.named_scope("attn_out"):
            return self.o_proj(out)

    def forward(self, hidden, cos, sin, attn_mask=None, cache=None,
                position_offset=0, norm_weight=None, norm_eps=None):
        B, T = hidden.shape[0], hidden.shape[1]
        # head count derived from the projection's ACTUAL width: under
        # manual TP (shard_map pipeline stages) q/k/v are mp-local shards
        # holding num_heads/mp heads; under GSPMD they are global
        if norm_weight is not None:
            # fused serving epilogue: the decoder layer skipped its
            # input_layernorm and handed us the UNNORMALIZED hidden —
            # the norm folds into each projection's matmul prologue, so
            # the normalized activation never round-trips HBM.  The row
            # scale is computed once and shared by q/k/v.
            def _fused_qkv(hv, nw, wq, wk, wv):
                from ..kernels.fused_norm_linear import (fused_norm_linear,
                                                         rms_scale)

                rs = rms_scale(hv, norm_eps)
                return (fused_norm_linear(hv, rs, nw, wq),
                        fused_norm_linear(hv, rs, nw, wk),
                        fused_norm_linear(hv, rs, nw, wv))

            with jax.named_scope("attn_qkv"):
                q, k, v = apply("fused_rmsnorm_qkv", _fused_qkv, hidden,
                                norm_weight, self.q_proj.weight,
                                self.k_proj.weight, self.v_proj.weight)
            q = q.reshape([B, T, -1, self.head_dim])
            k = k.reshape([B, T, -1, self.head_dim])
            v = v.reshape([B, T, -1, self.head_dim])
        else:
            with jax.named_scope("attn_qkv"):
                q = self.q_proj(hidden).reshape([B, T, -1, self.head_dim])
                k = self.k_proj(hidden).reshape([B, T, -1, self.head_dim])
                v = self.v_proj(hidden).reshape([B, T, -1, self.head_dim])

        if isinstance(cache, PagedKVCache) and T == 1 \
                and jnp.ndim(position_offset) == 1 and attn_mask is None:
            from ..kernels.fusion import fusion_enabled

            # the kernel consumes the whole pool through the block
            # table; under a live mesh (GSPMD sharded pools / manual-mp
            # shard_map) it has no partitioning rule, so fusion_enabled
            # sends those to the gather path below
            if fusion_enabled():
                # fused decode hot path: RoPE + pool scatter + block
                # gather + split-K attention in one kernel (XLA
                # fallback off-TPU) — models/generation.py's paged
                # decode step pins the mode via serving_fusion()
                bt = cache.block_table
                offs = jnp.asarray(position_offset)

                def _fused_decode(qv, kv, vv, kp, vp, *scales):
                    from ..kernels.paged_attention import fused_paged_decode

                    ks, vs = scales if scales else (None, None)
                    return fused_paged_decode(qv, kv, vv, kp, vp, bt,
                                              offs, cos, sin,
                                              k_scale=ks, v_scale=vs,
                                              kv_cache_dtype=cache.kv_dtype)

                # (rope and the new token's pool write are inside the
                # fused kernel's wrapper, which scopes the write itself)
                if cache.kv_dtype is not None:
                    # quantized pools: the kernel scatter-quantizes the
                    # new token's row and returns updated scale sidecars
                    with jax.named_scope("attn"):
                        out, k_pool, v_pool, k_sc, v_sc = apply(
                            "fused_paged_attention", _fused_decode, q, k,
                            v, Tensor(cache.k), Tensor(cache.v),
                            Tensor(cache.k_scale), Tensor(cache.v_scale))
                    new_cache = PagedKVCache(
                        k_pool._value, v_pool._value, bt,
                        k_sc._value, v_sc._value, kv_dtype=cache.kv_dtype)
                else:
                    with jax.named_scope("attn"):
                        out, k_pool, v_pool = apply(
                            "fused_paged_attention", _fused_decode, q, k,
                            v, Tensor(cache.k), Tensor(cache.v))
                    new_cache = PagedKVCache(k_pool._value, v_pool._value,
                                             bt)
                out = out.reshape([B, T, -1])
                return self._out(out), new_cache

        def _rope_fn(xv):
            # the fused kernel takes a scalar offset; per-sequence vector
            # offsets (continuous-batching decode) use the gather path
            if _pallas_kernels_on() and not jnp.ndim(position_offset):
                from ..kernels.rope import fused_rope

                return fused_rope(xv, cos, sin, position_offset)
            return apply_rope(xv, cos, sin, position_offset)
        with jax.named_scope("attn_qkv"):
            q = apply("rope", _rope_fn, q)
            k = apply("rope", _rope_fn, k)

        if isinstance(cache, PagedKVCache):
            # serving decode (T == 1) or a chunked-prefill chunk (T ==
            # chunk size): position_offset is a [B] vector of
            # per-sequence frontiers.  Write the chunk's k/v into each
            # sequence's blocks, then attend over the gathered block
            # views — all fixed shapes, one executable forever.  When
            # attn_mask is given it is the [B, T] WRITE-VALIDITY mask of
            # a padded chunk: padded positions scatter into the reserved
            # garbage block 0 instead of a live block, and causal
            # masking hides them from attention (their rope/score junk
            # is never read by a real query).
            bs = cache.k.shape[1]
            bt = cache.block_table
            offsets = jnp.asarray(position_offset)
            pos = offsets[:, None] + jnp.arange(T)          # [B, T]
            wmask = None
            if attn_mask is not None:
                m = attn_mask._value if isinstance(attn_mask, Tensor) \
                    else attn_mask
                wmask = jnp.asarray(m).astype(bool)         # [B, T]

            def _scatter(pool, new):
                return paged_scatter(pool, new, bt, pos, wmask)

            k_sc = v_sc = None
            if cache.kv_dtype is not None:
                # quantize-at-write: codes and per-row scales scatter
                # through the SAME flat index (padded rows land their
                # code+scale in garbage block 0, masked from attention)
                def _scatter_q(pool, scales, new):
                    from ..kernels.kv_quant import quantize_kv

                    nb = pool.shape[0]
                    rows = jnp.arange(bt.shape[0])[:, None]
                    col = jnp.minimum(pos // bs, bt.shape[1] - 1)
                    idx = bt[rows, col] * bs + pos % bs     # [B, T]
                    if wmask is not None:
                        idx = jnp.where(wmask, idx, 0)
                    codes, sc = quantize_kv(new, cache.kv_dtype)
                    flat = pool.reshape(nb * bs, pool.shape[2],
                                        pool.shape[3])
                    flat = flat.at[idx.reshape(-1)].set(
                        codes.reshape(-1, codes.shape[2], codes.shape[3]))
                    sflat = scales.reshape(nb * bs).at[
                        idx.reshape(-1)].set(sc.reshape(-1))
                    return flat.reshape(pool.shape), \
                        sflat.reshape(scales.shape)

                with jax.named_scope("kv_write"):
                    k_pool, k_sc = apply(
                        "paged_kv_update_quant", _scatter_q,
                        Tensor(cache.k), Tensor(cache.k_scale), k)
                    v_pool, v_sc = apply(
                        "paged_kv_update_quant", _scatter_q,
                        Tensor(cache.v), Tensor(cache.v_scale), v)
                new_cache = PagedKVCache(k_pool._value, v_pool._value,
                                         bt, k_sc._value, v_sc._value,
                                         kv_dtype=cache.kv_dtype)
            else:
                with jax.named_scope("kv_write"):
                    k_pool = apply("paged_kv_update", _scatter,
                                   Tensor(cache.k), k)
                    v_pool = apply("paged_kv_update", _scatter,
                                   Tensor(cache.v), v)
                new_cache = PagedKVCache(k_pool._value, v_pool._value, bt)

            if T > 1:
                from ..kernels.fusion import fusion_enabled

                # same mesh caveat as the fused decode intercept: the
                # kernel reads the whole pool through the block table
                if fusion_enabled():
                    # fused chunked-prefill hot path: block gather +
                    # causal mask + online softmax + context in one
                    # kernel (XLA fallback off-TPU) — the #1 candidate
                    # mined by analysis/fusionminer on the fused
                    # prefill trace
                    def _fused_chunk(qv, kp, vp, *scales):
                        from ..kernels.chunked_prefill import \
                            fused_chunked_attention

                        ks, vs = scales if scales else (None, None)
                        return fused_chunked_attention(
                            qv, kp, vp, bt, offsets, k_scale=ks,
                            v_scale=vs, kv_cache_dtype=cache.kv_dtype)

                    chunk_args = (q, k_pool, v_pool)
                    if cache.kv_dtype is not None:
                        chunk_args += (k_sc, v_sc)
                    with jax.named_scope("attn"):
                        out = apply("fused_chunked_attention",
                                    _fused_chunk, *chunk_args)
                    out = out.reshape([B, T, -1])
                    return self._out(out), new_cache

            def _paged_attn(qv, kp, vp, *scales):
                # contiguous per-sequence views of the block pool: the
                # same full-buffer masked attention as the static cache,
                # just gathered through the block table (quantized
                # pools dequantize the gathered copy — this is the
                # unfused parity oracle for the fused kernels)
                kb, vb = kp[bt], vp[bt]         # [B, nbs, bs, kvh, hd]
                if cache.kv_dtype is not None:
                    from ..kernels.kv_quant import decode_codes

                    ksc, vsc = scales
                    kb = (decode_codes(kb, cache.kv_dtype)
                          * ksc[bt][..., None, None]).astype(qv.dtype)
                    vb = (decode_codes(vb, cache.kv_dtype)
                          * vsc[bt][..., None, None]).astype(qv.dtype)
                kb = kb.reshape(bt.shape[0], -1, kp.shape[2],
                                kp.shape[3])
                vb = vb.reshape(bt.shape[0], -1, vp.shape[2],
                                vp.shape[3])
                rep = qv.shape[2] // kb.shape[2]
                if rep > 1:
                    kb = jnp.repeat(kb, rep, axis=2)
                    vb = jnp.repeat(vb, rep, axis=2)
                scores = jnp.einsum(
                    "bthd,bshd->bhts", qv, kb,
                    preferred_element_type=jnp.float32)
                scores = scores / math.sqrt(self.head_dim)
                q_pos = pos                                 # [B, T]
                k_pos = jnp.arange(kb.shape[1])
                valid = k_pos[None, None, :] <= q_pos[:, :, None]
                scores = jnp.where(valid[:, None], scores, -1e30)
                probs = jax.nn.softmax(scores, axis=-1).astype(qv.dtype)
                return jnp.einsum("bhts,bshd->bthd", probs, vb)

            attn_args = (q, k_pool, v_pool)
            if cache.kv_dtype is not None:
                attn_args += (k_sc, v_sc)
            with jax.named_scope("attn"):
                out = apply("paged_attention", _paged_attn, *attn_args)
            out = out.reshape([B, T, -1])
            return self._out(out), new_cache

        if isinstance(cache, StaticKVCache):
            # fixed-size buffer write; one compiled program per decode
            def _upd(buf, new):
                return jax.lax.dynamic_update_slice(
                    buf, new.astype(buf.dtype), (0, position_offset, 0, 0))

            with jax.named_scope("kv_write"):
                k_buf = apply("kv_cache_update", _upd, Tensor(cache.k), k)
                v_buf = apply("kv_cache_update", _upd, Tensor(cache.v), v)
            new_cache = StaticKVCache(k_buf._value, v_buf._value)
            max_len = cache.k.shape[1]

            def _static_attn(qv, kb, vb):
                # attend over the full buffer, masking positions beyond
                # the write frontier (and future positions within this
                # chunk, for multi-token prefill into the buffer)
                rep = qv.shape[2] // kb.shape[2]
                if rep > 1:
                    kb = jnp.repeat(kb, rep, axis=2)
                    vb = jnp.repeat(vb, rep, axis=2)
                scores = jnp.einsum(
                    "bthd,bshd->bhts", qv, kb,
                    preferred_element_type=jnp.float32)
                scores = scores / math.sqrt(self.head_dim)
                q_pos = position_offset + jnp.arange(qv.shape[1])
                k_pos = jnp.arange(max_len)
                valid = k_pos[None, :] <= q_pos[:, None]  # [T, max_len]
                scores = jnp.where(valid[None, None], scores, -1e30)
                probs = jax.nn.softmax(scores, axis=-1).astype(qv.dtype)
                return jnp.einsum("bhts,bshd->bthd", probs, vb)

            with jax.named_scope("attn"):
                out = apply("static_cache_attention", _static_attn, q,
                            k_buf, v_buf)
            out = out.reshape([B, T, -1])
            return self._out(out), new_cache

        if cache is not None:
            from ..ops.manipulation import concat

            k = concat([cache[0], k], axis=1)
            v = concat([cache[1], v], axis=1)
            new_cache = (k, v)
        else:
            new_cache = None

        # ALWAYS causal with bottom-right alignment: query row i sees keys
        # up to i + (Tk - Tq).  Covers no-cache training (Tk == Tq), cached
        # prefill (past == 0, so plain causal — the old `causal = cache is
        # None` made cached prefill bidirectional, corrupting generation),
        # and single-token decode (row 0 sees all past keys).
        causal = True

        cp = getattr(self.config, "context_parallel", "")
        if cp and cache is None:
            # sequence-parallel full-sequence attention: ring rotates KV
            # shards over the `sp` axis, Ulysses re-shards heads with
            # all-to-alls.  Both resolve the active mesh themselves and
            # fall back to dense attention when there is no `sp` axis —
            # that fallback IS the CPU parity path.
            def _cp_attn(qv, kv, vv):
                from ..distributed.mesh import get_mesh

                m = get_mesh()
                baxis = "data" if (m is not None
                                   and "data" in m.shape) else None
                if cp == "ulysses":
                    from ..kernels.ulysses_attention import ulysses_attention

                    return ulysses_attention(qv, kv, vv, causal=causal,
                                             batch_axis=baxis)
                from ..kernels.ring_attention import ring_attention

                return ring_attention(qv, kv, vv, causal=causal,
                                      batch_axis=baxis)

            with jax.named_scope("attn"):
                out = apply("context_parallel_attention", _cp_attn, q, k,
                            v)
            out = out.reshape([B, T, -1])
            return self._out(out)

        def _attn(qv, kv, vv):
            from ..kernels.flash_attention import (_attn_reference,
                                                   flash_attention_bthd)

            if self.config.use_flash_attention and _pallas_kernels_on():
                return flash_attention_bthd(qv, kv, vv, causal=causal)
            # reference path with GQA repeat
            rep = qv.shape[2] // kv.shape[2]
            if rep > 1:
                kv = jnp.repeat(kv, rep, axis=2)
                vv = jnp.repeat(vv, rep, axis=2)
            qt = jnp.swapaxes(qv, 1, 2)
            kt = jnp.swapaxes(kv, 1, 2)
            vt = jnp.swapaxes(vv, 1, 2)
            out = _attn_reference(qt, kt, vt, causal,
                                  1.0 / math.sqrt(self.head_dim))
            return jnp.swapaxes(out, 1, 2)

        with jax.named_scope("attn"):
            out = apply("attention", _attn, q, k, v)
        out = out.reshape([B, T, -1])
        out = self._out(out)
        if cache is not None:
            return out, new_cache
        return out


class LlamaMLP(nn.Layer):
    def __init__(self, config: LlamaConfig):
        super().__init__()
        h, m = config.hidden_size, config.intermediate_size
        self.gate_proj = ColumnParallelLinear(h, m, has_bias=False,
                                              gather_output=False)
        self.up_proj = ColumnParallelLinear(h, m, has_bias=False,
                                            gather_output=False)
        self.down_proj = RowParallelLinear(m, h, has_bias=False,
                                           input_is_parallel=True)

    def forward(self, x, norm_weight=None, norm_eps=None):
        if norm_weight is not None:
            # fused serving epilogue: the post-attention RMSNorm folds
            # into gate/up's matmul prologue (row scale computed once),
            # and silu rides as gate's epilogue
            def _fused(xv, nw, wg, wu, wd):
                from ..kernels.fused_norm_linear import (fused_norm_linear,
                                                         rms_scale)

                rs = rms_scale(xv, norm_eps)
                g = fused_norm_linear(xv, rs, nw, wg, activation="silu")
                u = fused_norm_linear(xv, rs, nw, wu)
                return jnp.dot(g * u, wd.astype(g.dtype))

            return apply("fused_rmsnorm_mlp", _fused, x,
                         norm_weight, self.gate_proj.weight,
                         self.up_proj.weight, self.down_proj.weight)
        return self.down_proj(F.silu(self.gate_proj(x)) * self.up_proj(x))


class LlamaMoEMLP(nn.Layer):
    """Top-k routed mixture-of-experts MLP (GShard capacity-padded
    dispatch through kernels/moe_dispatch).

    Stacked expert weights: w_gate/w_up [E, h, m], w_down [E, m, h] —
    the leading expert dim shards on the canonical `expert` mesh axis
    (distributed.sharding moe_* roles); the router is a few KiB and
    stays replicated.  Routing: softmax over router logits, lax.top_k,
    then a running-count capacity-slot assignment; choices past the
    expert's capacity C = ceil(cf*T*K/E) get slot >= C and are dropped
    by dispatch/combine (the GShard contract).
    """

    def __init__(self, config: LlamaConfig):
        super().__init__()
        from ..nn import initializer as I

        h, m = config.hidden_size, config.intermediate_size
        E = config.moe_num_experts
        self.num_experts = E
        self.top_k = config.moe_top_k
        self.capacity_factor = config.moe_capacity_factor
        self.router = nn.Linear(h, E, bias_attr=False)
        std = 1.0 / math.sqrt(h)
        init = I.Normal(std=std)
        self.w_gate = self.create_parameter([E, h, m],
                                            default_initializer=init)
        self.w_up = self.create_parameter([E, h, m],
                                          default_initializer=init)
        self.w_down = self.create_parameter(
            [E, m, h], default_initializer=I.Normal(std=1.0 / math.sqrt(m)))

    def forward(self, x):
        from ..kernels.moe_dispatch import (moe_capacity, moe_combine,
                                            moe_dispatch)

        E, K, cf = self.num_experts, self.top_k, self.capacity_factor
        logits = self.router(x)  # [B, T, E]

        def _moe(xv, lg, wg, wu, wd):
            B, T, H = xv.shape
            n_tok = B * T
            C = moe_capacity(n_tok, E, K, cf)
            tokens = xv.reshape(n_tok, H)
            probs = jax.nn.softmax(
                lg.reshape(n_tok, E).astype(jnp.float32), axis=-1)
            gate, eidx = jax.lax.top_k(probs, K)       # [n_tok, K]
            gate = (gate / jnp.maximum(gate.sum(-1, keepdims=True),
                                       1e-9)).astype(xv.dtype)
            eidx = eidx.astype(jnp.int32)
            # capacity slot per routed choice: running count of earlier
            # choices bound to the same expert (t-major, k-minor
            # priority); overflow (slot >= C) is dropped downstream
            flat_e = eidx.reshape(-1)
            oh = jax.nn.one_hot(flat_e, E, dtype=jnp.int32)
            pos = jnp.cumsum(oh, axis=0) - oh
            sidx = jnp.take_along_axis(pos, flat_e[:, None], axis=1)[:, 0]
            sidx = sidx.reshape(n_tok, K).astype(jnp.int32)
            disp = moe_dispatch(tokens, eidx, sidx, jnp.ones_like(gate),
                                E, C)                  # [E, C, H]
            g = jnp.einsum("ech,ehm->ecm", disp, wg.astype(disp.dtype))
            u = jnp.einsum("ech,ehm->ecm", disp, wu.astype(disp.dtype))
            act = (jax.nn.silu(g.astype(jnp.float32)).astype(disp.dtype)
                   * u)
            eo = jnp.einsum("ecm,emh->ech", act, wd.astype(disp.dtype))
            out = moe_combine(eo, eidx, sidx, gate)    # [n_tok, H]
            return out.reshape(B, T, H)

        return apply("moe_mlp", _moe, x, logits, self.w_gate, self.w_up,
                     self.w_down)


class LlamaDecoderLayer(nn.Layer):
    def __init__(self, config: LlamaConfig):
        super().__init__()
        self.input_layernorm = LlamaRMSNorm(config.hidden_size,
                                            config.rms_norm_eps)
        self.self_attn = LlamaAttention(config)
        self.post_attention_layernorm = LlamaRMSNorm(config.hidden_size,
                                                     config.rms_norm_eps)
        self.mlp = (LlamaMoEMLP(config)
                    if getattr(config, "moe_num_experts", 0) > 0
                    else LlamaMLP(config))

    def _fuse_epilogues(self, cache):
        """Fold RMSNorms into the following projections?  Serving-only
        (cache present), and only when the projections run as plain
        local matmuls: fused_norm_linear consumes the raw weights, so
        any mesh sharding annotation or manual-mp collective the
        ColumnParallelLinear forward would have applied must be absent.
        MoE routes through stacked expert weights — not this shape."""
        if cache is None:
            return False
        from ..kernels.fusion import fusion_enabled

        return fusion_enabled() and isinstance(self.mlp, LlamaMLP)

    def forward(self, hidden, cos, sin, attn_mask=None, cache=None,
                position_offset=0):
        fuse_epi = self._fuse_epilogues(cache)
        residual = hidden
        if cache is not None:
            if fuse_epi:
                h, new_cache = self.self_attn(
                    hidden, cos, sin, attn_mask, cache, position_offset,
                    norm_weight=self.input_layernorm.weight,
                    norm_eps=self.input_layernorm._epsilon)
            else:
                with jax.named_scope("attn_qkv"):
                    normed = self.input_layernorm(hidden)
                h, new_cache = self.self_attn(normed, cos, sin, attn_mask,
                                              cache, position_offset)
        else:
            with jax.named_scope("attn_qkv"):
                normed = self.input_layernorm(hidden)
            h = self.self_attn(normed, cos, sin, attn_mask)
            new_cache = None
        hidden = residual + h
        residual = hidden
        with jax.named_scope("mlp"):
            if fuse_epi:
                h = self.mlp(
                    hidden,
                    norm_weight=self.post_attention_layernorm.weight,
                    norm_eps=self.post_attention_layernorm._epsilon)
            else:
                h = self.mlp(self.post_attention_layernorm(hidden))
        hidden = residual + h
        if cache is not None:
            return hidden, new_cache
        return hidden


class LlamaModel(nn.Layer):
    def __init__(self, config: LlamaConfig):
        super().__init__()
        self.config = config
        bf16 = config.dtype == "bfloat16"

        def built(layer):
            # parameters are created in float32; narrowing each part as
            # it is built (same values as one cast at the end) keeps the
            # float32 transient to one part — a whole float32 model is
            # twice what the chip will hold of it
            return layer.bfloat16() if bf16 else layer

        self.embed_tokens = built(VocabParallelEmbedding(
            config.vocab_size, config.hidden_size))
        self.layers = nn.LayerList(
            [built(LlamaDecoderLayer(config))
             for _ in range(config.num_hidden_layers)])
        self.norm = LlamaRMSNorm(config.hidden_size, config.rms_norm_eps)
        head_dim = config.hidden_size // config.num_attention_heads
        cos, sin = precompute_rope(head_dim, config.max_position_embeddings,
                                   config.rope_theta)
        self.register_buffer("rope_cos", Tensor(cos), persistable=False)
        self.register_buffer("rope_sin", Tensor(sin), persistable=False)
        if bf16:
            self.bfloat16()     # the norm, the rope tables, every _dtype

    def forward(self, input_ids, attn_mask=None, caches=None,
                position_offset=0):
        with jax.named_scope("embed"):
            hidden = self.embed_tokens(input_ids)
        if self.config.sequence_parallel:
            from ..distributed.sharding import shard_tensor

            hidden = shard_tensor(hidden, placements=[None, "sp", None])
        cos, sin = self.rope_cos._value, self.rope_sin._value
        new_caches = [] if caches is not None else None
        for i, layer in enumerate(self.layers):
            if caches is not None:
                hidden, c = layer(hidden, cos, sin, attn_mask, caches[i],
                                  position_offset)
                new_caches.append(c)
            elif self.config.recompute:
                from ..distributed.recompute import recompute

                hidden = recompute(layer, hidden, cos, sin, attn_mask)
            else:
                hidden = layer(hidden, cos, sin, attn_mask)
        with jax.named_scope("final_norm"):
            hidden = self.norm(hidden)
        if caches is not None:
            return hidden, new_caches
        return hidden


def _fused_causal_lm_loss(hidden, w, labels, *, w_is_vocab_major, chunk):
    """Next-token cross-entropy computed per token-chunk so the full
    [tokens, vocab] logits never live in HBM.  lax.scan over chunks; each
    chunk's lm-head matmul + logsumexp runs under jax.checkpoint so the
    backward recomputes the chunk logits instead of saving them.

    Replaces the reference's softmax_with_cross_entropy over full logits
    (/root/reference/paddle/fluid/operators/softmax_with_cross_entropy_op.cu)
    with the memory-lean TPU formulation.
    """
    h = hidden[:, :-1]
    lab = labels[:, 1:].astype(jnp.int32)
    B, T, H = h.shape
    n_tok = B * T
    hf = h.reshape(n_tok, H)
    labf = lab.reshape(n_tok)
    n_chunks = max(1, -(-n_tok // chunk))
    csize = -(-n_tok // n_chunks)
    pad = n_chunks * csize - n_tok
    if pad:
        hf = jnp.pad(hf, ((0, pad), (0, 0)))
        labf = jnp.pad(labf, (0, pad), constant_values=-1)
    hs = hf.reshape(n_chunks, csize, H)
    labs = labf.reshape(n_chunks, csize)
    wt = w.T if w_is_vocab_major else w  # [H, V]

    def chunk_nll(h_c, lab_c, wt):
        logits = jnp.einsum("td,dv->tv", h_c, wt.astype(h_c.dtype),
                            preferred_element_type=jnp.float32)
        lse = jax.nn.logsumexp(logits, axis=-1)
        picked = jnp.take_along_axis(
            logits, jnp.maximum(lab_c, 0)[:, None], axis=-1)[:, 0]
        valid = (lab_c >= 0).astype(jnp.float32)
        return jnp.sum((lse - picked) * valid)

    def body(tot, xs):
        h_c, lab_c = xs
        return tot + jax.checkpoint(chunk_nll)(h_c, lab_c, wt), None

    total, _ = jax.lax.scan(body, jnp.float32(0.0), (hs, labs))
    return total / n_tok


class LlamaForCausalLM(nn.Layer):
    def __init__(self, config: LlamaConfig):
        super().__init__()
        self.config = config
        self.model = LlamaModel(config)
        if not config.tie_word_embeddings:
            self.lm_head = ColumnParallelLinear(
                config.hidden_size, config.vocab_size, has_bias=False,
                gather_output=True)
            if config.dtype == "bfloat16":
                self.lm_head.bfloat16()

    def forward(self, input_ids, labels=None, attn_mask=None, caches=None,
                position_offset=0):
        if caches is not None:
            hidden, new_caches = self.model(input_ids, attn_mask, caches,
                                            position_offset)
        else:
            hidden = self.model(input_ids, attn_mask)
        if labels is not None and self.config.fused_lm_loss:
            w = (self.model.embed_tokens.weight
                 if self.config.tie_word_embeddings else self.lm_head.weight)
            with jax.named_scope("lm_loss"):
                loss = apply(
                    "fused_causal_lm_loss", _fused_causal_lm_loss, hidden,
                    w, labels,
                    w_is_vocab_major=self.config.tie_word_embeddings,
                    chunk=self.config.lm_loss_chunk)
            return loss, None
        with jax.named_scope("lm_head"):
            if self.config.tie_word_embeddings:
                def _tied(h, w):
                    return h @ w.T.astype(h.dtype)
                logits = apply("lm_head_tied", _tied, hidden,
                               self.model.embed_tokens.weight)
            else:
                logits = self.lm_head(hidden)
        if labels is not None:
            def _loss(lg, lab):
                lg = lg[:, :-1].astype(jnp.float32)
                lab = lab[:, 1:]
                logp = jax.nn.log_softmax(lg, axis=-1)
                picked = jnp.take_along_axis(
                    logp, lab[..., None].astype(jnp.int32), axis=-1)[..., 0]
                return -jnp.mean(picked)
            with jax.named_scope("lm_loss"):
                loss = apply("causal_lm_loss", _loss, logits, labels)
            return loss, logits
        if caches is not None:
            return logits, new_caches
        return logits

    # --------------------------------------------------------- generation
    def cache_layers(self):
        """The model's description of its cache, a record a layer
        (``serving/cache.py::LayerCache``): full attention, one K/V
        geometry, nothing beside K and V."""
        from ..serving.cache import LayerCache
        from .generation import _cache_dims

        return [LayerCache(*_cache_dims(self))
                for _ in range(self.config.num_hidden_layers)]

    def generate(self, input_ids, max_new_tokens=32, temperature=1.0,
                 top_k: Optional[int] = None, top_p: float = 1.0,
                 do_sample: Optional[bool] = None, num_beams: int = 1,
                 eos_token_id: Optional[int] = None, seed=None,
                 use_static_cache: bool = False, stop_sequences=None,
                 tokenizer=None):
        """Decode with the KV cache (models/generation.py): greedy,
        temperature/top-k/top-p sampling, or beam search.

        Back-compat: temperature==0.0 means greedy (the old contract);
        otherwise sampling is on unless do_sample=False."""
        from ..core.dispatch import no_grad_ctx
        from .generation import generate as _generate

        if temperature == 0.0:
            # the documented greedy contract wins over do_sample=True
            do_sample = False
            temperature = 1.0
        if do_sample is None:
            do_sample = True
        if do_sample and num_beams > 1:
            raise ValueError(
                "sampling + beam search is not supported; pass "
                "do_sample=False (or temperature=0.0) with num_beams>1")
        with no_grad_ctx():
            return _generate(
                self, input_ids, max_new_tokens=max_new_tokens,
                do_sample=do_sample, temperature=temperature,
                top_k=top_k or 0, top_p=top_p, num_beams=num_beams,
                eos_token_id=eos_token_id, seed=seed,
                use_static_cache=use_static_cache,
                stop_sequences=stop_sequences, tokenizer=tokenizer)
