# lint-tpu: disable-file=L004 -- a served model drives raw device buffers
# (like models/llama.py); new backend code belongs under core/ ops/
# kernels/ static/ distributed/ (README: Repo lint)
"""SDAR-MoE: a decoder that generates by DIFFUSION OVER BLOCKS
(``model_type`` ``sdar_moe``: JetLM/SDAR-30B-A3B-Chat).

What differs from ``models/llama.py`` and why it is a file beside it:

- **block-causal attention**: position ``i`` sees every position up to
  the END of its own block of ``block_length`` (blocks aligned at
  multiples of it), so the ``L`` positions of a block are denoised
  together, each seeing the others' current tokens (``[MASK]``
  included);
- **a norm on q and k**: RMSNorm over the ``head_dim`` lanes of every
  q and k head (one learned weight each), before RoPE;
- **every layer routed**: a dropless softmax top-k layer of
  ``num_experts`` experts (:class:`DroplessMoE`: no capacity, no
  dropped token, told which experts it holds), no shared expert;
- **the logits at position i are for the token AT i** (a mask is
  predicted in place, no shift).

What is shared: the embedding, ``LlamaRMSNorm``, the RoPE tables and
``apply_rope``, grouped-query attention at a head size of 128,
``fused_norm_linear`` for the projections, the chunked-prefill kernel
(its mask made block-causal), the paged decode kernel's walk over live
pages (``kernels/paged_attention.paged_context_partials``), the paged
pool and its block tables.

Served forward passes (``models/generation.py`` wraps them in the step
programs):

- :meth:`SDARMoEForCausalLM.prefill_chunk`: a chunk of WHOLE blocks of
  the prompt under the block-causal mask, K/V (and the routing
  witness) written to the pool; no logits (nothing is predicted from a
  prompt's whole blocks);
- :meth:`SDARMoEForCausalLM.block_step`: ``[S, L]`` query tokens a
  slot (one block each) against the slot's pages plus the block
  itself; K/V kept only where a slot COMMITS (the block's tokens are
  final), else dropped.

``forward(ids)`` is the plain full pass (no cache), for tests.
"""
from __future__ import annotations

import functools
import math
from dataclasses import dataclass
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from .. import nn
from ..core.tensor import Tensor
from ..distributed.parallel_layers import (ColumnParallelLinear,
                                           RowParallelLinear,
                                           VocabParallelEmbedding)
from .llama import (LlamaRMSNorm, PagedKVCache, apply_rope, paged_scatter,
                    precompute_rope)

REMASKING_RULES = ("low_confidence_static", "low_confidence_dynamic")


@dataclass
class SDARMoEConfig:
    """The published ``config.json`` keys, then what this replica holds
    and how it generates (the family's released loop; the config has no
    key for these)."""

    vocab_size: int = 151936
    hidden_size: int = 2048
    num_hidden_layers: int = 48
    num_attention_heads: int = 32
    num_key_value_heads: int = 4
    head_dim: int = 128
    max_position_embeddings: int = 32768
    rms_norm_eps: float = 1e-6
    rope_theta: float = 1000000.0
    tie_word_embeddings: bool = False
    moe_intermediate_size: int = 768
    num_experts: int = 128
    num_experts_per_tok: int = 8
    norm_topk_prob: bool = True
    # the experts this replica HOLDS (ids among num_experts; None: all).
    # The router scores all of them; an assignment to one that is not
    # held adds nothing here
    held_experts: Optional[Tuple[int, ...]] = None
    # generation: a block of ``block_length`` positions, at most
    # ``denoising_steps`` forwards a block, and the rule that picks what
    # a step unmasks
    block_length: int = 4
    denoising_steps: int = 4
    remasking: str = "low_confidence_dynamic"
    confidence_threshold: float = 0.9
    mask_token_id: int = 151669
    dtype: str = "bfloat16"

    def __post_init__(self):
        if self.remasking not in REMASKING_RULES:
            raise ValueError(f"remasking must be one of {REMASKING_RULES}, "
                             f"got {self.remasking!r}")
        if not 1 <= self.denoising_steps <= self.block_length:
            raise ValueError(
                f"denoising_steps ({self.denoising_steps}) must lie in "
                f"1..block_length ({self.block_length})")
        if self.held_experts is not None:
            self.held_experts = tuple(int(e) for e in self.held_experts)

    @staticmethod
    def tiny(**overrides):
        cfg = SDARMoEConfig(
            vocab_size=256, hidden_size=64, num_hidden_layers=2,
            num_attention_heads=4, num_key_value_heads=2, head_dim=16,
            max_position_embeddings=128, moe_intermediate_size=32,
            num_experts=16, num_experts_per_tok=4, mask_token_id=255,
            dtype="float32")
        for k, v in overrides.items():
            setattr(cfg, k, v)
        cfg.__post_init__()
        return cfg


class RoutedKVCache(PagedKVCache):
    """A paged K/V view that also carries, per cached position, the
    experts the layer's router chose for it (``chosen [num_blocks,
    block_size * k]`` int32, a row a block): the routing witness of
    everything a step program writes to the pool."""

    __slots__ = ("chosen",)

    def __init__(self, k, v, block_table, chosen):
        super().__init__(k, v, block_table)
        self.chosen = chosen

    def tree_flatten(self):
        return (self.k, self.v, self.block_table, self.chosen), None

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(*children)


jax.tree_util.register_pytree_node(
    RoutedKVCache, lambda c: c.tree_flatten(), RoutedKVCache.tree_unflatten)


def scatter_block_rows(pool, new, block_table, start, wmask):
    """Write ``new [B, T, k]``, the values of the consecutive positions
    ``start[b] + t``, into a sidecar kept a row a block (``pool
    [num_blocks, block_size * k]``) through the table: the rows the
    positions reach are read, changed where ``wmask [B, T]`` is True and
    written back whole, in place and in the layout the device stores
    them in.  A row with nothing to write goes to the garbage block 0,
    as a masked position of :func:`paged_scatter` does."""
    B, T, k = new.shape
    bs = pool.shape[1] // k
    n_rows = (T + 2 * bs - 2) // bs        # what T positions can span
    blk = start[:, None] // bs + jnp.arange(n_rows)             # [B, R]
    # the token of each (row, offset), where there is one
    t = blk[:, :, None] * bs + jnp.arange(bs) - start[:, None, None]
    flat = jnp.clip(t, 0, T - 1).reshape(B, n_rows * bs)
    live = ((t >= 0) & (t < T)).reshape(B, -1) \
        & jnp.take_along_axis(wmask, flat, axis=1)
    live = live.reshape(B, n_rows, bs)
    vals = jnp.take_along_axis(new, flat[:, :, None], axis=1)
    rows = block_table[jnp.arange(B)[:, None],
                       jnp.minimum(blk, block_table.shape[1] - 1)]
    rows = jnp.where(live.any(-1), rows, 0).reshape(-1)
    old = pool[rows].reshape(B, n_rows, bs, k)
    out = jnp.where(live[..., None], vals.reshape(old.shape), old)
    return pool.at[rows].set(out.reshape(-1, bs * k).astype(pool.dtype))


def _rms(x, w, eps):
    """``LlamaRMSNorm``'s arithmetic on raw arrays."""
    x32 = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(x32), axis=-1, keepdims=True)
    return (x32 * jax.lax.rsqrt(var + eps)).astype(x.dtype) * w


@functools.partial(jax.jit, static_argnames=("shape", "std", "dtype"))
def _normal(key, shape, std, dtype):
    return (jax.random.normal(key, shape, jnp.float32) * std).astype(dtype)


class _ChunkedNormal:
    """``initializer.Normal`` for a stacked expert matrix, made a few
    experts at a time by ONE compiled program and narrowed as made: the
    whole matrix in float32 (2.4 GB at the published widths) never
    exists."""

    def __init__(self, std, chunks=8):
        self.std, self.chunks = std, chunks

    def _generate(self, shape, np_dtype):
        from ..ops import random as rnd

        n = max(1, min(self.chunks, shape[0]))
        bounds = [shape[0] * i // n for i in range(n + 1)]
        return jnp.concatenate([
            _normal(rnd.next_key(), (hi - lo,) + tuple(shape[1:]),
                    float(self.std), jnp.dtype(np_dtype))
            for lo, hi in zip(bounds, bounds[1:])])


class DroplessMoE(nn.Layer):
    """Top-k routed experts with no capacity and no dropped token.  The
    router scores ALL ``num_experts``; this layer holds the matrices of
    ``held`` (expert ids; ``None``: all) and computes the part of the
    output that its experts give.  Summed over holders that together
    hold every expert once, the parts are the whole layer's output.
    Weights are ``[E_held, M, H]`` for gate, up and (transposed) down:
    ``kernels/moe_experts``.

    The router is a softmax over the experts, or (``scores="sigmoid"``)
    a sigmoid each; ``selection_bias`` adds the buffer ``expert_bias
    [E]`` to the scores for the SELECTION only (a chosen expert's gate
    is its own score); ``normalize`` divides the chosen gates by their
    sum (plus ``norm_eps``) and ``route_scale`` multiplies them."""

    def __init__(self, hidden_size, intermediate_size, num_experts, top_k,
                 *, normalize=True, held=None, dtype="float32",
                 scores="softmax", selection_bias=False, route_scale=None,
                 norm_eps=None):
        super().__init__()
        from ..nn import initializer as I

        self.num_experts, self.top_k = num_experts, top_k
        self.normalize = normalize
        self.scores, self.route_scale = scores, route_scale
        self.norm_eps = norm_eps
        if selection_bias:
            # float32 whatever the layer is narrowed to later: whoever
            # narrows the model registers it again (models/afmoe.py)
            self.register_buffer("expert_bias", Tensor(
                jnp.zeros((num_experts,), jnp.float32)))
        self.held = None if held is None else tuple(held)
        n_held = num_experts if held is None else len(self.held)
        h, m = hidden_size, intermediate_size
        self.router = self.create_parameter(
            [h, num_experts], dtype=dtype,
            default_initializer=I.Normal(std=1.0 / math.sqrt(h)))
        self.w_gate = self.create_parameter(
            [n_held, m, h], dtype=dtype,
            default_initializer=_ChunkedNormal(1.0 / math.sqrt(h)))
        self.w_up = self.create_parameter(
            [n_held, m, h], dtype=dtype,
            default_initializer=_ChunkedNormal(1.0 / math.sqrt(h)))
        self.w_down = self.create_parameter(
            [n_held, m, h], dtype=dtype,
            default_initializer=_ChunkedNormal(1.0 / math.sqrt(m)))

    def route(self, x2d):
        from ..kernels.moe_experts import route_topk

        bias = self._buffers.get("expert_bias")
        with jax.named_scope("moe_router"):
            return route_topk(
                x2d, self.router._value, self.top_k,
                normalize=self.normalize, scores=self.scores,
                bias=None if bias is None else bias._value,
                scale=self.route_scale, norm_eps=self.norm_eps)

    def experts(self, x2d, chosen, gates, token_valid=None):
        from ..kernels.moe_experts import grouped_experts

        with jax.named_scope("moe_experts"):
            return grouped_experts(
                x2d, chosen, gates, self.w_gate._value, self.w_up._value,
                self.w_down._value, held=self.held,
                num_experts=self.num_experts, token_valid=token_valid)

    def run(self, x2d, token_valid=None):
        """``x2d [T, H]`` -> ``(out [T, H], chosen [T, k], RouteStats)``
        on raw arrays."""
        chosen, gates = self.route(x2d)
        out, stats = self.experts(x2d, chosen, gates, token_valid)
        return out, chosen, stats

    def forward(self, x):
        v = x._value if isinstance(x, Tensor) else x
        out, _, _ = self.run(v.reshape(-1, v.shape[-1]))
        return Tensor(out.reshape(v.shape))


class SDARAttention(nn.Layer):
    def __init__(self, config: SDARMoEConfig):
        super().__init__()
        h, d = config.hidden_size, config.head_dim
        self.num_heads = config.num_attention_heads
        self.num_kv_heads = config.num_key_value_heads
        self.head_dim = d
        self.q_proj = ColumnParallelLinear(h, self.num_heads * d,
                                           has_bias=False,
                                           gather_output=False)
        self.k_proj = ColumnParallelLinear(h, self.num_kv_heads * d,
                                           has_bias=False,
                                           gather_output=False)
        self.v_proj = ColumnParallelLinear(h, self.num_kv_heads * d,
                                           has_bias=False,
                                           gather_output=False)
        self.o_proj = RowParallelLinear(self.num_heads * d, h,
                                        has_bias=False,
                                        input_is_parallel=True)
        self.q_norm = LlamaRMSNorm(d, config.rms_norm_eps)
        self.k_norm = LlamaRMSNorm(d, config.rms_norm_eps)


class SDARDecoderLayer(nn.Layer):
    def __init__(self, config: SDARMoEConfig):
        super().__init__()
        self.input_layernorm = LlamaRMSNorm(config.hidden_size,
                                            config.rms_norm_eps)
        self.self_attn = SDARAttention(config)
        self.post_attention_layernorm = LlamaRMSNorm(config.hidden_size,
                                                     config.rms_norm_eps)
        self.mlp = DroplessMoE(
            config.hidden_size, config.moe_intermediate_size,
            config.num_experts, config.num_experts_per_tok,
            normalize=config.norm_topk_prob, held=config.held_experts,
            dtype=config.dtype)


class SDARMoEModel(nn.Layer):
    def __init__(self, config: SDARMoEConfig):
        super().__init__()
        self.config = config
        bf16 = config.dtype == "bfloat16"

        def built(layer):
            # parameters are created in float32; narrowing each part as
            # it is built keeps the float32 transient to one part
            return layer.bfloat16() if bf16 else layer

        self.embed_tokens = built(VocabParallelEmbedding(
            config.vocab_size, config.hidden_size))
        self.layers = nn.LayerList(
            [built(SDARDecoderLayer(config))
             for _ in range(config.num_hidden_layers)])
        self.norm = LlamaRMSNorm(config.hidden_size, config.rms_norm_eps)
        if bf16:
            self.bfloat16()
        # float32 tables (registered after the narrowing): at theta 1e6
        # a bf16 table is off by 0.4 % at every position
        cos, sin = precompute_rope(config.head_dim,
                                   config.max_position_embeddings,
                                   config.rope_theta)
        self.register_buffer("rope_cos", Tensor(cos), persistable=False)
        self.register_buffer("rope_sin", Tensor(sin), persistable=False)


def _block_causal(q_pos, k_pos, block):
    """``[.., Tq, Tk]`` True where a query at ``q_pos`` sees ``k_pos``."""
    return (k_pos[..., None, :] // block) <= (q_pos[..., :, None] // block)


class SDARMoEForCausalLM(nn.Layer):
    """The model and its three forward passes (module docstring)."""

    def __init__(self, config: SDARMoEConfig):
        super().__init__()
        self.config = config
        self.model = SDARMoEModel(config)
        self.lm_head = ColumnParallelLinear(
            config.hidden_size, config.vocab_size, has_bias=False,
            gather_output=True)
        if config.dtype == "bfloat16":
            self.lm_head.bfloat16()

    # ------------------------------------------------- what the engine asks
    @property
    def block_diffusion(self):
        """The generation settings ``serving.Engine`` reads: a model
        that has them is served by the block iteration."""
        return self.config

    def cache_layers(self):
        """The model's description of its cache, a record a layer
        (``serving/cache.py::LayerCache``): full attention everywhere,
        and beside K and V the routing witness, ``k`` expert ids a
        position."""
        from ..serving.cache import LayerCache

        cfg = self.config
        return [LayerCache(
            cfg.num_key_value_heads, cfg.head_dim,
            jnp.bfloat16 if cfg.dtype == "bfloat16" else jnp.float32,
            sidecars=(((cfg.num_experts_per_tok,), jnp.int32),))
            for _ in range(cfg.num_hidden_layers)]

    def paged_cache_views(self, pools, block_tables):
        return [RoutedKVCache(k, v, block_tables, chosen)
                for k, v, chosen in pools]

    @staticmethod
    def paged_pool_entries(caches):
        return [(c.k, c.v, c.chosen) for c in caches]

    # ------------------------------------------------------------ pieces
    def _qkv(self, layer, x, start):
        """Projections, the norm on q and k, RoPE at positions
        ``start[b] + t``:
        ``(q [B,T,Hq,D], k [B,T,KVH,D], v [B,T,KVH,D])``."""
        from ..kernels.fusion import fusion_enabled

        attn = layer.self_attn
        B, T, _ = x.shape
        nw, eps = layer.input_layernorm.weight._value, \
            self.config.rms_norm_eps
        wq, wk, wv = (attn.q_proj.weight._value, attn.k_proj.weight._value,
                      attn.v_proj.weight._value)
        with jax.named_scope("attn_qkv"):
            if fusion_enabled():
                from ..kernels.fused_norm_linear import (fused_norm_linear,
                                                         rms_scale)

                rs = rms_scale(x, eps)
                q, k, v = (fused_norm_linear(x, rs, nw, w)
                           for w in (wq, wk, wv))
            else:
                a = _rms(x, nw, eps)
                q, k, v = (jnp.dot(a, w.astype(a.dtype)) for w in
                           (wq, wk, wv))
            d = attn.head_dim
            q = q.reshape(B, T, -1, d)
            k = k.reshape(B, T, -1, d)
            v = v.reshape(B, T, -1, d)
        q, k = self._qk_norm(attn, q, k)
        with jax.named_scope("attn_qkv"):
            cos, sin = self.model.rope_cos._value, self.model.rope_sin._value
            return (apply_rope(q, cos, sin, start),
                    apply_rope(k, cos, sin, start), v)

    def _qk_norm(self, attn, q, k):
        """RMSNorm over the ``head_dim`` lanes of every q and k head."""
        eps = self.config.rms_norm_eps
        with jax.named_scope("qk_norm"):
            return (_rms(q, attn.q_norm.weight._value, eps),
                    _rms(k, attn.k_norm.weight._value, eps))

    def _after_attention(self, layer, x, ctx, token_valid):
        """Output projection and the routed experts; ``(x, chosen [B, T,
        k], RouteStats)``."""
        B, T, H = x.shape
        with jax.named_scope("attn_out"):
            wo = layer.self_attn.o_proj.weight._value
            x = x + jnp.dot(ctx.reshape(B, T, -1), wo.astype(ctx.dtype))
        with jax.named_scope("mlp"):
            b = _rms(x, layer.post_attention_layernorm.weight._value,
                     self.config.rms_norm_eps)
            out, chosen, stats = layer.mlp.run(b.reshape(B * T, H),
                                               token_valid)
        return x + out.reshape(B, T, H), chosen.reshape(B, T, -1), stats

    def _embed(self, ids):
        with jax.named_scope("embed"):
            return self.model.embed_tokens.weight._value[ids]

    def _logits(self, x):
        """float32 logits of ``x [.., H]`` (bf16 products, float32
        accumulation)."""
        with jax.named_scope("final_norm"):
            x = _rms(x, self.model.norm.weight._value,
                     self.config.rms_norm_eps)
        with jax.named_scope("lm_head"):
            w = self.lm_head.weight._value
            return jnp.dot(x, w.astype(x.dtype),
                           preferred_element_type=jnp.float32)

    @staticmethod
    def _sum_stats(stats):
        return jnp.sum(jnp.stack([s.as_vector() for s in stats]), axis=0)

    # ------------------------------------------------------ full forward
    def forward(self, input_ids):
        """Plain pass over whole rows ``[B, T]`` under the block-causal
        mask: logits ``[B, T, V]`` float32 (position i's are for the
        token AT i)."""
        ids = input_ids._value if isinstance(input_ids, Tensor) \
            else jnp.asarray(input_ids)
        B, T = ids.shape
        L = self.config.block_length
        see = _block_causal(jnp.arange(T), jnp.arange(T), L)
        x = self._embed(ids)
        for layer in self.model.layers:
            q, k, v = self._qkv(layer, x, jnp.zeros((B,), jnp.int32))
            rep = q.shape[2] // k.shape[2]
            with jax.named_scope("attn"):
                kr, vr = (jnp.repeat(t, rep, axis=2) for t in (k, v))
                s = jnp.einsum("bqhd,bkhd->bhqk", q, kr,
                               preferred_element_type=jnp.float32) \
                    / math.sqrt(q.shape[-1])
                p = jax.nn.softmax(jnp.where(see, s, -1e30), axis=-1)
                ctx = jnp.einsum("bhqk,bkhd->bqhd", p.astype(q.dtype), vr)
            x, _, _ = self._after_attention(layer, x, ctx, None)
        return Tensor(self._logits(x))

    # ------------------------------------------------------- served passes
    def prefill_chunk(self, ids, valid, caches, start):
        """One chunk ``ids [B, C]`` of WHOLE blocks at positions
        ``start[b] + t``; ``valid [B, C]`` False past the chunk's last
        real token (such a position writes to the garbage block and
        reads no expert).  Returns ``(stats [3] int32, new caches)``."""
        from ..kernels.chunked_prefill import fused_chunked_attention

        B, C = ids.shape
        L = self.config.block_length
        pos = start[:, None] + jnp.arange(C)
        token_valid = valid.reshape(-1)
        x = self._embed(ids)
        new_caches, stats = [], []
        for layer, cache in zip(self.model.layers, caches):
            q, k, v = self._qkv(layer, x, start)
            bt = cache.block_table
            with jax.named_scope("kv_write"):
                k_pool = paged_scatter(cache.k, k, bt, pos, valid)
                v_pool = paged_scatter(cache.v, v, bt, pos, valid)
            with jax.named_scope("attn"):
                ctx = fused_chunked_attention(q, k_pool, v_pool, bt, start,
                                              mask_block=L)
            x, chosen, st = self._after_attention(layer, x, ctx,
                                                  token_valid)
            with jax.named_scope("kv_write"):
                c_pool = scatter_block_rows(cache.chosen, chosen, bt, start,
                                            valid)
            new_caches.append(RoutedKVCache(k_pool, v_pool, bt, c_pool))
            stats.append(st)
        return self._sum_stats(stats), new_caches

    def block_step(self, ids, caches, start, commit, active):
        """One block a slot: ``ids [S, L]`` at positions ``start[s] +
        t`` against the slot's cached positions ``< start[s]`` and the
        block itself.  Where ``commit[s]`` the block's K/V (and routing
        witness) are written to the pool; elsewhere they are dropped.
        ``active[s]`` False (an idle slot) reads no expert.  Returns
        ``(logits [S, L, V] f32, chosen [layers, S, L, k], stats [3],
        new caches)``."""
        from ..kernels.paged_attention import (NEG_INF, _combine_splits,
                                               paged_context_partials)

        S, L = ids.shape
        pos = start[:, None] + jnp.arange(L)
        write = jnp.broadcast_to(commit[:, None], (S, L))
        token_valid = jnp.repeat(active, L)
        has_context = (start > 0)[:, None, None, None]
        x = self._embed(ids)
        new_caches, stats, chose = [], [], []
        for layer, cache in zip(self.model.layers, caches):
            q, k, v = self._qkv(layer, x, start)
            bt = cache.block_table
            KVH, D = k.shape[2], k.shape[3]
            rep = q.shape[2] // KVH
            with jax.named_scope("attn"):
                # rows of a KV head: (group member, block position)
                q_g = q.reshape(S, L, KVH, rep, D).transpose(0, 2, 3, 1, 4) \
                    .reshape(S, KVH, rep * L, D)
                acc, m, l = paged_context_partials(
                    q_g, cache.k, cache.v, bt, jnp.maximum(start - 1, 0))
                m = jnp.where(has_context, m, NEG_INF)
                l = jnp.where(has_context, l, 0.0)
                # the block itself: every position sees all L
                s_in = jnp.einsum(
                    "bkrd,blkd->bkrl", q_g.astype(jnp.float32),
                    k.astype(jnp.float32),
                    preferred_element_type=jnp.float32) / math.sqrt(D)
                m_in = jnp.max(s_in, axis=-1)
                p_in = jnp.exp(s_in - m_in[..., None])
                acc_in = jnp.einsum("bkrl,blkd->bkrd", p_in,
                                    v.astype(jnp.float32),
                                    preferred_element_type=jnp.float32)
                ctx = _combine_splits(
                    jnp.concatenate([acc, acc_in[:, None]], axis=1),
                    jnp.concatenate([m, m_in[:, None]], axis=1),
                    jnp.concatenate([l, jnp.sum(p_in, -1)[:, None]], axis=1))
                ctx = ctx.reshape(S, KVH, rep, L, D) \
                    .transpose(0, 3, 1, 2, 4).reshape(S, L, KVH * rep, D) \
                    .astype(q.dtype)
            with jax.named_scope("kv_write"):
                k_pool = paged_scatter(cache.k, k, bt, pos, write)
                v_pool = paged_scatter(cache.v, v, bt, pos, write)
            x, chosen, st = self._after_attention(layer, x, ctx,
                                                  token_valid)
            with jax.named_scope("kv_write"):
                c_pool = scatter_block_rows(cache.chosen, chosen, bt, start,
                                            write)
            new_caches.append(RoutedKVCache(k_pool, v_pool, bt, c_pool))
            stats.append(st)
            chose.append(chosen)
        return (self._logits(x), jnp.stack(chose), self._sum_stats(stats),
                new_caches)


def routing_witness(model, engine, tokens, block_table, prompt_tokens,
                    in_flight=None):
    """What the step programs chose for the row ``tokens``, as
    ``[layers, len(tokens), k]`` int32: the experts of the first
    ``prompt_tokens`` positions read back, through the row's block
    table, from the pool entries the chunk and commit steps wrote; those
    of the positions after them from ``in_flight [layers, n, k]``, the
    block step's own output for the block it has not committed.  (The
    benchmark's ``"witness"`` of an ``sdar_moe`` configuration.)"""
    size = engine.config.block_size
    at = np.arange(prompt_tokens)
    rows = np.asarray(block_table)[at // size]
    # (a row a block: a block's positions one after another)
    cached = np.stack([
        np.asarray(entry[2]).reshape(engine.pool.num_blocks, size, -1)[
            rows, at % size]
        for entry in engine.pool.layers[:model.config.num_hidden_layers]])
    if in_flight is None:
        return cached.astype(np.int32)
    return np.concatenate(
        [cached, np.asarray(in_flight)], axis=1).astype(np.int32)

