# lint-tpu: disable-file=L004 -- a served model drives raw device buffers
# (like models/llama.py); new backend code belongs under core/ ops/
# kernels/ static/ distributed/ (README: Repo lint)
"""AFMoE: a causal decoder with WINDOW and FULL attention layers mixed,
gated attention and a sigmoid-routed mixture of experts beside a shared
expert (``model_type`` ``afmoe``: arcee-ai/Trinity-Mini).

What differs from ``models/llama.py`` and why it is a file beside it:

- **two kinds of attention layer** (``layer_types``): a
  ``sliding_attention`` layer rotates q and k (RoPE) and lets query ``i``
  see key ``j`` iff ``0 <= i - j < sliding_window``; a
  ``full_attention`` layer has NO rotation and the plain causal mask.
  The model says so in :meth:`AfmoeForCausalLM.cache_layers`, a record
  a layer, and the engine keeps the two kinds' pages in two groups
  (``serving/cache.py``);
- **gated attention**: a fifth projection ``g`` of q's width;
  the attention's output is multiplied by ``sigmoid(g)`` before the
  output projection; a norm on q and k (one weight of ``head_dim``
  each, before any rotation);
- **four norms a layer**: on each branch's input AND on its output
  (``x + RMSNorm(branch(RMSNorm(x)))``);
- **the feed-forward**: a SiLU-gated MLP in the first
  ``num_dense_layers`` layers; in the others a dropless top-k layer of
  ``num_experts`` experts behind a SIGMOID router whose selection (and
  only the selection) is moved by a per-expert bias, the chosen scores
  normalised (``route_norm``) and scaled (``route_scale``), plus a
  shared expert that every token passes through
  (:class:`~paddle_tpu.models.sdar_moe.DroplessMoE`, told which experts
  it holds);
- **the embedding's output is multiplied by** ``sqrt(hidden_size)``
  (``mup_enabled``).

What is shared: the embedding, ``LlamaRMSNorm``, the RoPE tables and
``apply_rope``, ``fused_norm_linear`` for the projections, the two
serving kernels (``fused_paged_decode``, ``fused_chunked_attention``,
each with ``window=`` on a window layer), ``DroplessMoE`` and its
grouped-experts kernel, the routing sidecar and its witness.

Served forward passes (``models/generation.py`` wraps them in the step
programs ``paged_decode_step`` and ``chunked_prefill_step``):

- :meth:`AfmoeForCausalLM.prefill_chunk`: one chunk of a prompt, K/V
  (and the routing witness) written to the pool, the logits of the
  chunk's last real token;
- :meth:`AfmoeForCausalLM.decode_token`: one token a slot.

Both take the pool's entries and ``tables = (full group's block table,
window group's)``.  ``forward(ids)`` is the plain full pass (no cache).
"""
from __future__ import annotations

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
from .llama import LlamaRMSNorm, apply_rope, paged_scatter, precompute_rope
from .sdar_moe import DroplessMoE, _normal, _rms, scatter_block_rows

SLIDING, FULL = "sliding_attention", "full_attention"


@dataclass
class AfmoeConfig:
    """The published ``config.json`` keys (the program reads
    ``layer_types``, never a period), then what this replica holds and
    how its seeded initialisation draws what a checkpoint would bring."""

    vocab_size: int = 200192
    hidden_size: int = 2048
    intermediate_size: int = 6144
    moe_intermediate_size: int = 1024
    num_hidden_layers: int = 32
    num_dense_layers: int = 2
    num_attention_heads: int = 32
    num_key_value_heads: int = 4
    head_dim: int = 128
    max_position_embeddings: int = 131072
    rms_norm_eps: float = 1e-5
    rope_theta: float = 10000.0
    tie_word_embeddings: bool = False
    sliding_window: int = 2048
    # a kind a layer; more than ``num_hidden_layers`` entries are a cut
    # model's published list, of which the first are kept.  None: three
    # window layers then a full one, over and over
    layer_types: Optional[Tuple[str, ...]] = None
    num_experts: int = 128
    num_experts_per_tok: int = 8
    num_shared_experts: int = 1
    score_func: str = "sigmoid"
    route_norm: bool = True
    route_scale: float = 2.826
    mup_enabled: bool = True
    n_group: int = 1
    topk_group: int = 1
    num_expert_groups: int = 1
    num_limited_groups: int = 1
    # the experts this replica HOLDS (ids among num_experts; None: all)
    held_experts: Optional[Tuple[int, ...]] = None
    # the seeded initialisation's selection bias: a trained checkpoint's
    # is not zero, and at zero the selection-only rule is tested by
    # nothing
    expert_bias_std: float = 0.02
    dtype: str = "bfloat16"

    def __post_init__(self):
        n = self.num_hidden_layers
        if self.layer_types is None:
            self.layer_types = tuple(FULL if i % 4 == 3 else SLIDING
                                     for i in range(n))
        self.layer_types = tuple(self.layer_types)[:n]
        if len(self.layer_types) != n or \
                set(self.layer_types) - {SLIDING, FULL}:
            raise ValueError(
                f"layer_types must name {n} layers as {SLIDING!r} or "
                f"{FULL!r}, got {self.layer_types}")
        if self.score_func not in ("sigmoid", "softmax"):
            raise ValueError(f"score_func {self.score_func!r}")
        groups = (self.n_group, self.topk_group, self.num_expert_groups,
                  self.num_limited_groups)
        if groups != (1, 1, 1, 1):
            raise ValueError(
                f"group-limited expert selection is not built (n_group, "
                f"topk_group, num_expert_groups, num_limited_groups = "
                f"{groups}; all must be 1)")
        if self.tie_word_embeddings:
            raise ValueError("a tied head is not built for this model")
        if self.held_experts is not None:
            self.held_experts = tuple(int(e) for e in self.held_experts)

    @staticmethod
    def tiny(**overrides):
        kwargs = dict(
            vocab_size=256, hidden_size=64, intermediate_size=128,
            moe_intermediate_size=32, num_hidden_layers=3,
            num_dense_layers=1, num_attention_heads=4,
            num_key_value_heads=2, head_dim=16,
            max_position_embeddings=256, sliding_window=32,
            layer_types=(SLIDING, SLIDING, FULL), num_experts=16,
            num_experts_per_tok=4, dtype="float32")
        kwargs.update(overrides)
        return AfmoeConfig(**kwargs)


class AfmoeMLP(nn.Layer):
    """A SiLU-gated MLP: the dense layers' feed-forward and the shared
    expert."""

    def __init__(self, hidden_size, width):
        super().__init__()
        self.gate_proj = ColumnParallelLinear(hidden_size, width,
                                              has_bias=False,
                                              gather_output=False)
        self.up_proj = ColumnParallelLinear(hidden_size, width,
                                            has_bias=False,
                                            gather_output=False)
        self.down_proj = RowParallelLinear(width, hidden_size,
                                           has_bias=False,
                                           input_is_parallel=True)

    def run(self, x, norm_weight, eps):
        """``down(silu(gate(n)) * up(n))`` of ``n = RMSNorm(x)``, the
        norm folded into the two projections where the step is fused."""
        from ..kernels.fusion import fusion_enabled

        wg, wu, wd = (self.gate_proj.weight._value,
                      self.up_proj.weight._value,
                      self.down_proj.weight._value)
        if fusion_enabled():
            from ..kernels.fused_norm_linear import (fused_norm_linear,
                                                     rms_scale)

            rs = rms_scale(x, eps)
            g = fused_norm_linear(x, rs, norm_weight, wg, activation="silu")
            u = fused_norm_linear(x, rs, norm_weight, wu)
        else:
            n = _rms(x, norm_weight, eps)
            g = jax.nn.silu(jnp.dot(n, wg.astype(n.dtype)))
            u = jnp.dot(n, wu.astype(n.dtype))
        return jnp.dot(g * u, wd.astype(g.dtype))


class AfmoeAttention(nn.Layer):
    def __init__(self, config: AfmoeConfig):
        super().__init__()
        h, d = config.hidden_size, config.head_dim
        self.num_heads = config.num_attention_heads
        self.num_kv_heads = config.num_key_value_heads
        self.head_dim = d

        def column(width):
            return ColumnParallelLinear(h, width, has_bias=False,
                                        gather_output=False)

        self.q_proj = column(self.num_heads * d)
        self.k_proj = column(self.num_kv_heads * d)
        self.v_proj = column(self.num_kv_heads * d)
        self.gate_proj = column(self.num_heads * d)
        self.o_proj = RowParallelLinear(self.num_heads * d, h,
                                        has_bias=False,
                                        input_is_parallel=True)
        self.q_norm = LlamaRMSNorm(d, config.rms_norm_eps)
        self.k_norm = LlamaRMSNorm(d, config.rms_norm_eps)


class AfmoeDecoderLayer(nn.Layer):
    def __init__(self, config: AfmoeConfig, index: int):
        super().__init__()
        h, eps = config.hidden_size, config.rms_norm_eps
        #: the window in keys, None for a full layer (which has no RoPE)
        self.window = config.sliding_window \
            if config.layer_types[index] == SLIDING else None
        self.routed = index >= config.num_dense_layers
        self.input_layernorm = LlamaRMSNorm(h, eps)
        self.self_attn = AfmoeAttention(config)
        self.post_attention_layernorm = LlamaRMSNorm(h, eps)
        self.pre_mlp_layernorm = LlamaRMSNorm(h, eps)
        if self.routed:
            self.mlp = DroplessMoE(
                h, config.moe_intermediate_size, config.num_experts,
                config.num_experts_per_tok, normalize=config.route_norm,
                held=config.held_experts, dtype=config.dtype,
                scores=config.score_func, selection_bias=True,
                route_scale=config.route_scale, norm_eps=1e-20)
            self.shared_expert = AfmoeMLP(
                h, config.moe_intermediate_size * config.num_shared_experts)
        else:
            self.mlp = AfmoeMLP(h, config.intermediate_size)
        self.post_mlp_layernorm = LlamaRMSNorm(h, eps)


class AfmoeModel(nn.Layer):
    def __init__(self, config: AfmoeConfig):
        super().__init__()
        from ..nn import initializer as I
        from ..nn.layer.layers import ParamAttr
        from ..ops import random as rnd

        self.config = config
        bf16 = config.dtype == "bfloat16"

        def built(layer):
            # parameters are created in float32; narrowing each part as
            # it is built keeps the float32 transient to one part
            return layer.bfloat16() if bf16 else layer

        # rows of unit RMS once the mup multiplier is on them
        self.embed_tokens = built(VocabParallelEmbedding(
            config.vocab_size, config.hidden_size,
            weight_attr=ParamAttr(initializer=I.Normal(
                0.0, 1.0 / math.sqrt(config.hidden_size)
                if config.mup_enabled else 1.0))))
        self.layers = nn.LayerList(
            [built(AfmoeDecoderLayer(config, i))
             for i in range(config.num_hidden_layers)])
        self.norm = LlamaRMSNorm(config.hidden_size, config.rms_norm_eps)
        if bf16:
            self.bfloat16()
        # float32 whatever the weights are (registered after the
        # narrowing): the rope tables, and the selection bias, which is
        # added to float32 scores
        cos, sin = precompute_rope(config.head_dim,
                                   config.max_position_embeddings,
                                   config.rope_theta)
        self.register_buffer("rope_cos", Tensor(cos), persistable=False)
        self.register_buffer("rope_sin", Tensor(sin), persistable=False)
        for layer in self.layers:
            if layer.routed:
                layer.mlp.register_buffer("expert_bias", Tensor(_normal(
                    rnd.next_key(), (config.num_experts,),
                    float(config.expert_bias_std), jnp.float32)))


class AfmoeForCausalLM(nn.Layer):
    """The model and its forward passes (module docstring)."""

    def __init__(self, config: AfmoeConfig):
        super().__init__()
        self.config = config
        self.model = AfmoeModel(config)
        self.lm_head = ColumnParallelLinear(
            config.hidden_size, config.vocab_size, has_bias=False,
            gather_output=True)
        if config.dtype == "bfloat16":
            self.lm_head.bfloat16()

    # ------------------------------------------------- what the engine asks
    def cache_layers(self):
        """The model's description of its cache, a record a layer
        (``serving/cache.py::LayerCache``): the layer's kind, its K/V
        geometry, and beside them the routing witness (``k`` expert ids a
        position) of a routed layer."""
        from ..serving.cache import LayerCache

        cfg = self.config
        dtype = jnp.bfloat16 if cfg.dtype == "bfloat16" else jnp.float32
        witness = (((cfg.num_experts_per_tok,), jnp.int32),)
        return [LayerCache(cfg.num_key_value_heads, cfg.head_dim, dtype,
                           window=layer.window,
                           sidecars=witness if layer.routed else ())
                for layer in self.model.layers]

    # ------------------------------------------------------------ pieces
    def _projections(self, layer, x):
        """The input norm into q, k, v and the gate, the norm on q and
        k (no rotation): ``(q [B,T,Hq,D], k, v [B,T,KVH,D], g [B,T,
        Hq*D])``."""
        from ..kernels.fusion import fusion_enabled

        attn = layer.self_attn
        B, T, _ = x.shape
        nw, eps = layer.input_layernorm.weight._value, \
            self.config.rms_norm_eps
        weights = [p.weight._value for p in (
            attn.q_proj, attn.k_proj, attn.v_proj, attn.gate_proj)]
        with jax.named_scope("attn_qkv"):
            if fusion_enabled():
                from ..kernels.fused_norm_linear import (fused_norm_linear,
                                                         rms_scale)

                rs = rms_scale(x, eps)
                q, k, v, g = (fused_norm_linear(x, rs, nw, w)
                              for w in weights)
            else:
                a = _rms(x, nw, eps)
                q, k, v, g = (jnp.dot(a, w.astype(a.dtype))
                              for w in weights)
            d = attn.head_dim
            q = q.reshape(B, T, -1, d)
            k = k.reshape(B, T, -1, d)
            v = v.reshape(B, T, -1, d)
        with jax.named_scope("qk_norm"):
            q = _rms(q, attn.q_norm.weight._value, eps)
            k = _rms(k, attn.k_norm.weight._value, eps)
        return q, k, v, g

    def _rotate(self, layer, q, k, start):
        """RoPE at positions ``start[b] + t`` on a window layer; a full
        layer has no position encoding."""
        if layer.window is None:
            return q, k
        with jax.named_scope("attn_qkv"):
            cos, sin = self.model.rope_cos._value, self.model.rope_sin._value
            return (apply_rope(q, cos, sin, start),
                    apply_rope(k, cos, sin, start))

    def _after_attention(self, layer, x, ctx, g, token_valid):
        """The gate, the output projection and the feed-forward, each
        branch's output normed before it joins the stream: ``(x, chosen
        [B, T, k] or None, RouteStats or None)``."""
        B, T, H = x.shape
        eps = self.config.rms_norm_eps
        with jax.named_scope("attn_gate"):
            a = (ctx.reshape(B, T, -1).astype(jnp.float32)
                 * jax.nn.sigmoid(g.astype(jnp.float32))).astype(x.dtype)
        with jax.named_scope("attn_out"):
            wo = layer.self_attn.o_proj.weight._value
            x = x + _rms(jnp.dot(a, wo.astype(a.dtype)),
                         layer.post_attention_layernorm.weight._value, eps)
        nw = layer.pre_mlp_layernorm.weight._value
        chosen = stats = None
        with jax.named_scope("mlp"):
            if layer.routed:
                b = _rms(x, nw, eps)
                m, chosen, stats = layer.mlp.run(b.reshape(B * T, H),
                                                 token_valid)
                with jax.named_scope("moe_shared"):
                    m = m.reshape(B, T, H) \
                        + layer.shared_expert.run(x, nw, eps)
                chosen = chosen.reshape(B, T, -1)
            else:
                m = layer.mlp.run(x, nw, eps)
            x = x + _rms(m, layer.post_mlp_layernorm.weight._value, eps)
        return x, chosen, stats

    def _embed(self, ids):
        with jax.named_scope("embed"):
            x = self.model.embed_tokens.weight._value[ids]
            if self.config.mup_enabled:
                x = x * jnp.asarray(math.sqrt(self.config.hidden_size),
                                    x.dtype)
            return x

    def _logits(self, x):
        """float32 logits of ``x [.., H]`` (products in the weights'
        type, float32 accumulation)."""
        with jax.named_scope("final_norm"):
            x = _rms(x, self.model.norm.weight._value,
                     self.config.rms_norm_eps)
        with jax.named_scope("lm_head"):
            w = self.lm_head.weight._value
            return jnp.dot(x, w.astype(x.dtype),
                           preferred_element_type=jnp.float32)

    @staticmethod
    def _sum_stats(stats):
        if not stats:                       # every layer dense
            return jnp.zeros((3,), jnp.int32)
        return jnp.sum(jnp.stack([s.as_vector() for s in stats]), axis=0)

    # ------------------------------------------------------ full forward
    def forward(self, input_ids):
        """Plain pass over whole rows ``[B, T]``, every layer under its
        own mask: logits ``[B, T, V]`` float32."""
        ids = input_ids._value if isinstance(input_ids, Tensor) \
            else jnp.asarray(input_ids)
        B, T = ids.shape
        at = jnp.arange(T)
        back = at[:, None] - at[None, :]                # i - j
        x = self._embed(ids)
        for layer in self.model.layers:
            q, k, v, g = self._projections(layer, x)
            q, k = self._rotate(layer, q, k, jnp.zeros((B,), jnp.int32))
            see = back >= 0
            if layer.window is not None:
                see = see & (back < layer.window)
            rep = q.shape[2] // k.shape[2]
            with jax.named_scope("attn"):
                kr, vr = (jnp.repeat(t, rep, axis=2) for t in (k, v))
                s = jnp.einsum("bqhd,bkhd->bhqk", q, kr,
                               preferred_element_type=jnp.float32) \
                    / math.sqrt(q.shape[-1])
                p = jax.nn.softmax(jnp.where(see, s, -1e30), axis=-1)
                ctx = jnp.einsum("bhqk,bkhd->bqhd", p.astype(q.dtype), vr)
            x, _, _ = self._after_attention(layer, x, ctx, g, None)
        return Tensor(self._logits(x))

    # ------------------------------------------------------- served passes
    @staticmethod
    def _table(layer, tables):
        """The layer's group's block table of ``tables = (full group's,
        window group's)``."""
        return tables[0] if layer.window is None else tables[1]

    def prefill_chunk(self, ids, valid, pools, tables, start, last_index):
        """One chunk ``ids [B, C]`` at positions ``start[b] + t``;
        ``valid [B, C]`` False past the chunk's last real token (such a
        position writes to the garbage block and reads no routed
        expert).  ``pools`` are the pool's entries, a tuple a layer:
        ``(k, v)`` and, for a routed layer, the witness.  Returns
        ``(logits [B, V] f32 of the token at ``last_index``, stats [3]
        int32, new pools)``."""
        from ..kernels.chunked_prefill import fused_chunked_attention

        B, C = ids.shape
        pos = start[:, None] + jnp.arange(C)
        token_valid = valid.reshape(-1)
        x = self._embed(ids)
        new_pools, stats = [], []
        for layer, entry in zip(self.model.layers, pools):
            bt = self._table(layer, tables)
            q, k, v, g = self._projections(layer, x)
            q, k = self._rotate(layer, q, k, start)
            with jax.named_scope("kv_write"):
                k_pool = paged_scatter(entry[0], k, bt, pos, valid)
                v_pool = paged_scatter(entry[1], v, bt, pos, valid)
            with jax.named_scope("attn"):
                ctx = fused_chunked_attention(q, k_pool, v_pool, bt, start,
                                              window=layer.window)
            x, chosen, st = self._after_attention(layer, x, ctx, g,
                                                  token_valid)
            if layer.routed:
                with jax.named_scope("kv_write"):
                    # the witness outlives a window layer's pages: it
                    # lies with the FULL group's
                    c_pool = scatter_block_rows(entry[2], chosen,
                                                tables[0], start, valid)
                new_pools.append((k_pool, v_pool, c_pool))
                stats.append(st)
            else:
                new_pools.append((k_pool, v_pool))
        last = jax.lax.dynamic_index_in_dim(x, last_index, axis=1,
                                            keepdims=False)
        return self._logits(last), self._sum_stats(stats), new_pools

    def decode_token(self, tok, pools, tables, lengths):
        """One token a slot: ``tok [S, 1]`` at position ``lengths[s]``
        against the slot's cached positions.  A slot of length 0 is idle
        (a running one holds its prompt): it reads no routed expert and
        writes to the garbage block.  Returns ``(logits [S, V] f32,
        stats [3] int32, new pools)``."""
        from ..kernels.paged_attention import fused_paged_decode

        active = lengths > 0
        x = self._embed(tok)
        new_pools, stats = [], []
        for layer, entry in zip(self.model.layers, pools):
            bt = self._table(layer, tables)
            q, k, v, g = self._projections(layer, x)
            rope = (None, None) if layer.window is None else (
                self.model.rope_cos._value, self.model.rope_sin._value)
            with jax.named_scope("attn"):
                ctx, k_pool, v_pool = fused_paged_decode(
                    q, k, v, entry[0], entry[1], bt, lengths, *rope,
                    window=layer.window)
            x, chosen, st = self._after_attention(layer, x, ctx, g, active)
            if layer.routed:
                with jax.named_scope("kv_write"):
                    c_pool = scatter_block_rows(
                        entry[2], chosen, tables[0], lengths,
                        active[:, None])
                new_pools.append((k_pool, v_pool, c_pool))
                stats.append(st)
            else:
                new_pools.append((k_pool, v_pool))
        return self._logits(x[:, 0]), self._sum_stats(stats), new_pools


def routing_witness(model, engine, tokens, block_table, prompt_tokens=None):
    """What the step programs chose for the row ``tokens``, as ``[routed
    layers, len(tokens), k]`` int32: read back, through the row's block
    table of the FULL group, from the pool entries the chunk and decode
    steps wrote (a window layer's witness lies there too, so it holds
    every position long after the layer's pages went back).  (The
    benchmark's ``"witness"`` of an ``afmoe`` configuration.)"""
    size = engine.config.block_size
    at = np.arange(len(tokens))
    rows = np.asarray(block_table)[at // size]
    return np.stack([
        np.asarray(entry[2]).reshape(engine.pool.num_blocks, size, -1)[
            rows, at % size]
        for layer, entry in zip(model.model.layers, engine.pool.layers)
        if layer.routed]).astype(np.int32)
