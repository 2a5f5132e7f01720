# lint-tpu: disable-file=L004 -- a served model drives raw device buffers
# (like models/llama.py); new backend code belongs under core/ ops/
# kernels/ static/ distributed/ (README: Repo lint)
"""GLM-4-MoE-Lite: a causal decoder with multi-head LATENT attention
(MLA: a low-rank query, one compressed key/value and one shared rotary
key a position) and a sigmoid-routed mixture of experts beside a shared
expert (``model_type`` ``glm4_moe_lite``: zai-org/GLM-4.7-Flash).

What differs from ``models/llama.py`` and ``models/afmoe.py`` and why it
is a file beside them:

- **the query is low-rank**: ``c_q = RMSNorm(h.W_qa)`` (``q_lora_rank``),
  ``q = c_q.W_qb`` split a head into ``q_nope`` (``qk_nope_head_dim``)
  and ``q_rope`` (``qk_rope_head_dim``, rotated);
- **keys and values are ONE compressed array a position**: ``[c_raw |
  k_r] = h.W_kva``, ``c_kv = RMSNorm(c_raw)`` (``kv_lora_rank``), ``k_rope
  = RoPE(k_r)``, ONE rotary key shared by every head; a head's ``k_nope``
  and ``v`` are ``c_kv.W_kvb`` (``W_UK_h``, ``W_UV_h``);
- **what is cached is the latent** ``e = [c_kv | k_rope]``, no heads, no
  separate V (:meth:`Glm4MoeLiteForCausalLM.cache_layers`: a record of
  kind ``latent``), and the served passes attend in the ABSORBED form:
  ``q~_h = [q_nope_h.W_UK_h^T | q_rope_h]``, ``score = q~_h.e / sqrt(
  qk_head_dim)``, ``o~_h = sum p.e[:kv_lora_rank]``, ``o_h = o~_h.W_UV_h``
  (``kernels/latent_attention.py``: a page is read once and per-head
  keys or values of the context never exist).  ``forward`` (the plain
  full pass) computes the EXPANDED form, per-head keys and values; the
  two are the same algebra in another order of roundings;
- **two norms a layer**, both on a branch's input;
- **the feed-forward**: a SiLU-gated MLP in the first
  ``first_k_dense_replace`` layers; in the others a dropless top-k layer
  (``topk_method`` ``noaux_tc``) of ``n_routed_experts`` experts behind a
  sigmoid router whose selection (only) is moved by a per-expert bias,
  the chosen scores normalised (``norm_topk_prob``) and scaled
  (``routed_scaling_factor``), plus a shared expert
  (:class:`~paddle_tpu.models.sdar_moe.DroplessMoE`, told which experts
  it holds).

``num_nextn_predict_layers`` (the checkpoint's extra layer that predicts
a second token) is read and NOT built: the forward pass does not run it,
and served as a self-draft it needs speculation over a latent pool.

Served forward passes (``models/generation.py`` wraps them in the step
programs ``paged_decode_step`` and ``chunked_prefill_step``), one block
table (every layer is a full layer: one group):

- :meth:`Glm4MoeLiteForCausalLM.prefill_chunk`: one chunk of a prompt,
  the latent entries (and the routing witness) written to the pool, the
  logits of the chunk's last real token;
- :meth:`Glm4MoeLiteForCausalLM.decode_token`: one token a slot.
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
from .afmoe import AfmoeMLP
from .llama import LlamaRMSNorm, apply_rope, paged_scatter, precompute_rope
from .sdar_moe import DroplessMoE, _normal, _rms, scatter_block_rows


@dataclass
class Glm4MoeLiteConfig:
    """The published ``config.json`` keys, then what this replica holds
    and how its seeded initialisation draws what a checkpoint would
    bring."""

    vocab_size: int = 154880
    hidden_size: int = 2048
    intermediate_size: int = 10240
    moe_intermediate_size: int = 1536
    num_hidden_layers: int = 47
    num_attention_heads: int = 20
    num_key_value_heads: int = 20
    q_lora_rank: int = 768
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 192
    qk_rope_head_dim: int = 64
    v_head_dim: int = 256
    n_routed_experts: int = 64
    n_shared_experts: int = 1
    num_experts_per_tok: int = 4
    first_k_dense_replace: int = 1
    norm_topk_prob: bool = True
    routed_scaling_factor: float = 1.8
    topk_method: str = "noaux_tc"
    n_group: int = 1
    topk_group: int = 1
    num_nextn_predict_layers: int = 1
    partial_rotary_factor: float = 1.0
    max_position_embeddings: int = 202752
    rms_norm_eps: float = 1e-5
    rope_theta: float = 1000000.0
    rope_scaling: Optional[dict] = None
    attention_bias: bool = False
    tie_word_embeddings: bool = False
    hidden_act: str = "silu"
    # the experts this replica HOLDS (ids among n_routed_experts; None: all)
    held_experts: Optional[Tuple[int, ...]] = None
    # the seeded initialisation (a trained checkpoint brings its own): the
    # selection bias is not zero, or the selection-only rule is tested by
    # nothing
    expert_bias_std: float = 0.02
    dtype: str = "bfloat16"

    def __post_init__(self):
        for key, want in (("topk_method", "noaux_tc"), ("n_group", 1),
                          ("topk_group", 1), ("rope_scaling", None),
                          ("partial_rotary_factor", 1),
                          ("attention_bias", False),
                          ("tie_word_embeddings", False),
                          ("hidden_act", "silu")):
            if getattr(self, key) != want:
                raise ValueError(
                    f"{key}={getattr(self, key)!r} is not built for this "
                    f"model (only {want!r}: no group-limited selection, "
                    "no rope scaling, every rotary lane turned, no bias, "
                    "an untied head)")
        # (``num_key_value_heads`` is read by nothing: every head has its
        # own up-projection of the one latent)
        if self.held_experts is not None:
            self.held_experts = tuple(int(e) for e in self.held_experts)

    @property
    def qk_head_dim(self) -> int:
        return self.qk_nope_head_dim + self.qk_rope_head_dim

    @property
    def latent_dim(self) -> int:
        """What a position caches a layer: ``[c_kv | k_rope]``."""
        return self.kv_lora_rank + self.qk_rope_head_dim

    @staticmethod
    def tiny(**overrides):
        kwargs = dict(
            vocab_size=256, hidden_size=64, intermediate_size=128,
            moe_intermediate_size=32, num_hidden_layers=3,
            first_k_dense_replace=1, num_attention_heads=4,
            num_key_value_heads=4, q_lora_rank=32, kv_lora_rank=48,
            qk_nope_head_dim=24, qk_rope_head_dim=16, v_head_dim=32,
            n_routed_experts=16, num_experts_per_tok=4,
            max_position_embeddings=256, dtype="float32")
        kwargs.update(overrides)
        return Glm4MoeLiteConfig(**kwargs)


# The seeded initialisation draws ``W_qb`` this many times the
# unit-variance scale (scores of that standard deviation), so that a
# softmax over thousands of random keys does not average the values to
# nothing and a fault in the attention reaches the logits.  Not a
# published key and not an option: a trained checkpoint brings its own.
_Q_GAIN = 2.0


class Glm4MoeLiteAttention(nn.Layer):
    def __init__(self, config: Glm4MoeLiteConfig):
        super().__init__()
        from ..nn import initializer as I
        from ..nn.layer.layers import ParamAttr

        h, heads = config.hidden_size, config.num_attention_heads
        self.num_heads = heads

        def column(fan_in, width, gain=1.0):
            # unit-variance outputs for unit-variance inputs, times gain
            return ColumnParallelLinear(
                fan_in, width, has_bias=False, gather_output=False,
                weight_attr=ParamAttr(initializer=I.Normal(
                    0.0, gain / math.sqrt(fan_in))))

        self.q_a_proj = column(h, config.q_lora_rank)
        self.q_a_layernorm = LlamaRMSNorm(config.q_lora_rank,
                                          config.rms_norm_eps)
        self.q_b_proj = column(config.q_lora_rank,
                               heads * config.qk_head_dim,
                               _Q_GAIN)
        self.kv_a_proj_with_mqa = column(h, config.latent_dim)
        self.kv_a_layernorm = LlamaRMSNorm(config.kv_lora_rank,
                                           config.rms_norm_eps)
        self.kv_b_proj = column(
            config.kv_lora_rank,
            heads * (config.qk_nope_head_dim + config.v_head_dim))
        self.o_proj = RowParallelLinear(
            heads * config.v_head_dim, h, has_bias=False,
            input_is_parallel=True,
            weight_attr=ParamAttr(initializer=I.Normal(
                0.0, 1.0 / math.sqrt(heads * config.v_head_dim))))


class Glm4MoeLiteDecoderLayer(nn.Layer):
    def __init__(self, config: Glm4MoeLiteConfig, index: int):
        super().__init__()
        h, eps = config.hidden_size, config.rms_norm_eps
        self.routed = index >= config.first_k_dense_replace
        self.input_layernorm = LlamaRMSNorm(h, eps)
        self.self_attn = Glm4MoeLiteAttention(config)
        self.post_attention_layernorm = LlamaRMSNorm(h, eps)
        if self.routed:
            self.mlp = DroplessMoE(
                h, config.moe_intermediate_size, config.n_routed_experts,
                config.num_experts_per_tok,
                normalize=config.norm_topk_prob, held=config.held_experts,
                dtype=config.dtype, scores="sigmoid", selection_bias=True,
                route_scale=config.routed_scaling_factor, norm_eps=1e-20)
            self.shared_experts = AfmoeMLP(
                h, config.moe_intermediate_size * config.n_shared_experts)
        else:
            self.mlp = AfmoeMLP(h, config.intermediate_size)


class Glm4MoeLiteModel(nn.Layer):
    def __init__(self, config: Glm4MoeLiteConfig):
        super().__init__()
        from ..ops import random as rnd

        self.config = config
        bf16 = config.dtype == "bfloat16"

        def built(layer):
            # parameters are created in float32; narrowing each part as
            # it is built keeps the float32 transient to one part
            return layer.bfloat16() if bf16 else layer

        # rows of unit RMS (the embedding's default): a branch's output
        # is of the stream's own size
        self.embed_tokens = built(VocabParallelEmbedding(
            config.vocab_size, config.hidden_size))
        self.layers = nn.LayerList(
            [built(Glm4MoeLiteDecoderLayer(config, i))
             for i in range(config.num_hidden_layers)])
        self.norm = LlamaRMSNorm(config.hidden_size, config.rms_norm_eps)
        if bf16:
            self.bfloat16()
        # float32 whatever the weights are (registered after the
        # narrowing): the rope tables, and the selection bias, which is
        # added to float32 scores
        cos, sin = precompute_rope(config.qk_rope_head_dim,
                                   config.max_position_embeddings,
                                   config.rope_theta)
        self.register_buffer("rope_cos", Tensor(cos), persistable=False)
        self.register_buffer("rope_sin", Tensor(sin), persistable=False)
        for layer in self.layers:
            if layer.routed:
                layer.mlp.register_buffer("expert_bias", Tensor(_normal(
                    rnd.next_key(), (config.n_routed_experts,),
                    float(config.expert_bias_std), jnp.float32)))


class Glm4MoeLiteForCausalLM(nn.Layer):
    """The model and its forward passes (module docstring)."""

    def __init__(self, config: Glm4MoeLiteConfig):
        super().__init__()
        self.config = config
        self.model = Glm4MoeLiteModel(config)
        self.lm_head = ColumnParallelLinear(
            config.hidden_size, config.vocab_size, has_bias=False,
            gather_output=True)
        if config.dtype == "bfloat16":
            self.lm_head.bfloat16()

    # ------------------------------------------------- what the engine asks
    def cache_layers(self):
        """The model's description of its cache, a LATENT record a layer
        (``serving/cache.py::LayerCache``): one key of ``kv_lora_rank +
        qk_rope_head_dim`` numbers a position whose first ``kv_lora_rank``
        are the value, and beside it the routing witness (``k`` expert
        ids a position) of a routed layer."""
        from ..serving.cache import LayerCache

        cfg = self.config
        dtype = jnp.bfloat16 if cfg.dtype == "bfloat16" else jnp.float32
        witness = (((cfg.num_experts_per_tok,), jnp.int32),)
        return [LayerCache(1, cfg.latent_dim, dtype,
                           value_dim=cfg.kv_lora_rank,
                           sidecars=witness if layer.routed else ())
                for layer in self.model.layers]

    # ------------------------------------------------------------ pieces
    def _latents(self, layer, x, start):
        """The input norm into the two low-rank projections, their norms
        and the rotations: ``(q_nope [B,T,H,nope], q_rope [B,T,H,rope]
        rotated, c_kv [B,T,rank] normed, k_rope [B,T,rope] rotated)`` for
        tokens at positions ``start[b] + t``."""
        from ..kernels.fusion import fusion_enabled

        cfg, attn = self.config, layer.self_attn
        B, T, _ = x.shape
        eps = cfg.rms_norm_eps
        nw = layer.input_layernorm.weight._value
        w_qa, w_qb, w_kva = (p.weight._value for p in (
            attn.q_a_proj, attn.q_b_proj, attn.kv_a_proj_with_mqa))
        qn, kn = (attn.q_a_layernorm.weight._value,
                  attn.kv_a_layernorm.weight._value)
        cos, sin = self.model.rope_cos._value, self.model.rope_sin._value
        if fusion_enabled():
            from ..kernels.fused_norm_linear import (fused_norm_linear,
                                                     rms_scale)

            rs = rms_scale(x, eps)
            with jax.named_scope("attn_q_latent"):
                c_q = fused_norm_linear(x, rs, nw, w_qa)
                q = fused_norm_linear(c_q, rms_scale(c_q, eps), qn, w_qb)
            with jax.named_scope("attn_kv_latent"):
                kv = fused_norm_linear(x, rs, nw, w_kva)
        else:
            a = _rms(x, nw, eps)
            with jax.named_scope("attn_q_latent"):
                q = jnp.dot(_rms(jnp.dot(a, w_qa.astype(a.dtype)), qn, eps),
                            w_qb.astype(a.dtype))
            with jax.named_scope("attn_kv_latent"):
                kv = jnp.dot(a, w_kva.astype(a.dtype))
        with jax.named_scope("attn_q_latent"):
            q = q.reshape(B, T, attn.num_heads, cfg.qk_head_dim)
            q_nope = q[..., :cfg.qk_nope_head_dim]
            q_rope = apply_rope(q[..., cfg.qk_nope_head_dim:], cos, sin,
                                start)
        with jax.named_scope("attn_kv_latent"):
            c_kv = _rms(kv[..., :cfg.kv_lora_rank], kn, eps)
            k_rope = self._rotate_key(kv[..., cfg.kv_lora_rank:], cos, sin,
                                      start)
        return q_nope, q_rope, c_kv, k_rope

    @staticmethod
    def _rotate_key(k_r, cos, sin, start):
        """The one rotary key a position, ``[B, T, rope]``."""
        return apply_rope(k_r[:, :, None, :], cos, sin, start)[:, :, 0]

    def _up_projections(self, layer):
        """``kv_b_proj`` a head: ``(W_UK [rank, H, nope], W_UV [rank, H,
        v])``."""
        cfg = self.config
        w = layer.self_attn.kv_b_proj.weight._value.reshape(
            cfg.kv_lora_rank, cfg.num_attention_heads,
            cfg.qk_nope_head_dim + cfg.v_head_dim)
        return w[..., :cfg.qk_nope_head_dim], w[..., cfg.qk_nope_head_dim:]

    def _entry(self, c_kv, k_rope, lanes):
        """What a position caches, ``[c_kv | k_rope]`` and zeros up to
        the pool's ``lanes``."""
        from ..kernels.paged_attention import pad_lanes

        return pad_lanes(jnp.concatenate([c_kv, k_rope], axis=-1), lanes)

    def _absorbed_query(self, layer, q_nope, q_rope, lanes):
        """``q~ = [q_nope.W_UK^T | q_rope] / sqrt(qk_head_dim)`` a head,
        zeros up to ``lanes``: ``[B, T, H, lanes]``."""
        from ..kernels.paged_attention import pad_lanes

        w_uk, _ = self._up_projections(layer)
        with jax.named_scope("attn_absorb"):
            q_lat = jnp.einsum("bthd,rhd->bthr", q_nope,
                               w_uk.astype(q_nope.dtype),
                               preferred_element_type=jnp.float32)
            q = jnp.concatenate([q_lat, q_rope.astype(jnp.float32)], axis=-1)
            q = (q / math.sqrt(self.config.qk_head_dim)).astype(q_nope.dtype)
            return pad_lanes(q, lanes)

    def _after_attention(self, layer, x, o_lat, token_valid):
        """``o = o~.W_UV`` a head, the output projection and the
        feed-forward: ``(x, chosen [B, T, k] or None, RouteStats or
        None)``; ``o_lat [B, T, H, rank]`` float32."""
        B, T, H = x.shape
        _, w_uv = self._up_projections(layer)
        with jax.named_scope("attn_absorb"):
            ctx = jnp.einsum("bthr,rhv->bthv", o_lat.astype(x.dtype),
                             w_uv.astype(x.dtype),
                             preferred_element_type=jnp.float32)
            ctx = ctx.astype(x.dtype).reshape(B, T, -1)
        return self._after_context(layer, x, ctx, token_valid)

    def _after_context(self, layer, x, ctx, token_valid):
        B, T, H = x.shape
        eps = self.config.rms_norm_eps
        with jax.named_scope("attn_out"):
            wo = layer.self_attn.o_proj.weight._value
            x = x + jnp.dot(ctx, wo.astype(ctx.dtype))
        nw = layer.post_attention_layernorm.weight._value
        chosen = stats = None
        with jax.named_scope("mlp"):
            if layer.routed:
                b = _rms(x, nw, eps)
                m, chosen, stats = layer.mlp.run(b.reshape(B * T, H),
                                                 token_valid)
                with jax.named_scope("moe_shared"):
                    m = m.reshape(B, T, H) \
                        + layer.shared_experts.run(x, nw, eps)
                chosen = chosen.reshape(B, T, -1)
            else:
                m = layer.mlp.run(x, nw, eps)
            x = x + m.astype(x.dtype)
        return x, chosen, stats

    def _embed(self, ids):
        with jax.named_scope("embed"):
            return self.model.embed_tokens.weight._value[ids]

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
        """Plain pass over whole rows ``[B, T]`` in the EXPANDED form
        (per-head keys and values from ``c_kv.W_kvb``), causal: logits
        ``[B, T, V]`` float32."""
        ids = input_ids._value if isinstance(input_ids, Tensor) \
            else jnp.asarray(input_ids)
        B, T = ids.shape
        see = jnp.tril(jnp.ones((T, T), bool))
        x = self._embed(ids)
        for layer in self.model.layers:
            q_nope, q_rope, c_kv, k_rope = self._latents(
                layer, x, jnp.zeros((B,), jnp.int32))
            w_uk, w_uv = self._up_projections(layer)
            with jax.named_scope("attn"):
                k_nope = jnp.einsum("btr,rhd->bthd", c_kv,
                                    w_uk.astype(c_kv.dtype))
                v = jnp.einsum("btr,rhv->bthv", c_kv,
                               w_uv.astype(c_kv.dtype))
                s = (jnp.einsum("bqhd,bkhd->bhqk", q_nope, k_nope,
                                preferred_element_type=jnp.float32)
                     + jnp.einsum("bqhd,bkd->bhqk", q_rope, k_rope,
                                  preferred_element_type=jnp.float32)) \
                    / math.sqrt(self.config.qk_head_dim)
                p = jax.nn.softmax(jnp.where(see, s, -1e30), axis=-1)
                ctx = jnp.einsum("bhqk,bkhv->bqhv", p.astype(v.dtype), v)
            x, _, _ = self._after_context(layer, x, ctx.reshape(B, T, -1),
                                          None)
        return Tensor(self._logits(x))

    # ------------------------------------------------------- served passes
    def prefill_chunk(self, ids, valid, pools, table, start, last_index):
        """One chunk ``ids [B, C]`` at positions ``start[b] + t``;
        ``valid [B, C]`` False past the chunk's last real token (such a
        position writes to the garbage block and reads no routed
        expert).  ``pools`` are the pool's entries, a tuple a layer: the
        latent pages and, for a routed layer, the witness; ``table [B,
        max_blocks]``.  Returns ``(logits [B, V] f32 of the token at
        ``last_index``, stats [3] int32, new pools)``."""
        from ..kernels.latent_attention import fused_latent_chunk

        B, C = ids.shape
        pos = start[:, None] + jnp.arange(C)
        token_valid = valid.reshape(-1)
        rank = self.config.kv_lora_rank
        x = self._embed(ids)
        new_pools, stats = [], []
        for layer, entry in zip(self.model.layers, pools):
            lanes = entry[0].shape[-1]
            q_nope, q_rope, c_kv, k_rope = self._latents(layer, x, start)
            with jax.named_scope("kv_write"):
                pages = paged_scatter(entry[0],
                                      self._entry(c_kv, k_rope, lanes),
                                      table, pos, valid)
            q = self._absorbed_query(layer, q_nope, q_rope, lanes)
            with jax.named_scope("attn"):
                o_lat = fused_latent_chunk(q, pages, table, start,
                                           value_lanes=rank)
            x, chosen, st = self._after_attention(layer, x, o_lat,
                                                  token_valid)
            if layer.routed:
                with jax.named_scope("kv_write"):
                    c_pool = scatter_block_rows(entry[1], chosen, table,
                                                start, valid)
                new_pools.append((pages, c_pool))
                stats.append(st)
            else:
                new_pools.append((pages,))
        last = jax.lax.dynamic_index_in_dim(x, last_index, axis=1,
                                            keepdims=False)
        return self._logits(last), self._sum_stats(stats), new_pools

    def decode_token(self, tok, pools, table, lengths):
        """One token a slot: ``tok [S, 1]`` at position ``lengths[s]``
        against the slot's cached positions.  A slot of length 0 is idle
        (a running one holds its prompt): it reads no routed expert and
        writes to the garbage block.  Returns ``(logits [S, V] f32,
        stats [3] int32, new pools)``."""
        from ..kernels.latent_attention import fused_latent_decode

        active = lengths > 0
        rank = self.config.kv_lora_rank
        x = self._embed(tok)
        new_pools, stats = [], []
        for layer, entry in zip(self.model.layers, pools):
            lanes = entry[0].shape[-1]
            q_nope, q_rope, c_kv, k_rope = self._latents(layer, x, lengths)
            q = self._absorbed_query(layer, q_nope, q_rope, lanes)
            with jax.named_scope("attn"):
                o_lat, pages = fused_latent_decode(
                    q[:, 0], self._entry(c_kv, k_rope, lanes)[:, 0],
                    entry[0], table, lengths, value_lanes=rank)
            x, chosen, st = self._after_attention(layer, x, o_lat[:, None],
                                                  active)
            if layer.routed:
                with jax.named_scope("kv_write"):
                    c_pool = scatter_block_rows(entry[1], chosen, table,
                                                lengths, active[:, None])
                new_pools.append((pages, c_pool))
                stats.append(st)
            else:
                new_pools.append((pages,))
        return self._logits(x[:, 0]), self._sum_stats(stats), new_pools


def routing_witness(model, engine, tokens, block_table, prompt_tokens=None):
    """What the step programs chose for the row ``tokens``, as ``[routed
    layers, len(tokens), k]`` int32: read back, through the row's block
    table, from the pool entries the chunk and decode steps wrote (a
    position served from the prefix cache is read from the block it was
    matched to: what was chosen when that block was filled).  (The
    benchmark's ``"witness"`` of a ``glm4_moe_lite`` configuration.)"""
    size = engine.config.block_size
    at = np.arange(len(tokens))
    rows = np.asarray(block_table)[at // size]
    return np.stack([
        np.asarray(entry[1]).reshape(engine.pool.num_blocks, size, -1)[
            rows, at % size]
        for layer, entry in zip(model.model.layers, engine.pool.layers)
        if layer.routed]).astype(np.int32)
