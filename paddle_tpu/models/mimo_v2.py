# lint-tpu: disable-file=L004 -- a served model drives raw device buffers
# (like models/llama.py); new backend code belongs under core/ ops/
# kernels/ static/ distributed/ (README: Repo lint)
"""MiMo-V2: a causal decoder with WINDOW and FULL attention layers of
DIFFERENT head counts, keys wider than their values, a sink logit in the
window layers' softmax, and a sigmoid-routed mixture of experts (``model_
type`` ``mimo_v2``: XiaomiMiMo/MiMo-V2.5's text path).

What differs from ``models/afmoe.py`` and why it is a file beside it:

- **two kinds of attention layer of two shapes** (``hybrid_layer_
  pattern``: 0 full, 1 window).  A full layer has ``num_attention_heads``
  q heads over ``num_key_value_heads`` KV heads, keys of ``head_dim`` and
  values of ``v_head_dim``, RoPE at ``rope_theta`` and the causal mask;
  a window layer ``swa_num_attention_heads`` over
  ``swa_num_key_value_heads``, ``swa_head_dim`` / ``swa_v_head_dim``,
  RoPE at ``swa_rope_theta``, and query ``i`` sees key ``j`` iff ``0 <=
  i - j < sliding_window``.  The model says so in
  :meth:`MimoV2ForCausalLM.cache_layers` (a K/V record a layer with a
  value width of its own), and the engine keeps the two kinds' pages in
  two groups (``serving/cache.py``);
- **partial rotation**: rotate-half RoPE on the first ``rotary_dim(d) =
  2 * floor(d * partial_rotary_factor / 2)`` lanes of each q and k head,
  the rest unrotated (``fused_paged_decode(rotary=)`` in the decode);
- **a sink** where ``add_swa_attention_sink_bias`` (and
  ``add_full_attention_sink_bias``) say: a learned logit a q head in the
  softmax's denominator only, ``p_j = exp(s_j) / (exp(sink) + sum_k
  exp(s_k))`` (the kernels' ``sink=``);
- **the value scale**: the attention's output times
  ``attention_value_scale`` before the output projection;
- **the feed-forward**: a SiLU-gated MLP where ``moe_layer_freq`` is 0;
  elsewhere a dropless top-k layer of ``n_routed_experts`` behind a
  SIGMOID router whose selection (and only the selection) is moved by a
  per-expert bias (``noaux_tc``), the chosen scores normalised
  (``norm_topk_prob``) and scaled (``routed_scaling_factor``, 1 where
  null); no shared expert
  (:class:`~paddle_tpu.models.sdar_moe.DroplessMoE`, told which experts
  it holds: a replica that holds a SHARE of the experts computes the
  part of the layer its experts give, and nothing stands in for the
  rest).

What is shared: the embedding, ``LlamaRMSNorm``, ``fused_norm_linear``
for the projections, the RoPE tables and ``apply_rope``, the two serving
kernels, ``DroplessMoE`` and its grouped-experts kernel, the routing
sidecar and its witness (``models/afmoe.py``).

Served forward passes (``models/generation.py`` wraps them in the step
programs ``paged_decode_step`` and ``chunked_prefill_step``):
:meth:`MimoV2ForCausalLM.prefill_chunk` and
:meth:`MimoV2ForCausalLM.decode_token`, over the pool's entries and
``tables = (full group's block table, window group's)``.  Their routing
counts are four numbers: experts read, the held experts' assignments,
the busiest's, and every (token, expert) choice the router made.
``forward(ids)`` is the plain full pass (no cache).
"""
from __future__ import annotations

import math
from dataclasses import dataclass
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from .. import nn
from ..core.tensor import Tensor
from ..distributed.parallel_layers import (ColumnParallelLinear,
                                           RowParallelLinear,
                                           VocabParallelEmbedding)
from .afmoe import AfmoeMLP
from .afmoe import routing_witness  # noqa: F401  (the configuration's)
from .llama import LlamaRMSNorm, apply_rope, paged_scatter, precompute_rope
from .sdar_moe import DroplessMoE, _normal, _rms, scatter_block_rows

FULL, WINDOW = 0, 1


def rotary_dim(head_dim: int, factor: float) -> int:
    """Lanes a head turns: an even count, ``2 * floor(head_dim * factor
    / 2)`` (64 of 192 at the published 0.334)."""
    return 2 * int(head_dim * factor / 2)


@dataclass
class MimoV2Config:
    """The published ``config.json`` keys the program reads, then what
    this replica holds and how its seeded initialisation draws what a
    checkpoint would bring."""

    vocab_size: int = 152576
    hidden_size: int = 4096
    intermediate_size: int = 16384
    moe_intermediate_size: int = 2048
    num_hidden_layers: int = 48
    num_attention_heads: int = 64
    num_key_value_heads: int = 4
    head_dim: int = 192
    v_head_dim: int = 128
    swa_num_attention_heads: int = 64
    swa_num_key_value_heads: int = 8
    swa_head_dim: int = 192
    swa_v_head_dim: int = 128
    sliding_window: int = 128
    # a kind a layer (0 full, 1 window) and a feed-forward a layer (0
    # dense, 1 routed); more entries than ``num_hidden_layers`` are a cut
    # model's published list, of which the first are kept.  None: every
    # layer full; every layer but the first routed
    hybrid_layer_pattern: Optional[Tuple[int, ...]] = None
    moe_layer_freq: Optional[Tuple[int, ...]] = None
    n_routed_experts: int = 256
    n_shared_experts: Optional[int] = None
    num_experts_per_tok: int = 8
    norm_topk_prob: bool = True
    routed_scaling_factor: Optional[float] = None
    scoring_func: str = "sigmoid"
    topk_method: str = "noaux_tc"
    n_group: int = 1
    topk_group: int = 1
    partial_rotary_factor: float = 0.334
    rope_theta: float = 10000000.0
    swa_rope_theta: float = 10000.0
    rope_scaling: Optional[dict] = None
    max_position_embeddings: int = 1048576
    layernorm_epsilon: float = 1e-5
    attention_value_scale: float = 0.707
    add_full_attention_sink_bias: bool = False
    add_swa_attention_sink_bias: bool = True
    attention_bias: bool = False
    tie_word_embeddings: bool = False
    hidden_act: str = "silu"
    # the experts this replica HOLDS (ids among n_routed_experts; None: all)
    held_experts: Optional[Tuple[int, ...]] = None
    # the seeded initialisation (a trained checkpoint brings its own): the
    # selection bias is not zero, or the selection-only rule is tested by
    # nothing; a sink logit near ln(window) takes a large share of a
    # window row's weight, so that leaving it out cannot hide
    expert_bias_std: float = 0.02
    sink_mean: float = math.log(128)
    sink_std: float = 1.0
    dtype: str = "bfloat16"

    def __post_init__(self):
        n = self.num_hidden_layers
        for key, want in (("topk_method", "noaux_tc"), ("n_group", 1),
                          ("topk_group", 1), ("scoring_func", "sigmoid"),
                          ("attention_bias", False),
                          ("tie_word_embeddings", False),
                          ("hidden_act", "silu")):
            if getattr(self, key) != want:
                raise ValueError(
                    f"{key}={getattr(self, key)!r} is not built for this "
                    f"model (only {want!r}: no group-limited selection, a "
                    "sigmoid router, no bias, an untied head, SiLU)")
        if self.n_shared_experts:
            raise ValueError("a shared expert is not built for this model")
        if (self.rope_scaling or {}).get("rope_type", "default") \
                != "default":
            raise ValueError(f"rope scaling {self.rope_scaling} is not "
                             "built for this model")
        pattern = (FULL,) * n if self.hybrid_layer_pattern is None \
            else tuple(int(k) for k in self.hybrid_layer_pattern)[:n]
        routed = (0,) + (1,) * (n - 1) if self.moe_layer_freq is None \
            else tuple(int(k) for k in self.moe_layer_freq)[:n]
        for name, got in (("hybrid_layer_pattern", pattern),
                          ("moe_layer_freq", routed)):
            if len(got) != n or set(got) - {0, 1}:
                raise ValueError(f"{name} must give {n} layers 0 or 1, "
                                 f"got {got}")
        self.hybrid_layer_pattern, self.moe_layer_freq = pattern, routed
        if self.held_experts is not None:
            self.held_experts = tuple(int(e) for e in self.held_experts)

    @property
    def rms_norm_eps(self) -> float:
        return self.layernorm_epsilon

    def attention_shape(self, kind: int) -> dict:
        """A layer kind's heads, widths, rotation, rope base and window."""
        if kind == WINDOW:
            d = self.swa_head_dim
            return dict(heads=self.swa_num_attention_heads,
                        kv_heads=self.swa_num_key_value_heads, head_dim=d,
                        v_head_dim=self.swa_v_head_dim,
                        rotary=rotary_dim(d, self.partial_rotary_factor),
                        theta=self.swa_rope_theta,
                        window=self.sliding_window,
                        sink=self.add_swa_attention_sink_bias)
        d = self.head_dim
        return dict(heads=self.num_attention_heads,
                    kv_heads=self.num_key_value_heads, head_dim=d,
                    v_head_dim=self.v_head_dim,
                    rotary=rotary_dim(d, self.partial_rotary_factor),
                    theta=self.rope_theta, window=None,
                    sink=self.add_full_attention_sink_bias)

    @staticmethod
    def tiny(**overrides):
        kwargs = dict(
            vocab_size=256, hidden_size=64, intermediate_size=128,
            moe_intermediate_size=32, num_hidden_layers=3,
            num_attention_heads=4, num_key_value_heads=1, head_dim=24,
            v_head_dim=16, swa_num_attention_heads=8,
            swa_num_key_value_heads=2, swa_head_dim=24, swa_v_head_dim=16,
            sliding_window=32, hybrid_layer_pattern=(FULL, WINDOW, FULL),
            moe_layer_freq=(0, 1, 1), n_routed_experts=16,
            num_experts_per_tok=4, max_position_embeddings=256,
            sink_mean=math.log(32), dtype="float32")
        kwargs.update(overrides)
        return MimoV2Config(**kwargs)


# The seeded initialisation draws ``q_proj`` this many times the
# unit-variance scale (scores of that standard deviation), so that a
# softmax over thousands of random keys does not average the values to
# nothing and a fault in the attention reaches the logits.  Not a
# published key and not an option: a trained checkpoint brings its own.
_Q_GAIN = 1.5


class MimoV2Attention(nn.Layer):
    def __init__(self, config: MimoV2Config, kind: int):
        super().__init__()
        from ..nn import initializer as I
        from ..nn.layer.layers import ParamAttr

        shape = config.attention_shape(kind)
        h = config.hidden_size
        self.num_heads, self.num_kv_heads = shape["heads"], shape["kv_heads"]
        self.head_dim, self.v_head_dim = shape["head_dim"], shape["v_head_dim"]

        def linear(cls, fan_in, width, gain=1.0, **kw):
            # unit-variance outputs for unit-variance inputs, times gain
            return cls(fan_in, width, has_bias=False, **kw,
                       weight_attr=ParamAttr(initializer=I.Normal(
                           0.0, gain / math.sqrt(fan_in))))

        self.q_proj = linear(ColumnParallelLinear, h,
                             self.num_heads * self.head_dim, _Q_GAIN,
                             gather_output=False)
        self.k_proj = linear(ColumnParallelLinear, h,
                             self.num_kv_heads * self.head_dim,
                             gather_output=False)
        self.v_proj = linear(ColumnParallelLinear, h,
                             self.num_kv_heads * self.v_head_dim,
                             gather_output=False)
        self.o_proj = linear(RowParallelLinear,
                             self.num_heads * self.v_head_dim, h,
                             input_is_parallel=True)


class MimoV2DecoderLayer(nn.Layer):
    def __init__(self, config: MimoV2Config, index: int):
        super().__init__()
        h, eps = config.hidden_size, config.rms_norm_eps
        self.kind = config.hybrid_layer_pattern[index]
        shape = config.attention_shape(self.kind)
        #: the window in keys, None for a full layer
        self.window = shape["window"]
        self.rotary = shape["rotary"]
        self.has_sink = shape["sink"]
        self.routed = bool(config.moe_layer_freq[index])
        self.input_layernorm = LlamaRMSNorm(h, eps)
        self.self_attn = MimoV2Attention(config, self.kind)
        self.post_attention_layernorm = LlamaRMSNorm(h, eps)
        if self.routed:
            self.mlp = DroplessMoE(
                h, config.moe_intermediate_size, config.n_routed_experts,
                config.num_experts_per_tok, normalize=config.norm_topk_prob,
                held=config.held_experts, dtype=config.dtype,
                scores="sigmoid", selection_bias=True,
                route_scale=config.routed_scaling_factor or 1.0,
                norm_eps=1e-20)
        else:
            self.mlp = AfmoeMLP(h, config.intermediate_size)


class MimoV2Model(nn.Layer):
    def __init__(self, config: MimoV2Config):
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
            [built(MimoV2DecoderLayer(config, i))
             for i in range(config.num_hidden_layers)])
        self.norm = LlamaRMSNorm(config.hidden_size, config.rms_norm_eps)
        if bf16:
            self.bfloat16()
        # float32 whatever the weights are (registered after the
        # narrowing): a layer kind's rope tables (a window layer's base
        # is not a full layer's), the selection bias, which is added to
        # float32 scores, and the sink logits, which join float32 ones
        for kind, prefix in ((FULL, ""), (WINDOW, "swa_")):
            shape = config.attention_shape(kind)
            cos, sin = precompute_rope(shape["rotary"],
                                       config.max_position_embeddings,
                                       shape["theta"])
            self.register_buffer(prefix + "rope_cos", Tensor(cos),
                                 persistable=False)
            self.register_buffer(prefix + "rope_sin", Tensor(sin),
                                 persistable=False)
        for layer in self.layers:
            if layer.has_sink:
                layer.self_attn.register_buffer(
                    "attention_sink_bias", Tensor(config.sink_mean + _normal(
                        rnd.next_key(), (layer.self_attn.num_heads,),
                        float(config.sink_std), jnp.float32)))
            if layer.routed:
                layer.mlp.register_buffer("expert_bias", Tensor(_normal(
                    rnd.next_key(), (config.n_routed_experts,),
                    float(config.expert_bias_std), jnp.float32)))

    def rope(self, layer):
        """The layer kind's ``(cos, sin)`` tables, ``[max_pos, rotary /
        2]`` float32."""
        prefix = "swa_" if layer.kind == WINDOW else ""
        return (getattr(self, prefix + "rope_cos")._value,
                getattr(self, prefix + "rope_sin")._value)


def _sink(layer):
    """A layer's sink logits ``[heads]`` f32, or None where it has none."""
    return layer.self_attn.attention_sink_bias._value \
        if layer.has_sink else None


class MimoV2ForCausalLM(nn.Layer):
    """The model and its forward passes (module docstring)."""

    def __init__(self, config: MimoV2Config):
        super().__init__()
        self.config = config
        self.model = MimoV2Model(config)
        self.lm_head = ColumnParallelLinear(
            config.hidden_size, config.vocab_size, has_bias=False,
            gather_output=True)
        if config.dtype == "bfloat16":
            self.lm_head.bfloat16()

    # ------------------------------------------------- what the engine asks
    def cache_layers(self):
        """The model's description of its cache, a K/V record a layer
        (``serving/cache.py::LayerCache``): the layer's kind, its heads,
        its key and value widths, and beside them the routing witness
        (``k`` expert ids a position) of a routed layer."""
        from ..serving.cache import LayerCache

        cfg = self.config
        dtype = jnp.bfloat16 if cfg.dtype == "bfloat16" else jnp.float32
        witness = (((cfg.num_experts_per_tok,), jnp.int32),)
        return [LayerCache(layer.self_attn.num_kv_heads,
                           layer.self_attn.head_dim, dtype,
                           window=layer.window,
                           sidecars=witness if layer.routed else (),
                           v_head_dim=layer.self_attn.v_head_dim)
                for layer in self.model.layers]

    # ------------------------------------------------------------ pieces
    def _projections(self, layer, x):
        """The input norm into q, k and v: ``(q [B,T,Hq,D], k [B,T,KVH,D],
        v [B,T,KVH,Dv])``, unrotated."""
        from ..kernels.fusion import fusion_enabled

        attn = layer.self_attn
        B, T, _ = x.shape
        nw, eps = layer.input_layernorm.weight._value, \
            self.config.rms_norm_eps
        weights = [p.weight._value for p in (
            attn.q_proj, attn.k_proj, attn.v_proj)]
        with jax.named_scope("attn_qkv"):
            if fusion_enabled():
                from ..kernels.fused_norm_linear import (fused_norm_linear,
                                                         rms_scale)

                rs = rms_scale(x, eps)
                q, k, v = (fused_norm_linear(x, rs, nw, w) for w in weights)
            else:
                a = _rms(x, nw, eps)
                q, k, v = (jnp.dot(a, w.astype(a.dtype)) for w in weights)
            return (q.reshape(B, T, -1, attn.head_dim),
                    k.reshape(B, T, -1, attn.head_dim),
                    v.reshape(B, T, -1, attn.v_head_dim))

    def _rotate(self, layer, x, start):
        """RoPE on the first ``layer.rotary`` lanes of every head of ``x
        [B, T, H, D]`` at positions ``start[b] + t``."""
        cos, sin = self.model.rope(layer)
        r = layer.rotary
        with jax.named_scope("attn_qkv"):
            return jnp.concatenate(
                [apply_rope(x[..., :r], cos, sin, start), x[..., r:]],
                axis=-1)

    def _after_attention(self, layer, x, ctx, token_valid):
        """The value scale, the output projection and the feed-forward:
        ``(x, chosen [B, T, k] or None, stats [4] int32 or None)``."""
        B, T, H = x.shape
        cfg = self.config
        eps = cfg.rms_norm_eps
        with jax.named_scope("attn_out"):
            wo = layer.self_attn.o_proj.weight._value
            a = ctx.reshape(B, T, -1)
            o = jnp.dot(a, wo.astype(a.dtype),
                        preferred_element_type=jnp.float32)
            x = x + (o * cfg.attention_value_scale).astype(x.dtype)
        nw = layer.post_attention_layernorm.weight._value
        chosen = stats = None
        with jax.named_scope("mlp"):
            if layer.routed:
                b = _rms(x, nw, eps)
                m, chosen, st = layer.mlp.run(b.reshape(B * T, H),
                                              token_valid)
                m = m.reshape(B, T, H)
                chosen = chosen.reshape(B, T, -1)
                # every (token, expert) choice the router made, held or not
                picks = chosen.shape[-1] * (
                    B * T if token_valid is None
                    else jnp.sum(token_valid.astype(jnp.int32)))
                stats = jnp.concatenate([st.as_vector(), jnp.reshape(
                    jnp.asarray(picks, jnp.int32), (1,))])
            else:
                m = layer.mlp.run(x, nw, eps)
            x = x + m
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
            return jnp.zeros((4,), jnp.int32)
        return jnp.sum(jnp.stack(stats), axis=0)

    # ------------------------------------------------------ full forward
    def forward(self, input_ids):
        """Plain pass over whole rows ``[B, T]``, every layer under its
        own mask and with its own sink: logits ``[B, T, V]`` float32."""
        ids = input_ids._value if isinstance(input_ids, Tensor) \
            else jnp.asarray(input_ids)
        B, T = ids.shape
        at = jnp.arange(T)
        back = at[:, None] - at[None, :]                # i - j
        start = jnp.zeros((B,), jnp.int32)
        x = self._embed(ids)
        for layer in self.model.layers:
            q, k, v = self._projections(layer, x)
            q, k = self._rotate(layer, q, start), self._rotate(layer, k, start)
            see = back >= 0
            if layer.window is not None:
                see = see & (back < layer.window)
            rep = q.shape[2] // k.shape[2]
            with jax.named_scope("attn"):
                kr, vr = (jnp.repeat(t, rep, axis=2) for t in (k, v))
                s = jnp.einsum("bqhd,bkhd->bhqk", q, kr,
                               preferred_element_type=jnp.float32) \
                    / math.sqrt(q.shape[-1])
                s = jnp.where(see, s, -1e30)
                m = jnp.max(s, axis=-1, keepdims=True)
                sink = _sink(layer)
                if sink is not None:
                    m = jnp.maximum(m, sink[None, :, None, None])
                p = jnp.exp(s - m)
                total = jnp.sum(p, axis=-1, keepdims=True)
                if sink is not None:
                    total = total + jnp.exp(sink[None, :, None, None] - m)
                ctx = jnp.einsum("bhqk,bkhd->bqhd",
                                 (p / total).astype(q.dtype), vr)
            x, _, _ = self._after_attention(layer, x, ctx, None)
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
        ``(logits [B, V] f32 of the token at ``last_index``, stats [4]
        int32, new pools)``."""
        from ..kernels.chunked_prefill import fused_chunked_attention
        from ..kernels.paged_attention import pad_lanes

        B, C = ids.shape
        pos = start[:, None] + jnp.arange(C)
        token_valid = valid.reshape(-1)
        x = self._embed(ids)
        new_pools, stats = [], []
        for layer, entry in zip(self.model.layers, pools):
            bt = self._table(layer, tables)
            q, k, v = self._projections(layer, x)
            q, k = self._rotate(layer, q, start), self._rotate(layer, k, start)
            with jax.named_scope("kv_write"):
                k_pool = paged_scatter(entry[0],
                                       pad_lanes(k, entry[0].shape[-1]),
                                       bt, pos, valid)
                v_pool = paged_scatter(entry[1], v, bt, pos, valid)
            with jax.named_scope("attn"):
                ctx = fused_chunked_attention(q, k_pool, v_pool, bt, start,
                                              window=layer.window,
                                              sink=_sink(layer))
            x, chosen, st = self._after_attention(layer, x, ctx, token_valid)
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
        stats [4] int32, new pools)``."""
        from ..kernels.paged_attention import fused_paged_decode

        active = lengths > 0
        x = self._embed(tok)
        new_pools, stats = [], []
        for layer, entry in zip(self.model.layers, pools):
            bt = self._table(layer, tables)
            q, k, v = self._projections(layer, x)
            with jax.named_scope("attn"):
                ctx, k_pool, v_pool = fused_paged_decode(
                    q, k, v, entry[0], entry[1], bt, lengths,
                    *self.model.rope(layer), window=layer.window,
                    sink=_sink(layer), rotary=layer.rotary)
            x, chosen, st = self._after_attention(layer, x, ctx, active)
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
