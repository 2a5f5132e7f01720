# lint-tpu: disable-file=L004 -- grandfathered direct jax use; new backend code belongs under core/ ops/ kernels/ static/ distributed/ (README: Repo lint)
"""Pipeline-parallel Llama: functional per-stage forward for the compiled
1F1B schedule.

Reference: python/paddle/distributed/fleet/meta_parallel/pipeline_parallel.py
runs Llama-style models as a PipelineLayer of per-rank sublayers with P2P
send/recv; shared embeddings sync grads across stages (SharedLayerDesc).
TPU-native: the decoder stack is extracted into pp-stacked functional params
([S, L/S, ...] leaves) and driven by distributed.pipeline.pipeline_1f1b —
embedding lives in stage 0's branch, final-norm + lm-head + loss in stage
S-1's, tied-embedding grads are summed by the schedule's closing psum.

The functional math mirrors models/llama.py layer-for-layer (RMSNorm in
f32, rotary on q/k, GQA repeat, SwiGLU MLP) so pp>=2 losses match the eager
single-device model bit-for-bit up to reduction order.
"""
from __future__ import annotations

import math
from typing import Optional

import jax
import jax.numpy as jnp

from ..kernels.flash_attention import _attn_reference
from .llama import LlamaConfig, apply_rope, precompute_rope

__all__ = ["extract_pipeline_params", "make_llama_stage_fn",
           "llama_1f1b_step_fn", "LlamaForCausalLMPipe"]


def extract_pipeline_params(model):
    """Split a LlamaForCausalLM into (shared, per-layer-stacked) pytrees.

    shared: embed / final norm / lm head (absent when tied).
    stacked: each decoder-layer weight stacked over the layer axis [L, ...].
    """
    def layer_leaves(layer):
        a, m = layer.self_attn, layer.mlp
        return {
            "in_ln": layer.input_layernorm.weight._value,
            "q": a.q_proj.weight._value,
            "k": a.k_proj.weight._value,
            "v": a.v_proj.weight._value,
            "o": a.o_proj.weight._value,
            "post_ln": layer.post_attention_layernorm.weight._value,
            "gate": m.gate_proj.weight._value,
            "up": m.up_proj.weight._value,
            "down": m.down_proj.weight._value,
        }

    per_layer = [layer_leaves(l) for l in model.model.layers]
    stacked = jax.tree_util.tree_map(
        lambda *xs: jnp.stack(xs), *per_layer)
    shared = {
        "embed": model.model.embed_tokens.weight._value,
        "norm": model.model.norm.weight._value,
    }
    if not model.config.tie_word_embeddings:
        shared["head"] = model.lm_head.weight._value
    return shared, stacked


def load_pipeline_params(model, shared, stacked):
    """Write updated functional params back into the eager model."""
    model.model.embed_tokens.weight.set_value(shared["embed"])
    model.model.norm.weight.set_value(shared["norm"])
    if not model.config.tie_word_embeddings:
        model.lm_head.weight.set_value(shared["head"])
    for i, layer in enumerate(model.model.layers):
        a, m = layer.self_attn, layer.mlp
        layer.input_layernorm.weight.set_value(stacked["in_ln"][i])
        a.q_proj.weight.set_value(stacked["q"][i])
        a.k_proj.weight.set_value(stacked["k"][i])
        a.v_proj.weight.set_value(stacked["v"][i])
        a.o_proj.weight.set_value(stacked["o"][i])
        layer.post_attention_layernorm.weight.set_value(
            stacked["post_ln"][i])
        m.gate_proj.weight.set_value(stacked["gate"][i])
        m.up_proj.weight.set_value(stacked["up"][i])
        m.down_proj.weight.set_value(stacked["down"][i])


def _use_pallas(cfg: LlamaConfig) -> bool:
    from ..kernels.fusion import pallas_lowering

    return bool(cfg.use_flash_attention) and pallas_lowering()[0]


def _rms(x, w, eps, use_pallas=False):
    if use_pallas:
        from ..kernels.rms_norm import rms_norm as pallas_rms

        return pallas_rms(x, w, eps)
    xf = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(xf), axis=-1, keepdims=True)
    return (xf * jax.lax.rsqrt(var + eps)).astype(x.dtype) * w


def _decoder_layer(h, lp, cos, sin, cfg: LlamaConfig, use_pallas=False):
    """Functional mirror of models/llama.py LlamaDecoderLayer.forward,
    including its Pallas dispatch (flash attention + fused
    RMSNorm on TPU, reference math elsewhere)."""
    B, T = h.shape[0], h.shape[1]
    n_h = cfg.num_attention_heads
    n_kv = cfg.num_key_value_heads
    hd = cfg.hidden_size // n_h
    eps = cfg.rms_norm_eps

    x = _rms(h, lp["in_ln"], eps, use_pallas)
    q = (x @ lp["q"]).reshape(B, T, n_h, hd)
    k = (x @ lp["k"]).reshape(B, T, n_kv, hd)
    v = (x @ lp["v"]).reshape(B, T, n_kv, hd)
    if use_pallas:
        from ..kernels.rope import fused_rope

        q = fused_rope(q, cos, sin)
        k = fused_rope(k, cos, sin)
    else:
        q = apply_rope(q, cos, sin)
        k = apply_rope(k, cos, sin)
    if use_pallas:
        from ..kernels.flash_attention import flash_attention_bthd

        attn = flash_attention_bthd(q, k, v, causal=True)
    else:
        rep = n_h // n_kv
        if rep > 1:
            k = jnp.repeat(k, rep, axis=2)
            v = jnp.repeat(v, rep, axis=2)
        qt, kt, vt = (jnp.swapaxes(t, 1, 2) for t in (q, k, v))
        attn = _attn_reference(qt, kt, vt, True, 1.0 / math.sqrt(hd))
        attn = jnp.swapaxes(attn, 1, 2)
    attn = attn.reshape(B, T, n_h * hd)
    h = h + attn @ lp["o"]

    x2 = _rms(h, lp["post_ln"], eps, use_pallas)
    mlp = (jax.nn.silu(x2 @ lp["gate"]) * (x2 @ lp["up"])) @ lp["down"]
    return h + mlp


def make_llama_stage_fn(cfg: LlamaConfig, n_stages: int):
    """Build stage_fn(stage, shared, local, x, tokens, labels) for
    pipeline_1f1b.  local leaves are [L/S, ...] per-stage layer stacks."""
    hd = cfg.hidden_size // cfg.num_attention_heads
    cos, sin = precompute_rope(hd, cfg.max_position_embeddings,
                               cfg.rope_theta)
    use_pallas = _use_pallas(cfg)

    def stage_fn(stage, shared, local, x, tokens, labels):
        h = jax.lax.cond(
            stage == 0,
            lambda: shared["embed"][tokens].astype(x.dtype),
            lambda: x)

        def body(hh, lp):
            return _decoder_layer(hh, lp, cos, sin, cfg, use_pallas), None

        h, _ = jax.lax.scan(body, h, local)

        def loss_branch():
            hn = _rms(h, shared["norm"], cfg.rms_norm_eps, use_pallas)
            if cfg.tie_word_embeddings:
                logits = hn @ shared["embed"].T.astype(hn.dtype)
            else:
                logits = hn @ shared["head"]
            lg = logits[:, :-1].astype(jnp.float32)
            lab = labels[:, 1:]
            logp = jax.nn.log_softmax(lg, axis=-1)
            picked = jnp.take_along_axis(
                logp, lab[..., None].astype(jnp.int32), axis=-1)[..., 0]
            return -jnp.mean(picked)

        loss = jax.lax.cond(stage == n_stages - 1, loss_branch,
                            lambda: jnp.float32(0.0))
        return h, loss

    return stage_fn


# ---------------------------------------------------------------------------
# LlamaForCausalLMPipe — Llama as a PipelineLayer for the PUBLIC fleet API
# (fleet.distributed_model → PipelineParallel.train_batch → compiled 1F1B).
# The decoder blocks reuse the eager LlamaDecoderLayer, whose Column/Row
# parallel projections are mp-sharded; inside the compiled pipeline's
# shard_map the 1F1B builder hands each pp stage mp-LOCAL weight shards and
# the TP layers emit explicit collectives (manual_collective_axes), so
# pp×mp×dp compose in ONE program — the reference's 4-axis
# HybridCommunicateGroup layout (topology.py:133) with PipelineLayer
# segmentation (pp_layers.py:159).
# ---------------------------------------------------------------------------


def _make_pipe_classes():
    from .. import nn
    from ..core.tensor import Tensor
    from ..distributed.parallel_layers import (ColumnParallelLinear,
                                               VocabParallelEmbedding)
    from .llama import LlamaRMSNorm

    class EmbeddingPipe(nn.Layer):
        def __init__(self, cfg):
            super().__init__()
            self.embed_tokens = VocabParallelEmbedding(
                cfg.vocab_size, cfg.hidden_size)
            self._dtype_str = cfg.dtype
            if cfg.dtype == "bfloat16":
                self.bfloat16()

        def forward(self, ids):
            h = self.embed_tokens(ids)
            if self._dtype_str == "bfloat16":
                h = h.astype("bfloat16")
            return h

    class DecoderPipe(nn.Layer):
        def __init__(self, cfg):
            super().__init__()
            from .llama import LlamaDecoderLayer

            self.layer = LlamaDecoderLayer(cfg)
            hd = cfg.hidden_size // cfg.num_attention_heads
            cos, sin = precompute_rope(hd, cfg.max_position_embeddings,
                                       cfg.rope_theta)
            self.register_buffer("rope_cos", Tensor(cos), persistable=False)
            self.register_buffer("rope_sin", Tensor(sin), persistable=False)
            if cfg.dtype == "bfloat16":
                self.bfloat16()

        def forward(self, h):
            return self.layer(h, self.rope_cos._value, self.rope_sin._value)

    class HeadPipe(nn.Layer):
        def __init__(self, cfg):
            super().__init__()
            self.norm = LlamaRMSNorm(cfg.hidden_size, cfg.rms_norm_eps)
            self.lm_head = ColumnParallelLinear(
                cfg.hidden_size, cfg.vocab_size, has_bias=False,
                gather_output=True)
            if cfg.dtype == "bfloat16":
                self.bfloat16()

        def forward(self, h):
            return self.lm_head(self.norm(h))

    return EmbeddingPipe, DecoderPipe, HeadPipe


def _llama_pipe_loss(logits, labels):
    """Next-token shift + cross entropy, matching LlamaForCausalLM's
    labels=... path (llama.py loss: logits[:, :-1] vs labels[:, 1:])."""
    from ..nn import functional as F

    vocab = logits.shape[-1]
    lg = logits[:, :-1].reshape([-1, vocab])
    lab = labels[:, 1:].reshape([-1])
    return F.cross_entropy(lg, lab)


def LlamaForCausalLMPipe(cfg: LlamaConfig, num_stages: Optional[int] = None):
    """Build Llama as a PipelineLayer: [embedding] + decoder blocks +
    [final-norm + lm-head], loss_fn = shifted cross entropy.  Pass to
    fleet.distributed_model under a pp (optionally ×mp×dp) mesh."""
    from ..distributed.pipeline import PipelineLayer

    EmbeddingPipe, DecoderPipe, HeadPipe = _make_pipe_classes()
    layers = ([EmbeddingPipe(cfg)]
              + [DecoderPipe(cfg) for _ in range(cfg.num_hidden_layers)]
              + [HeadPipe(cfg)])
    return PipelineLayer(layers, num_stages=num_stages,
                         loss_fn=_llama_pipe_loss)


def llama_1f1b_step_fn(cfg: LlamaConfig, mesh, n_microbatches: int,
                       micro_batch: int, seq_len: int,
                       axis_name: str = "pp",
                       data_axis: Optional[str] = None):
    """Return step(shared, stacked_S, tokens, labels) ->
    (loss, g_stacked_S, g_shared), jit-ready.

    stacked_S leaves are [S, L/S, ...] (reshape the [L, ...] stacks from
    extract_pipeline_params).  tokens/labels: [M, micro, seq] microbatched;
    with data_axis set, micro is the GLOBAL microbatch size (sharded over
    that axis).
    """
    from ..distributed.pipeline import pipeline_1f1b

    S = mesh.shape[axis_name]
    stage_fn = make_llama_stage_fn(cfg, S)
    dp = mesh.shape.get(data_axis, 1) if data_axis else 1
    local_micro = micro_batch // dp
    dtype = jnp.bfloat16 if cfg.dtype == "bfloat16" else jnp.float32
    act_example = jnp.zeros((local_micro, seq_len, cfg.hidden_size), dtype)

    def step(shared, stacked, tokens, labels):
        return pipeline_1f1b(stage_fn, stacked, shared, tokens, labels,
                             act_example, mesh=mesh, axis_name=axis_name,
                             data_axis=data_axis)

    return step
