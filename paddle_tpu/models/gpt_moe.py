# lint-tpu: disable-file=L004 -- grandfathered direct jax use; new backend code belongs under core/ ops/ kernels/ static/ distributed/ (README: Repo lint)
"""MoE transformer LM — the expert-parallel pretrain config
(ERNIE-4.5-MoE / DeepSeek-V2 style).

DeepSeek-V2 recipe: dense first layer(s), then MoE FFNs with shared experts
alongside routed experts; GQA attention; RMSNorm.  Built from the Llama
attention stack + distributed.moe.MoELayer so routing rides the ep mesh
axis (reference analog: incubate MoELayer + global_scatter/gather ops).
"""
from __future__ import annotations

from dataclasses import dataclass

import jax.numpy as jnp

from .. import nn
from ..core.dispatch import apply
from ..distributed.moe import MoELayer
from ..nn import functional as F
from .llama import (LlamaAttention, LlamaConfig, LlamaMLP, LlamaRMSNorm,
                    precompute_rope)
from ..core.tensor import Tensor


@dataclass
class MoEConfig:
    vocab_size: int = 102400
    hidden_size: int = 2048
    intermediate_size: int = 5632
    moe_intermediate_size: int = 1408
    num_hidden_layers: int = 24
    num_attention_heads: int = 16
    num_key_value_heads: int = 16
    num_experts: int = 64
    num_shared_experts: int = 2
    top_k: int = 6
    first_dense_layers: int = 1
    capacity_factor: float = 1.25
    aux_loss_weight: float = 0.01
    max_position_embeddings: int = 4096
    rms_norm_eps: float = 1e-6
    rope_theta: float = 10000.0
    dtype: str = "bfloat16"

    @staticmethod
    def tiny(**overrides):
        cfg = MoEConfig(
            vocab_size=256, hidden_size=64, intermediate_size=128,
            moe_intermediate_size=64, num_hidden_layers=2,
            num_attention_heads=4, num_key_value_heads=2, num_experts=4,
            num_shared_experts=1, top_k=2, first_dense_layers=1,
            max_position_embeddings=128, dtype="float32")
        for k, v in overrides.items():
            setattr(cfg, k, v)
        return cfg

    def _as_llama(self) -> LlamaConfig:
        return LlamaConfig(
            vocab_size=self.vocab_size, hidden_size=self.hidden_size,
            intermediate_size=self.intermediate_size,
            num_hidden_layers=self.num_hidden_layers,
            num_attention_heads=self.num_attention_heads,
            num_key_value_heads=self.num_key_value_heads,
            max_position_embeddings=self.max_position_embeddings,
            rms_norm_eps=self.rms_norm_eps, rope_theta=self.rope_theta,
            dtype=self.dtype, use_flash_attention=self.dtype == "bfloat16")


class MoEDecoderLayer(nn.Layer):
    def __init__(self, config: MoEConfig, use_moe: bool):
        super().__init__()
        lcfg = config._as_llama()
        self.input_layernorm = LlamaRMSNorm(config.hidden_size,
                                            config.rms_norm_eps)
        self.self_attn = LlamaAttention(lcfg)
        self.post_attention_layernorm = LlamaRMSNorm(config.hidden_size,
                                                     config.rms_norm_eps)
        self.use_moe = use_moe
        if use_moe:
            self.moe = MoELayer(
                d_model=config.hidden_size,
                d_hidden=config.moe_intermediate_size,
                num_experts=config.num_experts, top_k=config.top_k,
                capacity_factor=config.capacity_factor, gate="gshard",
                activation="silu")
            if config.num_shared_experts > 0:
                shared_cfg = config._as_llama()
                shared_cfg.intermediate_size = (config.moe_intermediate_size
                                                * config.num_shared_experts)
                self.shared_expert = LlamaMLP(shared_cfg)
            else:
                self.shared_expert = None
        else:
            self.mlp = LlamaMLP(lcfg)

    def forward(self, hidden, cos, sin):
        residual = hidden
        h = self.self_attn(self.input_layernorm(hidden), cos, sin)
        hidden = residual + h
        residual = hidden
        h = self.post_attention_layernorm(hidden)
        if self.use_moe:
            routed = self.moe(h)
            if self.shared_expert is not None:
                routed = routed + self.shared_expert(h)
            h = routed
        else:
            h = self.mlp(h)
        return residual + h


class MoEForCausalLM(nn.Layer):
    def __init__(self, config: MoEConfig):
        super().__init__()
        self.config = config
        from ..distributed.parallel_layers import (ColumnParallelLinear,
                                                   VocabParallelEmbedding)

        self.embed_tokens = VocabParallelEmbedding(config.vocab_size,
                                                   config.hidden_size)
        self.layers = nn.LayerList([
            MoEDecoderLayer(config, use_moe=i >= config.first_dense_layers)
            for i in range(config.num_hidden_layers)])
        self.norm = LlamaRMSNorm(config.hidden_size, config.rms_norm_eps)
        self.lm_head = ColumnParallelLinear(config.hidden_size,
                                            config.vocab_size, has_bias=False)
        head_dim = config.hidden_size // config.num_attention_heads
        cos, sin = precompute_rope(head_dim, config.max_position_embeddings,
                                   config.rope_theta)
        self.register_buffer("rope_cos", Tensor(cos), persistable=False)
        self.register_buffer("rope_sin", Tensor(sin), persistable=False)
        if config.dtype == "bfloat16":
            self.bfloat16()

    def forward(self, input_ids, labels=None):
        hidden = self.embed_tokens(input_ids)
        cos, sin = self.rope_cos._value, self.rope_sin._value
        aux_total = None
        for layer in self.layers:
            hidden = layer(hidden, cos, sin)
            if layer.use_moe and layer.moe.aux_loss is not None:
                a = layer.moe.aux_loss
                aux_total = a if aux_total is None else aux_total + a
        hidden = self.norm(hidden)
        logits = self.lm_head(hidden)
        if labels is not None:
            def _loss(lg, lab):
                import jax

                lg = lg[:, :-1].astype(jnp.float32)
                lab = lab[:, 1:]
                logp = jax.nn.log_softmax(lg, axis=-1)
                picked = jnp.take_along_axis(
                    logp, lab[..., None].astype(jnp.int32), axis=-1)[..., 0]
                return -jnp.mean(picked)

            lm_loss = apply("moe_lm_loss", _loss, logits, labels)
            if aux_total is not None:
                lm_loss = lm_loss + self.config.aux_loss_weight * aux_total
            return lm_loss, logits
        return logits
