"""Operations a training step needs, from the configuration's sizes.

A configuration that is not a dense decoder with grouped-query attention
names its own counts: ``"costs": "<path from the root>.py"``, a file of
the benchmark with ``matmul_params(cfg)``, ``model_flops_per_token(cfg,
seq)`` and ``decode_step_bytes(cfg, context_tokens, counters)`` (see
``bytes.py``).  The functions here call that file's where one is named
and keep the dense formulas where none is, so every metric built on
them reads any configuration under its one name."""
from __future__ import annotations

from . import cells


def matmul_params(cfg) -> int:
    """Parameters of the matrices every token multiplies: the decoder
    layers' projections and the output head.  The embedding table is a
    lookup and does not count; norms are vectors."""
    costs = cells.config_module(cfg, "costs")
    if costs is not None:
        return costs.matmul_params(cfg)
    h, i = cfg["hidden_size"], cfg["intermediate_size"]
    heads, kv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    d = cfg.get("head_dim") or h // heads
    attn = h * heads * d + 2 * h * kv * d + heads * d * h
    mlp = 3 * h * i
    return cfg["num_hidden_layers"] * (attn + mlp) + h * cfg["vocab_size"]


def model_flops_per_token(cfg, seq: int) -> float:
    """Forward and backward operations one trained token needs at
    sequence length ``seq``: 6 per matrix parameter (2 forward, 4
    backward) plus causal attention, whose two products cost
    ``2 * seq * heads * d`` a token forward (half the square) and twice
    that backward.  Recomputed work does not count."""
    costs = cells.config_module(cfg, "costs")
    if costs is not None:
        return costs.model_flops_per_token(cfg, seq)
    heads = cfg["num_attention_heads"]
    d = cfg.get("head_dim") or cfg["hidden_size"] // heads
    attention = cfg["num_hidden_layers"] * 6 * seq * heads * d
    return 6.0 * matmul_params(cfg) + attention
