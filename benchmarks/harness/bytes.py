"""The least bytes a decode step must move through the chip's memory,
from the configuration's sizes alone.  Floors, not estimates: whatever else a
program reads or writes (activations, block tables, a pool it copies)
comes on top, so a share of the roofline built on these cannot pass
100 % unless the time is wrong.  The benchmark owns these counts;
nothing of the program is imported for them."""
from __future__ import annotations

from . import cells, flops

_DTYPE_BYTES = {"bfloat16": 2, "float16": 2, "float32": 4}


def weight_bytes(cfg) -> int:
    """Bytes of one parameter as the configuration holds it."""
    dtype = cfg.get("model_config_kwargs", {}).get("dtype") \
        or cfg.get("torch_dtype")
    return _DTYPE_BYTES[dtype]


def head_dim(cfg) -> int:
    return cfg.get("head_dim") \
        or cfg["hidden_size"] // cfg["num_attention_heads"]


def kv_bytes_per_token(cfg) -> int:
    """Bytes one token's keys and values take in the cache, over all
    layers, in the served type (an unquantized pool)."""
    return 2 * cfg["num_hidden_layers"] * cfg["num_key_value_heads"] \
        * head_dim(cfg) * weight_bytes(cfg)


def decode_step_bytes(cfg, context_tokens: float, counters=None) -> float:
    """One decode step over sequences that hold ``context_tokens`` tokens
    of context together: every matrix of the model is read once
    (whatever the batch) and every cached key and value of every live
    sequence once.  The embedding rows, the logits, the new token's
    write and the block tables are left out.  A configuration that names
    its own ``"costs"`` (``flops.py``) is asked instead, and given the
    window's ``counters`` too: a routed model reads only the experts a
    step chose, which only the program can count."""
    costs = cells.config_module(cfg, "costs")
    if costs is not None:
        return costs.decode_step_bytes(cfg, context_tokens, counters)
    return flops.matmul_params(cfg) * weight_bytes(cfg) \
        + context_tokens * kv_bytes_per_token(cfg)
