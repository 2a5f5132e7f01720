"""Cost counts of the ``sdar_moe`` configurations (a ``"costs"`` file:
``harness/flops.py``, ``harness/bytes.py``): grouped-query attention at
its own ``head_dim``, a router and ``num_experts_per_tok`` experts of
``num_experts`` a token in every layer, an untied head.  Floors: whatever
else a program moves comes on top, so a share of a roofline built on them
cannot pass 100 % unless the time is wrong.  Nothing of the program is
imported; what only the program can count (the experts a step really
read) comes in through the window's ``counters``."""

_DTYPE_BYTES = {"bfloat16": 2, "float16": 2, "float32": 4}


def weight_bytes(cfg) -> int:
    return _DTYPE_BYTES[cfg.get("model_config_kwargs", {}).get("dtype")
                        or cfg.get("torch_dtype") or "bfloat16"]


def attention_params(cfg) -> int:
    h, d = cfg["hidden_size"], cfg["head_dim"]
    heads, kv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    return h * heads * d + 2 * h * kv * d + heads * d * h


def expert_params(cfg) -> int:
    """One expert's three matrices."""
    return 3 * cfg["hidden_size"] * cfg["moe_intermediate_size"]


def expert_bytes(cfg) -> int:
    return expert_params(cfg) * weight_bytes(cfg)


def matmul_params(cfg) -> int:
    """Parameters of the matrices ONE token multiplies: a layer's
    projections, its router and the experts it is routed to, and the
    head.  The embedding is a lookup."""
    h = cfg["hidden_size"]
    layer = attention_params(cfg) + h * cfg["num_experts"] \
        + cfg["num_experts_per_tok"] * expert_params(cfg)
    return cfg["num_hidden_layers"] * layer + h * cfg["vocab_size"]


def model_flops_per_token(cfg, seq: int) -> float:
    """6 a matrix parameter a token touches (2 forward, 4 backward) plus
    attention at sequence length ``seq`` (as ``harness/flops.py`` counts
    it for a causal mask; a block-causal one sees half a block more)."""
    attention = cfg["num_hidden_layers"] * 6 * seq \
        * cfg["num_attention_heads"] * cfg["head_dim"]
    return 6.0 * matmul_params(cfg) + attention


def kv_bytes_per_token(cfg) -> int:
    return 2 * cfg["num_hidden_layers"] * cfg["num_key_value_heads"] \
        * cfg["head_dim"] * weight_bytes(cfg)


def program_runs(counters) -> int:
    """Runs of a step program that routes tokens: block steps and
    prefill chunks (each counts what it read into ``experts_read``)."""
    return counters.get("block_steps", 0) \
        + counters.get("prefill_chunks_run", 0)


def experts_read_per_layer_run(cfg, counters) -> float:
    """Mean number of experts one routed layer read in one program run,
    as the program counted them on the device."""
    runs = program_runs(counters)
    if not runs:
        return 0.0
    return counters["experts_read"] / runs / cfg["num_hidden_layers"]


def block_step_bytes(cfg, context_tokens: float, counters) -> float:
    """The least one block step must read: every matrix outside the
    experts once (projections, routers, the head), the experts the step
    READ (the counters' mean a layer, not the experts held) and the
    cached keys and values of ``context_tokens`` live positions.
    Activations, the pool's copy, the embedding rows and the tables are
    left out."""
    h = cfg["hidden_size"]
    dense = cfg["num_hidden_layers"] * (
        attention_params(cfg) + h * cfg["num_experts"]) \
        + h * cfg["vocab_size"]
    read = min(experts_read_per_layer_run(cfg, counters),
               cfg["num_experts"]) * cfg["num_hidden_layers"]
    return dense * weight_bytes(cfg) + read * expert_bytes(cfg) \
        + context_tokens * kv_bytes_per_token(cfg)


def decode_step_bytes(cfg, context_tokens: float, counters) -> float:
    """``harness/bytes.py``'s name for a step's bytes: this
    configuration's step is the block step."""
    return block_step_bytes(cfg, context_tokens, counters)


def expert_kernel_call_bytes(cfg, counters) -> float:
    """The least one call of the grouped-experts kernel (one layer of
    one program run) must move: the experts it read, and for every
    assignment one row in (the served type) and one row out
    (float32).  Rows of padding are left out."""
    runs = program_runs(counters) * cfg["num_hidden_layers"]
    if not runs:
        return 0.0
    rows = counters["expert_assignments"] / runs
    return min(experts_read_per_layer_run(cfg, counters),
               cfg["num_experts"]) * expert_bytes(cfg) \
        + rows * cfg["hidden_size"] * (weight_bytes(cfg) + 4)
