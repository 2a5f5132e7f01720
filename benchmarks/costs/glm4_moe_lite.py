"""Cost counts of the ``glm4_moe_lite`` configurations (a ``"costs"``
file: ``harness/flops.py``, ``harness/bytes.py``): multi-head latent
attention (a low-rank query, ONE compressed key/value of ``kv_lora_rank
+ qk_rope_head_dim`` numbers a position a layer, no heads in the cache),
a dense MLP in the first ``first_k_dense_replace`` layers and in the
others a router, ``num_experts_per_tok`` of ``n_routed_experts`` experts
a token and a shared expert, an untied head.  Floors: whatever else a
program moves comes on top (the pool's entries are padded to whole
registers, a chunk's padded rows are computed too), so a share of a
roofline built on them cannot pass 100 % unless the time is wrong.
Nothing of the program is imported; what only the program can count (the
experts a decode run really read, the keys a chunk's real queries see)
comes in through the window's ``counters``."""

_DTYPE_BYTES = {"bfloat16": 2, "float16": 2, "float32": 4}


def weight_bytes(cfg) -> int:
    return _DTYPE_BYTES[cfg.get("model_config_kwargs", {}).get("dtype")
                        or cfg.get("torch_dtype") or "bfloat16"]


def routed_layers(cfg) -> int:
    return cfg["num_hidden_layers"] - min(cfg["first_k_dense_replace"],
                                          cfg["num_hidden_layers"])


def latent_dim(cfg) -> int:
    """What a position caches a layer, as published: ``[c_kv |
    k_rope]``."""
    return cfg["kv_lora_rank"] + cfg["qk_rope_head_dim"]


def attention_params(cfg) -> int:
    """``W_qa``, ``W_qb``, ``W_kva``, ``W_kvb`` and the output
    projection."""
    h, heads = cfg["hidden_size"], cfg["num_attention_heads"]
    qk = cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"]
    return h * cfg["q_lora_rank"] + cfg["q_lora_rank"] * heads * qk \
        + h * latent_dim(cfg) \
        + cfg["kv_lora_rank"] * heads * (cfg["qk_nope_head_dim"]
                                         + cfg["v_head_dim"]) \
        + heads * cfg["v_head_dim"] * h


def expert_params(cfg) -> int:
    """One expert's three matrices."""
    return 3 * cfg["hidden_size"] * cfg["moe_intermediate_size"]


def expert_bytes(cfg) -> int:
    return expert_params(cfg) * weight_bytes(cfg)


def resident_params(cfg) -> int:
    """Parameters of every matrix outside the routed experts: the
    attention of every layer, the dense layers' MLP, the routed layers'
    router and shared expert, and the head.  The embedding is a lookup."""
    h = cfg["hidden_size"]
    routed = routed_layers(cfg)
    dense = cfg["num_hidden_layers"] - routed
    return cfg["num_hidden_layers"] * attention_params(cfg) \
        + dense * 3 * h * cfg["intermediate_size"] \
        + routed * (h * cfg["n_routed_experts"]
                    + cfg["n_shared_experts"] * expert_params(cfg)) \
        + h * cfg["vocab_size"]


def matmul_params(cfg) -> int:
    """Parameters of the matrices ONE token multiplies."""
    return resident_params(cfg) + routed_layers(cfg) \
        * cfg["num_experts_per_tok"] * expert_params(cfg)


def attention_flops_per_pair(cfg) -> int:
    """Operations one (query token, key) pair costs a layer in the
    ABSORBED form: every head's score over the entry's lanes and its
    weighted sum over the value's."""
    return 2 * cfg["num_attention_heads"] \
        * (latent_dim(cfg) + cfg["kv_lora_rank"])


def model_flops_per_token(cfg, seq: int) -> float:
    """6 a matrix parameter a token touches (2 forward, 4 backward) plus
    attention at ``seq`` keys in the expanded form a training step
    would compute (scores over ``qk`` lanes, values over ``v``)."""
    per_key = cfg["num_attention_heads"] * (
        cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"]
        + cfg["v_head_dim"])
    return 6.0 * matmul_params(cfg) \
        + 6 * cfg["num_hidden_layers"] * seq * per_key


def kv_bytes_per_token_layer(cfg) -> int:
    """The PUBLISHED entry, whatever a pool's layout pads it to."""
    return latent_dim(cfg) * weight_bytes(cfg)


def decode_kv_bytes(cfg, counters) -> float:
    """The cached entries ONE decode run's attention walks must cover:
    every live position (``decode_context_tokens`` a run) in every
    layer."""
    runs = counters.get("decode_iterations")
    if not runs:
        return 0.0
    return counters["decode_context_tokens"] / runs \
        * cfg["num_hidden_layers"] * kv_bytes_per_token_layer(cfg)


def experts_read_per_layer_decode(cfg, counters) -> float:
    """Mean number of experts one routed layer read in one decode run,
    as the program counted them on the device."""
    calls = counters.get("decode_iterations", 0) * routed_layers(cfg)
    if not calls:
        return 0.0
    return min(counters.get("experts_read_decode", 0) / calls,
               cfg["n_routed_experts"])


def decode_step_bytes(cfg, context_tokens: float, counters) -> float:
    """The least one decode step must read: every matrix outside the
    routed experts once, the experts the decode runs READ (the decode
    counters' mean a layer, not the experts held), and the cached
    entries its walks cover (``decode_kv_bytes``; ``context_tokens`` is
    in the counters too).  Activations, the embedding rows and the
    tables are left out."""
    return resident_params(cfg) * weight_bytes(cfg) \
        + experts_read_per_layer_decode(cfg, counters) \
        * routed_layers(cfg) * expert_bytes(cfg) \
        + decode_kv_bytes(cfg, counters)


def expert_kernel_call_bytes(cfg, counters) -> float:
    """The least one call of the grouped-experts kernel inside the
    decode program (one routed layer of one decode run) must move: the
    experts it read, and for every assignment one row in (the served
    type) and one row out (float32).  From the decode-only counters."""
    calls = counters.get("decode_iterations", 0) * routed_layers(cfg)
    if not calls:
        return 0.0
    rows = counters.get("expert_assignments_decode", 0) / calls
    return experts_read_per_layer_decode(cfg, counters) * expert_bytes(cfg) \
        + rows * cfg["hidden_size"] * (weight_bytes(cfg) + 4)


def chunk_attention_cost(cfg, counters):
    """``(flops, bytes)`` of the least work of the chunk kernel's calls
    in ONE chunk run (all layers): the operations of the (real query,
    key) pairs the chunks attended (``prefill_attended_pairs``) in the
    absorbed form, and the chunk's context read once a layer
    (``prefill_context_tokens``), both a run (``prefill_chunks_run``)."""
    runs = counters.get("prefill_chunks_run")
    if not runs or "prefill_attended_pairs" not in counters:
        return 0.0, 0.0
    layers = cfg["num_hidden_layers"]
    flops = counters["prefill_attended_pairs"] / runs * layers \
        * attention_flops_per_pair(cfg)
    size = counters["prefill_context_tokens"] / runs * layers \
        * kv_bytes_per_token_layer(cfg)
    return flops, size
