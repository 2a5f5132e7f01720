"""Cost counts of the ``afmoe`` configurations (a ``"costs"`` file:
``harness/flops.py``, ``harness/bytes.py``): grouped-query attention at
its own ``head_dim`` with a gate projection of q's width, window layers
beside full ones (``layer_types``), a dense MLP in the first
``num_dense_layers`` layers and in the others a router,
``num_experts_per_tok`` experts of ``num_experts`` a token and a shared
expert, an untied head.  Floors: whatever else a program moves comes on
top, so a share of a roofline built on them cannot pass 100 % unless the
time is wrong.  Nothing of the program is imported; what only the
program can count (the experts a decode run really read, the keys a
window layer's walk really covers) comes in through the window's
``counters``."""

_DTYPE_BYTES = {"bfloat16": 2, "float16": 2, "float32": 4}
SLIDING = "sliding_attention"


def weight_bytes(cfg) -> int:
    return _DTYPE_BYTES[cfg.get("model_config_kwargs", {}).get("dtype")
                        or cfg.get("torch_dtype") or "bfloat16"]


def layer_kinds(cfg):
    """``layer_types`` of the layers that are there."""
    return list(cfg["layer_types"])[:cfg["num_hidden_layers"]]


def window_layers(cfg) -> int:
    return sum(1 for kind in layer_kinds(cfg) if kind == SLIDING)


def full_layers(cfg) -> int:
    return cfg["num_hidden_layers"] - window_layers(cfg)


def routed_layers(cfg) -> int:
    return cfg["num_hidden_layers"] - min(cfg["num_dense_layers"],
                                          cfg["num_hidden_layers"])


def attention_params(cfg) -> int:
    """q, the gate (q's width), k, v and the output projection."""
    h, d = cfg["hidden_size"], cfg["head_dim"]
    heads, kv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    return 2 * h * heads * d + 2 * h * kv * d + heads * d * h


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
        + routed * (h * cfg["num_experts"]
                    + cfg["num_shared_experts"] * expert_params(cfg)) \
        + h * cfg["vocab_size"]


def matmul_params(cfg) -> int:
    """Parameters of the matrices ONE token multiplies."""
    return resident_params(cfg) + routed_layers(cfg) \
        * cfg["num_experts_per_tok"] * expert_params(cfg)


def model_flops_per_token(cfg, seq: int) -> float:
    """6 a matrix parameter a token touches (2 forward, 4 backward) plus
    attention: a full layer at ``seq`` keys, a window layer at no more
    than its window."""
    keys = full_layers(cfg) * seq \
        + window_layers(cfg) * min(seq, cfg["sliding_window"])
    return 6.0 * matmul_params(cfg) \
        + 6 * keys * cfg["num_attention_heads"] * cfg["head_dim"]


def kv_bytes_per_token_layer(cfg) -> int:
    return 2 * cfg["num_key_value_heads"] * cfg["head_dim"] \
        * weight_bytes(cfg)


def decode_kv_bytes(cfg, counters) -> float:
    """The cached keys and values ONE decode run's attention walks must
    cover: in a full layer every live position (``decode_context_tokens``
    a run), in a window layer ``min(context, window)`` a sequence
    (``decode_window_tokens`` a run), as the program counted them."""
    runs = counters.get("decode_iterations")
    if not runs:
        return 0.0
    context = counters["decode_context_tokens"] / runs
    # (a program without the counter has no window layer's walk to read)
    window = counters.get("decode_window_tokens",
                          counters["decode_context_tokens"]) / runs
    return (full_layers(cfg) * context + window_layers(cfg) * window) \
        * kv_bytes_per_token_layer(cfg)


def experts_read_per_layer_decode(cfg, counters) -> float:
    """Mean number of experts one routed layer read in one decode run,
    as the program counted them on the device."""
    calls = counters.get("decode_iterations", 0) * routed_layers(cfg)
    if not calls:
        return 0.0
    return min(counters.get("experts_read_decode", 0) / calls,
               cfg["num_experts"])


def decode_step_bytes(cfg, context_tokens: float, counters) -> float:
    """The least one decode step must read: every matrix outside the
    routed experts once, the experts the decode runs READ (the decode
    counters' mean a layer, not the experts held), and the cached keys
    and values its walks cover (``decode_kv_bytes``; ``context_tokens``
    is what the full layers' walk covers and is in the counters too).
    Activations, the embedding rows and the tables are left out."""
    return resident_params(cfg) * weight_bytes(cfg) \
        + experts_read_per_layer_decode(cfg, counters) \
        * routed_layers(cfg) * expert_bytes(cfg) \
        + decode_kv_bytes(cfg, counters)


def expert_kernel_call_bytes(cfg, counters) -> float:
    """The least one call of the grouped-experts kernel inside the
    decode program (one routed layer of one decode run) must move: the
    experts it read, and for every assignment one row in (the served
    type) and one row out (float32).  From the decode-only counters: a
    chunk reads nearly every expert, a decode run far fewer."""
    calls = counters.get("decode_iterations", 0) * routed_layers(cfg)
    if not calls:
        return 0.0
    rows = counters.get("expert_assignments_decode", 0) / calls
    return experts_read_per_layer_decode(cfg, counters) * expert_bytes(cfg) \
        + rows * cfg["hidden_size"] * (weight_bytes(cfg) + 4)
