"""Cost counts of the ``mimo_v2`` configurations (a ``"costs"`` file:
``harness/flops.py``, ``harness/bytes.py``): grouped-query attention in
two layer kinds of their own head counts and widths
(``hybrid_layer_pattern``: 0 full, 1 window), keys of ``head_dim`` and
values of ``v_head_dim`` numbers; a dense MLP where ``moe_layer_freq``
is 0 and elsewhere a router over all its experts and
``num_experts_per_tok`` experts a token, of which this chip HOLDS a
share; an untied head.  Keys count at their published width (192) and
not at the 256 lanes the pool stores them in: what padding costs shows
as a lower share of a roofline, never as a higher one.  Floors: whatever
else a program moves comes on top, so a share of a roofline built on
them cannot pass 100 % unless the time is wrong.  Nothing of the program
is imported; what only the program can count (the experts a decode run
really read, the keys the walks cover, the pairs a chunk attends) comes
in through the window's ``counters``."""

_DTYPE_BYTES = {"bfloat16": 2, "float16": 2, "float32": 4}
FULL, WINDOW = 0, 1


def weight_bytes(cfg) -> int:
    return _DTYPE_BYTES[cfg.get("model_config_kwargs", {}).get("dtype")
                        or cfg.get("torch_dtype") or "bfloat16"]


def _kept(cfg, key):
    return [int(k) for k in cfg[key]][:cfg["num_hidden_layers"]]


def layer_kinds(cfg):
    """``hybrid_layer_pattern`` of the layers that are there."""
    return _kept(cfg, "hybrid_layer_pattern")


def kind_layers(cfg, kind) -> int:
    return sum(1 for k in layer_kinds(cfg) if k == kind)


def routed_layers(cfg) -> int:
    return sum(_kept(cfg, "moe_layer_freq"))


def router_experts(cfg) -> int:
    """The router's outputs: every expert of the layer, held here or
    not (``model_config_kwargs`` where the file's key counts the
    share)."""
    return cfg.get("model_config_kwargs", {}).get(
        "n_routed_experts", cfg["n_routed_experts"])


def held_experts(cfg) -> int:
    """The experts whose matrices this chip holds."""
    held = cfg.get("model_config_kwargs", {}).get("held_experts")
    return len(held) if held is not None else router_experts(cfg)


def shape(cfg, kind):
    """``(q heads, kv heads, key width, value width)`` of a layer kind."""
    p = "swa_" if kind == WINDOW else ""
    return (cfg[p + "num_attention_heads"], cfg[p + "num_key_value_heads"],
            cfg[p + "head_dim"], cfg[p + "v_head_dim"])


def attention_params(cfg, kind) -> int:
    """q, k, v and the output projection of a layer of that kind."""
    h = cfg["hidden_size"]
    heads, kv, d, dv = shape(cfg, kind)
    return h * heads * d + h * kv * (d + dv) + heads * dv * h


def expert_params(cfg) -> int:
    """One expert's three matrices."""
    return 3 * cfg["hidden_size"] * cfg["moe_intermediate_size"]


def expert_bytes(cfg) -> int:
    return expert_params(cfg) * weight_bytes(cfg)


def resident_params(cfg) -> int:
    """Parameters of every matrix outside the routed experts: the
    attention of every layer, the dense layers' MLP, the routed layers'
    router (all its outputs) and the head.  The embedding is a lookup."""
    h = cfg["hidden_size"]
    routed = routed_layers(cfg)
    dense = cfg["num_hidden_layers"] - routed
    return sum(attention_params(cfg, k) for k in layer_kinds(cfg)) \
        + dense * 3 * h * cfg["intermediate_size"] \
        + routed * h * router_experts(cfg) + h * cfg["vocab_size"]


def matmul_params(cfg) -> int:
    """Parameters of the matrices ONE token multiplies in the stated
    deployment (its ``num_experts_per_tok`` experts, wherever they are
    held)."""
    return resident_params(cfg) + routed_layers(cfg) \
        * cfg["num_experts_per_tok"] * expert_params(cfg)


def model_flops_per_token(cfg, seq: int) -> float:
    """6 a matrix parameter a token touches (2 forward, 4 backward) plus
    attention, a key's q.k and p.v a head: a full layer at ``seq`` keys,
    a window layer at no more than its window."""
    attn = 0
    for kind in layer_kinds(cfg):
        heads, _, d, dv = shape(cfg, kind)
        keys = seq if kind == FULL else min(seq, cfg["sliding_window"])
        attn += 3 * keys * heads * (d + dv)
    return 6.0 * matmul_params(cfg) + attn


def kv_bytes_per_token_layer(cfg, kind) -> int:
    """A position's keys and values in a layer of that kind, at their
    published widths."""
    _, kv, d, dv = shape(cfg, kind)
    return kv * (d + dv) * weight_bytes(cfg)


def decode_kv_bytes(cfg, counters) -> float:
    """The cached keys and values ONE decode run's attention walks must
    cover: in a full layer every live position (``decode_context_tokens``
    a run), in a window layer ``min(context, window)`` a sequence
    (``decode_window_tokens`` a run), as the program counted them."""
    runs = counters.get("decode_iterations")
    if not runs:
        return 0.0
    context = counters["decode_context_tokens"] / runs
    window = counters.get("decode_window_tokens",
                          counters["decode_context_tokens"]) / runs
    return kind_layers(cfg, FULL) * context \
        * kv_bytes_per_token_layer(cfg, FULL) \
        + kind_layers(cfg, WINDOW) * window \
        * kv_bytes_per_token_layer(cfg, WINDOW)


def experts_read_per_layer_decode(cfg, counters) -> float:
    """Mean number of HELD experts one routed layer read in one decode
    run, as the program counted them on the device."""
    calls = counters.get("decode_iterations", 0) * routed_layers(cfg)
    if not calls:
        return 0.0
    return min(counters.get("experts_read_decode", 0) / calls,
               held_experts(cfg))


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
    experts it read, and for every assignment to them one row in (the
    served type) and one row out (float32)."""
    calls = counters.get("decode_iterations", 0) * routed_layers(cfg)
    if not calls:
        return 0.0
    rows = counters.get("expert_assignments_decode", 0) / calls
    return experts_read_per_layer_decode(cfg, counters) * expert_bytes(cfg) \
        + rows * cfg["hidden_size"] * (weight_bytes(cfg) + 4)


def chunk_attention_flops(cfg, counters) -> float:
    """The least operations ONE chunk run's attention kernels must do:
    for every (real query, key) pair the chunks attended, a head's q.k
    and p.v, ``2 (d + dv)`` operations a head, in the full layers over
    ``prefill_attended_pairs`` and in the window layers over
    ``prefill_window_pairs``, each a run."""
    runs = counters.get("prefill_chunks_run")
    if not runs or "prefill_window_pairs" not in counters:
        return 0.0
    total = 0.0
    for kind, pairs in ((FULL, counters.get("prefill_attended_pairs", 0)),
                        (WINDOW, counters["prefill_window_pairs"])):
        heads, _, d, dv = shape(cfg, kind)
        total += kind_layers(cfg, kind) * pairs * 2 * heads * (d + dv)
    return total / runs
