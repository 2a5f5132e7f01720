"""Cost counts the tests' served configuration names: a dense decoder's
(the formulas of ``harness/flops.py`` and ``harness/bytes.py``, written
out), with the bytes of a decode step's cache taken from the window's
counters, which a ``costs`` file is given."""


def _head_dim(cfg):
    return cfg.get("head_dim") \
        or cfg["hidden_size"] // cfg["num_attention_heads"]


def matmul_params(cfg) -> int:
    h, d = cfg["hidden_size"], _head_dim(cfg)
    heads, kv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    layer = 2 * h * heads * d + 2 * h * kv * d \
        + 3 * h * cfg["intermediate_size"]
    return cfg["num_hidden_layers"] * layer + h * cfg["vocab_size"]


def model_flops_per_token(cfg, seq: int) -> float:
    return 6.0 * matmul_params(cfg) + cfg["num_hidden_layers"] * 6 * seq \
        * cfg["num_attention_heads"] * _head_dim(cfg)


def decode_step_bytes(cfg, context_tokens: float, counters) -> float:
    context = counters["decode_context_tokens"] \
        / counters["decode_iterations"]
    return 2.0 * (matmul_params(cfg) + context * 2
                  * cfg["num_hidden_layers"] * cfg["num_key_value_heads"]
                  * _head_dim(cfg))
