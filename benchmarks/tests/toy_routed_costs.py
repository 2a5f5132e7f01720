"""Cost counts of the tests' routed toy (``toy_routed.py``), as a
configuration's ``"costs"`` file gives them: a token multiplies the
gate of the short convolution, the router and the ``k`` experts it was
routed to, never all of them."""


def _layer(cfg, experts):
    h, m = cfg["hidden_size"], cfg["moe_intermediate_size"]
    return h * h + h * cfg["num_experts"] + experts * 3 * h * m


def matmul_params(cfg) -> int:
    return cfg["num_hidden_layers"] * _layer(
        cfg, cfg["num_experts_per_tok"]) \
        + cfg["hidden_size"] * cfg["vocab_size"]


def model_flops_per_token(cfg, seq: int) -> float:
    """6 a matrix parameter a token touches, and the convolution's taps
    (an elementwise product: 2 forward, 4 backward); no attention."""
    taps = cfg["num_hidden_layers"] * cfg["conv_L_cache"] \
        * cfg["hidden_size"]
    return 6.0 * (matmul_params(cfg) + taps)


def decode_step_bytes(cfg, context_tokens: float, counters) -> float:
    """Every expert that some sequence of the step chose is read once:
    the program counts them (``experts_read`` / ``decode_iterations``);
    the state is ``conv_L_cache`` rows a layer a sequence, whatever the
    context."""
    read = counters["experts_read"] / counters["decode_iterations"]
    layers = cfg["num_hidden_layers"]
    return 2.0 * (layers * _layer(cfg, 0) + read * 3 * cfg["hidden_size"]
                  * cfg["moe_intermediate_size"]
                  + cfg["hidden_size"] * cfg["vocab_size"])
