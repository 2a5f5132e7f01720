"""K/V tokens the decode walks of the configuration's layers cover, as a
share of what they would cover were every layer a full one: ``(full
layers x decode_context_tokens + window layers x decode_window_tokens)
/ (layers x decode_context_tokens)``, from the program's counters
(``decode_window_tokens``: the sum over decode iterations and running
slots of ``min(context, window)``) and the configuration's
``layer_types``.  100 where no sequence has left the window."""

SLIDING = "sliding_attention"


def read(run):
    counters = run.get("counters") or {}
    config = run["config"]
    context = counters.get("decode_context_tokens")
    kinds = list(config.get("layer_types") or ())[
        :config.get("num_hidden_layers")]
    if not context or not kinds or "decode_window_tokens" not in counters:
        return None
    windowed = sum(1 for kind in kinds if kind == SLIDING)
    covered = (len(kinds) - windowed) * context \
        + windowed * counters["decode_window_tokens"]
    return 100.0 * covered / (len(kinds) * context)
