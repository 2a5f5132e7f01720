"""Share of decode slots that produced a token: tokens emitted by decode
iterations inside the window (all tokens less the first tokens, which
prefill emits) over ``decode_iterations`` x ``max_batch_size``, from the
``ServingMetrics`` counter's change over the window.  Not the
``batch_occupancy_avg`` gauge, which averages since the engine started."""


def read(run):
    counters = run.get("counters")
    if not counters or not counters.get("decode_iterations"):
        return None
    decoded = run["tokens"] - run["first_tokens"]
    slots = counters["decode_iterations"] * run["max_batch_size"]
    return 100.0 * decoded / slots
