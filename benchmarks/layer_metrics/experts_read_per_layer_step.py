"""Experts one routed layer read in one run of a step program, on
average over the window: the program's counter ``experts_read`` (experts
with at least one token, counted on the device, summed over layers) over
the runs that route tokens (``block_steps`` and ``prefill_chunks_run``)
and the configuration's layers.  What the expert stream's bytes are
counted from."""


def read(run):
    counters = run.get("counters") or {}
    runs = counters.get("block_steps", 0) \
        + counters.get("prefill_chunks_run", 0)
    layers = run["config"].get("num_hidden_layers")
    if not runs or not layers or "experts_read" not in counters:
        return None
    return counters["experts_read"] / runs / layers
