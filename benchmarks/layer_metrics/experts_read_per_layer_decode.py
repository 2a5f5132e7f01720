"""Experts one routed layer read in one run of the one-token decode
program, on average over the window: the program's counter
``experts_read_decode`` (experts with at least one row, counted on the
device, summed over the routed layers of the decode runs alone) over
``decode_iterations`` and the configuration's routed layers
(``num_hidden_layers - num_dense_layers``).  What the decode step's
expert bytes are counted from."""


def read(run):
    counters = run.get("counters") or {}
    config = run["config"]
    runs = counters.get("decode_iterations")
    layers = config.get("num_hidden_layers")
    if not runs or not layers or "experts_read_decode" not in counters:
        return None
    routed = layers - min(config.get("num_dense_layers", 0), layers)
    if not routed:
        return None
    return counters["experts_read_decode"] / runs / routed
