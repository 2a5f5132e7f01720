"""Window-group pages one running sequence holds, a window layer, on
average over the decode iterations of the window: the program's counters
``window_pages_live`` / ``window_seq_steps`` (counted after the
iteration's release and allocation).  The manager's bound is
``ceil((window + chunk) / block) + 1`` during prefill and ``ceil(window
/ block) + 1`` between decode steps, whatever the sequence's length."""


def read(run):
    counters = run.get("counters") or {}
    steps = counters.get("window_seq_steps")
    if not steps:
        return None
    return counters["window_pages_live"] / steps
