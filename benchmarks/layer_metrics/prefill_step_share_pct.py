"""Share of the window's engine steps that carried at least one prefill
chunk (``prefill_steps`` / ``engine_steps``, the program's counters): a
step with a chunk on board costs a decode step plus a chunk."""


def read(run):
    counters = run.get("counters") or {}
    steps = counters.get("engine_steps")
    if not steps or "prefill_steps" not in counters:
        return None
    return 100.0 * counters["prefill_steps"] / steps
