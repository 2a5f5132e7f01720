"""Time of one ``Engine.step()`` that none of its eight phases owns: the
program's ``step_wall_ns`` (its own clock around the whole step) less
the sum of its ``step_ns.<phase>`` counters, over ``engine_steps``, in
microseconds.  The watchdogs' bookkeeping and the calls between two
phases, and the step's own account."""


def read(run):
    counters = run.get("counters") or {}
    steps = counters.get("engine_steps")
    if not steps or "step_wall_ns" not in counters:
        return None
    owned = sum(v for k, v in counters.items() if k.startswith("step_ns."))
    return (counters["step_wall_ns"] - owned) / steps / 1e3
