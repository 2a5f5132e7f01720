"""Share of the window's ``Engine.step()`` time that its slow steps took
beyond a usual step's: the program's counter ``slow_step_excess_ns``
(a step is slow over ``serving.metrics.SLOW_STEP_FACTOR`` times the
running mean of the steps before it; the excess is its wall time less
that mean) over ``step_wall_ns``.  A side whose ``serve_tok_s`` holds
stalls reads them here, in the same line: 1.5 % of stalls is told from
1.5 % of code.  What the host was doing in each slow step is in
``Engine.stats()["slow_steps"]``."""


def read(run):
    counters = run.get("counters") or {}
    wall = counters.get("step_wall_ns")
    if not wall or "slow_step_excess_ns" not in counters:
        return None
    return 100.0 * counters["slow_step_excess_ns"] / wall
