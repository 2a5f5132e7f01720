"""How uneven the routing is: the busiest expert's assignments over the
mean expert's, averaged over every routed layer of every step-program
run of the window (counters ``expert_assignments_max``, the sum over
those of the largest group, and ``expert_assignments`` over the
configuration's ``num_experts``).  1.0 is a perfectly even load; a
grouped kernel pays the busiest group in tiles."""


def read(run):
    counters = run.get("counters") or {}
    experts = run["config"].get("num_experts")
    total = counters.get("expert_assignments")
    if not total or not experts:
        return None
    return counters["expert_assignments_max"] / (total / experts)
