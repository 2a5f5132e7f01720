"""Share of the device's busy time spent in the routed experts: the
operations under the named scopes ``moe_router`` (scores, top-k) and
``moe_experts`` (grouping, the grouped-experts kernel, the combine) of
every step program."""
from benchmarks.harness import program_trace


def read(run):
    parts = [program_trace.scope_pct(run, scope)
             for scope in ("moe_router", "moe_experts")]
    found = [p for p in parts if p is not None]
    return sum(found) if found else None
