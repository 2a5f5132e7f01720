"""Share of the router's (token, expert) choices that land on the
experts this chip HOLDS, over every step-program run of the window
(counters ``held_picks`` / ``route_picks`` × 100).  A chip that holds
16 of 256 experts holds an even 6.25 %; routing on random weights is
skewed, so this says how far this chip's expert work lies from a
deployment's mean chip.  ``None`` where the program counts no routed
choice apart (a replica that holds every expert reads 100)."""


def read(run):
    counters = run.get("counters") or {}
    picks = counters.get("route_picks")
    if not picks:
        return None
    return 100.0 * counters["held_picks"] / picks
