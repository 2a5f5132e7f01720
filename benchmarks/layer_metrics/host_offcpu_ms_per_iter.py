"""Host time of one ``Engine.step()`` in which the engine's own code held
no CPU, at the least.  Its own code is the step without the two phases
that wait for the device (``WAITS``, as ``engine_host_ms_per_iter`` has
them): wall time ``step_wall_ns`` less their ``step_ns``.  The program's
counter ``step_cpu_ns`` is the calling thread's CPU time while the
engine steps, the waits' share and the caller's own turn between two
steps included (the CPU clock is a system call, dear on a v5e's host:
the program reads it after every few steps and compares readings).  So
wall time of the steps' own code beyond ALL of that CPU time was
certainly no CPU time: that, over the window, over ``engine_steps``;
never under 0, and under the truth by what the thread burns inside its
waits and between the steps.  It reads 0 where the host's code keeps its
CPU, and rises when the process stands still in it.
``engine_host_ms_per_iter`` is the same code's wall time: where this is
a large part of it, the host was descheduled or asleep, not slow."""

WAITS = ("step_ns.decode_fetch", "step_ns.first_token")


def read(run):
    counters = run.get("counters") or {}
    steps = counters.get("engine_steps")
    if not steps or "step_cpu_ns" not in counters:
        return None
    wall = counters["step_wall_ns"] - sum(counters.get(k, 0) for k in WAITS)
    return max(0.0, wall - counters["step_cpu_ns"]) / steps / 1e6
