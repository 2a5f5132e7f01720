"""Host time of one ``Engine.step()`` that is the engine's own: the sum
of the program's ``step_ns.<phase>`` counters over the window, without
the two phases that wait for the device (``decode_fetch`` and
``first_token``: reading a program's result), over ``engine_steps``.
What is left of ``engine_iter_p50_ms`` once the two programs' device
times are taken out should be about this."""

WAITS = ("step_ns.decode_fetch", "step_ns.first_token")


def read(run):
    counters = run.get("counters") or {}
    steps = counters.get("engine_steps")
    if not steps:
        return None
    host_ns = sum(v for k, v in counters.items()
                  if k.startswith("step_ns.") and k not in WAITS)
    return host_ns / steps / 1e6
