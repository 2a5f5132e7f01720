"""Runs one cell and prints the contract's last line."""
from __future__ import annotations

import dataclasses
import json
import math
import os
import sys

import jax

from . import cells, device
from .stats import clock
from .tracing import SliceTracer


@dataclasses.dataclass
class Context:
    """What a kind's ``run(ctx)`` is given."""
    cell: cells.Cell
    seed: int
    seconds: float
    t_start: float              # the process's start on ``stats.clock``
    tracer: SliceTracer
    compiles: device.CompileCounter
    device: dict

    @staticmethod
    def say(**fields):
        """An earlier line of output: one JSON object worth reading."""
        print(json.dumps(fields), flush=True)


def _finite(value):
    return isinstance(value, (int, float)) and math.isfinite(value)


def run_cell(workload: str, seed: int, seconds: float, trace: bool, *,
             t_start: float, root: str = cells.REPO_ROOT,
             require_device=device.require_accelerator) -> dict:
    """Run the cell and return the last line's object.  ``root`` holds
    ``BENCHMARK.json`` and the benchmark's directories;
    ``require_device(chips) -> {"platform", "kind", "count"}`` is the
    device check (the tests' CPU rehearsal passes its own)."""
    from paddle_tpu.core.compile_cache import enable_compile_cache

    cell = cells.load_cell(workload, root)
    imported_s = clock() - t_start
    dev = require_device(cell.chips)
    cache_dir = enable_compile_cache()
    # keep every program, however quick its compile: some forty small
    # ones fall under JAX's one-second floor and would otherwise be
    # compiled anew in every run's set-up
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    ctx = Context(
        cell=cell, seed=seed, seconds=seconds, t_start=t_start,
        tracer=SliceTracer(trace, os.path.join(
            root, ".bench_trace", workload)),
        compiles=device.CompileCounter(), device=dev)
    ctx.say(workload=workload, config=cell.config_name,
            traffic=cell.traffic_name, kind=cell.traffic["kind"],
            seed=seed, seconds=seconds, trace=trace, device=dev,
            compile_cache_dir=cache_dir, imported_s=imported_s,
            device_ready_s=clock() - t_start)
    result = cell.kind.run(ctx)
    setup_s = result["window_start"] - t_start
    run = dict(result["window"], end_to_end=result["end_to_end"],
               config=cell.config, traffic=cell.traffic, device=dev,
               chips=cell.chips,
               trace=ctx.tracer.reduced, trace_path=ctx.tracer.path)
    ctx.say(phase="done", setup_s=setup_s, end_to_end=result["end_to_end"],
            compile_requests=ctx.compiles.count,
            compile_seconds=ctx.compiles.seconds,
            total_s=clock() - t_start)

    dev = dict(dev, memory_peak_bytes=device.memory_peak_bytes())
    if trace:
        values = {m["name"]: cell.readers[m["name"]].read(run)
                  for m in cell.per_layer}
        declared = cell.per_layer
        reduced = ctx.tracer.reduced
        if reduced is not None:
            dev.update(busy_s=reduced["busy_s"], window_s=reduced["window_s"])
    else:
        values = dict(result["end_to_end"], setup_s=setup_s)
        declared = cell.end_to_end
        missing = [m["name"] for m in declared
                   if not _finite(values.get(m["name"]))]
        if missing:
            raise RuntimeError(
                f"{workload}: no finite value for end-to-end metrics "
                f"{missing}")
    line = {
        "correct": bool(result["correct"]),
        "attempted": int(result["attempted"]),
        "failed": int(result["failed"]),
        "metrics": {m["name"]: {"value": values[m["name"]],
                                "unit": m["unit"]}
                    for m in declared if _finite(values.get(m["name"]))},
        "device": dev,
    }
    if trace and ctx.tracer.reduced is not None:
        line["breakdown"] = {
            "device_ops": ctx.tracer.reduced["device_ops"],
            "idle_gaps": ctx.tracer.reduced["idle_gaps"]}
    # every number ``correct`` compared beside its limit, last in the
    # line and last on standard error: what is kept of a run that fails
    line["compared"] = {name: {"value": value, "limit": limit}
                        for name, (value, limit)
                        in result.get("compared", {}).items()}
    for name, pair in line["compared"].items():
        print(f"compared {name}: {pair['value']!r} (limit {pair['limit']!r})",
              file=sys.stderr, flush=True)
    return line
