"""From a ``jax.profiler`` trace to device busy time, kernel time, the
operations that took most time and what the host was doing in the idle
gaps.

A gap's owner is the innermost host span around it: the program's own
``<part>::<phase>`` span where the host was inside one, else the
benchmark's ``bench.<what>`` span.

``reduce_trace`` is a pure function over ``(plane, line, name, start_ns,
dur_ns)`` tuples, so it is tested on a hand-built list; ``read_xplane``
turns the profiler's ``.xplane.pb`` into such tuples with
``jax.profiler.ProfileData`` and nothing else.

What the planes, lines and events of a v5e trace are called is written
down in PERF.md section 3 ("Reading a trace").
"""
from __future__ import annotations

import glob
import os
import re

from .stats import union_seconds

HOST_SPAN_PREFIX = "bench."
PROGRAM_SPAN = re.compile(r"^[a-z_0-9]+::[a-z_0-9]+$")
_NS = 1e-9
_SUFFIX = re.compile(r"[.\-_]?\d+$")


def is_device_op(plane: str, line: str) -> bool:
    """One event for each operation the chip ran: the ``XLA Ops`` line of
    a ``/device:TPU:<n>`` plane.  (``XLA Modules`` holds whole programs
    and ``Async XLA Ops`` the copies that run beside the operations:
    counting them would double the busy time.)"""
    return plane.startswith("/device:TPU:") and line == "XLA Ops"


PALLAS_PREFIX = "pallas:"


def is_pallas(name: str) -> bool:
    """A Pallas kernel's event, as ``short_name`` marks it."""
    return name.startswith(PALLAS_PREFIX)


def short_name(event_name: str) -> str:
    """A device event is named by its whole HLO instruction,
    ``%fused_paged_decode.17 = (f32[...]) custom-call(...),
    custom_call_target="tpu_custom_call", ...``.  Kept: the instruction's
    name without ``%`` and its number, so that ``fusion.123`` and
    ``fusion.7`` are one row of the breakdown; a Mosaic (Pallas) kernel,
    whose instruction is named after its ``pallas_call``, gets
    ``pallas:`` in front."""
    name = _SUFFIX.sub("", event_name.split(" = ", 1)[0].lstrip("%"))
    if 'custom_call_target="tpu_custom_call"' in event_name:
        return PALLAS_PREFIX + name
    return name or event_name


def newest_xplane(trace_dir: str) -> str:
    found = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return max(found, key=os.path.getmtime)


def is_host_span(name: str) -> bool:
    """A span the host opened on purpose: the benchmark's own
    (``bench.<what>``) or the program's (``<part>::<phase>``)."""
    return name.startswith(HOST_SPAN_PREFIX) \
        or PROGRAM_SPAN.match(name) is not None


def read_xplane(path: str):
    """``(plane, line, name, start_ns, dur_ns)`` for every device
    operation (named by ``short_name``) and every host span
    (``is_host_span``) of the trace at ``path``."""
    from jax.profiler import ProfileData

    out = []
    for plane in ProfileData.from_file(path).planes:
        for line in plane.lines:
            device = is_device_op(plane.name, line.name)
            for ev in line.events:
                name = ev.name
                if device:
                    name = short_name(name)
                elif not is_host_span(name):
                    continue
                out.append((plane.name, line.name, name,
                            int(ev.start_ns), int(ev.duration_ns)))
    return out


def _gaps(intervals, lo, hi):
    """The parts of ``[lo, hi]`` no interval covers, in order."""
    gaps, at = [], lo
    for s, e in sorted(intervals):
        if s > at:
            gaps.append((at, min(s, hi)))
        at = max(at, e)
        if at >= hi:
            break
    if at < hi:
        gaps.append((at, hi))
    return [(s, e) for s, e in gaps if e > s]


def innermost(spans):
    """Disjoint ``(start, end, name)`` pieces of possibly nested
    ``(start, end, name, ...)`` spans: where several cover a moment, the
    one that began last owns it."""
    spans = sorted(spans)
    edges = sorted({t for s, e, *_ in spans for t in (s, e)})
    pieces, active, i = [], [], 0
    for a, b in zip(edges, edges[1:]):
        while i < len(spans) and spans[i][0] <= a:
            active.append(spans[i])
            i += 1
        active = [sp for sp in active if sp[1] >= b]
        if active:
            pieces.append((a, b, max(active)[2]))
    return pieces


def charge(gaps, pieces, outside):
    """``{name: ns}``: each gap's time to the piece it falls in,
    ``outside`` where it falls in none.  Both lists are in order."""
    out, i = {}, 0
    for gs, ge in gaps:
        while i < len(pieces) and pieces[i][1] <= gs:
            i += 1
        j, covered = i, 0
        while j < len(pieces) and pieces[j][0] < ge:
            part = min(ge, pieces[j][1]) - max(gs, pieces[j][0])
            if part > 0:
                out[pieces[j][2]] = out.get(pieces[j][2], 0) + part
                covered += part
            j += 1
        if ge - gs > covered:
            out[outside] = out.get(outside, 0) + (ge - gs) - covered
    return out


def reduce_trace(events):
    """``None`` when no device operation ran inside the traced window,
    else a dict: ``window_s`` (from the first ``bench.*`` span's start, or
    the first device operation's where that is later, to the last
    span's end; the device events' own range where there is no span),
    ``busy_s`` (union of device-operation intervals, averaged over the
    device planes), ``pallas_s`` (summed Pallas kernel time, same average), ``device_ops`` and ``idle_gaps`` (the ten largest
    ``[name, seconds]`` each; a gap is charged to the host span it falls
    in, the innermost where they nest: the program's ``<part>::<phase>``
    where the host was inside one, else the ``bench.*`` span around it,
    ``bench.outside`` where it was in none)."""
    spans = sorted((s, s + d, name) for plane, line, name, s, d in events
                   if name.startswith(HOST_SPAN_PREFIX)
                   and not is_device_op(plane, line))
    program_spans = [(s, s + d, name) for plane, line, name, s, d in events
                     if PROGRAM_SPAN.match(name)
                     and not is_device_op(plane, line)]
    ops = {}
    for plane, line, name, s, d in events:
        if is_device_op(plane, line):
            ops.setdefault(plane, []).append((s, s + d, name))
    if not ops:
        return None
    first_op = min(s for v in ops.values() for s, _, _ in v)
    if spans:
        # the profiler records host spans before its device line is
        # live (some 140 ms on a v5e): the slice starts when both are
        lo, hi = max(spans[0][0], first_op), max(e for _, e, _ in spans)
    else:
        lo, hi = first_op, max(e for v in ops.values() for _, e, _ in v)
    busy = pallas = 0.0
    by_op = {}
    for plane_ops in ops.values():
        clipped = [(max(s, lo), min(e, hi), n) for s, e, n in plane_ops
                   if e > lo and s < hi]
        busy += union_seconds([(s, e) for s, e, _ in clipped])
        for s, e, n in clipped:
            by_op[n] = by_op.get(n, 0.0) + (e - s)
            if is_pallas(n):
                pallas += e - s
    n_planes = len(ops)
    if busy <= 0:
        return None
    # idle gaps of the first device, by what the host was doing
    first = ops[sorted(ops)[0]]
    by_span = charge(_gaps([(s, e) for s, e, _ in first], lo, hi),
                     innermost(spans + program_spans),
                     HOST_SPAN_PREFIX + "outside")

    def top(d, scale):
        rows = sorted(d.items(), key=lambda kv: -kv[1])[:10]
        return [[k, v * scale] for k, v in rows]

    return {"window_s": (hi - lo) * _NS,
            "busy_s": busy * _NS / n_planes,
            "pallas_s": pallas * _NS / n_planes,
            "devices": n_planes,
            "device_ops": top(by_op, _NS / n_planes),
            "idle_gaps": top(by_span, _NS)}


def idle_pct(run):
    """Per-layer reader: share of the traced slice in which no operation
    ran on the device; ``None`` without a trace."""
    trace = run.get("trace")
    if not trace:
        return None
    return 100.0 * (1.0 - trace["busy_s"] / trace["window_s"])


def pallas_pct(run):
    """Per-layer reader: share of the device's busy time spent in Pallas
    kernels; ``None`` without a trace."""
    trace = run.get("trace")
    if not trace:
        return None
    return 100.0 * trace["pallas_s"] / trace["busy_s"]
