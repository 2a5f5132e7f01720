"""What the PROGRAM said it was doing, read out of the trace a run wrote:
its own host spans (``<part>::<phase>`` ``TraceAnnotation``s), the idle
gaps of the device charged to those spans, its step programs by name
(the ``XLA Modules`` line) and device time by ``jax.named_scope``.

``trace_reduce`` reduces the same file to busy time, kernel time and
the breakdown; a run carries that reduction and the file's path
(``run["trace_path"]``), which ``load(run)`` reads.  All times are
clipped to the slice ``trace_reduce`` uses.

Where things are in a v5e trace is written down in PERF.md section 3
("Reading a trace").  In short: a program's run is an event
``jit_<name>(<fingerprint>)`` on line ``XLA Modules``; an ``XLA Ops``
event is named by its HLO instruction and carries neither its program
nor its ``op_name``, so an operation is given the program whose run
contains it, and its ``op_name`` (``jit(step)/mlp/dot_general``: the
named scopes) is looked up by instruction name in that program's HLO
(the optimized one: fusions and the compiler's own copies), which the
profiler stores in plane ``/host:metadata``.
``jax.profiler.ProfileData`` does not show that plane's contents, so
the few protobuf fields needed are decoded here, with the standard
library alone.
"""
from __future__ import annotations

import bisect
import os
import re

from . import cells, trace_reduce
from .stats import percentile, union_seconds

_NS = 1e-9
OUTSIDE = "outside"
_MODULE = re.compile(r"^jit_(.+?)(\(\d+\))?$")
_TRANSFORM = re.compile(r"^(?:transpose|jvp|vmap|pmap|remat|checkpoint)"
                        r"\((.*)\)$")


# --------------------------------------------------------------- protobuf
def _varint(buf, at):
    value = shift = 0
    while True:
        b = buf[at]
        at += 1
        value |= (b & 0x7F) << shift
        shift += 7
        if b < 0x80:
            return value, at


def _fields(buf):
    """``(field number, value)`` of one protobuf message: an ``int`` for
    a varint or fixed-width field, ``bytes`` for a length-delimited one
    (a string, bytes, or a nested message to decode in turn)."""
    buf = memoryview(buf)
    at, end = 0, len(buf)
    while at < end:
        key, at = _varint(buf, at)
        number, wire = key >> 3, key & 7
        if wire == 0:
            value, at = _varint(buf, at)
        elif wire == 2:
            size, at = _varint(buf, at)
            value = bytes(buf[at:at + size])
            at += size
        elif wire in (1, 5):
            width = 8 if wire == 1 else 4
            value = int.from_bytes(buf[at:at + width], "little")
            at += width
        else:
            raise ValueError(f"protobuf wire type {wire}")
        yield number, value


def _field(buf, number, default=b""):
    for n, v in _fields(buf):
        if n == number:
            return v
    return default


def _varints(packed: bytes):
    out, at = [], 0
    while at < len(packed):
        value, at = _varint(packed, at)
        out.append(value)
    return out


def _owner(own: str, inner) -> str:
    """The ``op_name`` a fusion is read under, from its fused
    instructions ``[(opcode, op_name)]``.  The fusion's own is that of
    ONE of them, often a stray (a weight's AdamW update fused behind its
    gradient's matrix product reads as either): so the matrix product's
    where it holds one (that is where its time goes), else the named
    scopes most of its instructions share, else its own."""
    named = [(opcode, op) for opcode, op in inner if op]
    for opcode, op in named:
        if opcode in ("convolution", "dot"):
            return op
    votes = {}
    for _, op in named:
        votes.setdefault(scopes_of(op), []).append(op)
    if not votes:
        return own
    return max(votes.values(), key=len)[0]


def hlo_scopes(xplane_path: str) -> dict:
    """``{program: {instruction: scopes}}`` from the HLO protos of plane
    ``/host:metadata`` (one event metadata a program, named as on the
    ``XLA Modules`` line, with the ``HloProto`` as a bytes stat).
    ``scopes`` is ``scopes_of`` the instruction's ``op_name`` (a
    fusion's: ``_owner``'s).  An instruction the compiler made itself
    has no ``op_name`` (a copy of a buffer that is not donated): it
    takes that of the instruction that uses its result; one of the
    program's own outside every scope stays outside.  Field numbers: ``XSpace.planes`` 1; ``XPlane.name`` 2,
    ``.event_metadata`` 4 (a map: value 2); ``XEventMetadata.name`` 2,
    ``.stats`` 5; ``XStat.bytes_value`` 6; ``HloProto.hlo_module`` 1;
    ``HloModuleProto.computations`` 3; ``HloComputationProto
    .instructions`` 2, ``.id`` 5; ``HloInstructionProto.name`` 1,
    ``.opcode`` 2, ``.metadata`` 7, ``.id`` 35, ``.operand_ids`` 36 and
    ``.called_computation_ids`` 38 (both packed);
    ``OpMetadata.op_name`` 2."""
    with open(xplane_path, "rb") as f:
        space = f.read()
    out = {}
    for n, plane in _fields(space):
        if n != 1 or _field(plane, 2) != b"/host:metadata":
            continue
        for n, entry in _fields(plane):
            if n != 4:
                continue
            meta = _field(entry, 2)
            program = out.setdefault(
                program_name(_field(meta, 2).decode()), {})
            for n, stat in _fields(meta):
                if n == 5:
                    program.update(_module_scopes(
                        _field(_field(stat, 6), 1)))
    return out


def _module_scopes(module: bytes) -> dict:
    computations = {}   # id -> [(name, opcode, op_name, id, operands, calls)]
    for n, computation in _fields(module):
        if n == 3:
            computations[_field(computation, 5, 0)] = [
                (_field(ins, 1).decode(), _field(ins, 2).decode(),
                 _field(_field(ins, 7), 2).decode(), _field(ins, 35, 0),
                 _varints(_field(ins, 36)), _varints(_field(ins, 38)))
                for n, ins in _fields(computation) if n == 2]
    out = {}
    for instructions in computations.values():
        resolved, user = {}, {}     # id -> (name, op_name); id -> its user's
        for name, opcode, op_name, uid, operands, calls in instructions:
            if opcode == "fusion":
                op_name = _owner(op_name, [
                    (o, op) for c in calls
                    for _, o, op, *_ in computations.get(c, ())])
            resolved[uid] = (name, op_name)
            for operand in operands:
                user.setdefault(operand, uid)
        for uid, (name, op_name) in resolved.items():
            at = uid
            for _ in range(4):      # a copy-start's user is its copy-done
                if op_name or at not in user:
                    break
                at = user[at]
                op_name = resolved[at][1]
            out[name] = scopes_of(op_name)
    return out


# ------------------------------------------------------------------ names
def program_name(module_event: str) -> str:
    """``jit_paged_decode_step(3199895713881727950)`` ->
    ``paged_decode_step``."""
    m = _MODULE.match(module_event)
    return m.group(1) if m else module_event


def instruction_name(op_event: str) -> str:
    """``%fusion.481 = pred[...] fusion(...)`` -> ``fusion.481``."""
    return op_event.split(" = ", 1)[0].lstrip("%")


def scopes_of(op_name: str) -> tuple:
    """The named scopes of an ``op_name``, outermost first:
    ``jit(train_step)/attn_qkv/transpose(jvp())/dot_general`` ->
    ``("attn_qkv",)``.  The leading ``jit(...)`` and the trailing
    primitive go; a transform's wrapper goes and what it wraps stays."""
    out = []
    # (instructions the compiler merged carry their names joined by ";")
    for part in op_name.split(";")[0].split("/")[1:-1]:
        while True:
            m = _TRANSFORM.match(part)
            if not m:
                break
            part = m.group(1)
        if part:
            out.append(part)
    return tuple(out)


# ----------------------------------------------------------------- events
def read_events(xplane_path: str):
    """``(plane, line, name, start_ns, dur_ns, stats)`` of every event
    this module or ``trace_reduce`` reads: device operations and program
    runs under their full names, host spans of the benchmark and of the
    program with their stats."""
    from jax.profiler import ProfileData

    out = []
    for plane in ProfileData.from_file(xplane_path).planes:
        device = plane.name.startswith("/device:TPU:")
        for line in plane.lines:
            if device and line.name not in ("XLA Ops", "XLA Modules"):
                continue
            for ev in line.events:
                name, stats = ev.name, None
                if not device:
                    if trace_reduce.PROGRAM_SPAN.match(name):
                        stats = {k: v for k, v in ev.stats}
                    elif not name.startswith(
                            trace_reduce.HOST_SPAN_PREFIX):
                        continue
                out.append((plane.name, line.name, name,
                            int(ev.start_ns), int(ev.duration_ns), stats))
    return out


class ProgramTrace:
    """The reduction; every number in seconds, inside the slice."""

    def __init__(self, events, scopes=None):
        """``events`` as ``read_events`` gives them; ``scopes`` as
        ``hlo_scopes`` does (without it no operation has a scope)."""
        scopes = scopes or {}
        reduced = trace_reduce.reduce_trace(
            [(p, ln, trace_reduce.short_name(n)
              if trace_reduce.is_device_op(p, ln) else n, s, d)
             for p, ln, n, s, d, _ in events])
        if reduced is None:
            raise ValueError("no device operation in the trace")
        self.busy_s, self.window_s = reduced["busy_s"], reduced["window_s"]
        self.devices = reduced["devices"]
        bench = [(s, s + d) for p, ln, n, s, d, _ in events
                 if n.startswith(trace_reduce.HOST_SPAN_PREFIX)
                 and not p.startswith("/device:")]
        ops = {}
        for p, ln, n, s, d, _ in events:
            if trace_reduce.is_device_op(p, ln):
                ops.setdefault(p, []).append((s, s + d, n))
        first_op = min(s for v in ops.values() for s, _, _ in v)
        # the slice, as ``trace_reduce.reduce_trace`` cuts it
        if bench:
            lo = max(min(s for s, _ in bench), first_op)
            hi = max(e for _, e in bench)
        else:
            lo, hi = first_op, max(e for v in ops.values() for _, e, _ in v)
        self.lo, self.hi = lo, hi
        assert abs((hi - lo) * _NS - self.window_s) < 1e-9

        self.spans = sorted(
            (s, s + d, n, stats) for p, ln, n, s, d, stats in events
            if stats is not None and s + d > lo and s < hi)
        first = sorted(ops)[0]
        self.idle_by_span = {
            name: ns * _NS for name, ns in trace_reduce.charge(
                trace_reduce._gaps([(s, e) for s, e, _ in ops[first]],
                                   lo, hi),
                trace_reduce.innermost(self.spans), OUTSIDE).items()}
        self.program_runs = {}      # program -> [seconds], first device
        self.ops_by_scope = {}      # (program, scopes, short name) -> s
        self._by_scope = {}         # (plane, scope) -> [(start, end)]
        for plane, plane_ops in ops.items():
            runs = sorted((s, s + d, program_name(n))
                          for p, ln, n, s, d, _ in events
                          if p == plane and ln == "XLA Modules")
            if plane == first:
                for s, e, program in runs:
                    if s >= lo and e <= hi:         # whole runs only
                        self.program_runs.setdefault(program, []).append(
                            (e - s) * _NS)
            starts = [s for s, _, _ in runs]
            for s, e, n in plane_ops:
                if e <= lo or s >= hi:
                    continue
                # an operation belongs to the program whose run holds it
                i = bisect.bisect_right(starts, s) - 1
                program = runs[i][2] if i >= 0 and s < runs[i][1] else ""
                under = scopes.get(program, {}).get(instruction_name(n), ())
                s, e = max(s, lo), min(e, hi)
                for scope in under:
                    self._by_scope.setdefault((plane, scope), []).append(
                        (s, e))
                key = (program, "/".join(under),
                       trace_reduce.short_name(n))
                self.ops_by_scope[key] = self.ops_by_scope.get(key, 0.0) \
                    + (e - s) * _NS / len(ops)

    def scope_seconds(self, scope: str) -> float:
        """Device time of the operations under the named scope (as
        ``hlo_scopes`` reads them: each operation under one ``op_name``),
        averaged over the devices (a union: a ``while`` holds its body's
        operations)."""
        return sum(union_seconds(v) for (_, name), v in self._by_scope.items()
                   if name == scope) * _NS / self.devices


# ------------------------------------------------------------------- load
_loaded = {}        # path -> ProgramTrace or None


def load(run):
    """The ``ProgramTrace`` of the trace this run wrote
    (``run["trace_path"]``), parsed once a process; ``None`` when the
    run was not traced or its trace holds no device operation."""
    path = run.get("trace_path")
    if not path or not os.path.isfile(path):
        return None
    if path not in _loaded:
        try:
            _loaded[path] = ProgramTrace(read_events(path), hlo_scopes(path))
        except ValueError:
            _loaded[path] = None
    return _loaded[path]


# ---------------------------------------------------------------- readers
# (each takes the names it reads from the layer metric that calls it)
def program_ms(run, program: str):
    """Median device milliseconds of one whole run of ``program`` inside
    the slice; ``None`` without this run's trace or without such a run."""
    trace = load(run)
    runs = trace.program_runs.get(program) if trace else None
    return 1e3 * percentile(runs, 50) if runs else None


def scope_pct(run, scope: str):
    """Device time under the named ``scope`` (``scope_seconds``) as a
    share of busy time, in percent; ``None`` where the trace holds no
    such scope."""
    trace = load(run)
    seconds = trace.scope_seconds(scope) if trace else 0.0
    return 100.0 * seconds / trace.busy_s if seconds else None


def idle_pct_inside(run, spans):
    """Idle time of the device while the host was inside one of the
    program's ``spans``, as a share of the slice, in percent; ``None``
    where the trace holds none of the program's spans at all."""
    trace = load(run)
    if trace is None or not trace.spans:
        return None
    idle = sum(trace.idle_by_span.get(name, 0.0) for name in spans)
    return 100.0 * idle / trace.window_s


def main(argv):
    """``python -m benchmarks.harness.program_trace [xplane.pb]``: the
    tables PERF.md section 5 is written from."""
    path = argv[1] if len(argv) > 1 else trace_reduce.newest_xplane(
        os.path.join(cells.REPO_ROOT, ".bench_trace"))
    t = ProgramTrace(read_events(path), hlo_scopes(path))
    idle = t.window_s - t.busy_s
    print(f"{path}\nslice {t.window_s:.6f} s, busy {t.busy_s:.6f} s, "
          f"idle {idle:.6f} s ({100 * idle / t.window_s:.3f} %)")
    print("idle gaps by program span (s, % of slice):")
    for name, s in sorted(t.idle_by_span.items(), key=lambda kv: -kv[1]):
        print(f"  {name:28s} {s:.6f} {100 * s / t.window_s:7.3f}")
    print("host spans (calls, total s, median ms):")
    by = {}
    for s, e, n, _ in t.spans:
        by.setdefault(n, []).append((e - s) * _NS)
    for n, ds in sorted(by.items(), key=lambda kv: -sum(kv[1])):
        print(f"  {n:28s} {len(ds):5d} {sum(ds):.6f} "
              f"{1e3 * sorted(ds)[len(ds) // 2]:9.3f}")
    print("program runs (whole runs in the slice, median ms):")
    for n, ds in t.program_runs.items():
        print(f"  {n:28s} {len(ds):5d} {1e3 * sorted(ds)[len(ds) // 2]:9.3f}")
    print("device time by program (s; sums of its operations):")
    by = {}
    for (program, _, _), s in t.ops_by_scope.items():
        by[program] = by.get(program, 0.0) + s
    for program, s in sorted(by.items(), key=lambda kv: -kv[1]):
        print(f"  {program or '-':28s} {s:.6f}")
    print("device time by program, scope and operation "
          "(s, % of busy; sums, so a while counts its body twice):")
    for (program, scope, op), s in sorted(
            t.ops_by_scope.items(), key=lambda kv: -kv[1])[:40]:
        print(f"  {program:22s} {scope or '-':30s} {op:40s} {s:.6f} "
              f"{100 * s / t.busy_s:6.2f}")
    return 0


if __name__ == "__main__":
    import sys

    sys.exit(main(sys.argv))
