"""Jaxpr-level program X-ray: static cost, memory, donation, and
sharding-readiness analysis.

The AST scanners in :mod:`paddle_tpu.analysis.hazards` see *source*; this
module sees the *traced program*.  "Operator Fusion in XLA: Analysis and
Evaluation" (PAPERS.md) shows fusion/TPU wins are governed by the
arithmetic intensity of the ops around each kernel, and the remaining
ROADMAP items (mesh sharding, fused paged attention) all need per-op
FLOP/byte facts the AST cannot produce.  So: trace any registered step
to a jaxpr and walk it.

What :func:`analyze` produces (a :class:`ProgramReport`):

- **per-primitive FLOP/byte cost model** — dot_general from its
  contraction dims, conv from kernel volume, gathers/scatters and
  elementwise from element counts; bytes are operand+result sizes.
- **roofline classification** — each primitive's aggregate arithmetic
  intensity (FLOP/byte) against the chip's ridge point
  (peak FLOPs / HBM bandwidth): ``compute``- or ``memory``-bound.
- **peak-live-HBM** — a linear-scan liveness walk over the jaxpr
  (invars/constvars live from entry to last use, eqn outvars from
  definition to last use, program outputs through the end; call-like
  eqns contribute their inner peak as a transient), gated against a
  configurable per-chip HBM budget (**H110** ERROR when exceeded).

Jaxpr-level hazards (Diagnostic codes continue hazards.py's space):

- **H108 missing-donation** (WARNING) — a large undonated input whose
  shape/dtype matches an output: XLA must double-buffer it, costing its
  full size in HBM.  Train steps donate state via ``jit.to_static``
  (donate_argnums=(0,)) and serving steps donate the paged KV pool
  they return (``models/generation.py::cached_step(..., donate=)``):
  both audit clean, so an H108 on either is a regression.
- **H109 host round-trip in compiled region** (ERROR; ``debug_callback``
  WARNING) — ``pure_callback``/``io_callback``/``outside_call``
  primitives found ANYWHERE in the jaxpr: a device→host→device round
  trip per execution that no amount of fusion can hide.  This is the
  traced-program superset of AST H102/H106 — it sees through helper
  indirection the source scan cannot.
- **H103 f64 in traced program** (ERROR) — an equation producing
  float64/complex128: software-emulated on TPU (same code as the AST
  scan; this half catches dtypes built out of sight of the source).

Sharding readiness (S201–S204, :func:`check_sharding_readiness`):
validates a ``{param_role: PartitionSpec}`` layout dict against an
abstract mesh ``{axis: size}`` and the parameter shapes — unknown mesh
axis (S201), duplicate axis within one spec (S202), spec rank exceeding
the param rank (S203), dimension not divisible by the product of its
mesh axes (S204) — so the upcoming ``paddle_tpu.distributed`` mesh PR
lands against a verifier that already exists.
"""
from __future__ import annotations

import dataclasses
import functools
import math
from typing import Any, Dict, List, Optional, Sequence, Tuple

import jax
import numpy as np
from jax.extend.core import Literal

from .verifier import ERROR, WARNING, Diagnostic

__all__ = [
    "ChipProfile",
    "CHIPS",
    "OpCost",
    "ProgramReport",
    "analyze",
    "analyze_train_step",
    "audit_default_steps",
    "check_sharding_readiness",
    "estimate_collective_time",
    "estimate_compute_time",
    "export_report_gauges",
]


# ---------------------------------------------------------------------------
# chip roofline profiles
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class ChipProfile:
    """Peak compute / memory figures for the roofline ridge point.

    Public per-chip specs (bf16 peak, HBM bandwidth, HBM capacity);
    ``cpu`` is a deliberately modest dev-box stand-in so CPU CI still
    exercises the classification logic.
    """

    name: str
    peak_flops: float        # FLOP/s (bf16)
    hbm_bandwidth: float     # bytes/s
    hbm_bytes: int           # capacity per chip
    ici_bandwidth: float = 1e11   # bytes/s per chip over the interconnect
    ici_latency: float = 1e-6     # per-collective launch latency, seconds
    dcn_bandwidth: float = 6.25e9  # bytes/s per chip over the data-center net
    dcn_latency: float = 1e-5      # per-collective DCN launch latency, seconds

    @property
    def ridge(self) -> float:
        """Arithmetic intensity (FLOP/byte) where the roofline bends."""
        return self.peak_flops / self.hbm_bandwidth


# ICI figures are aggregate per-chip interconnect bandwidth from the
# public Cloud TPU system-architecture pages: v4 has 6 links x 50 GB/s
# (3D torus, 2400 Gbps aggregate); v5e 4 links x 400 Gbps (1600 Gbps,
# 2D torus); v5p 4800 Gbps over 6 links (3D torus); v6e (Trillium)
# 3584 Gbps over 4 links.  Latency is the one-hop launch overhead, order
# 1 us on real ICI.  "cpu" is loopback shared memory on the dev box —
# fast and near-zero-latency so CPU CI classifies the tiny model as
# compute-heavy the way a real topology-free single host would.
#
# DCN figures are the per-chip share of the host NIC from the public
# multislice / system-architecture pages: v4 and v5e hosts carry
# 100–200 Gbps NICs over 4 chips, v5p and v6e (Trillium) quote 400 Gbps
# per host.  DCN latency is cross-host (order 10 us), an order of
# magnitude above one ICI hop — the multi-host planner prices DCN edges
# from these instead of needing another CHIPS schema change.  "cpu"'s
# DCN, like its ICI, is loopback: an emulated multi-host topology on
# one dev box crosses no real NIC, and CPU CI must classify the tiny
# model the way the real chips would (compute-bound when the layout is
# sane) while keeping DCN strictly slower than ICI so the level split
# stays visible in every report.
CHIPS: Dict[str, ChipProfile] = {
    "v4": ChipProfile("v4", 275e12, 1228e9, 32 << 30, 300e9, 1e-6,
                      6.25e9, 1e-5),
    "v5e": ChipProfile("v5e", 197e12, 819e9, 16 << 30, 200e9, 1e-6,
                       3.125e9, 1e-5),
    "v5p": ChipProfile("v5p", 459e12, 2765e9, 95 << 30, 600e9, 1e-6,
                       12.5e9, 1e-5),
    "v6e": ChipProfile("v6e", 918e12, 1640e9, 32 << 30, 448e9, 1e-6,
                       12.5e9, 1e-5),
    "cpu": ChipProfile("cpu", 5e11, 50e9, 8 << 30, 200e9, 0.0,
                       25e9, 2e-7),
}


def estimate_compute_time(flops: float, bytes_moved: float,
                          chip: ChipProfile) -> float:
    """Roofline step-time estimate: the max of the compute-bound and
    memory-bound times.  Shared by the xray summary and shardplan's S207
    so compute-vs-comm classification is consistent between the two."""
    return max(flops / chip.peak_flops,
               bytes_moved / chip.hbm_bandwidth)


def estimate_collective_time(bytes_on_wire: float,
                             chip: ChipProfile,
                             level: str = "ici") -> float:
    """Time for one collective that puts ``bytes_on_wire`` on each
    chip's links (ring-formula bytes, computed by the caller).
    ``level`` selects the link profile: ``"ici"`` (intra-host, the
    default — single-host plans never say otherwise) or ``"dcn"``
    (cross-host phases of a hierarchically decomposed collective)."""
    if level == "dcn":
        return bytes_on_wire / chip.dcn_bandwidth + chip.dcn_latency
    return bytes_on_wire / chip.ici_bandwidth + chip.ici_latency


# ---------------------------------------------------------------------------
# sizes and helpers
# ---------------------------------------------------------------------------

def _aval_bytes(aval) -> int:
    shape = getattr(aval, "shape", None)
    dtype = getattr(aval, "dtype", None)
    if shape is None or dtype is None:
        return 0  # tokens / effects / abstract non-arrays
    try:
        return int(np.prod(shape, dtype=np.int64)) * np.dtype(dtype).itemsize
    except TypeError:
        return 0


def _var_bytes(v) -> int:
    if isinstance(v, Literal):
        return 0  # inlined scalar constants
    return _aval_bytes(v.aval)


def _elems(aval) -> int:
    shape = getattr(aval, "shape", None)
    if shape is None:
        return 0
    return int(np.prod(shape, dtype=np.int64))


# call-like primitives and where their sub-jaxprs live; validated
# against jax 0.9.0 primitive params (jit carries a ClosedJaxpr,
# custom_* carry call_jaxpr, scan multiplies by its trip count)
_TRANSCENDENTAL = {
    "exp", "log", "log1p", "expm1", "tanh", "sin", "cos", "tan",
    "erf", "erfc", "erf_inv", "logistic", "pow", "cbrt", "atan2",
    "digamma", "lgamma",
}
# pure data movement: 0 FLOPs, bytes still counted
_MOVEMENT = {
    "broadcast_in_dim", "reshape", "transpose", "squeeze", "rev",
    "slice", "dynamic_slice", "dynamic_update_slice", "gather",
    "scatter", "concatenate", "pad", "iota", "copy", "device_put",
    "convert_element_type", "bitcast_convert_type", "select_n",
    "stop_gradient", "split", "expand_dims",
}
_CALLBACKS = {
    "pure_callback": ERROR,
    "io_callback": ERROR,
    "outside_call": ERROR,
    "debug_callback": WARNING,
    "debug_print": WARNING,        # what jax.debug.print traces to
}


def _sub_jaxprs(eqn):
    """Yield (inner open jaxpr, static trip multiplier) for call-like
    equations.  ``cond`` yields every branch (cost walk takes the max;
    liveness takes the max transient)."""
    name = eqn.primitive.name
    params = eqn.params
    if name == "pallas_call":
        # a priced LEAF, not a call: the kernel body jaxpr under
        # params["jaxpr"] is per-BLOCK code — walking it would charge
        # one grid cell as if it were the whole op.  The kernel's cost
        # comes from the kernels.costs registry (or its own
        # CostEstimate) in _eqn_flops/_eqn_bytes.
        return []
    if name == "cond":
        return [(b.jaxpr, 1) for b in params["branches"]]
    if name == "while":
        return [(params["cond_jaxpr"].jaxpr, 1),
                (params["body_jaxpr"].jaxpr, 1)]
    if name == "scan":
        return [(params["jaxpr"].jaxpr, int(params.get("length", 1)))]
    # custom_vjp_call_jaxpr keeps its primal body under ``fun_jaxpr``
    # (custom_jvp uses call_jaxpr) — without it the analyzers are blind
    # to anything wrapped for a hand-written backward, e.g. moe_dispatch
    for key in ("jaxpr", "call_jaxpr", "fun_jaxpr"):
        inner = params.get(key)
        if inner is not None:
            inner = getattr(inner, "jaxpr", inner)  # Closed -> open
            return [(inner, 1)]
    return []


def _is_call_like(eqn) -> bool:
    return bool(_sub_jaxprs(eqn))


# ---------------------------------------------------------------------------
# FLOP model
# ---------------------------------------------------------------------------

def _dot_flops(eqn) -> float:
    ((lc, rc), (lb, _rb)) = eqn.params["dimension_numbers"]
    lhs = eqn.invars[0].aval.shape
    rhs = eqn.invars[1].aval.shape
    batch = int(np.prod([lhs[i] for i in lb], dtype=np.int64)) if lb else 1
    k = int(np.prod([lhs[i] for i in lc], dtype=np.int64)) if lc else 1
    m = int(np.prod([lhs[i] for i in range(len(lhs))
                     if i not in tuple(lc) + tuple(lb)], dtype=np.int64))
    n = int(np.prod([rhs[i] for i in range(len(rhs))
                     if i not in tuple(rc) + tuple(_rb)], dtype=np.int64))
    return 2.0 * batch * m * n * k


def _conv_flops(eqn) -> float:
    out = eqn.outvars[0].aval
    rhs = eqn.invars[1].aval
    dn = eqn.params.get("dimension_numbers")
    out_feature_dim = dn.rhs_spec[0] if dn is not None else 0
    kernel_elems = _elems(rhs)
    out_ch = rhs.shape[out_feature_dim] if rhs.shape else 1
    # per output element: one MAC per kernel tap feeding it
    per_out = kernel_elems / max(1, out_ch)
    return 2.0 * _elems(out) * per_out


def _pallas_kernel_name(eqn) -> str:
    """The kernel's ``name=`` as it appears in the cost registry."""
    info = eqn.params.get("name_and_src_info")
    name = getattr(info, "name", None) or eqn.params.get("name")
    return str(name) if name else "unnamed"


def _pallas_cost(eqn):
    """Registered KernelCost for a pallas_call eqn, else the kernel's
    own CostEstimate param, else None (generic pricing)."""
    from ..kernels.costs import price_eqn_avals

    in_avals = [(tuple(v.aval.shape), str(v.aval.dtype))
                for v in eqn.invars
                if not isinstance(v, Literal)]
    out_avals = [(tuple(v.aval.shape), str(v.aval.dtype))
                 for v in eqn.outvars]
    cost = price_eqn_avals(_pallas_kernel_name(eqn), in_avals, out_avals)
    if cost is not None:
        return cost
    est = eqn.params.get("cost_estimate")
    if est is not None and getattr(est, "bytes_accessed", 0):
        return est
    return None


def _eqn_flops(eqn) -> float:
    name = eqn.primitive.name
    if name == "pallas_call":
        cost = _pallas_cost(eqn)
        if cost is not None:
            return float(cost.flops)
    if name == "dot_general":
        return _dot_flops(eqn)
    if name == "conv_general_dilated":
        return _conv_flops(eqn)
    if name in _MOVEMENT:
        return 0.0
    in_elems = max((_elems(v.aval) for v in eqn.invars
                    if not isinstance(v, Literal)), default=0)
    out_elems = max((_elems(v.aval) for v in eqn.outvars), default=0)
    if name in ("sort", "top_k"):
        n = max(in_elems, 1)
        return n * max(1.0, math.log2(n))
    if name.startswith(("reduce_", "cum", "arg")):
        return float(in_elems)
    if name in _TRANSCENDENTAL:
        # several fused hardware ops per element; a fixed weight keeps
        # the model honest about transcendental-heavy regions without
        # pretending to cycle accuracy
        return 10.0 * float(max(in_elems, out_elems))
    return float(max(in_elems, out_elems))


def _eqn_bytes(eqn) -> float:
    if eqn.primitive.name == "pallas_call":
        cost = _pallas_cost(eqn)
        if cost is not None:
            # the registered/declared traffic model — e.g. paged decode
            # reads the pool THROUGH the block table, so its bytes are
            # the gathered context, not the whole pool operand
            return float(cost.bytes_accessed)
    return float(sum(_var_bytes(v) for v in eqn.invars)
                 + sum(_var_bytes(v) for v in eqn.outvars))


# ---------------------------------------------------------------------------
# recursive cost walk
# ---------------------------------------------------------------------------

def _collect_costs(jaxpr, mul: float, acc: Dict[str, List[float]]):
    for eqn in jaxpr.eqns:
        subs = _sub_jaxprs(eqn)
        if subs:
            if eqn.primitive.name == "cond":
                # branches are exclusive: charge the most expensive one
                best, best_cost = None, -1.0
                for inner, m in subs:
                    trial: Dict[str, List[float]] = {}
                    _collect_costs(inner, mul * m, trial)
                    cost = sum(v[0] for v in trial.values())
                    if cost > best_cost:
                        best, best_cost = trial, cost
                for k, (f, b, c) in (best or {}).items():
                    cur = acc.setdefault(k, [0.0, 0.0, 0.0])
                    cur[0] += f
                    cur[1] += b
                    cur[2] += c
            else:
                for inner, m in subs:
                    _collect_costs(inner, mul * m, acc)
            continue
        key = eqn.primitive.name
        if key == "pallas_call":
            # per-kernel row so the fused steps read as their kernels,
            # not one anonymous pallas bucket
            key = f"pallas_call:{_pallas_kernel_name(eqn)}"
        cur = acc.setdefault(key, [0.0, 0.0, 0.0])
        cur[0] += mul * _eqn_flops(eqn)
        cur[1] += mul * _eqn_bytes(eqn)
        cur[2] += mul


def _count_eqns(jaxpr) -> int:
    n = 0
    for eqn in jaxpr.eqns:
        subs = _sub_jaxprs(eqn)
        n += sum(_count_eqns(inner) for inner, _ in subs) if subs else 1
    return n


# ---------------------------------------------------------------------------
# liveness walk (peak HBM)
# ---------------------------------------------------------------------------

def _var_dtype(v) -> str:
    dt = getattr(getattr(v, "aval", None), "dtype", None)
    return str(dt) if dt is not None else "opaque"


def _peak_live_by_dtype(jaxpr, var_bytes=_var_bytes
                        ) -> Tuple[int, Dict[str, int]]:
    """Linear-scan liveness over one open jaxpr: a var is live from its
    definition (entry for invars/constvars) to its last use (program end
    for outputs).  Call-like eqns add ``inner_peak - boundary`` as a
    transient — the inner program's scratch beyond what the caller
    already accounts for at the call boundary.

    Returns ``(peak_bytes, {dtype: bytes held at the peak})`` — the
    breakdown is a snapshot of the live set when the peak is reached
    (call-like transients attributed by the inner program's own dtype
    mix beyond the boundary), so int8/fp8 KV or weight buffers show up
    as their own line instead of vanishing into one total.

    ``var_bytes`` maps a jaxpr var (or Literal) to its byte size;
    shardplan passes a shard-aware callback that divides each buffer by
    its shard count, turning this same walk into *per-chip* peak HBM."""
    n = len(jaxpr.eqns)
    last_use: Dict[Any, int] = {}
    for i, eqn in enumerate(jaxpr.eqns):
        for v in eqn.invars:
            if not isinstance(v, Literal):
                last_use[v] = i
    for v in jaxpr.outvars:
        if not isinstance(v, Literal):
            last_use[v] = n  # live through the end
    live: Dict[Any, int] = {}
    by_dtype: Dict[str, int] = {}

    def _add(v):
        b = var_bytes(v)
        live[v] = b
        if b:
            dt = _var_dtype(v)
            by_dtype[dt] = by_dtype.get(dt, 0) + b
        return b

    def _drop(v):
        b = live.pop(v)
        if b:
            dt = _var_dtype(v)
            rem = by_dtype.get(dt, 0) - b
            if rem > 0:
                by_dtype[dt] = rem
            else:
                by_dtype.pop(dt, None)
        return b

    for v in tuple(jaxpr.invars) + tuple(jaxpr.constvars):
        _add(v)
    current = sum(live.values())
    peak = current
    snap = dict(by_dtype)
    for i, eqn in enumerate(jaxpr.eqns):
        for v in eqn.outvars:
            if v not in live:
                current += _add(v)
        transient = 0
        extra_bd: Dict[str, int] = {}
        subs = _sub_jaxprs(eqn)
        if subs:
            boundary = (sum(var_bytes(v) for v in eqn.invars)
                        + sum(var_bytes(v) for v in eqn.outvars))
            inner_peak, inner_bd = -1, {}
            for inner, _ in subs:
                ip, ibd = _peak_live_by_dtype(inner, var_bytes)
                if ip > inner_peak:
                    inner_peak, inner_bd = ip, ibd
            transient = max(0, inner_peak - boundary)
            if transient > 0:
                # attribute the scratch beyond the boundary by the inner
                # program's dtype mix (minus what the boundary already
                # holds per dtype), rescaled to sum to the transient
                bound_bd: Dict[str, int] = {}
                for v in tuple(eqn.invars) + tuple(eqn.outvars):
                    b = var_bytes(v)
                    if b:
                        dt = _var_dtype(v)
                        bound_bd[dt] = bound_bd.get(dt, 0) + b
                extra = {dt: max(0, b - bound_bd.get(dt, 0))
                         for dt, b in inner_bd.items()}
                s = sum(extra.values())
                if s > 0:
                    extra_bd = {dt: int(round(b * transient / s))
                                for dt, b in extra.items() if b}
                else:
                    extra_bd = {"opaque": transient}
        if current + transient > peak:
            peak = current + transient
            snap = dict(by_dtype)
            for dt, b in extra_bd.items():
                snap[dt] = snap.get(dt, 0) + b
        for v in tuple(eqn.invars) + tuple(eqn.outvars):
            if isinstance(v, Literal):
                continue
            if last_use.get(v, -1) <= i and v in live:
                current -= _drop(v)
    return peak, snap


def _peak_live_bytes(jaxpr, var_bytes=_var_bytes) -> int:
    """Peak-only view of :func:`_peak_live_by_dtype` (same walk)."""
    return _peak_live_by_dtype(jaxpr, var_bytes)[0]


# ---------------------------------------------------------------------------
# hazards over the traced program
# ---------------------------------------------------------------------------

def _scan_callbacks(jaxpr, diags: List[Diagnostic], where: str):
    for eqn in jaxpr.eqns:
        name = eqn.primitive.name
        if name in _CALLBACKS:
            diags.append(Diagnostic(
                "H109", _CALLBACKS[name],
                f"'{name}' inside the compiled program: a device→host→"
                "device round trip EVERY execution — XLA cannot fuse or "
                "overlap across it.  Hoist the host work outside the "
                "step (this is the traced-program form of H102/H106; it "
                "sees through helper indirection)", where))
        for inner, _ in _sub_jaxprs(eqn):
            _scan_callbacks(inner, diags, where)


def _scan_f64(jaxpr, diags: List[Diagnostic], where: str):
    for eqn in jaxpr.eqns:
        subs = _sub_jaxprs(eqn)
        if subs:
            for inner, _ in subs:
                _scan_f64(inner, diags, where)
            continue
        for v in eqn.outvars:
            dt = getattr(v.aval, "dtype", None)
            if dt is not None and str(dt) in ("float64", "complex128"):
                diags.append(Diagnostic(
                    "H103", ERROR,
                    f"'{eqn.primitive.name}' produces {dt} inside the "
                    "traced program: TPUs have no native f64 — this op "
                    "(and everything fused with it) runs software-"
                    "emulated", where))


def _scan_donation(jaxpr, donated: Sequence[bool], min_bytes: int,
                   diags: List[Diagnostic], where: str):
    """H108: an undonated input whose shape/dtype matches an output that
    is not the input itself — XLA must keep both alive (double-buffered
    HBM for its full size)."""
    out_pool: List[Any] = [v for v in jaxpr.outvars
                           if not isinstance(v, Literal)]
    for i, v in enumerate(jaxpr.invars):
        if i < len(donated) and donated[i]:
            continue
        size = _var_bytes(v)
        if size < min_bytes:
            continue
        aval = v.aval
        match = None
        for o in out_pool:
            if o is v:
                continue  # passed straight through: aliasing is free
            if (getattr(o.aval, "shape", None) == aval.shape
                    and getattr(o.aval, "dtype", None) == aval.dtype):
                match = o
                break
        if match is not None:
            out_pool.remove(match)
            diags.append(Diagnostic(
                "H108", WARNING,
                f"input {i} ({tuple(aval.shape)} {aval.dtype}, "
                f"{size / 2**20:.1f} MiB) is not donated but an output "
                "of identical shape/dtype exists — XLA double-buffers "
                "it; donate the argument (jax.jit donate_argnums / "
                "jit.to_static state donation) so the output reuses the "
                "input's HBM", where))


# ---------------------------------------------------------------------------
# report
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class OpCost:
    """Aggregate cost of one primitive across the whole program."""

    primitive: str
    count: int
    flops: float
    bytes: float

    @property
    def intensity(self) -> float:
        return self.flops / self.bytes if self.bytes else 0.0

    def bound(self, chip: ChipProfile) -> str:
        return "compute" if self.intensity >= chip.ridge else "memory"


@dataclasses.dataclass
class ProgramReport:
    """Static X-ray of one traced step (see module docstring)."""

    name: str
    chip: ChipProfile
    flops: float
    bytes: float
    peak_hbm_bytes: int
    ops: List[OpCost]
    n_eqns: int
    donated: Tuple[bool, ...]
    hazards: List[Diagnostic]
    hbm_budget_bytes: Optional[int] = None
    # dtype -> bytes held when the liveness walk hits its peak; sums to
    # peak_hbm_bytes (groundwork for int8/fp8 KV accounting)
    peak_hbm_by_dtype: Dict[str, int] = dataclasses.field(
        default_factory=dict)

    @property
    def arithmetic_intensity(self) -> float:
        return self.flops / self.bytes if self.bytes else 0.0

    @property
    def compute_time_s(self) -> float:
        """Roofline single-chip step-time estimate (shared formula with
        shardplan's comm-vs-compute classification)."""
        return estimate_compute_time(self.flops, self.bytes, self.chip)

    def errors(self) -> List[Diagnostic]:
        return [d for d in self.hazards if d.severity == ERROR]

    def table(self, top: int = 12) -> str:
        """Roofline table: primitive, calls, MFLOPs, MiB, FLOP/B, bound
        (the README "Program X-ray" section documents the columns)."""
        rows = [f"{'primitive':<24}{'calls':>7}{'MFLOPs':>10}"
                f"{'MiB':>9}{'FLOP/B':>9}  bound"]
        for op in self.ops[:top]:
            rows.append(
                f"{op.primitive:<24}{op.count:>7.0f}"
                f"{op.flops / 1e6:>10.2f}{op.bytes / 2**20:>9.2f}"
                f"{op.intensity:>9.2f}  {op.bound(self.chip)}")
        return "\n".join(rows)

    def to_json(self) -> Dict[str, Any]:
        """Machine-readable report (``lint_tpu --xray --json``) —
        diagnostics use the same shape as shardplan's ``to_json``."""
        return {
            "name": self.name,
            "chip": self.chip.name,
            "flops": float(self.flops),
            "bytes": float(self.bytes),
            "arithmetic_intensity": float(self.arithmetic_intensity),
            "compute_time_s": float(self.compute_time_s),
            "peak_hbm_bytes": int(self.peak_hbm_bytes),
            "peak_hbm_by_dtype": {k: int(v) for k, v in
                                  self.peak_hbm_by_dtype.items()},
            "hbm_budget_bytes": (int(self.hbm_budget_bytes)
                                 if self.hbm_budget_bytes else None),
            "n_eqns": int(self.n_eqns),
            "donated": list(self.donated),
            "ops": [
                {"primitive": op.primitive, "count": int(op.count),
                 "flops": float(op.flops), "bytes": float(op.bytes),
                 "intensity": float(op.intensity),
                 "bound": op.bound(self.chip)}
                for op in self.ops],
            "diagnostics": [
                {"code": d.code, "severity": d.severity,
                 "message": d.message, "where": d.where}
                for d in self.hazards],
        }

    def summary(self) -> str:
        budget = (f" / budget {self.hbm_budget_bytes / 2**30:.2f} GiB"
                  if self.hbm_budget_bytes else "")
        return (f"[xray] {self.name}: {self.flops / 1e9:.3f} GFLOP, "
                f"{self.bytes / 2**30:.3f} GiB moved, intensity "
                f"{self.arithmetic_intensity:.2f} FLOP/B "
                f"(ridge {self.chip.ridge:.1f} @ {self.chip.name}, "
                f"ici {self.chip.ici_bandwidth / 1e9:.0f} GB/s), "
                f"est step {self.compute_time_s * 1e3:.3f} ms, "
                f"peak HBM {self.peak_hbm_bytes / 2**20:.2f} MiB{budget}, "
                f"{self.n_eqns} eqns, {len(self.hazards)} hazard(s)")


# ---------------------------------------------------------------------------
# entry points
# ---------------------------------------------------------------------------

def _as_abstract(x):
    v = getattr(x, "_value", x)  # paddle Tensor -> backing array
    if isinstance(x, jax.ShapeDtypeStruct):
        return x
    if hasattr(v, "shape") and hasattr(v, "dtype"):
        return jax.ShapeDtypeStruct(np.shape(v), np.dtype(v.dtype))
    return v


def _donated_mask(closed, abstract_args, donate_argnums) -> Tuple[bool, ...]:
    n_in = len(closed.jaxpr.invars)
    mask = [False] * n_in
    if donate_argnums:
        donate = set(donate_argnums)
        pos = 0
        for i, a in enumerate(abstract_args):
            leaves = len(jax.tree_util.tree_leaves(a))
            if i in donate:
                for j in range(pos, min(pos + leaves, n_in)):
                    mask[j] = True
            pos += leaves
    # a jitted step traces to ONE jit eqn that carries the real
    # donated_invars — trust it over the caller's donate_argnums
    eqns = closed.jaxpr.eqns
    if len(eqns) == 1 and eqns[0].primitive.name == "jit":
        flags = eqns[0].params.get("donated_invars")
        if flags is not None:
            by_var = {v: f for v, f in zip(eqns[0].invars, flags)
                      if not isinstance(v, Literal)}
            mask = [by_var.get(v, False) for v in closed.jaxpr.invars]
    return tuple(mask)


def analyze(step, abstract_args: Sequence[Any], *,
            name: Optional[str] = None,
            donate_argnums: Sequence[int] = (),
            chip: str = "v5e",
            hbm_budget_bytes: Optional[int] = None,
            min_donation_bytes: int = 1 << 20) -> ProgramReport:
    """X-ray ``step`` (a jitted or plain function) called with
    ``abstract_args`` (ShapeDtypeStructs, arrays, Tensors, or pytrees of
    them — values are never computed, only shapes).  Returns a
    :class:`ProgramReport`; raises nothing on hazards (callers gate on
    ``report.errors()``)."""
    fn = step
    if hasattr(fn, "_fn") and hasattr(fn, "compiles"):
        fn = fn._fn  # observability track_compiles/warn_on_retrace wrapper
    args = [jax.tree_util.tree_map(_as_abstract, a,
                                   is_leaf=lambda x: hasattr(x, "_value"))
            for a in abstract_args]
    closed = jax.make_jaxpr(fn)(*args)
    donated = _donated_mask(closed, args, donate_argnums)
    return analyze_jaxpr(
        closed, donated=donated,
        name=name or getattr(step, "__name__", "<step>"), chip=chip,
        hbm_budget_bytes=hbm_budget_bytes,
        min_donation_bytes=min_donation_bytes)


def analyze_jaxpr(closed, *, donated: Sequence[bool] = (),
                  name: str = "<jaxpr>", chip: str = "v5e",
                  hbm_budget_bytes: Optional[int] = None,
                  min_donation_bytes: int = 1 << 20) -> ProgramReport:
    """The jaxpr-in half of :func:`analyze` — use when the trace came
    from elsewhere (``StaticFunction.trace_jaxpr``, ``jax.make_jaxpr``)."""
    profile = CHIPS[chip] if isinstance(chip, str) else chip
    jaxpr = closed.jaxpr
    acc: Dict[str, List[float]] = {}
    _collect_costs(jaxpr, 1.0, acc)
    ops = sorted((OpCost(k, int(c), f, b) for k, (f, b, c) in acc.items()),
                 key=lambda o: (-o.flops, -o.bytes, o.primitive))
    diags: List[Diagnostic] = []
    where = f"xray:{name}"
    _scan_callbacks(jaxpr, diags, where)
    _scan_f64(jaxpr, diags, where)
    donated = tuple(donated) or (False,) * len(jaxpr.invars)
    _scan_donation(jaxpr, donated, min_donation_bytes, diags, where)
    peak, peak_by_dtype = _peak_live_by_dtype(jaxpr)
    budget = hbm_budget_bytes
    if budget is not None and peak > budget:
        diags.append(Diagnostic(
            "H110", ERROR,
            f"peak live HBM {peak / 2**30:.3f} GiB exceeds the "
            f"{budget / 2**30:.3f} GiB budget — this program cannot fit "
            "the configured chip; shrink the batch/model, enable remat, "
            "or shard before deploying", where))
    from .hazards import sort_diagnostics

    return ProgramReport(
        name=name, chip=profile,
        flops=sum(o.flops for o in ops),
        bytes=sum(o.bytes for o in ops),
        peak_hbm_bytes=peak, ops=ops, n_eqns=_count_eqns(jaxpr),
        donated=donated, hazards=sort_diagnostics(diags),
        hbm_budget_bytes=budget, peak_hbm_by_dtype=peak_by_dtype)


def analyze_train_step(step_fn, inputs, labels, *,
                       name: str = "hapi::train_step", chip: str = "v5e",
                       hbm_budget_bytes: Optional[int] = None,
                       min_donation_bytes: int = 1 << 20) -> ProgramReport:
    """X-ray a ``jit.to_static`` train step (or the
    ``observability.track_compiles`` wrapper around one) on sample
    ``inputs``/``labels``.  Uses ``StaticFunction.trace_jaxpr``, which
    donates the state leaves exactly like the real call path."""
    sfn = getattr(step_fn, "_fn", step_fn)   # TrackedFunction -> static fn
    closed, donated = sfn.trace_jaxpr(inputs, labels)
    return analyze_jaxpr(closed, donated=donated, name=name, chip=chip,
                         hbm_budget_bytes=hbm_budget_bytes,
                         min_donation_bytes=min_donation_bytes)


# ---------------------------------------------------------------------------
# sharding readiness (S201–S204)
# ---------------------------------------------------------------------------

def _spec_entries(spec) -> List[Any]:
    """Normalize a PartitionSpec-like object to a list of per-dimension
    entries (each None, an axis name, or a tuple of axis names)."""
    if spec is None:
        return []
    return list(spec)


def _entry_axes(entry) -> List[str]:
    if entry is None:
        return []
    if isinstance(entry, (tuple, list)):
        return [str(a) for a in entry]
    return [str(entry)]


def check_sharding_readiness(layout: Dict[str, Any],
                             param_shapes: Dict[str, Sequence[int]],
                             mesh: Dict[str, int]) -> List[Diagnostic]:
    """Validate a ``{param_role: PartitionSpec}`` layout against an
    abstract mesh ``{axis_name: size}`` and the parameter shapes.

    - **S201** unknown mesh axis — the spec names an axis the mesh
      doesn't have.
    - **S202** duplicate axis within one spec — one axis cannot shard
      two dimensions of the same tensor.
    - **S203** rank mismatch — more partitioned entries than the param
      has dimensions.
    - **S204** non-divisible dimension — a dimension not divisible by
      the product of the mesh axes sharding it (GSPMD would pad or
      reject; either way the layout is not deployment-ready).

    All findings are ERROR severity: a layout that trips any of these
    cannot be handed to ``jax.jit(..., in_shardings=...)``.
    """
    mesh_sizes = dict(getattr(mesh, "shape", None) or mesh)
    diags: List[Diagnostic] = []
    for role in sorted(layout):
        spec = layout[role]
        where = f"layout[{role!r}]"
        entries = _spec_entries(spec)
        seen: Dict[str, int] = {}
        for dim, entry in enumerate(entries):
            for axis in _entry_axes(entry):
                if axis not in mesh_sizes:
                    diags.append(Diagnostic(
                        "S201", ERROR,
                        f"spec names mesh axis {axis!r} but the mesh has "
                        f"axes {sorted(mesh_sizes)} — unknown axis can "
                        "never be materialized", where))
                if axis in seen:
                    diags.append(Diagnostic(
                        "S202", ERROR,
                        f"axis {axis!r} appears in dims {seen[axis]} and "
                        f"{dim} of the same spec — one mesh axis cannot "
                        "shard two dimensions of one tensor", where))
                else:
                    seen[axis] = dim
        shape = param_shapes.get(role)
        if shape is None:
            continue
        shape = tuple(int(s) for s in shape)
        if len(entries) > len(shape):
            diags.append(Diagnostic(
                "S203", ERROR,
                f"spec has {len(entries)} entries but param {role!r} has "
                f"rank {len(shape)} ({shape}) — rank mismatch", where))
            continue
        for dim, entry in enumerate(entries):
            axes = [a for a in _entry_axes(entry) if a in mesh_sizes]
            if not axes:
                continue
            factor = int(np.prod([mesh_sizes[a] for a in axes],
                                 dtype=np.int64))
            if factor and shape[dim] % factor != 0:
                product = " × ".join(f"{a}={mesh_sizes[a]}" for a in axes)
                diags.append(Diagnostic(
                    "S204", ERROR,
                    f"dim {dim} of {role!r} has size {shape[dim]}, not "
                    f"divisible by the mesh-axis product {product} = "
                    f"{factor} — GSPMD would pad every shard; pick a "
                    "divisible dim or resize the mesh", where))
    from .hazards import sort_diagnostics

    return sort_diagnostics(diags)


# ---------------------------------------------------------------------------
# observability mirror + registered-step audit
# ---------------------------------------------------------------------------

def export_report_gauges(report: ProgramReport):
    """Mirror a report's headline statics into the observability
    registry (no-op when telemetry is disabled)."""
    from .. import observability

    if not observability.enabled():
        return
    reg = observability.get_registry()
    reg.gauge("xray_static_flops",
              "statically-modeled FLOPs of a traced step").set(
        report.flops, step=report.name)
    reg.gauge("xray_static_bytes",
              "statically-modeled HBM bytes moved by a traced step").set(
        report.bytes, step=report.name)
    reg.gauge("xray_peak_hbm_bytes",
              "liveness-walk peak live HBM of a traced step").set(
        report.peak_hbm_bytes, step=report.name)
    g = reg.gauge("xray_peak_hbm_bytes_by_dtype",
                  "bytes of one dtype held at the liveness-walk peak")
    for dt, b in sorted(report.peak_hbm_by_dtype.items()):
        g.set(b, step=report.name, dtype=dt)


def _serving_abstract_args(model, *, batch, num_blocks, block_size,
                           max_blocks_per_seq, chunk_tokens,
                           kv_cache_dtype=None):
    """Engine-shaped abstract args for the paged decode and chunked
    prefill steps (mirrors Engine.__init__'s concrete buffers).
    ``kv_cache_dtype`` of "int8"/"fp8" mirrors a QUANTIZED pool: int8
    code pools plus per-(block, token)-row f32 scale sidecars, so the
    liveness walk prices the real (quantized) HBM bytes per dtype."""
    from ..kernels.kv_quant import resolve_kv_cache_dtype
    from ..models.generation import _cache_dims

    kv_heads, head_dim, dtype = _cache_dims(model)
    scheme = resolve_kv_cache_dtype(kv_cache_dtype)
    sds = jax.ShapeDtypeStruct
    if scheme is not None:
        pool_sds = sds((num_blocks, block_size, kv_heads, head_dim),
                       np.int8)
        scale_sds = sds((num_blocks, block_size), np.float32)
        pool = [(pool_sds, pool_sds, scale_sds, scale_sds)
                for _ in range(model.config.num_hidden_layers)]
    else:
        pool = [(sds((num_blocks, block_size, kv_heads, head_dim), dtype),
                 sds((num_blocks, block_size, kv_heads, head_dim), dtype))
                for _ in range(model.config.num_hidden_layers)]
    decode = (sds((batch, 1), np.int32), pool,
              sds((batch, max_blocks_per_seq), np.int32),
              sds((batch,), np.int32))
    prefill = (sds((1, chunk_tokens), np.int32), pool,
               sds((1, max_blocks_per_seq), np.int32),
               sds((1,), np.int32),
               sds((), np.int32))
    return decode, prefill


def audit_default_steps(*, chip: str = "cpu",
                        hbm_budget_bytes: Optional[int] = None,
                        fused: bool = False
                        ) -> List[ProgramReport]:
    """Build tiny Llama models and X-ray all five default step kinds
    (train, paged decode, chunked prefill, MoE block, ring/sp block) —
    the ``lint_tpu.py --xray`` / CI entry point.  Returns the reports;
    callers gate on ``report.errors()``.

    ``fused=True`` additionally audits the FUSED serving steps
    (``serving::decode_step[fused]`` / ``serving::prefill_step[fused]``,
    forced via models.generation's ``fused=True`` so the programs carry
    the fused kernels even off-TPU) — the ``lint_tpu.py --xray --fused``
    / CI gate that the pallas_call leaves price cleanly."""
    import paddle_tpu as paddle
    from .. import nn
    from ..models import LlamaConfig, LlamaForCausalLM
    from ..optimizer import AdamW

    paddle.seed(0)
    cfg = LlamaConfig.tiny()
    net = LlamaForCausalLM(cfg)
    reports: List[ProgramReport] = []

    model = paddle.Model(net)
    model.prepare(AdamW(1e-3, parameters=net.parameters()),
                  nn.CrossEntropyLoss())
    ids = np.zeros((2, 16), np.int64)
    reports.append(analyze_train_step(
        model._train_step_fn, [paddle.to_tensor(ids[:, :-1])],
        [paddle.to_tensor(ids[:, 1:])], chip=chip,
        hbm_budget_bytes=hbm_budget_bytes))

    from ..models.generation import (make_chunked_prefill_step,
                                     make_paged_decode_step)

    net.eval()
    decode_args, prefill_args = _serving_abstract_args(
        net, batch=4, num_blocks=32, block_size=8,
        max_blocks_per_seq=8, chunk_tokens=32)
    reports.append(analyze(
        make_paged_decode_step(net), decode_args,
        name="serving::decode_step", chip=chip,
        hbm_budget_bytes=hbm_budget_bytes))
    reports.append(analyze(
        make_chunked_prefill_step(net), prefill_args,
        name="serving::prefill_step", chip=chip,
        hbm_budget_bytes=hbm_budget_bytes))

    # sampled + speculative serving steps (ISSUE 19): same pool/table
    # geometry as the plain decode step, plus the per-slot sampling
    # state (temps/top_ks/top_ps/keys/counters) and, for verify, the
    # K-token draft proposals with their filtered distributions
    from ..serving.sampling import make_sampled_decode_step
    from ..serving.speculative import make_spec_verify_step

    sds = jax.ShapeDtypeStruct
    batch, num_draft = 4, 4
    sampling_state = (sds((batch,), np.float32),          # temps
                     sds((batch,), np.int32),             # top_ks
                     sds((batch,), np.float32),           # top_ps
                     sds((batch, 2), np.uint32),          # keys
                     sds((batch,), np.int32))             # counters
    reports.append(analyze(
        make_sampled_decode_step(net), decode_args + sampling_state,
        name="serving::sampled_decode_step", chip=chip,
        hbm_budget_bytes=hbm_budget_bytes))
    pool_arg, table_arg, lengths_arg = decode_args[1:4]
    verify_args = (sds((batch,), np.int32),               # pending
                   sds((batch, num_draft), np.int32),     # proposals
                   sds((batch, num_draft, cfg.vocab_size),
                       np.float32),                       # draft_probs
                   pool_arg, table_arg, lengths_arg) + sampling_state
    reports.append(analyze(
        make_spec_verify_step(net, num_draft), verify_args,
        name="serving::spec_verify_step", chip=chip,
        hbm_budget_bytes=hbm_budget_bytes))
    if fused:
        reports.append(analyze(
            make_paged_decode_step(net, fused=True), decode_args,
            name="serving::decode_step[fused]", chip=chip,
            hbm_budget_bytes=hbm_budget_bytes))
        reports.append(analyze(
            make_chunked_prefill_step(net, fused=True), prefill_args,
            name="serving::prefill_step[fused]", chip=chip,
            hbm_budget_bytes=hbm_budget_bytes))
        # off-TPU the fused steps lower to the XLA fallback, so ALSO
        # audit the decode kernel itself in interpret mode — this is
        # the gate that a real pallas_call leaf prices through the
        # kernels.costs registry on any backend
        from ..kernels.paged_attention import fused_paged_decode

        hd = cfg.hidden_size // cfg.num_attention_heads
        kvh = cfg.num_key_value_heads
        f32 = np.float32
        sds32 = jax.ShapeDtypeStruct
        kernel_args = (
            sds32((4, 1, cfg.num_attention_heads, hd), f32),    # q
            sds32((4, 1, kvh, hd), f32),                        # k_new
            sds32((4, 1, kvh, hd), f32),                        # v_new
            sds32((32, 8, kvh, hd), f32),                       # k_pool
            sds32((32, 8, kvh, hd), f32),                       # v_pool
            sds32((4, 8), np.int32),                            # table
            sds32((4,), np.int32),                              # pos
            sds32((cfg.max_position_embeddings, hd // 2), f32),  # cos
            sds32((cfg.max_position_embeddings, hd // 2), f32),  # sin
        )
        reports.append(analyze(
            functools.partial(fused_paged_decode, use_pallas=True,
                              interpret=True),
            kernel_args, name="kernel::fused_paged_decode", chip=chip,
            hbm_budget_bytes=hbm_budget_bytes))

        from ..kernels.chunked_prefill import fused_chunked_attention

        prefill_kernel_args = (
            sds32((4, 32, cfg.num_attention_heads, hd), f32),   # q chunk
            sds32((32, 8, kvh, hd), f32),                       # k_pool
            sds32((32, 8, kvh, hd), f32),                       # v_pool
            sds32((4, 8), np.int32),                            # table
            sds32((4,), np.int32),                              # pos
        )
        reports.append(analyze(
            functools.partial(fused_chunked_attention, use_pallas=True,
                              interpret=True),
            prefill_kernel_args, name="kernel::fused_chunked_prefill",
            chip=chip, hbm_budget_bytes=hbm_budget_bytes))

        # quantized serving (ISSUE 20): the int8-KV fused steps and the
        # quantized decode kernel, so the costs registry is exercised on
        # int8 pool operands (quantized bytes, not fp32) in the same
        # --xray --fused CI gate
        q_decode_args, q_prefill_args = _serving_abstract_args(
            net, batch=4, num_blocks=32, block_size=8,
            max_blocks_per_seq=8, chunk_tokens=32, kv_cache_dtype="int8")
        reports.append(analyze(
            make_paged_decode_step(net, fused=True, kv_cache_dtype="int8"),
            q_decode_args, name="serving::decode_step[fused,int8]",
            chip=chip, hbm_budget_bytes=hbm_budget_bytes))
        reports.append(analyze(
            make_chunked_prefill_step(net, fused=True,
                                      kv_cache_dtype="int8"),
            q_prefill_args, name="serving::prefill_step[fused,int8]",
            chip=chip, hbm_budget_bytes=hbm_budget_bytes))

        def _q_decode_kernel(q, kn, vn, kp, vp, bt, pos, cos, sin,
                             ksc, vsc):
            return fused_paged_decode(
                q, kn, vn, kp, vp, bt, pos, cos, sin, use_pallas=True,
                interpret=True, k_scale=ksc, v_scale=vsc,
                kv_cache_dtype="int8")

        q_kernel_args = kernel_args[:3] + (
            sds32((32, 8, kvh, hd), np.int8),               # k_pool codes
            sds32((32, 8, kvh, hd), np.int8),               # v_pool codes
        ) + kernel_args[5:] + (
            sds32((32, 8), f32),                            # k_scale
            sds32((32, 8), f32),                            # v_scale
        )
        reports.append(analyze(
            _q_decode_kernel, q_kernel_args,
            name="kernel::fused_paged_decode[int8]", chip=chip,
            hbm_budget_bytes=hbm_budget_bytes))

    from ..distributed.mesh import abstract_mesh
    from ..models.generation import make_moe_block_step, make_ring_sp_step

    sds = jax.ShapeDtypeStruct
    moe_net = LlamaForCausalLM(LlamaConfig.tiny(
        moe_num_experts=4, moe_top_k=2, moe_capacity_factor=2.0))
    moe_net.eval()
    reports.append(analyze(
        make_moe_block_step(moe_net), (sds((4, 16), np.int32),),
        name="moe::block_step", chip=chip,
        hbm_budget_bytes=hbm_budget_bytes))

    ring_net = LlamaForCausalLM(LlamaConfig.tiny(context_parallel="ring"))
    ring_net.eval()
    ring_mesh = abstract_mesh({"data": 2, "sp": 2, "tp": 2})
    reports.append(analyze(
        make_ring_sp_step(ring_net, mesh=ring_mesh),
        (sds((4, 32), np.int32),),
        name="ring::sp_step", chip=chip,
        hbm_budget_bytes=hbm_budget_bytes))
    for r in reports:
        export_report_gauges(r)
    return reports
