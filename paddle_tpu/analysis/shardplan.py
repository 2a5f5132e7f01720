"""Static SPMD plan analyzer: sharding propagation, per-chip memory,
and communication cost from the jaxpr.

PR 6's X-ray answers "what does this program cost on ONE chip"; this
module answers "what does it cost on a MESH" — before any mesh exists.
Given a traced step (``jit.StaticFunction.trace_jaxpr`` or
``jax.make_jaxpr``), an **abstract mesh** (named axis sizes, no real
devices — the whole analysis runs on CPU tier-1), and a
:class:`~paddle_tpu.distributed.sharding.SpecLayout`, it propagates
shardings through the jaxpr the way GSPMD's partitioner would
(dot_general/conv from dimension numbers, elementwise union rules,
reshape split/merge, transpose permutation, recursion through
pjit/scan/while/cond like the cost model) and emits a
:class:`PlanReport`:

- **per-chip sharded peak HBM** — the xray liveness pass re-run with a
  shard-aware ``var_bytes`` callback that divides each buffer by its
  shard count, gated by ``hbm_budget_bytes`` *per chip* (H110 ERROR).
- **collective inventory** — every implied all-reduce / all-gather /
  reduce-scatter / all-to-all with ring-formula bytes on the wire
  (all-reduce moves ``2·S·(n-1)/n`` per chip, the others ``S·(n-1)/n``)
  and estimated time against the chip's ICI profile
  (:data:`~paddle_tpu.analysis.xray.CHIPS`).
- **diagnostics** — S205 resharding hotspot (a producer/consumer spec
  conflict forcing an *unplanned* gather above a byte threshold, ERROR),
  S206 fully-replicated large parameter (WARNING — HBM burned on every
  chip), S207 collective-bound step (estimated comm time exceeds the
  roofline compute time, ERROR), S208 batch dim not sharded on the
  ``data`` axis (WARNING — chunked prefill legitimately runs batch=1).

**Planned vs unplanned.**  A collective the layout *implies* is
planned: a sharded contraction ends in an all-reduce (row-parallel
matmul, data-parallel grad sync), a one-sided sharded contraction
all-gathers the sharded operand (the ZeRO-3/FSDP resolution), a lookup
into a vocab-sharded embedding lowers to masked-gather + all-reduce.
Unplanned collectives come from spec *conflicts* — the same mesh axis
claimed by two output dims, or an elementwise op whose operands
disagree — and are what S205 reports: they mean the layout fights
itself on that edge.

The propagation is a single forward pass (no GSPMD fix-point): loop
carries keep their entry spec, and unknown primitives inherit from a
same-shaped operand or fall back to replicated without inventing
collectives.  That makes the analysis conservative in the safe
direction — it can miss a resharding XLA would insert, but a *clean*
report means the layout is self-consistent on every edge this pass
understands.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Any, Dict, List, Optional, Sequence, Tuple

import jax
import numpy as np
from jax.extend.core import Literal

from .topology import Topology, format_recommendations, rank_layouts
from .verifier import ERROR, WARNING, Diagnostic
from .xray import (CHIPS, ChipProfile, _aval_bytes, _collect_costs,
                   _peak_live_by_dtype, _peak_live_bytes, _var_bytes,
                   estimate_collective_time, estimate_compute_time)

__all__ = [
    "Collective",
    "MoEStatics",
    "PlanReport",
    "PlanRequest",
    "Topology",
    "audit_shardplan",
    "export_plan_gauges",
    "plan_jaxpr",
    "plan_step",
    "plan_train_step",
    "recommend_layouts",
]

#: step kinds where a request round-trips the step on the critical
#: path — any DCN-crossing collective inside one is an S213 ERROR
LATENCY_CRITICAL_STEP_KINDS = frozenset(
    {"decode", "beam_decode", "paged_decode", "prefill",
     "chunked_prefill", "sampled_decode", "draft_propose",
     "spec_verify"})

#: S213 noise floor: a DCN edge must move at least this many wire
#: bytes per step to be flagged — scalar-sized control reduces (the
#: conservative gather rule prices an aligned per-shard lookup as an
#: 8-byte all_reduce) are priced into the totals but not latency-gated
_S213_FLOOR_BYTES = 256


# ---------------------------------------------------------------------------
# spec algebra: a ShardSpec is a per-dimension tuple of mesh-axis names
# ---------------------------------------------------------------------------

ShardSpec = Tuple[Tuple[str, ...], ...]


def _rep(rank: int) -> ShardSpec:
    return ((),) * rank


def _rank(v) -> int:
    return len(getattr(v.aval, "shape", ()) or ())


def _normalize_spec(spec, rank: int) -> ShardSpec:
    """PartitionSpec / tuple / None → canonical per-dim axis tuples,
    padded with replicated entries to ``rank``."""
    if spec is None:
        return _rep(rank)
    entries: List[Tuple[str, ...]] = []
    for e in tuple(spec)[:rank]:
        if e is None:
            entries.append(())
        elif isinstance(e, (tuple, list)):
            entries.append(tuple(str(a) for a in e))
        else:
            entries.append((str(e),))
    while len(entries) < rank:
        entries.append(())
    return tuple(entries)


def _axes_product(axes: Sequence[str], mesh: Dict[str, int]) -> int:
    n = 1
    for a in axes:
        n *= int(mesh.get(a, 1))
    return n


def _shard_count(spec: ShardSpec, mesh: Dict[str, int]) -> int:
    n = 1
    for entry in spec:
        n *= _axes_product(entry, mesh)
    return max(1, n)


def _spec_str(spec: ShardSpec) -> str:
    def one(entry):
        if not entry:
            return "·"
        return "+".join(entry)
    return "(" + ", ".join(one(e) for e in spec) + ")"


# primitives that carry an axis_name param but move no tensor bytes —
# they must not trip the S210 unpriced-collective detector
_AXIS_NAME_FREE = {"axis_index", "axis_size", "pvary"}


# ---------------------------------------------------------------------------
# report dataclasses
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class Collective:
    """One implied collective.  ``payload_bytes`` is the logical tensor
    size being communicated (already divided by its shard count over
    the *other* axes); ``bytes_moved`` is per-chip wire traffic from the
    ring formula; ``count`` is the static trip multiplier (scan)."""

    kind: str                 # all_reduce | all_gather | reduce_scatter | all_to_all
    axes: Tuple[str, ...]
    payload_bytes: int
    bytes_moved: int
    time_s: float
    planned: bool
    primitive: str
    count: float = 1.0
    # link level the bytes ride: "ici" (intra-host, the only level a
    # flat single-host plan has) or "dcn" (cross-host phase of a
    # topology-decomposed collective)
    level: str = "ici"

    @property
    def total_bytes(self) -> float:
        return self.bytes_moved * self.count

    @property
    def total_time_s(self) -> float:
        return self.time_s * self.count


@dataclasses.dataclass(frozen=True)
class MoEStatics:
    """Static description of one capacity-padded MoE dispatch (GShard
    style ``[E, C, M]`` buffers).  Lets the planner (a) price the expert
    exchange as an all_to_all sized from the padded payload instead of a
    worst-case all-reduce and (b) statically check capacity overflow
    (S211: ``tokens·top_k > experts·capacity`` drops routed tokens)."""

    experts: int               # E
    capacity: int              # C slots per expert
    top_k: int                 # routing choices per token
    tokens: int                # tokens routed per step (batch · seq)
    capacity_factor: float = 1.0
    expert_axis: str = "expert"


@dataclasses.dataclass
class PlanReport:
    """Static mesh-execution plan for one traced step."""

    name: str
    chip: ChipProfile
    mesh: Dict[str, int]
    n_chips: int
    per_chip_peak_hbm_bytes: int
    collectives: List[Collective]
    flops: float               # whole-program, all chips
    bytes: float               # whole-program HBM bytes, all chips
    diagnostics: List[Diagnostic]
    param_specs: Dict[str, str]
    hbm_budget_bytes: Optional[int] = None
    # dtype -> per-chip bytes held at the liveness peak (sums to
    # per_chip_peak_hbm_bytes); the dtype-aware gauge for int8/fp8 KV
    per_chip_peak_hbm_by_dtype: Dict[str, int] = dataclasses.field(
        default_factory=dict)
    # multi-host pricing context.  When a Topology is set,
    # ``collectives`` holds the hierarchically decomposed per-link
    # phases and ``flat_collectives`` keeps the raw single-level
    # inventory the propagation produced (what the layout recommender
    # reprices under other assignments); without one the two lists are
    # the same object.
    topology: Optional[Topology] = None
    flat_collectives: List[Collective] = dataclasses.field(
        default_factory=list)
    step_kind: Optional[str] = None

    @property
    def comm_bytes(self) -> float:
        return sum(c.total_bytes for c in self.collectives)

    @property
    def comm_time_s(self) -> float:
        return sum(c.total_time_s for c in self.collectives)

    @property
    def ici_comm_bytes(self) -> float:
        return sum(c.total_bytes for c in self.collectives
                   if c.level != "dcn")

    @property
    def dcn_comm_bytes(self) -> float:
        return sum(c.total_bytes for c in self.collectives
                   if c.level == "dcn")

    @property
    def ici_comm_time_s(self) -> float:
        return sum(c.total_time_s for c in self.collectives
                   if c.level != "dcn")

    @property
    def dcn_comm_time_s(self) -> float:
        return sum(c.total_time_s for c in self.collectives
                   if c.level == "dcn")

    @property
    def chips_per_host_count(self) -> int:
        if self.topology is not None:
            return self.topology.chips_per_host_count
        return max(1, self.n_chips)   # single host holds the mesh

    @property
    def per_host_peak_hbm_bytes(self) -> int:
        """HBM the busiest host must hold: per-chip peak × chips on
        one host (every chip of a host peaks in the same SPMD step)."""
        return self.per_chip_peak_hbm_bytes * self.chips_per_host_count

    @property
    def dcn_bytes_per_host(self) -> float:
        """DCN ingress+egress through one host's NIC per step — every
        resident chip's DCN wire bytes funnel through the host."""
        return self.dcn_comm_bytes * self.chips_per_host_count

    def to_json(self) -> Dict[str, Any]:
        """Machine-readable plan for ``lint_tpu --shardplan --json`` —
        CI diffs these across PRs instead of grepping the text table."""
        topo = self.topology
        return {
            "name": self.name,
            "step_kind": self.step_kind,
            "chip": self.chip.name,
            "mesh": dict(self.mesh),
            "n_chips": int(self.n_chips),
            "hosts": int(topo.hosts) if topo else 1,
            "chips_per_host": (list(topo.chips_per_host) if topo
                               else [max(1, self.n_chips)]),
            "axis_levels": ({a: topo.level_of(a, self.mesh)
                             for a in self.mesh} if topo else
                            {a: "ici" for a in self.mesh}),
            "per_chip_peak_hbm_bytes": int(self.per_chip_peak_hbm_bytes),
            "per_host_peak_hbm_bytes": int(self.per_host_peak_hbm_bytes),
            "per_chip_peak_hbm_by_dtype": {
                k: int(v)
                for k, v in sorted(self.per_chip_peak_hbm_by_dtype.items())},
            "hbm_budget_bytes": self.hbm_budget_bytes,
            "wire_bytes": {"ici": int(self.ici_comm_bytes),
                           "dcn": int(self.dcn_comm_bytes)},
            "comm_time_s": {"ici": self.ici_comm_time_s,
                            "dcn": self.dcn_comm_time_s},
            "dcn_bytes_per_host": int(self.dcn_bytes_per_host),
            "compute_time_s": self.compute_time_s,
            "unplanned_collectives": sum(
                1 for c in self.collectives if not c.planned),
            "collectives": [
                {"kind": c.kind, "axes": list(c.axes), "level": c.level,
                 "payload_bytes": int(c.payload_bytes),
                 "bytes_moved": int(c.bytes_moved), "count": c.count,
                 "time_s": c.time_s, "planned": c.planned,
                 "primitive": c.primitive}
                for c in self.collectives],
            "diagnostics": [
                {"code": d.code, "severity": d.severity,
                 "message": d.message, "where": d.where}
                for d in self.diagnostics],
            "param_specs": dict(self.param_specs),
        }

    @property
    def compute_time_s(self) -> float:
        """Per-chip roofline time: the program's cost divided over the
        mesh, against the same formula xray's summary uses."""
        n = max(1, self.n_chips)
        return estimate_compute_time(self.flops / n, self.bytes / n,
                                     self.chip)

    def errors(self) -> List[Diagnostic]:
        return [d for d in self.diagnostics if d.severity == ERROR]

    def table(self, top: int = 12) -> str:
        """Collective inventory: kind, mesh axes, wire KiB/chip, µs,
        planned-or-conflict, producing primitive."""
        rows = [f"{'collective':<16}{'axes':<14}{'link':<6}"
                f"{'KiB/chip':>10}{'µs':>8}  plan  primitive"]
        ordered = sorted(self.collectives,
                         key=lambda c: (-c.total_bytes, c.kind, c.primitive))
        for c in ordered[:top]:
            rows.append(
                f"{c.kind:<16}{'×'.join(c.axes):<14}{c.level:<6}"
                f"{c.total_bytes / 1024:>10.2f}{c.total_time_s * 1e6:>8.2f}"
                f"  {'yes' if c.planned else 'NO':<4}  {c.primitive}")
        return "\n".join(rows)

    def summary(self) -> str:
        budget = (f" / budget {self.hbm_budget_bytes / 2**30:.2f} GiB"
                  if self.hbm_budget_bytes else "")
        mesh = ",".join(f"{k}={v}" for k, v in self.mesh.items())
        unplanned = sum(1 for c in self.collectives if not c.planned)
        if self.topology is not None:
            topo = (f" [{self.topology.hosts} host(s) × "
                    f"{self.chips_per_host_count} chips]")
            comm = (f"comm {self.comm_time_s * 1e6:.1f} µs "
                    f"(ICI {self.ici_comm_time_s * 1e6:.1f} + "
                    f"DCN {self.dcn_comm_time_s * 1e6:.1f})")
            host_hbm = (f", per-host peak HBM "
                        f"{self.per_host_peak_hbm_bytes / 2**20:.2f} MiB"
                        f", DCN {self.dcn_bytes_per_host / 2**20:.3f} "
                        "MiB/host/step")
        else:
            topo = ""
            comm = f"comm {self.comm_time_s * 1e6:.1f} µs"
            host_hbm = ""
        return (f"[shardplan] {self.name} on ({mesh}){topo} "
                f"@ {self.chip.name}: per-chip peak HBM "
                f"{self.per_chip_peak_hbm_bytes / 2**20:.2f} MiB{budget}, "
                f"{len(self.collectives)} collective(s) "
                f"({unplanned} unplanned, "
                f"{self.comm_bytes / 2**20:.3f} MiB on wire), "
                f"{comm} vs compute "
                f"{self.compute_time_s * 1e6:.1f} µs{host_hbm}, "
                f"{len(self.diagnostics)} diagnostic(s)")


@dataclasses.dataclass
class PlanRequest:
    """Opt-in config for ``Model.fit(shardplan=...)`` /
    ``ServingConfig.shardplan`` and the CLI — everything
    :func:`plan_train_step` / :func:`plan_step` need beyond the trace."""

    mesh: Dict[str, int] = dataclasses.field(
        default_factory=lambda: {"data": 2, "fsdp": 2, "tp": 2})
    layout: Any = None            # SpecLayout; None → default
    chip: str = "cpu"
    hbm_budget_bytes: Optional[int] = None
    s205_bytes: int = 1 << 20     # unplanned-gather ERROR threshold
    s206_bytes: int = 8 << 20     # replicated-param WARNING threshold
    raise_on_error: bool = True
    moe: Optional[MoEStatics] = None  # set for MoE steps (S211 + a2a pricing)
    # multi-host topology: when set, collectives over host-spanning
    # axes decompose into ICI/DCN phases and per-host budgets apply
    topology: Optional[Topology] = None

    def resolved_layout(self):
        if self.layout is not None:
            return self.layout
        from ..distributed.sharding import SpecLayout

        return SpecLayout()


# ---------------------------------------------------------------------------
# the propagator
# ---------------------------------------------------------------------------

class _Planner:
    """Single forward sharding-propagation pass over a (nested) jaxpr.

    ``env`` maps every visited jaxpr Var to its ShardSpec — including
    vars of inner jaxprs, so the shard-aware liveness callback can
    resolve any var the peak-HBM walk touches."""

    def __init__(self, mesh: Dict[str, int], chip: ChipProfile,
                 moe: Optional[MoEStatics] = None):
        self.mesh = dict(mesh)
        self.chip = chip
        self.moe = moe
        self.env: Dict[Any, ShardSpec] = {}
        self.collectives: List[Collective] = []
        # (primitive, axes) pairs that carried an axis_name but have no
        # pricing rule — the S210 silent-blind-spot inventory
        self.unknown_collectives: List[Tuple[str, Tuple[str, ...]]] = []

    # -- env ---------------------------------------------------------------

    def spec_of(self, v) -> ShardSpec:
        if isinstance(v, Literal):
            return _rep(_rank(v))
        return self.env.get(v, _rep(_rank(v)))

    def set_spec(self, v, spec: ShardSpec):
        if isinstance(v, Literal):
            return
        self.env[v] = self._drop_indivisible(v, spec)

    def _drop_indivisible(self, v, spec: ShardSpec) -> ShardSpec:
        """A dim not divisible by its axis product cannot actually be
        sharded — treat it as replicated here (S204 complains at the
        layout level)."""
        shape = getattr(v.aval, "shape", ()) or ()
        out = []
        for dim, entry in enumerate(spec):
            n = _axes_product(entry, self.mesh)
            if n > 1 and dim < len(shape) and int(shape[dim]) % n != 0:
                out.append(())
            else:
                out.append(entry)
        return tuple(out)

    # -- collective emission -----------------------------------------------

    def emit(self, kind: str, axes: Sequence[str], payload: float,
             planned: bool, primitive: str, mul: float,
             factor: Optional[float] = None):
        axes = tuple(a for a in axes if self.mesh.get(a, 1) > 1)
        n = _axes_product(axes, self.mesh)
        if n <= 1 or payload <= 0:
            return
        if factor is None:
            factor = (2.0 * (n - 1) / n if kind == "all_reduce"
                      else (n - 1) / n)
        moved = int(payload * factor)
        self.collectives.append(Collective(
            kind=kind, axes=axes, payload_bytes=int(payload),
            bytes_moved=moved,
            time_s=estimate_collective_time(moved, self.chip),
            planned=planned, primitive=primitive, count=mul))

    def _dedupe(self, spec: ShardSpec, used: set, out_bytes: float,
                primitive: str, mul: float, planned: bool = False
                ) -> ShardSpec:
        """Drop axes already claimed elsewhere in the output; every drop
        of a real (>1) axis means the value must be gathered along it."""
        result: List[Tuple[str, ...]] = []
        for entry in spec:
            kept = []
            for a in entry:
                if a in used:
                    if self.mesh.get(a, 1) > 1:
                        self.emit("all_gather", (a,),
                                  out_bytes / _axes_product([a], self.mesh),
                                  planned, primitive, mul)
                else:
                    used.add(a)
                    kept.append(a)
            result.append(tuple(kept))
        return tuple(result)

    # -- walk --------------------------------------------------------------

    def run(self, jaxpr, mul: float = 1.0):
        for eqn in jaxpr.eqns:
            self._eqn(eqn, mul)

    def _eqn(self, eqn, mul: float):
        name = eqn.primitive.name
        handler = _RULES.get(name)
        if handler is not None:
            handler(self, eqn, mul)
        elif name == "pallas_call":
            # a priced LEAF, not a call: its params carry a "jaxpr" (the
            # per-block kernel body), but walking that would misread
            # one grid cell as the whole op — and its internal grid axes
            # must never read as unknown collectives (S210).  The fused
            # serving kernels run unsharded (models/llama.py falls back
            # to the gather path under a live mesh), so outputs
            # replicate and no wire traffic is emitted.
            self._default_specs_only(eqn)
        elif name in ("cond", "while", "scan", "jit") or \
                "jaxpr" in eqn.params or "call_jaxpr" in eqn.params \
                or "fun_jaxpr" in eqn.params:
            self._call_like(eqn, mul)
        else:
            if name not in _AXIS_NAME_FREE and (
                    "axis_name" in eqn.params
                    or "axis_index_groups" in eqn.params):
                # a collective-looking primitive the planner cannot
                # price — record it so S210 surfaces the blind spot
                axes = eqn.params.get("axis_name", ())
                if isinstance(axes, str):
                    axes = (axes,)
                axes = tuple(str(a) for a in (axes or ()))
                if not axes or _axes_product(axes, self.mesh) > 1:
                    self.unknown_collectives.append((name, axes))
            self._default(eqn, mul)

    # -- generic rules -----------------------------------------------------

    def _default(self, eqn, mul: float):
        """Elementwise/unknown: per-dim union across broadcast-compatible
        operands (right-aligned; size-1 dims contribute nothing);
        disagreeing operands lose their axes (unplanned gather);
        unknown shapes replicate without inventing traffic."""
        for out in eqn.outvars:
            out_shape = tuple(getattr(out.aval, "shape", ()) or ())
            rank = len(out_shape)
            merged: List[Tuple[str, ...]] = [()] * rank
            conflict_axes: set = set()
            for v in eqn.invars:
                if isinstance(v, Literal):
                    continue
                v_shape = tuple(getattr(v.aval, "shape", None) or ())
                off = rank - len(v_shape)
                if off < 0 or any(
                        s != out_shape[off + i] and s != 1
                        for i, s in enumerate(v_shape)):
                    continue
                spec = self.spec_of(v)
                for i, s in enumerate(v_shape):
                    d = off + i
                    if s != out_shape[d] or not spec[i]:
                        continue
                    if not merged[d]:
                        merged[d] = spec[i]
                    elif merged[d] != spec[i]:
                        conflict_axes.update(set(spec[i]) - set(merged[d]))
            for a in sorted(conflict_axes):
                self.emit("all_gather", (a,),
                          _aval_bytes(out.aval)
                          / _axes_product([a], self.mesh),
                          False, eqn.primitive.name, mul)
            used: set = set()
            final = self._dedupe(tuple(merged), used,
                                 _aval_bytes(out.aval),
                                 eqn.primitive.name, mul)
            self.set_spec(out, final)

    def _default_specs_only(self, eqn):
        """Replicated outputs, zero emitted traffic — for opaque priced
        leaves (pallas_call) whose operands the planner must not try to
        reshard through broadcast rules."""
        for out in eqn.outvars:
            rank = len(tuple(getattr(out.aval, "shape", ()) or ()))
            self.set_spec(out, _rep(rank))

    def _match_specs(self, outer_vars, inner_vars, outer_to_inner: bool):
        """Shape-aware pairing for call-like eqns: equal shapes copy the
        spec; a rank-1 difference with a matching tail is scan's
        stacked/per-iteration relationship (strip or prepend the leading
        dim); anything else replicates."""
        for ov, iv in zip(outer_vars, inner_vars):
            src, dst = (ov, iv) if outer_to_inner else (iv, ov)
            if isinstance(dst, Literal):
                continue
            s_shape = tuple(getattr(src.aval, "shape", ()) or ())
            d_shape = tuple(getattr(dst.aval, "shape", ()) or ())
            spec = self.spec_of(src)
            if s_shape == d_shape:
                self.set_spec(dst, spec)
            elif len(s_shape) == len(d_shape) + 1 and s_shape[1:] == d_shape:
                self.set_spec(dst, spec[1:])
            elif len(d_shape) == len(s_shape) + 1 and d_shape[1:] == s_shape:
                self.set_spec(dst, ((),) + spec)
            else:
                self.set_spec(dst, _rep(len(d_shape)))

    def _call_like(self, eqn, mul: float):
        name = eqn.primitive.name
        params = eqn.params
        if name == "cond":
            branches = params["branches"]
            ops = eqn.invars[1:]
            # propagate every branch (liveness needs the env), but only
            # keep the most expensive branch's collectives — branches
            # are exclusive, same policy as the cost walk
            base = len(self.collectives)
            best: List[Collective] = []
            best_cost = -1.0
            for b in branches:
                inner = b.jaxpr
                self._match_specs(ops, inner.invars, True)
                self.run(inner, mul)
                mine = self.collectives[base:]
                del self.collectives[base:]
                cost = sum(c.total_bytes for c in mine)
                if cost > best_cost:
                    best, best_cost = mine, cost
                    self._match_specs(eqn.outvars, inner.outvars, False)
            self.collectives.extend(best)
            return
        if name == "while":
            cn = int(params.get("cond_nconsts", 0))
            bn = int(params.get("body_nconsts", 0))
            cond_j = params["cond_jaxpr"].jaxpr
            body_j = params["body_jaxpr"].jaxpr
            carry = eqn.invars[cn + bn:]
            self._match_specs(eqn.invars[:cn] + carry, cond_j.invars, True)
            self._match_specs(eqn.invars[cn:cn + bn] + carry,
                              body_j.invars, True)
            self.run(cond_j, mul)
            self.run(body_j, mul)
            self._match_specs(eqn.outvars, body_j.outvars, False)
            return
        if name == "scan":
            inner = params["jaxpr"].jaxpr
            trips = float(params.get("length", 1))
            self._match_specs(eqn.invars, inner.invars, True)
            self.run(inner, mul * trips)
            self._match_specs(eqn.outvars, inner.outvars, False)
            return
        # custom_vjp_call_jaxpr keeps its primal body under fun_jaxpr —
        # recursing through it makes hand-differentiated kernels
        # (moe_dispatch/combine) transparent instead of opaque leaves
        inner = params.get("jaxpr",
                           params.get("call_jaxpr",
                                      params.get("fun_jaxpr")))
        inner = getattr(inner, "jaxpr", inner)
        self._match_specs(eqn.invars, inner.invars, True)
        self.run(inner, mul)
        self._match_specs(eqn.outvars, inner.outvars, False)


# ---------------------------------------------------------------------------
# primitive-specific propagation rules
# ---------------------------------------------------------------------------

def _rule_dot_general(pl: _Planner, eqn, mul: float):
    ((lc, rc), (lb, rb)) = eqn.params["dimension_numbers"]
    lhs, rhs = eqn.invars[0], eqn.invars[1]
    ls, rs = pl.spec_of(lhs), pl.spec_of(rhs)
    out = eqn.outvars[0]
    out_bytes = _aval_bytes(out.aval)

    # contraction: axes sharded on BOTH sides → partial sums, one
    # planned all-reduce of the (already-assembled) output; axes on one
    # side only → planned all-gather of that operand (FSDP resolution)
    reduce_axes: List[str] = []
    for li, ri in zip(lc, rc):
        both = set(ls[li]) & set(rs[ri])
        reduce_axes.extend(sorted(both))
        for side_spec, side_var, dim in ((ls, lhs, li), (rs, rhs, ri)):
            only = set(side_spec[dim]) - both
            for a in sorted(only):
                payload = (_aval_bytes(side_var.aval)
                           / _shard_count(pl.spec_of(side_var), pl.mesh)
                           * _axes_product([a], pl.mesh))
                pl.emit("all_gather", (a,), payload, True,
                        "dot_general", mul)

    # output dims: batch, then lhs free, then rhs free
    used: set = set(reduce_axes)
    out_spec: List[Tuple[str, ...]] = []
    for li, ri in zip(lb, rb):
        axes = tuple(ls[li]) if ls[li] else tuple(rs[ri])
        if ls[li] and rs[ri] and set(ls[li]) != set(rs[ri]):
            for a in sorted(set(rs[ri]) - set(ls[li])):
                pl.emit("all_gather", (a,),
                        out_bytes / _axes_product([a], pl.mesh),
                        False, "dot_general", mul)
        out_spec.append(axes)
    for i in range(len(ls)):
        if i not in tuple(lc) + tuple(lb):
            out_spec.append(tuple(ls[i]))
    for i in range(len(rs)):
        if i not in tuple(rc) + tuple(rb):
            out_spec.append(tuple(rs[i]))
    final = pl._dedupe(tuple(out_spec), used, out_bytes, "dot_general", mul)
    out_shape = tuple(getattr(out.aval, "shape", ()) or ())
    moe = pl.moe
    # GShard MoE dispatch: a token-sharded contraction assembling the
    # capacity-padded [E, C, M] buffer that the expert axis consumes.
    # GSPMD lowers that exchange to an all_to_all over 'expert' (each
    # chip keeps only its experts' slots) plus the token-axis reduction
    # of the surviving local slice — not an all-reduce of the full
    # padded buffer on every chip.
    is_moe_dispatch = (
        moe is not None and reduce_axes and len(out_shape) >= 2
        and int(out_shape[0]) == int(moe.experts)
        and int(out_shape[1]) == int(moe.capacity)
        and pl.mesh.get(moe.expert_axis, 1) > 1
        and moe.expert_axis not in {a for e in final for a in e})
    if is_moe_dispatch and not final[0]:
        final = ((moe.expert_axis,),) + final[1:]
    pl.set_spec(out, final)
    if reduce_axes:
        if is_moe_dispatch:
            e_ax = moe.expert_axis
            e_n = _axes_product([e_ax], pl.mesh)
            payload = out_bytes / _shard_count(final[1:], pl.mesh)
            pl.emit("all_to_all", (e_ax,), payload, True,
                    "dot_general(moe_dispatch)", mul)
            rest = tuple(a for a in sorted(set(reduce_axes)) if a != e_ax)
            if rest:
                pl.emit("all_reduce", rest, payload / e_n, True,
                        "dot_general(moe_dispatch)", mul)
        else:
            payload = out_bytes / _shard_count(final, pl.mesh)
            pl.emit("all_reduce", tuple(sorted(set(reduce_axes))), payload,
                    True, "dot_general", mul)


def _rule_transpose(pl: _Planner, eqn, mul: float):
    perm = eqn.params["permutation"]
    spec = pl.spec_of(eqn.invars[0])
    pl.set_spec(eqn.outvars[0], tuple(spec[p] for p in perm))


def _rule_broadcast_in_dim(pl: _Planner, eqn, mul: float):
    bdims = eqn.params["broadcast_dimensions"]
    in_v, out = eqn.invars[0], eqn.outvars[0]
    spec = pl.spec_of(in_v)
    in_shape = tuple(getattr(in_v.aval, "shape", ()) or ())
    out_shape = tuple(out.aval.shape)
    out_spec = [()] * len(out_shape)
    for i, j in enumerate(bdims):
        if i < len(in_shape) and in_shape[i] == out_shape[j]:
            out_spec[j] = spec[i]
    pl.set_spec(out, tuple(out_spec))


def _reshape_groups(src: Sequence[int], dst: Sequence[int]):
    """Pair contiguous runs of src/dst dims with equal element products.
    Yields (src_dims, dst_dims) groups, or None if the factorization
    doesn't line up (fallback: drop all sharding)."""
    groups = []
    i = j = 0
    while i < len(src) or j < len(dst):
        si, sj = i, j
        pi = pj = 1
        if i < len(src):
            pi = src[i]
            i += 1
        if j < len(dst):
            pj = dst[j]
            j += 1
        while pi != pj:
            if pi < pj:
                if i >= len(src):
                    return None
                pi *= src[i]
                i += 1
            else:
                if j >= len(dst):
                    return None
                pj *= dst[j]
                j += 1
        groups.append((list(range(si, i)), list(range(sj, j))))
    return groups


def _rule_reshape(pl: _Planner, eqn, mul: float):
    in_v, out = eqn.invars[0], eqn.outvars[0]
    spec = pl.spec_of(in_v)
    src = [int(s) for s in (getattr(in_v.aval, "shape", ()) or ())]
    dst = [int(s) for s in out.aval.shape]
    groups = _reshape_groups(src, dst)
    out_spec: List[Tuple[str, ...]] = [()] * len(dst)
    gathered: List[str] = []
    if groups is None:
        gathered = [a for e in spec for a in e]
    else:
        for sdims, ddims in groups:
            sharded = [(d, spec[d]) for d in sdims if spec[d]]
            if not sharded:
                continue
            # sharding survives a split/merge only when it lives on the
            # MAJOR (outermost non-size-1) dim of the group and the
            # receiving major dim divides by the axis product
            major_s = [d for d in sdims if src[d] > 1]
            major_d = [d for d in ddims if dst[d] > 1]
            if len(sharded) == 1 and major_s and major_d and \
                    sharded[0][0] == major_s[0]:
                axes = sharded[0][1]
                n = _axes_product(axes, pl.mesh)
                if dst[major_d[0]] % max(1, n) == 0:
                    out_spec[major_d[0]] = axes
                    continue
            gathered.extend(a for _, e in sharded for a in e)
    for a in sorted(set(gathered)):
        if pl.mesh.get(a, 1) > 1:
            pl.emit("all_gather", (a,),
                    _aval_bytes(out.aval) / _axes_product([a], pl.mesh),
                    False, "reshape", mul)
    pl.set_spec(out, tuple(out_spec))


def _rule_reduce(pl: _Planner, eqn, mul: float):
    axes = tuple(eqn.params.get("axes", ()))
    in_v, out = eqn.invars[0], eqn.outvars[0]
    spec = pl.spec_of(in_v)
    out_spec = tuple(e for d, e in enumerate(spec) if d not in axes)
    reduce_axes = sorted({a for d in axes if d < len(spec)
                          for a in spec[d]})
    pl.set_spec(out, out_spec)
    if reduce_axes:
        payload = (_aval_bytes(out.aval)
                   / _shard_count(pl.spec_of(out), pl.mesh))
        pl.emit("all_reduce", tuple(reduce_axes), payload, True,
                eqn.primitive.name, mul)


def _rule_gather(pl: _Planner, eqn, mul: float):
    dn = eqn.params["dimension_numbers"]
    operand, indices = eqn.invars[0], eqn.invars[1]
    out = eqn.outvars[0]
    ospec = pl.spec_of(operand)
    ispec = pl.spec_of(indices)
    slice_sizes = tuple(eqn.params.get("slice_sizes", ()))
    op_shape = tuple(getattr(operand.aval, "shape", ()) or ())
    out_rank = len(out.aval.shape)
    offset = tuple(dn.offset_dims)
    collapsed = set(dn.collapsed_slice_dims)
    out_spec: List[Tuple[str, ...]] = [()] * out_rank
    # offset output dims ← non-collapsed operand dims, in order; the
    # spec survives only full (unsliced) dims
    slice_dims = [d for d in range(len(op_shape)) if d not in collapsed]
    for pos, d in zip(sorted(offset), slice_dims):
        full = (d < len(slice_sizes)
                and int(slice_sizes[d]) == int(op_shape[d]))
        if full:
            out_spec[pos] = ospec[d]
    # batch output dims ← indices dims (minus the index vector dim)
    batch_pos = [p for p in range(out_rank) if p not in offset]
    for p, d in zip(batch_pos, range(len(ispec))):
        out_spec[p] = ispec[d]
    used: set = set()
    final = pl._dedupe(tuple(out_spec), used, _aval_bytes(out.aval),
                       "gather", mul)
    pl.set_spec(out, final)
    # the vocab-parallel pattern: looking up along a SHARDED operand dim
    # lowers to a masked local lookup + one planned all-reduce
    lookup_axes = sorted({a for d in range(len(op_shape))
                          if d in collapsed or (
                              d < len(slice_sizes)
                              and int(slice_sizes[d]) < int(op_shape[d]))
                          for a in ospec[d]})
    if lookup_axes:
        payload = (_aval_bytes(out.aval)
                   / _shard_count(pl.spec_of(out), pl.mesh))
        moe = pl.moe
        # MoE combine: tokens read their slots back out of the
        # expert-sharded [E, C, M] buffer — each chip redistributes its
        # local expert slice over the expert axis (all_to_all of the
        # local slice), rather than all-reducing the gathered output
        if (moe is not None and len(op_shape) >= 2
                and int(op_shape[0]) == int(moe.experts)
                and int(op_shape[1]) == int(moe.capacity)
                and moe.expert_axis in lookup_axes):
            local = (_aval_bytes(operand.aval)
                     / _shard_count(ospec, pl.mesh))
            pl.emit("all_to_all", (moe.expert_axis,), local, True,
                    "gather(moe_combine)", mul)
            rest = tuple(a for a in lookup_axes if a != moe.expert_axis)
            if rest:
                pl.emit("all_reduce", rest, payload, True, "gather", mul)
        else:
            pl.emit("all_reduce", tuple(lookup_axes), payload, True,
                    "gather", mul)


def _rule_scatter(pl: _Planner, eqn, mul: float):
    operand, updates = eqn.invars[0], eqn.invars[-1]
    out = eqn.outvars[0]
    ospec = pl.spec_of(operand)
    pl.set_spec(out, ospec)
    # scatter-add into a differently-sharded target (embedding grad):
    # each chip owns partial updates — a planned grad-sync all-reduce
    if eqn.primitive.name in ("scatter-add", "scatter_add"):
        op_axes = {a for e in ospec for a in e}
        upd_axes = {a for e in pl.spec_of(updates) for a in e}
        sync = sorted(upd_axes - op_axes)
        if sync:
            payload = (_aval_bytes(out.aval)
                       / _shard_count(ospec, pl.mesh))
            pl.emit("all_reduce", tuple(sync), payload, True,
                    eqn.primitive.name, mul)


def _rule_concatenate(pl: _Planner, eqn, mul: float):
    dim = int(eqn.params["dimension"])
    out = eqn.outvars[0]
    rank = len(out.aval.shape)
    merged: List[Tuple[str, ...]] = [()] * rank
    for v in eqn.invars:
        if isinstance(v, Literal):
            continue
        spec = pl.spec_of(v)
        for d in range(min(rank, len(spec))):
            if d != dim and spec[d] and not merged[d]:
                merged[d] = spec[d]
    used: set = set()
    pl.set_spec(out, pl._dedupe(tuple(merged), used,
                                _aval_bytes(out.aval), "concatenate", mul))


def _rule_squeeze(pl: _Planner, eqn, mul: float):
    dims = set(eqn.params.get("dimensions", ()))
    spec = pl.spec_of(eqn.invars[0])
    pl.set_spec(eqn.outvars[0],
                tuple(e for d, e in enumerate(spec) if d not in dims))


def _rule_expand_dims(pl: _Planner, eqn, mul: float):
    dims = set(eqn.params.get("dimensions", ()))
    spec = list(pl.spec_of(eqn.invars[0]))
    out_rank = len(eqn.outvars[0].aval.shape)
    out_spec: List[Tuple[str, ...]] = []
    it = iter(spec)
    for d in range(out_rank):
        out_spec.append(() if d in dims else next(it, ()))
    pl.set_spec(eqn.outvars[0], tuple(out_spec))


def _rule_shape_preserving(pl: _Planner, eqn, mul: float):
    """Ops where output dims correspond 1:1 to input dims but a dim's
    EXTENT may shrink (slice, pad, dynamic_slice...): keep the spec on
    untouched dims, drop it where the extent changed."""
    in_v, out = eqn.invars[0], eqn.outvars[0]
    spec = pl.spec_of(in_v)
    in_shape = tuple(getattr(in_v.aval, "shape", ()) or ())
    out_shape = tuple(out.aval.shape)
    if len(in_shape) != len(out_shape):
        pl.set_spec(out, _rep(len(out_shape)))
        return
    pl.set_spec(out, tuple(
        spec[d] if in_shape[d] == out_shape[d] else ()
        for d in range(len(out_shape))))


def _rule_dynamic_update_slice(pl: _Planner, eqn, mul: float):
    pl.set_spec(eqn.outvars[0], pl.spec_of(eqn.invars[0]))


def _rule_replicated(pl: _Planner, eqn, mul: float):
    for out in eqn.outvars:
        pl.set_spec(out, _rep(_rank(out)))


def _rule_top_k(pl: _Planner, eqn, mul: float):
    """top_k reduces the trailing dim to k: leading dims keep their
    sharding, the shrunken last dim replicates (MoE routing keeps its
    token sharding through the expert choice)."""
    spec = pl.spec_of(eqn.invars[0])
    out_spec = (spec[:-1] + ((),)) if spec else ()
    for out in eqn.outvars:
        pl.set_spec(out, out_spec)


def _rule_ppermute(pl: _Planner, eqn, mul: float):
    """One ring hop: every chip forwards its LOCAL buffer to one
    neighbor over a single ICI edge, so wire bytes = the payload itself
    (factor 1.0), not the ring ``(n-1)/n`` formula.  The ×ring-length
    multiplier arrives through ``mul``: ring attention's fori_loop
    lowers to a scan whose trip count is the ring length."""
    axes = eqn.params.get("axis_name", ())
    if isinstance(axes, str):
        axes = (axes,)
    axes = tuple(str(a) for a in (axes or ()))
    for v, out in zip(eqn.invars, eqn.outvars):
        payload = (_aval_bytes(getattr(v, "aval", None) or out.aval)
                   / _shard_count(pl.spec_of(v), pl.mesh))
        pl.emit("ppermute", axes, payload, True, "ppermute", mul,
                factor=1.0)
        pl.set_spec(out, pl.spec_of(v))


def _names_to_spec(names, rank: int) -> ShardSpec:
    """shard_map in_names/out_names entry ({dim: (axes, ...)}) → spec."""
    spec: List[Tuple[str, ...]] = [()] * rank
    if isinstance(names, dict):
        for dim, axes in names.items():
            d = int(dim)
            if 0 <= d < rank:
                if isinstance(axes, str):
                    axes = (axes,)
                spec[d] = tuple(str(a) for a in axes)
        return tuple(spec)
    return _normalize_spec(names, rank)


def _rule_shard_map(pl: _Planner, eqn, mul: float):
    """Recurse into the per-shard body.  Inner avals are already LOCAL
    (divided by the axes in in_names), so inner invars start replicated
    — every byte and collective payload inside is per-chip as-is — and
    the outer outputs take their global spec straight from out_names."""
    inner = eqn.params["jaxpr"]
    inner = getattr(inner, "jaxpr", inner)
    for iv in inner.invars:
        pl.set_spec(iv, _rep(_rank(iv)))
    pl.run(inner, mul)
    out_names = tuple(eqn.params.get("out_names", ()) or ())
    for i, ov in enumerate(eqn.outvars):
        rank = _rank(ov)
        if i < len(out_names):
            pl.set_spec(ov, _names_to_spec(out_names[i], rank))
        else:
            pl.set_spec(ov, _rep(rank))


def _make_collective_rule(kind: str):
    def rule(pl: _Planner, eqn, mul: float):
        axes = eqn.params.get("axes", eqn.params.get("axis_name", ()))
        if isinstance(axes, str):
            axes = (axes,)
        axes = tuple(str(a) for a in (axes or ()))
        for v, out in zip(eqn.invars, eqn.outvars):
            payload = (_aval_bytes(getattr(v, "aval", None) or out.aval)
                       / _shard_count(pl.spec_of(v), pl.mesh))
            pl.emit(kind, axes, payload, True, eqn.primitive.name, mul)
            pl.set_spec(out, _rep(_rank(out)))
    return rule


_RULES = {
    "dot_general": _rule_dot_general,
    "transpose": _rule_transpose,
    "broadcast_in_dim": _rule_broadcast_in_dim,
    "reshape": _rule_reshape,
    "reduce_sum": _rule_reduce,
    "reduce_max": _rule_reduce,
    "reduce_min": _rule_reduce,
    "reduce_prod": _rule_reduce,
    "reduce_and": _rule_reduce,
    "reduce_or": _rule_reduce,
    "argmax": _rule_reduce,
    "argmin": _rule_reduce,
    "gather": _rule_gather,
    "scatter": _rule_scatter,
    "scatter-add": _rule_scatter,
    "scatter_add": _rule_scatter,
    "concatenate": _rule_concatenate,
    "squeeze": _rule_squeeze,
    "expand_dims": _rule_expand_dims,
    "slice": _rule_shape_preserving,
    "dynamic_slice": _rule_shape_preserving,
    "pad": _rule_shape_preserving,
    "rev": _rule_shape_preserving,
    "dynamic_update_slice": _rule_dynamic_update_slice,
    "iota": _rule_replicated,
    "psum": _make_collective_rule("all_reduce"),
    "all_gather": _make_collective_rule("all_gather"),
    "psum_scatter": _make_collective_rule("reduce_scatter"),
    "all_to_all": _make_collective_rule("all_to_all"),
    "ppermute": _rule_ppermute,
    "shard_map": _rule_shard_map,
    "top_k": _rule_top_k,
}


# ---------------------------------------------------------------------------
# plan_jaxpr — the core entry every wrapper funnels into
# ---------------------------------------------------------------------------

def plan_jaxpr(closed, invar_specs: Sequence[Any], *,
               mesh: Dict[str, int],
               name: str = "<jaxpr>",
               chip: str = "cpu",
               hbm_budget_bytes: Optional[int] = None,
               constvar_specs: Optional[Sequence[Any]] = None,
               extra_var_specs: Sequence[Tuple[Any, Any]] = (),
               param_info: Sequence[Tuple[str, int, Any]] = (),
               data_inputs: Sequence[Tuple[str, int]] = (),
               data_axis: str = "data",
               s205_bytes: int = 1 << 20,
               s206_bytes: int = 8 << 20,
               moe: Optional[MoEStatics] = None,
               topology: Optional[Topology] = None,
               step_kind: Optional[str] = None) -> PlanReport:
    """Propagate ``invar_specs`` (one PartitionSpec-like or None per
    jaxpr invar; ``constvar_specs`` likewise for constvars) through
    ``closed`` on the abstract ``mesh`` and build the
    :class:`PlanReport`.

    ``param_info`` is ``[(name, nbytes, spec)]`` for S206;
    ``data_inputs`` is ``[(label, invar_index)]`` naming which invars
    carry a batch dimension S208 should check.  A ``topology``
    hierarchically decomposes host-spanning collectives into per-link
    ICI/DCN phases; ``step_kind`` names the registered step kind for
    the S213 latency-criticality check.
    """
    profile = CHIPS[chip] if isinstance(chip, str) else chip
    mesh = {str(k): int(v) for k, v in dict(mesh).items()}
    if topology is not None:
        topology.validate(mesh)
    n_chips = 1
    for v in mesh.values():
        n_chips *= v
    jaxpr = closed.jaxpr
    pl = _Planner(mesh, profile, moe=moe)
    for v, spec in zip(jaxpr.invars, list(invar_specs) or []):
        pl.set_spec(v, _normalize_spec(spec, _rank(v)))
    for v, spec in zip(jaxpr.constvars, list(constvar_specs or [])):
        pl.set_spec(v, _normalize_spec(spec, _rank(v)))
    for v, spec in extra_var_specs:
        pl.set_spec(v, _normalize_spec(spec, _rank(v)))
    pl.run(jaxpr)

    # hierarchical decomposition: each flat collective whose axes span
    # hosts becomes per-link phases, re-priced against the matching
    # link profile; the flat list survives for the layout recommender
    flat_collectives = pl.collectives
    if topology is None:
        collectives = flat_collectives
    else:
        collectives = []
        for c in flat_collectives:
            pay = float(c.payload_bytes)
            f0 = (c.bytes_moved / pay
                  if c.kind == "ppermute" and pay else None)
            for ph in topology.phases(c.kind, c.axes, pay, mesh,
                                      factor=f0):
                moved = int(ph.payload_bytes * ph.factor)
                if moved <= 0:
                    continue
                collectives.append(Collective(
                    kind=ph.kind, axes=ph.axes,
                    payload_bytes=int(ph.payload_bytes),
                    bytes_moved=moved,
                    time_s=estimate_collective_time(moved, profile,
                                                    level=ph.level),
                    planned=c.planned, primitive=c.primitive,
                    count=c.count, level=ph.level))

    # whole-program cost (all chips) for the S207 comparison
    acc: Dict[str, List[float]] = {}
    _collect_costs(jaxpr, 1.0, acc)
    flops = sum(v[0] for v in acc.values())
    byts = sum(v[1] for v in acc.values())

    def sharded_bytes(v) -> int:
        b = _var_bytes(v)
        if isinstance(v, Literal) or b == 0:
            return b
        n = _shard_count(pl.spec_of(v), pl.mesh)
        return -(-b // n)  # ceil: padding never under-counts

    peak, peak_by_dtype = _peak_live_by_dtype(jaxpr, sharded_bytes)

    where = f"shardplan:{name}"
    diags: List[Diagnostic] = []

    # S205 — resharding hotspots: unplanned gathers grouped per
    # (primitive, axes) edge so one conflicted layer reads as one finding
    grouped: Dict[Tuple[str, Tuple[str, ...], str], float] = {}
    for c in pl.collectives:
        if not c.planned:
            key = (c.primitive, c.axes, c.kind)
            grouped[key] = grouped.get(key, 0.0) + c.total_bytes
    for (prim, axes, kind), total in sorted(grouped.items()):
        if total >= s205_bytes:
            diags.append(Diagnostic(
                "S205", ERROR,
                f"resharding hotspot: spec conflict at '{prim}' forces an "
                f"unplanned {kind} over mesh axes {list(axes)} moving "
                f"{total / 1024:.1f} KiB/chip — the layout fights itself "
                "on this edge; re-shard the producer or consumer so both "
                "agree", where))

    # S206 — fully-replicated large parameter: every chip burns its
    # full size (undonated-style HBM waste times the whole mesh)
    for pname, nbytes, spec in param_info:
        nspec = _normalize_spec(spec, len(spec or ()))
        if any(e for e in nspec) or nbytes < s206_bytes:
            continue
        diags.append(Diagnostic(
            "S206", WARNING,
            f"param {pname!r} ({nbytes / 2**20:.1f} MiB) is fully "
            f"replicated across all {n_chips} chips — "
            f"{nbytes * n_chips / 2**20:.1f} MiB of mesh HBM for one "
            "tensor; shard it on 'fsdp' unless it is genuinely tiny",
            where))

    # S207 — collective-bound step, level-aware: the bound is the
    # slowest link the step actually touches, not aggregate bandwidth
    comm_t = sum(c.total_time_s for c in collectives)
    compute_t = estimate_compute_time(flops / max(1, n_chips),
                                      byts / max(1, n_chips), profile)
    if comm_t > compute_t:
        ici_t = sum(c.total_time_s for c in collectives
                    if c.level != "dcn")
        dcn_t = comm_t - ici_t
        if topology is not None and dcn_t > 0:
            slow = "DCN" if dcn_t >= ici_t else "ICI"
            split = (f" (ICI {ici_t * 1e6:.1f} µs + DCN "
                     f"{dcn_t * 1e6:.1f} µs; bound by the {slow} link)")
            hint = ("move the heaviest axis onto ICI, shard less "
                    "aggressively, or grow the per-chip work")
        else:
            split = ""
            hint = ("shard less aggressively or grow the per-chip "
                    "work")
            slow = "ICI"
        diags.append(Diagnostic(
            "S207", ERROR,
            f"collective-bound: estimated comm {comm_t * 1e6:.1f} µs "
            f"exceeds per-chip compute {compute_t * 1e6:.1f} µs on "
            f"{profile.name}{split} — the mesh spends the step waiting "
            f"on {slow}; {hint}", where))

    # S208 — batch dim not on the data axis
    d_size = mesh.get(data_axis, 1)
    if d_size > 1:
        for label, idx in data_inputs:
            if idx >= len(jaxpr.invars):
                continue
            v = jaxpr.invars[idx]
            shape = tuple(getattr(v.aval, "shape", ()) or ())
            if not shape or shape[0] <= 1 or shape[0] % d_size != 0:
                continue  # batch=1 (chunked prefill) legitimately can't
            spec = pl.spec_of(v)
            if data_axis not in (spec[0] if spec else ()):
                diags.append(Diagnostic(
                    "S208", WARNING,
                    f"batch dim of input {label!r} {shape} is not sharded "
                    f"on the {data_axis!r} axis (size {d_size}) — the "
                    "whole batch is replicated; data parallelism buys "
                    "nothing for this input", where))

    # S210 — unpriced collective primitive: the plan silently omits its
    # traffic, which defeats the whole point of planning first
    for prim, axes in sorted(set(pl.unknown_collectives)):
        diags.append(Diagnostic(
            "S210", ERROR,
            f"unpriced collective primitive '{prim}' over mesh axes "
            f"{list(axes) or '<unknown>'}: the planner has no "
            "propagation/pricing rule for it, so its wire traffic is "
            "MISSING from this plan — add a rule to shardplan._RULES "
            "before trusting any number in this report", where))

    # S211 — static expert capacity overflow: top-k routing mass vs the
    # declared capacity-padded buffer; overflowing slots drop tokens
    if moe is not None:
        demand = int(moe.tokens) * int(moe.top_k)
        supply = int(moe.experts) * int(moe.capacity)
        if demand > supply:
            diags.append(Diagnostic(
                "S211", ERROR,
                f"static expert capacity overflow: {moe.tokens} tokens × "
                f"top-{moe.top_k} = {demand} routed slots but E×C = "
                f"{moe.experts}×{moe.capacity} = {supply} at capacity "
                f"factor {moe.capacity_factor:g} — "
                f"{demand - supply} routing choices are statically "
                "guaranteed to drop; raise the capacity factor or the "
                "expert count", where))

    # S212 — ring hop that cannot hide under compute: the per-hop
    # permute must overlap one hop's worth of local attention compute
    # (ICI hops only — a DCN-priced hop is S215's finding)
    for c in collectives:
        if c.kind != "ppermute" or c.level == "dcn":
            continue
        hops = max(1.0, float(c.count))
        window = compute_t / hops
        if c.time_s > window:
            diags.append(Diagnostic(
                "S212", WARNING,
                f"ring/sp hop moves {c.bytes_moved / 1024:.1f} KiB over "
                f"{list(c.axes)} taking {c.time_s * 1e6:.1f} µs on "
                f"{profile.name} ICI, but only {window * 1e6:.1f} µs of "
                "per-hop compute exists to hide it — the ring is "
                "ICI-bound; grow the per-chip sequence chunk or use a "
                "faster interconnect", where))

    # S213 — DCN-crossing collective inside a latency-critical step:
    # decode/prefill sit on the request critical path, and one 10 µs+
    # DCN round per layer is the difference between serving and not.
    # Edges under the floor (scalar-sized control reduces the
    # conservative gather rule prices) stay priced but unflagged.
    if topology is not None and step_kind in LATENCY_CRITICAL_STEP_KINDS:
        edge_bytes: Dict[Tuple[str, Tuple[str, ...]], float] = {}
        for c in collectives:
            if c.level == "dcn":
                key = (c.kind, c.axes)
                edge_bytes[key] = edge_bytes.get(key, 0.0) + c.total_bytes
        hot = {k: b for k, b in edge_bytes.items()
               if b >= _S213_FLOOR_BYTES}
        if hot:
            total = sum(hot.values())
            n = sum(1 for c in collectives if c.level == "dcn"
                    and (c.kind, c.axes) in hot)
            edges = sorted(f"{kind} over {'×'.join(axes)}"
                           for kind, axes in hot)
            diags.append(Diagnostic(
                "S213", ERROR,
                f"DCN-crossing collective in latency-critical step "
                f"kind {step_kind!r}: {n} phase(s) "
                f"({'; '.join(edges)}) move {total / 1024:.1f} KiB/chip "
                "over the data-center network on the request critical "
                "path — keep every serving axis (tp/sp) inside one "
                "host's ICI domain and cross hosts only on the batch "
                "axis, which decode never reduces over", where))

    # S214 — a hotter axis rides DCN while a colder same-size axis
    # rides ICI: swapping the assignment is free at plan time
    if topology is not None:
        axis_splits = topology.splits(mesh)
        traffic: Dict[str, float] = {}
        for c in flat_collectives:
            for a in c.axes:
                traffic[a] = traffic.get(a, 0.0) + c.total_bytes
        dcn_axes = [a for a in mesh
                    if axis_splits.get(a, (1, 1))[1] > 1]
        ici_axes = [a for a in mesh if mesh[a] > 1
                    and axis_splits.get(a, (1, 1))[1] == 1]
        best = None
        for d in dcn_axes:
            for i in ici_axes:
                if mesh[d] != mesh[i]:
                    continue  # unequal sizes: swap changes the layout
                gain = traffic.get(d, 0.0) - traffic.get(i, 0.0)
                if gain > 0 and (best is None or gain > best[0]):
                    best = (gain, d, i)
        if best is not None:
            _, d, i = best
            diags.append(Diagnostic(
                "S214", WARNING,
                f"high-traffic axis {d!r} "
                f"({traffic.get(d, 0.0) / 1024:.1f} KiB/chip) is mapped "
                f"to DCN while axis {i!r} "
                f"({traffic.get(i, 0.0) / 1024:.1f} KiB/chip) rides "
                f"ICI — both are size {mesh[d]}; swap the assignment "
                f"(axis_levels={{{i!r}: 'dcn', {d!r}: 'ici'}}) to move "
                "the heavy traffic onto the fast link", where))

    # S215 — DCN phase that cannot hide behind the step's compute
    # window (the cross-host mirror of S212's ICI check); one finding
    # per (kind, axes) edge, reporting its slowest phase
    if topology is not None:
        worst: Dict[Tuple[str, Tuple[str, ...]], Collective] = {}
        for c in collectives:
            if c.level != "dcn":
                continue
            window = compute_t / max(1.0, float(c.count))
            if c.time_s <= window:
                continue
            key = (c.kind, c.axes)
            if key not in worst or c.time_s > worst[key].time_s:
                worst[key] = c
        for (kind, axes), c in sorted(worst.items()):
            window = compute_t / max(1.0, float(c.count))
            diags.append(Diagnostic(
                "S215", WARNING,
                f"DCN phase {kind} over {list(axes)} moves "
                f"{c.bytes_moved / 1024:.1f} KiB/chip taking "
                f"{c.time_s * 1e6:.1f} µs on {profile.name} DCN, but "
                f"only {window * 1e6:.1f} µs of per-occurrence compute "
                "exists to hide it — the cross-host traffic sits "
                "exposed on the step's critical path; overlap it "
                "against compute or move the axis onto ICI", where))

    if hbm_budget_bytes is not None and peak > hbm_budget_bytes:
        diags.append(Diagnostic(
            "H110", ERROR,
            f"per-chip peak live HBM {peak / 2**30:.3f} GiB exceeds the "
            f"{hbm_budget_bytes / 2**30:.3f} GiB per-chip budget on this "
            f"{_mesh_str(mesh)} mesh — shard further, shrink the batch, "
            "or pick a bigger chip", where))

    from .hazards import sort_diagnostics

    param_specs = {pname: _spec_str(_normalize_spec(spec, len(spec or ())))
                   for pname, _, spec in param_info}
    return PlanReport(
        name=name, chip=profile, mesh=mesh, n_chips=n_chips,
        per_chip_peak_hbm_bytes=peak, collectives=collectives,
        flops=flops, bytes=byts, diagnostics=sort_diagnostics(diags),
        param_specs=param_specs, hbm_budget_bytes=hbm_budget_bytes,
        per_chip_peak_hbm_by_dtype=peak_by_dtype, topology=topology,
        flat_collectives=flat_collectives, step_kind=step_kind)


def _mesh_str(mesh: Dict[str, int]) -> str:
    return "(" + ",".join(f"{k}={v}" for k, v in mesh.items()) + ")"


# ---------------------------------------------------------------------------
# wrappers: train step, serving step, the default audit
# ---------------------------------------------------------------------------

def _param_names(sfn) -> Dict[int, str]:
    """id(param) → qualified name, walked over the layers the static
    function discovered (the model is always among them)."""
    names: Dict[int, str] = {}
    for layer in (sfn._layers or ()):
        for n, p in layer.named_parameters():
            names.setdefault(id(p), n)
    return names


def plan_train_step(step_fn, inputs, labels, *,
                    request: Optional[PlanRequest] = None,
                    name: str = "hapi::train_step") -> PlanReport:
    """Plan a ``jit.to_static`` train step (or its observability
    wrapper) on sample ``inputs``/``labels``.  The trace's invar layout
    is ``state ++ dyn ++ lrs ++ rng``; params take the layout's role
    spec, optimizer slots inherit their param's spec, inputs take the
    batch spec, everything else replicates."""
    req = request or PlanRequest()
    layout = req.resolved_layout()
    sfn = getattr(step_fn, "_fn", step_fn)
    closed, _donated = sfn.trace_jaxpr(inputs, labels)
    state = sfn._state
    names = _param_names(sfn)
    by_id: Dict[int, Any] = {}
    param_info: List[Tuple[str, int, Any]] = []
    for i, p in enumerate(state.params):
        pname = names.get(id(p), f"param{i}")
        spec = layout.param_spec(pname)
        by_id[id(p)] = spec
        param_info.append((pname, _aval_bytes(p._value), spec))

    n_in = len(closed.jaxpr.invars)
    n_p, n_b = len(state.params), len(state.buffers)
    slots = state.opt_slots()
    specs: List[Any] = [None] * n_in
    for i, p in enumerate(state.params):
        if i < n_in:
            specs[i] = by_id[id(p)]
    for j, (_store, key) in enumerate(slots):
        idx = n_p + n_b + j
        if idx < n_in and key in by_id:
            specs[idx] = by_id[key]      # slot keyed by id(param)
    dyn_lo, dyn_hi = n_p + n_b + len(slots), n_in - 2
    data_inputs: List[Tuple[str, int]] = []
    batch = layout.batch_spec()
    for idx in range(dyn_lo, dyn_hi):
        specs[idx] = batch
        data_inputs.append((f"dyn{idx - dyn_lo}", idx))
    return plan_jaxpr(
        closed, specs, mesh=req.mesh, name=name, chip=req.chip,
        hbm_budget_bytes=req.hbm_budget_bytes, param_info=param_info,
        data_inputs=data_inputs, data_axis=layout.data_axis,
        s205_bytes=req.s205_bytes, s206_bytes=req.s206_bytes,
        moe=req.moe, topology=req.topology, step_kind="train")


def plan_step(step, abstract_args: Sequence[Any], *, model,
              arg_specs: Sequence[Any],
              request: Optional[PlanRequest] = None,
              name: str = "<step>",
              data_input_leaves: Sequence[Tuple[str, int]] = (),
              step_kind: Optional[str] = None) -> PlanReport:
    """Plan a serving-style step traced with ``jax.make_jaxpr``.  The
    model weights are captured as jit CONSTANTS, so they surface as
    jaxpr constvars — matched back to named parameters by identity.
    ``arg_specs`` mirrors ``abstract_args``' pytree structure;
    ``data_input_leaves`` names flat leaf indices S208 should check."""
    from .xray import _as_abstract

    req = request or PlanRequest()
    layout = req.resolved_layout()
    fn = step
    if hasattr(fn, "_fn") and hasattr(fn, "compiles"):
        fn = fn._fn
    args = [jax.tree_util.tree_map(_as_abstract, a,
                                   is_leaf=lambda x: hasattr(x, "_value"))
            for a in abstract_args]
    closed = jax.make_jaxpr(fn)(*args)
    flat_specs: List[Any] = []
    for spec, arg in zip(arg_specs, args):
        _flatten_specs_like(spec, arg, flat_specs)
    # jitted steps trace to one pjit eqn: the captured weights are
    # consts of NESTED closed jaxprs, not the top level — walk them all
    by_value: Dict[int, str] = {id(p._value): n
                                for n, p in model.named_parameters()}
    extra: List[Tuple[Any, Any]] = []
    param_info: List[Tuple[str, int, Any]] = []
    seen: set = set()
    for var, val in _iter_const_bindings(closed):
        pname = by_value.get(id(val))
        if pname is None:
            continue
        spec = layout.param_spec(pname)
        extra.append((var, spec))
        if pname not in seen:
            seen.add(pname)
            param_info.append((pname, _var_bytes(var), spec))
    return plan_jaxpr(
        closed, flat_specs, mesh=req.mesh, name=name, chip=req.chip,
        hbm_budget_bytes=req.hbm_budget_bytes,
        extra_var_specs=extra, param_info=param_info,
        data_inputs=data_input_leaves, data_axis=layout.data_axis,
        s205_bytes=req.s205_bytes, s206_bytes=req.s206_bytes,
        moe=req.moe, topology=req.topology, step_kind=step_kind)


def _iter_const_bindings(closed):
    """Yield ``(constvar, const_value)`` pairs for a ClosedJaxpr and
    every ClosedJaxpr nested in its equations (pjit / scan / while /
    cond / custom_* all carry their own consts)."""
    yield from zip(closed.jaxpr.constvars, closed.consts)
    for eqn in closed.jaxpr.eqns:
        for key in ("jaxpr", "call_jaxpr", "fun_jaxpr", "cond_jaxpr",
                    "body_jaxpr"):
            inner = eqn.params.get(key)
            if inner is not None and hasattr(inner, "consts"):
                yield from _iter_const_bindings(inner)
        for b in eqn.params.get("branches", ()):
            if hasattr(b, "consts"):
                yield from _iter_const_bindings(b)


def _flatten_specs_like(spec, arg, out: List[Any]):
    """Walk ``spec`` alongside ``arg``'s container structure, emitting
    one spec per array leaf in jax flattening order.  A PartitionSpec
    (or None) against a container broadcasts over every leaf under it."""
    from jax.sharding import PartitionSpec

    if isinstance(arg, dict):
        for k in sorted(arg):
            sub = spec.get(k) if isinstance(spec, dict) else spec
            _flatten_specs_like(sub, arg[k], out)
        return
    if isinstance(arg, (list, tuple)):
        broadcast = (spec is None or isinstance(spec, PartitionSpec)
                     or not isinstance(spec, (list, tuple)))
        for i, a in enumerate(arg):
            _flatten_specs_like(spec if broadcast else spec[i], a, out)
        return
    out.append(spec)


def _serving_arg_specs(model, layout, decode_args, prefill_args):
    """Specs mirroring ``xray._serving_abstract_args``' structure: KV
    pools shard kv-heads on ``tp`` (SNIPPETS [3] style), per-sequence
    buffers shard batch on ``data``; prefill runs batch=1, replicated.
    Quantized pool entries carry two extra per-row scale sidecars
    ([num_blocks, block_size], no kv-head axis) that REPLICATE — the
    spec tuples mirror the entry arity so spec flattening stays
    one-to-one with the args."""
    from jax.sharding import PartitionSpec

    tp = layout.tp_axis
    pool_spec = []
    for entry in decode_args[1]:
        specs = (PartitionSpec(None, None, tp, None),
                 PartitionSpec(None, None, tp, None))
        if len(entry) == 4:
            specs += (PartitionSpec(None, None),
                      PartitionSpec(None, None))
        pool_spec.append(specs)
    batch = layout.batch_spec()
    decode = (batch, pool_spec, batch, batch)
    prefill = (PartitionSpec(), pool_spec, PartitionSpec(),
               PartitionSpec(), PartitionSpec())
    return decode, prefill


#: audit_shardplan's default step set and the canonical mesh each step
#: falls back to when the caller's mesh lacks its required axis
DEFAULT_AUDIT_STEPS = ("train", "decode", "prefill", "sampled_decode",
                       "spec_verify", "moe", "ring")
_MOE_AUDIT_MESH = {"data": 2, "fsdp": 2, "expert": 2}
_RING_AUDIT_MESH = {"data": 2, "sp": 2, "tp": 2}


def audit_shardplan(*, chip: str = "cpu",
                    hbm_budget_bytes: Optional[int] = None,
                    mesh: Optional[Dict[str, int]] = None,
                    layout: Any = None,
                    s205_bytes: int = 1 << 10,
                    s206_bytes: int = 8 << 20,
                    steps: Sequence[str] = DEFAULT_AUDIT_STEPS,
                    topology: Optional[Topology] = None
                    ) -> List[PlanReport]:
    """Plan the default step kinds (train, paged decode, chunked
    prefill, MoE block, ring/sp block) for tiny Llamas against the
    canonical llama SpecLayout — entirely on CPU, no devices.  The
    ``lint_tpu.py --shardplan`` / CI entry point; callers gate on
    ``report.errors()``.

    Train/decode/prefill plan on the caller's mesh (default
    ``(data=2, fsdp=2, tp=2)``); the MoE step needs an ``expert`` axis
    and the ring step an ``sp`` axis, so each falls back to its
    canonical mesh (``_MOE_AUDIT_MESH`` / ``_RING_AUDIT_MESH``) when
    the caller's mesh lacks it.  ``steps`` filters which kinds run.

    The S205 threshold defaults to 1 KiB here (not the production
    1 MiB): the CI model is tiny, and a CLEAN layout emits zero
    unplanned collectives regardless of scale — any unplanned byte on
    this model means real conflict at any size."""
    import paddle_tpu as paddle
    from .. import nn
    from ..models import LlamaConfig, LlamaForCausalLM
    from ..optimizer import AdamW

    req = PlanRequest(mesh=mesh or {"data": 2, "fsdp": 2, "tp": 2},
                      layout=layout, chip=chip,
                      hbm_budget_bytes=hbm_budget_bytes,
                      s205_bytes=s205_bytes, s206_bytes=s206_bytes,
                      topology=topology)
    lay = req.resolved_layout()
    paddle.seed(0)
    cfg = LlamaConfig.tiny()
    net = LlamaForCausalLM(cfg)
    reports: List[PlanReport] = []

    if "train" in steps:
        model = paddle.Model(net)
        model.prepare(AdamW(1e-3, parameters=net.parameters()),
                      nn.CrossEntropyLoss())
        ids = np.zeros((2, 16), np.int64)
        reports.append(plan_train_step(
            model._train_step_fn, [paddle.to_tensor(ids[:, :-1])],
            [paddle.to_tensor(ids[:, 1:])], request=req))

    from ..models.generation import (make_chunked_prefill_step,
                                     make_moe_block_step,
                                     make_paged_decode_step,
                                     make_ring_sp_step)
    from .xray import _serving_abstract_args

    net.eval()
    serving_kinds = {"decode", "prefill", "fused_decode", "fused_prefill",
                     "sampled_decode", "spec_verify"}
    if serving_kinds & set(steps):
        decode_args, prefill_args = _serving_abstract_args(
            net, batch=4, num_blocks=32, block_size=8,
            max_blocks_per_seq=8, chunk_tokens=32)
        decode_specs, prefill_specs = _serving_arg_specs(
            net, lay, decode_args, prefill_args)
        if "decode" in steps:
            reports.append(plan_step(
                make_paged_decode_step(net), decode_args, model=net,
                arg_specs=decode_specs, request=req,
                name="serving::decode_step",
                data_input_leaves=(("tokens", 0),),
                step_kind="paged_decode"))
        if "prefill" in steps:
            reports.append(plan_step(
                make_chunked_prefill_step(net), prefill_args, model=net,
                arg_specs=prefill_specs, request=req,
                name="serving::prefill_step",
                data_input_leaves=(("chunk_ids", 0),),
                step_kind="chunked_prefill"))
        # fused serving steps (kernels/fusion forced on, XLA fallback
        # off-TPU): same shapes and latency-critical step kinds as the
        # unfused plans — the CI gate that the fused programs plan
        # without S210 unknown-collective blind spots
        # sampled decode + speculative verify (ISSUE 19): the decode/
        # chunked-prefill shapes plus per-slot sampling state.  All the
        # sampling-state arrays are slot-indexed, so they shard exactly
        # like the batch inputs; draft proposal distributions [S, K, V]
        # likewise shard on the slot dim only.
        if {"sampled_decode", "spec_verify"} & set(steps):
            from ..serving.sampling import make_sampled_decode_step
            from ..serving.speculative import make_spec_verify_step

            sds_ = jax.ShapeDtypeStruct
            s_batch, num_draft = 4, 4
            b_spec = lay.batch_spec()
            sampling_args = (sds_((s_batch,), np.float32),
                             sds_((s_batch,), np.int32),
                             sds_((s_batch,), np.float32),
                             sds_((s_batch, 2), np.uint32),
                             sds_((s_batch,), np.int32))
            sampling_specs = (b_spec,) * 5
            if "sampled_decode" in steps:
                reports.append(plan_step(
                    make_sampled_decode_step(net),
                    decode_args + sampling_args, model=net,
                    arg_specs=decode_specs + sampling_specs,
                    request=req, name="serving::sampled_decode_step",
                    data_input_leaves=(("tokens", 0),),
                    step_kind="sampled_decode"))
            if "spec_verify" in steps:
                pool_spec = decode_specs[1]
                verify_args = (
                    sds_((s_batch,), np.int32),
                    sds_((s_batch, num_draft), np.int32),
                    sds_((s_batch, num_draft, cfg.vocab_size),
                         np.float32),
                    decode_args[1], decode_args[2], decode_args[3]
                ) + sampling_args
                # slot-indexed verify args stay REPLICATED: the
                # acceptance math reshapes [S, K+1] into [S*(K+1)],
                # and a batch-sharded slot dim would turn that reshape
                # into data-axis collectives on the decode critical
                # path (S213).  The pool still shards on tp like the
                # plain decode step.
                from jax.sharding import PartitionSpec
                rep = PartitionSpec()
                verify_specs = (rep, rep, rep, pool_spec,
                                rep, rep) + (rep,) * 5
                reports.append(plan_step(
                    make_spec_verify_step(net, num_draft), verify_args,
                    model=net, arg_specs=verify_specs, request=req,
                    name="serving::spec_verify_step",
                    data_input_leaves=(("pending", 0),),
                    step_kind="spec_verify"))
        if "fused_decode" in steps:
            reports.append(plan_step(
                make_paged_decode_step(net, fused=True), decode_args,
                model=net, arg_specs=decode_specs, request=req,
                name="serving::decode_step[fused]",
                data_input_leaves=(("tokens", 0),),
                step_kind="paged_decode"))
        if "fused_prefill" in steps:
            reports.append(plan_step(
                make_chunked_prefill_step(net, fused=True), prefill_args,
                model=net, arg_specs=prefill_specs, request=req,
                name="serving::prefill_step[fused]",
                data_input_leaves=(("chunk_ids", 0),),
                step_kind="chunked_prefill"))

    sds = jax.ShapeDtypeStruct
    if "moe" in steps:
        from ..kernels.moe_dispatch import moe_capacity

        moe_mesh = (req.mesh if "expert" in (req.mesh or {})
                    else dict(_MOE_AUDIT_MESH))
        E, K, cf = 4, 2, 2.0
        B, T = 4, 16
        moe_req = dataclasses.replace(
            req, mesh=moe_mesh,
            moe=MoEStatics(experts=E, capacity=moe_capacity(B * T, E, K, cf),
                           top_k=K, tokens=B * T, capacity_factor=cf))
        moe_net = LlamaForCausalLM(LlamaConfig.tiny(
            moe_num_experts=E, moe_top_k=K, moe_capacity_factor=cf))
        moe_net.eval()
        reports.append(plan_step(
            make_moe_block_step(moe_net), (sds((B, T), np.int32),),
            model=moe_net, arg_specs=(lay.batch_spec(),),
            request=moe_req, name="moe::block_step",
            data_input_leaves=(("tokens", 0),),
            step_kind="moe_block"))

    if "ring" in steps:
        from ..distributed.mesh import abstract_mesh

        ring_mesh = (req.mesh if "sp" in (req.mesh or {})
                     else dict(_RING_AUDIT_MESH))
        ring_req = dataclasses.replace(req, mesh=ring_mesh, moe=None)
        ring_net = LlamaForCausalLM(LlamaConfig.tiny(
            context_parallel="ring"))
        ring_net.eval()
        reports.append(plan_step(
            make_ring_sp_step(ring_net, mesh=abstract_mesh(ring_mesh)),
            (sds((4, 32), np.int32),),
            model=ring_net, arg_specs=(lay.batch_spec(),),
            request=ring_req, name="ring::sp_step",
            data_input_leaves=(("tokens", 0),),
            step_kind="ring_sp"))

    for r in reports:
        export_plan_gauges(r)
    return reports


def export_plan_gauges(report: PlanReport):
    """Mirror a plan's headline numbers into the observability registry
    (no-op when telemetry is disabled)."""
    from .. import observability

    if not observability.enabled():
        return
    reg = observability.get_registry()
    reg.gauge("shardplan_comm_bytes",
              "total per-chip collective wire bytes of a planned step"
              ).set(report.comm_bytes, step=report.name)
    reg.gauge("shardplan_ici_comm_bytes",
              "per-chip wire bytes a planned step puts on intra-host ICI"
              ).set(report.ici_comm_bytes, step=report.name)
    reg.gauge("shardplan_dcn_comm_bytes",
              "per-chip wire bytes a planned step puts on cross-host DCN"
              ).set(report.dcn_comm_bytes, step=report.name)
    reg.gauge("shardplan_per_chip_peak_hbm_bytes",
              "shard-aware liveness peak HBM per chip of a planned step"
              ).set(report.per_chip_peak_hbm_bytes, step=report.name)
    g = reg.gauge("shardplan_per_chip_peak_hbm_bytes_by_dtype",
                  "per-chip bytes of one dtype at the liveness peak")
    for dt, b in sorted(report.per_chip_peak_hbm_by_dtype.items()):
        g.set(b, step=report.name, dtype=dt)


def recommend_layouts(report: PlanReport, *,
                      hosts: Optional[int] = None,
                      chips_per_host: Optional[Tuple[int, ...]] = None):
    """Rank every valid axis→level assignment for ``report``'s mesh by
    the comm time it would give this step — repricing the flat
    collective inventory the propagation already produced (no
    re-trace).  ``hosts`` defaults to the report's topology.  Returns
    :class:`~paddle_tpu.analysis.topology.RankedLayout` objects, best
    first; render with
    :func:`~paddle_tpu.analysis.topology.format_recommendations`."""
    if hosts is None:
        if report.topology is None:
            raise ValueError(
                "recommend_layouts needs hosts=: the report was "
                "planned without a Topology")
        hosts = report.topology.hosts
        if chips_per_host is None:
            chips_per_host = report.topology.chips_per_host
    flat = report.flat_collectives or report.collectives
    return rank_layouts(flat, report.mesh, report.chip, hosts,
                        chips_per_host)
