"""Fusion-candidate miner: xray-driven static fusion analysis.

PR 13 fused the serving decode hot path BY HAND (paged gather + RoPE +
attention; RMSNorm→matmul prologues).  The fusion literature
(FusionStitching, arXiv:2009.10924; "Operator Fusion in XLA",
arXiv:2301.13062) argues the durable win is *systematic* discovery of
memory-bound fusion chains — so this module closes the ROADMAP's
"analysis-driven fusion expansion" loop: walk any traced step's jaxpr
with xray's cost model and let the analyzer rank the next kernel.

Algorithm (:func:`mine_jaxpr`):

1. **Classify** every equation at each jaxpr level (recursing through
   pjit/scan/cond/while/custom_* exactly like xray's ``_sub_jaxprs``;
   ``pallas_call`` is a priced leaf): matmuls/convs are *anchors*,
   elementwise/movement/reduction/transcendental equations are
   *fusible*, scatters/sorts/callbacks are *barriers*.  A call-like
   equation whose body is entirely fusible (jnp helpers like ``_take``,
   ``silu``, ``floor_divide``) is folded in as one fusible node instead
   of fragmenting the chain.
2. **Chain** fusible equations into maximal groups: a producer joins
   its consumers' group when every consumer of the connecting variable
   is fusible and lands in ONE group (single-consumer dataflow edges
   plus diamond closure — e.g. softmax's ``exp`` feeding both its
   ``reduce_sum`` and the final ``div``), iterated to a fixpoint.
3. **Absorb across anchors**: a chain output consumed only by matmuls
   can fuse as their prologue; a chain input produced by a matmul whose
   only consumer is the chain can fuse as its epilogue.  Chains
   connected through a *data* anchor (both operands locally produced —
   attention's score and context matmuls) merge into one region, the
   shape of a flash-attention kernel; *weight* anchors (an operand is a
   program input) bound regions the way a real GEMM bounds an XLA
   fusion group.
4. **Price** each region with xray's per-primitive byte model: an
   intermediate that stays in VMEM saves one HBM write + one read
   (``2 × bytes``); a chain output absorbed into ``n`` anchors saves
   ``(1 + n) × bytes``; scan-carried chains multiply by the trip
   count.  Time saved = bytes / the chip profile's HBM bandwidth (the
   roofline memory leg — these chains are memory-bound by
   construction).
5. **Rank and report** structurally-identical regions grouped by
   (code, source site, primitive signature) as F-series diagnostics:

   - **F001** fusible elementwise/movement chain (generic)
   - **F002** norm→matmul prologue candidate (reduce+rescale chain
     feeding only matmuls — the ``fused_norm_linear`` shape)
   - **F003** reduction→elementwise epilogue candidate (region
     containing a reduction downstream of an anchor — softmax /
     attention-region shape)
   - **F004** already-fused leaf (a priced ``pallas_call``; reported
     for coverage, excluded from ranking)

   Ranking: bytes-saved descending, ties by (file, line).  Diagnostics
   go through ``hazards.sort_diagnostics`` and honor the lint-tpu
   suppression comments (``# lint-tpu: disable=F001 -- reason`` on the
   flagged line, ``disable-file=`` anywhere in the file).

Surfaced by ``tools/lint_tpu.py --xray --fusion [--json]`` and the CI
fusion stage; validated in tests/test_fusionminer.py by rediscovering
both PR 13 hand-built fusions as the top-ranked candidates on the
unfused serving traces and as F004-covered on the fused ones.
"""
from __future__ import annotations

import dataclasses
import os
from typing import Any, Dict, List, Optional, Sequence, Tuple

import jax
import numpy as np
from jax.extend.core import Literal

from .verifier import ERROR, INFO, WARNING, Diagnostic
from .hazards import _where_key, sort_diagnostics
from .xray import (CHIPS, ChipProfile, _as_abstract, _eqn_bytes,
                   _pallas_kernel_name, _sub_jaxprs, _var_bytes)

__all__ = [
    "FusionCandidate",
    "FusionReport",
    "audit_fusion",
    "mine",
    "mine_jaxpr",
]

_ANCHORS = {"dot_general", "conv_general_dilated"}
# fusible data movement; scatter/dynamic_update_slice rewrite a full
# buffer in place (the output escapes by construction) and sort/top_k
# reorder globally — none of those belong inside a memory-bound chain
_BARRIERS = {
    "scatter", "scatter_add", "scatter_mul", "scatter_min", "scatter_max",
    "dynamic_update_slice", "sort", "top_k", "copy", "device_put",
    "pure_callback", "io_callback", "outside_call", "debug_callback",
    "debug_print",
    "rng_bit_generator", "random_seed", "random_wrap", "random_bits",
    "infeed", "outfeed", "custom_call",
}
_REDUCES = ("reduce_", "cum", "arg")


# the repo's own op-dispatch plumbing: frames here emitted the eqn but
# the line a human would fuse (and suppress) lives one level up, in
# model/kernel code
_INTERNAL_FRAMES = (os.sep + os.path.join("paddle_tpu", "core") + os.sep,
                    os.sep + os.path.join("paddle_tpu", "ops") + os.sep,
                    os.sep + os.path.join("paddle_tpu", "nn") + os.sep)


def _source_where(eqn) -> str:
    """``file:line`` of the innermost NON-PLUMBING user frame that
    emitted ``eqn`` (the same location the lint-tpu suppression
    comments key on)."""
    # private: jax.extend.source_info_util exposes no frame walker
    from jax._src import source_info_util

    frames = list(source_info_util.user_frames(eqn.source_info.traceback))
    frame = None
    for fr in frames:
        if not any(part in fr.file_name for part in _INTERNAL_FRAMES):
            frame = fr
            break
    if frame is None:
        frame = frames[0] if frames else None
    if frame is None:
        return "<unknown>:0"
    return f"{frame.file_name}:{frame.start_line}"


def _eqn_kind(eqn) -> str:
    name = eqn.primitive.name
    if name == "pallas_call":
        return "fused_leaf"
    if name in _ANCHORS:
        return "anchor"
    if name in _BARRIERS:
        return "barrier"
    if _sub_jaxprs(eqn):
        return "call"
    return "fusible"


def _transparent(jaxpr) -> bool:
    """A call body made ONLY of fusible equations (recursively): the
    call folds into the surrounding chain as one node instead of
    splitting it — jnp helpers (``_take``, ``_where``, ``silu``,
    ``floor_divide``) trace as tiny pjits."""
    for eqn in jaxpr.eqns:
        kind = _eqn_kind(eqn)
        if kind == "call":
            subs = _sub_jaxprs(eqn)
            if len(subs) != 1 or not _transparent(subs[0][0]):
                return False
        elif kind != "fusible":
            return False
    return True


def _inner_interior_bytes(jaxpr) -> float:
    """Bytes of a transparent call body's own intermediates (everything
    its equations define short of the body outputs)."""
    outs = set(v for v in jaxpr.outvars
               if not isinstance(v, Literal))
    total = 0.0
    for eqn in jaxpr.eqns:
        for inner, _ in _sub_jaxprs(eqn):
            total += _inner_interior_bytes(inner)
        for v in eqn.outvars:
            if v not in outs and not isinstance(v, jax.core.DropVar):
                total += _var_bytes(v)
    return total


def _leaf_primitives(eqn) -> List[str]:
    subs = _sub_jaxprs(eqn)
    if not subs:
        return [eqn.primitive.name]
    names: List[str] = []
    for inner, _ in subs:
        for e in inner.eqns:
            names.extend(_leaf_primitives(e))
    return names


def _contains_reduce(eqn) -> bool:
    return any(p.startswith(_REDUCES) for p in _leaf_primitives(eqn))


class _UnionFind:
    def __init__(self):
        self.parent: Dict[int, int] = {}

    def find(self, x: int) -> int:
        root = x
        while self.parent.setdefault(root, root) != root:
            root = self.parent[root]
        while self.parent[x] != root:
            self.parent[x], x = root, self.parent[x]
        return root

    def union(self, a: int, b: int) -> bool:
        ra, rb = self.find(a), self.find(b)
        if ra == rb:
            return False
        self.parent[max(ra, rb)] = min(ra, rb)
        return True


@dataclasses.dataclass
class _Region:
    """One mined fusion region before cross-layer grouping."""

    code: str
    where: str
    path: str
    primitives: Tuple[str, ...]        # leaf primitive signature
    n_eqns: int
    bytes_saved: float
    prologue_anchors: Tuple[str, ...]  # anchor primitive names fed
    epilogue_anchors: Tuple[str, ...]  # anchor primitive names followed
    interior_anchors: int              # data matmuls inside the region


@dataclasses.dataclass
class FusionCandidate:
    """One ranked fusion opportunity (structurally identical regions
    grouped across layers/sites)."""

    code: str                  # F001 / F002 / F003
    where: str                 # file:line of the region's first eqn
    path: str                  # jaxpr call path ("pjit", "pjit/scan")
    primitives: Tuple[str, ...]
    n_eqns: int                # leaf eqns in ONE region
    count: int                 # structurally identical regions merged
    bytes_saved: float         # HBM round-trip bytes across all sites
    time_saved_s: float        # bytes_saved / chip HBM bandwidth
    prologue_anchors: Tuple[str, ...]
    epilogue_anchors: Tuple[str, ...]
    interior_anchors: int
    rank: Optional[int] = None
    suppressed: bool = False

    def describe(self) -> str:
        prims = ", ".join(self.primitives[:6])
        if len(self.primitives) > 6:
            prims += f", +{len(self.primitives) - 6} more"
        rank = f"#{self.rank}: " if self.rank else ""
        sites = f" x{self.count} site(s)" if self.count > 1 else ""
        edges = []
        if self.interior_anchors:
            edges.append(f"spans {self.interior_anchors} data matmul(s)")
        if self.epilogue_anchors:
            edges.append("follows " + "/".join(
                sorted(set(self.epilogue_anchors))))
        if self.prologue_anchors:
            edges.append("feeds " + "/".join(
                sorted(set(self.prologue_anchors))))
        tail = f" [{'; '.join(edges)}]" if edges else ""
        return (f"{rank}fusible chain of {self.n_eqns} memory-bound "
                f"eqn(s) ({prims}){sites} — est "
                f"{self.bytes_saved / 2**10:.1f} KiB HBM round-trips "
                f"saved ({self.time_saved_s * 1e6:.2f} us){tail}")

    def to_json(self) -> Dict[str, Any]:
        return {
            "code": self.code,
            "rank": self.rank,
            "where": self.where,
            "path": self.path,
            "primitives": list(self.primitives),
            "n_eqns": self.n_eqns,
            "count": self.count,
            "bytes_saved": float(self.bytes_saved),
            "time_saved_s": float(self.time_saved_s),
            "prologue_anchors": list(self.prologue_anchors),
            "epilogue_anchors": list(self.epilogue_anchors),
            "interior_anchors": self.interior_anchors,
            "suppressed": self.suppressed,
        }


@dataclasses.dataclass
class FusionReport:
    """Mined fusion candidates of one traced step."""

    name: str
    chip: ChipProfile
    candidates: List[FusionCandidate]   # ranked, F001–F003
    covered: List[FusionCandidate]      # F004 pallas leaves, unranked
    diagnostics: List[Diagnostic]       # through sort_diagnostics
    threshold_bytes: float = 0.0

    def errors(self) -> List[Diagnostic]:
        return [d for d in self.diagnostics if d.severity == ERROR]

    def above_threshold(self) -> List[FusionCandidate]:
        """Unsuppressed non-F004 candidates at/over the bytes gate —
        what the CI fused-step stage requires to be EMPTY."""
        return [c for c in self.candidates
                if not c.suppressed and c.bytes_saved >= self.threshold_bytes]

    def summary(self) -> str:
        total = sum(c.bytes_saved for c in self.candidates
                    if not c.suppressed)
        n_sup = sum(1 for c in self.candidates if c.suppressed)
        sup = f", {n_sup} suppressed" if n_sup else ""
        return (f"[fusion] {self.name}: {len(self.candidates)} "
                f"candidate(s) ({len(self.above_threshold())} at/above "
                f"{self.threshold_bytes / 2**10:.0f} KiB{sup}), "
                f"{len(self.covered)} fused leaf group(s), est "
                f"{total / 2**20:.2f} MiB HBM round-trips recoverable "
                f"@ {self.chip.name}")

    def table(self, top: int = 8) -> str:
        rows = [f"{'rank':<6}{'code':<6}{'KiB saved':>10}{'us':>8}"
                f"{'sites':>6}  where"]
        for c in self.candidates[:top]:
            mark = " (suppressed)" if c.suppressed else ""
            rows.append(
                f"{('#' + str(c.rank)) if c.rank else '-':<6}{c.code:<6}"
                f"{c.bytes_saved / 2**10:>10.1f}"
                f"{c.time_saved_s * 1e6:>8.2f}{c.count:>6}  "
                f"{os.path.basename(c.where)}{mark}")
        for c in self.covered:
            rows.append(
                f"{'-':<6}{c.code:<6}{'-':>10}{'-':>8}{c.count:>6}  "
                f"{os.path.basename(c.where)} (already fused)")
        return "\n".join(rows)

    def to_json(self) -> Dict[str, Any]:
        """Machine-readable report (``lint_tpu --xray --fusion --json``)
        — diagnostics use the same shape as shardplan's ``to_json``."""
        return {
            "name": self.name,
            "chip": self.chip.name,
            "threshold_bytes": float(self.threshold_bytes),
            "candidates": [c.to_json() for c in self.candidates],
            "covered": [c.to_json() for c in self.covered],
            "n_above_threshold": len(self.above_threshold()),
            "diagnostics": [
                {"code": d.code, "severity": d.severity,
                 "message": d.message, "where": d.where}
                for d in self.diagnostics],
        }


# ---------------------------------------------------------------------------
# the mining walk
# ---------------------------------------------------------------------------

def _mine_level(jaxpr, mul: float, path: str, regions: List[_Region],
                leaves: List[Tuple[str, str, float]]):
    """Mine one open jaxpr level; recurse through non-transparent calls
    (scan trips multiply the savings).  ``leaves`` collects
    (kernel_name, where, priced_bytes) per pallas_call."""
    eqns = list(jaxpr.eqns)
    kinds: List[str] = []
    for eqn in eqns:
        kind = _eqn_kind(eqn)
        if kind == "call":
            subs = _sub_jaxprs(eqn)
            if len(subs) == 1 and _transparent(subs[0][0]):
                kind = "fusible"
            else:
                for inner, m in subs:
                    _mine_level(inner, mul * m,
                                f"{path}/{eqn.primitive.name}",
                                regions, leaves)
                kind = "barrier"
        elif kind == "fused_leaf":
            leaves.append((_pallas_kernel_name(eqn), _source_where(eqn),
                           mul * _eqn_bytes(eqn)))
        kinds.append(kind)

    free = set(v for v in tuple(jaxpr.invars) + tuple(jaxpr.constvars))
    escaping = set(v for v in jaxpr.outvars
                   if not isinstance(v, Literal))
    producer: Dict[Any, int] = {}
    consumers: Dict[Any, List[int]] = {}
    for i, eqn in enumerate(eqns):
        for v in eqn.outvars:
            if not isinstance(v, jax.core.DropVar):
                producer[v] = i
        for v in eqn.invars:
            if not isinstance(v, Literal):
                consumers.setdefault(v, []).append(i)

    # chain growth to a fixpoint: a fusible producer joins its
    # consumers when every consumer is fusible and already in ONE
    # group (covers single-consumer edges and softmax-style diamonds)
    uf = _UnionFind()
    changed = True
    while changed:
        changed = False
        for v, prod in producer.items():
            if kinds[prod] != "fusible" or v in escaping:
                continue
            cons = sorted(set(consumers.get(v, ())))
            if not cons or any(kinds[c] != "fusible" for c in cons):
                continue
            roots = {uf.find(c) for c in cons}
            if len(roots) == 1:
                changed |= uf.union(prod, roots.pop())

    comp_eqns: Dict[int, List[int]] = {}
    for i, kind in enumerate(kinds):
        if kind == "fusible":
            comp_eqns.setdefault(uf.find(i), []).append(i)

    # per-component savings and anchor edges
    stats: Dict[int, Dict[str, Any]] = {}
    weight_anchor: Dict[int, bool] = {}
    for i, kind in enumerate(kinds):
        if kind == "anchor":
            weight_anchor[i] = any(
                v in free for v in eqns[i].invars
                if not isinstance(v, Literal))
    for root, members in comp_eqns.items():
        mset = set(members)
        interior = 0.0
        n_leaf = 0
        prims: List[str] = []
        reduce_flag = False
        for i in members:
            leaf = _leaf_primitives(eqns[i])
            prims.extend(leaf)
            n_leaf += len(leaf)
            reduce_flag |= _contains_reduce(eqns[i])
            interior += _inner_interior_bytes_of_call(eqns[i])
            for v in eqns[i].outvars:
                if isinstance(v, jax.core.DropVar) or v in escaping:
                    continue
                cons = set(consumers.get(v, ()))
                if cons and cons <= mset:
                    interior += 2.0 * _var_bytes(v)
        prologue = 0.0
        prologue_to: List[int] = []
        epilogue = 0.0
        epilogue_from: List[int] = []
        seen_in: set = set()
        for i in members:
            for v in eqns[i].invars:
                if isinstance(v, Literal) or v in seen_in:
                    continue
                seen_in.add(v)
                prod = producer.get(v)
                if prod is None or prod in mset:
                    continue
                if kinds[prod] == "anchor" and v not in escaping and \
                        set(consumers.get(v, ())) <= mset:
                    epilogue += 2.0 * _var_bytes(v)
                    epilogue_from.append(prod)
            for v in eqns[i].outvars:
                if isinstance(v, jax.core.DropVar) or v in escaping:
                    continue
                outside = sorted(set(consumers.get(v, ())) - mset)
                if outside and all(kinds[c] == "anchor" for c in outside):
                    prologue += (1.0 + len(outside)) * _var_bytes(v)
                    prologue_to.extend(outside)
        stats[root] = {
            "members": members, "interior": interior,
            "prologue": prologue, "prologue_to": prologue_to,
            "epilogue": epilogue, "epilogue_from": epilogue_from,
            "prims": prims, "n_leaf": n_leaf, "reduce": reduce_flag,
        }

    # region merge THROUGH data anchors (both operands locally
    # produced: attention score/context matmuls); weight anchors bound
    # regions like a real GEMM bounds an XLA fusion group
    ruf = _UnionFind()
    anchor_feeders: Dict[int, List[int]] = {}
    anchor_followers: Dict[int, List[int]] = {}
    for root, st in stats.items():
        for a in st["prologue_to"]:
            anchor_feeders.setdefault(a, []).append(root)
        for a in st["epilogue_from"]:
            anchor_followers.setdefault(a, []).append(root)
    for a, is_weight in weight_anchor.items():
        if is_weight:
            continue
        linked = anchor_feeders.get(a, []) + anchor_followers.get(a, [])
        for other in linked[1:]:
            ruf.union(linked[0], other)

    merged: Dict[int, List[int]] = {}
    for root in stats:
        merged.setdefault(ruf.find(root), []).append(root)

    for mroot, group in merged.items():
        interior = sum(stats[r]["interior"] for r in group)
        prologue = sum(stats[r]["prologue"] for r in group)
        epilogue = sum(stats[r]["epilogue"] for r in group)
        bytes_saved = (interior + prologue + epilogue) * mul
        if bytes_saved <= 0.0:
            continue
        members = sorted(i for r in group for i in stats[r]["members"])
        prims: List[str] = []
        for r in group:
            prims.extend(stats[r]["prims"])
        reduce_flag = any(stats[r]["reduce"] for r in group)
        # a data matmul fed by one of this region's chains AND followed
        # by another is interior: the region spans it (flash-attention
        # shape — both attention matmuls live inside the fused kernel)
        group_set = set(group)
        anchors_in = {
            a for a, is_weight in weight_anchor.items()
            if not is_weight
            and set(anchor_feeders.get(a, ())) & group_set
            and set(anchor_followers.get(a, ())) & group_set}
        prologue_names = sorted({
            eqns[a].primitive.name for r in group
            for a in stats[r]["prologue_to"] if a not in anchors_in})
        epilogue_names = sorted({
            eqns[a].primitive.name for r in group
            for a in stats[r]["epilogue_from"] if a not in anchors_in})
        if reduce_flag and (epilogue_names or anchors_in):
            code = "F003"
        elif reduce_flag and prologue_names:
            code = "F002"
        else:
            code = "F001"
        regions.append(_Region(
            code=code, where=_source_where(eqns[members[0]]), path=path,
            primitives=tuple(prims), n_eqns=len(prims),
            bytes_saved=bytes_saved,
            prologue_anchors=tuple(prologue_names),
            epilogue_anchors=tuple(epilogue_names),
            interior_anchors=len(anchors_in)))


def _inner_interior_bytes_of_call(eqn) -> float:
    """Interior bytes hidden inside a transparent call node (zero for a
    plain primitive)."""
    subs = _sub_jaxprs(eqn)
    if not subs:
        return 0.0
    return sum(_inner_interior_bytes(inner) for inner, _ in subs)


# ---------------------------------------------------------------------------
# suppression (the lint-tpu comment mechanism, applied to jaxpr sites)
# ---------------------------------------------------------------------------

_SUPPRESS_CACHE: Dict[str, Tuple[set, Dict[int, set]]] = {}


def _file_suppressions(path: str) -> Tuple[set, Dict[int, set]]:
    cached = _SUPPRESS_CACHE.get(path)
    if cached is not None:
        return cached
    from . import astlint

    try:
        with open(path, "r", encoding="utf-8") as fh:
            src = fh.read()
    except OSError:
        result: Tuple[set, Dict[int, set]] = (set(), {})
    else:
        result = astlint._suppressions(src)
    _SUPPRESS_CACHE[path] = result
    return result


def _is_suppressed(code: str, where: str) -> bool:
    fname, line = _where_key(where)
    if not fname or not os.path.isabs(fname):
        return False
    from . import astlint

    file_codes, line_codes = _file_suppressions(fname)
    return astlint._suppressed(code, line, file_codes, line_codes)


# ---------------------------------------------------------------------------
# entry points
# ---------------------------------------------------------------------------

def mine_jaxpr(closed, *, name: str = "<jaxpr>", chip: str = "v5e",
               threshold_bytes: float = 0.0,
               suppress: bool = True) -> FusionReport:
    """Mine a ClosedJaxpr for fusion candidates (see module docstring).

    ``threshold_bytes`` sets the severity split: candidates saving at
    least this much are WARNING (and count for ``above_threshold`` /
    the CI gate), smaller ones are INFO.  ``suppress=False`` keeps
    lint-tpu-suppressed candidates in the ranking (they are marked but
    never WARNING)."""
    profile = CHIPS[chip] if isinstance(chip, str) else chip
    regions: List[_Region] = []
    leaves: List[Tuple[str, str, float]] = []
    _mine_level(closed.jaxpr, 1.0, "", regions, leaves)

    # group structurally identical regions (same code, source site and
    # primitive signature — one model line traced per layer)
    grouped: Dict[Tuple[str, str, Tuple[str, ...]], FusionCandidate] = {}
    for r in regions:
        key = (r.code, r.where, tuple(sorted(r.primitives)))
        cand = grouped.get(key)
        if cand is None:
            grouped[key] = FusionCandidate(
                code=r.code, where=r.where, path=r.path,
                primitives=r.primitives, n_eqns=r.n_eqns, count=1,
                bytes_saved=r.bytes_saved,
                time_saved_s=r.bytes_saved / profile.hbm_bandwidth,
                prologue_anchors=r.prologue_anchors,
                epilogue_anchors=r.epilogue_anchors,
                interior_anchors=r.interior_anchors)
        else:
            cand.count += 1
            cand.bytes_saved += r.bytes_saved
            cand.time_saved_s = cand.bytes_saved / profile.hbm_bandwidth

    candidates = list(grouped.values())
    for c in candidates:
        c.suppressed = bool(suppress) and _is_suppressed(c.code, c.where)
    # ranking: bytes-saved desc, ties by (file, line); suppressed
    # candidates drop out of the ranking (and the exit-code gate)
    candidates.sort(key=lambda c: (-c.bytes_saved,) + _where_key(c.where))
    rank = 0
    for c in candidates:
        if c.suppressed:
            c.rank = None
        else:
            rank += 1
            c.rank = rank

    covered_by: Dict[Tuple[str, str], FusionCandidate] = {}
    for kernel, where, bytes_priced in leaves:
        key = (kernel, where)
        cand = covered_by.get(key)
        if cand is None:
            covered_by[key] = FusionCandidate(
                code="F004", where=where, path="", primitives=(kernel,),
                n_eqns=1, count=1, bytes_saved=0.0, time_saved_s=0.0,
                prologue_anchors=(), epilogue_anchors=(),
                interior_anchors=0)
        else:
            cand.count += 1
    covered = sorted(covered_by.values(),
                     key=lambda c: (c.primitives[0],) + _where_key(c.where))

    diags: List[Diagnostic] = []
    for c in candidates:
        if c.suppressed:
            continue
        sev = WARNING if c.bytes_saved >= threshold_bytes else INFO
        diags.append(Diagnostic(c.code, sev, c.describe(), c.where))
    for c in covered:
        diags.append(Diagnostic(
            "F004", INFO,
            f"already fused: pallas kernel '{c.primitives[0]}' "
            f"x{c.count} (priced via kernels.costs) — excluded from "
            "ranking", c.where))
    return FusionReport(
        name=name, chip=profile, candidates=candidates, covered=covered,
        diagnostics=sort_diagnostics(diags),
        threshold_bytes=float(threshold_bytes))


def mine(step, abstract_args: Sequence[Any], *,
         name: Optional[str] = None, chip: str = "v5e",
         threshold_bytes: float = 0.0,
         suppress: bool = True) -> FusionReport:
    """Trace ``step`` on abstract args (xray.analyze's convention) and
    mine the jaxpr."""
    fn = step
    if hasattr(fn, "_fn") and hasattr(fn, "compiles"):
        fn = fn._fn
    args = [jax.tree_util.tree_map(_as_abstract, a,
                                   is_leaf=lambda x: hasattr(x, "_value"))
            for a in abstract_args]
    closed = jax.make_jaxpr(fn)(*args)
    return mine_jaxpr(closed,
                      name=name or getattr(step, "__name__", "<step>"),
                      chip=chip, threshold_bytes=threshold_bytes,
                      suppress=suppress)


#: default CI gate: a fused serving step must leave nothing this big
#: unfused.  Calibrated on the tiny audit model: the kernel-scale
#: attention regions mine at ~1.6 MiB per step, while the largest
#: chain the fused steps legitimately leave behind (the chunk RoPE
#: gather chain) is ~340 KiB — the gate sits between the two
DEFAULT_THRESHOLD_BYTES = 512 * 1024


def audit_fusion(*, chip: str = "cpu",
                 threshold_bytes: float = DEFAULT_THRESHOLD_BYTES,
                 fused: bool = False,
                 suppress: bool = True) -> List[FusionReport]:
    """Mine the registered serving steps on the tiny audit model
    (mirrors ``xray.audit_default_steps``'s serving half) — the
    ``lint_tpu --xray --fusion`` / CI entry point.

    ``fused=True`` additionally mines the FUSED decode/prefill steps
    traced under ``force_pallas_interpret()`` so the programs carry the
    real ``pallas_call`` leaves on any backend: the hand-fused chains
    must come back as F004 coverage, not as candidates — CI gates that
    ``above_threshold()`` is empty for those reports."""
    import paddle_tpu as paddle
    from ..kernels.fusion import force_pallas_interpret
    from ..models import LlamaConfig, LlamaForCausalLM
    from ..models.generation import (make_chunked_prefill_step,
                                     make_paged_decode_step)
    from .xray import _serving_abstract_args

    paddle.seed(0)
    net = LlamaForCausalLM(LlamaConfig.tiny())
    net.eval()
    decode_args, prefill_args = _serving_abstract_args(
        net, batch=4, num_blocks=32, block_size=8,
        max_blocks_per_seq=8, chunk_tokens=32)
    reports = [
        mine(make_paged_decode_step(net, fused=False), decode_args,
             name="serving::decode_step", chip=chip,
             threshold_bytes=threshold_bytes, suppress=suppress),
        mine(make_chunked_prefill_step(net, fused=False), prefill_args,
             name="serving::prefill_step", chip=chip,
             threshold_bytes=threshold_bytes, suppress=suppress),
    ]
    if fused:
        with force_pallas_interpret():
            reports.append(mine(
                make_paged_decode_step(net, fused=True), decode_args,
                name="serving::decode_step[fused]", chip=chip,
                threshold_bytes=threshold_bytes, suppress=suppress))
            reports.append(mine(
                make_chunked_prefill_step(net, fused=True), prefill_args,
                name="serving::prefill_step[fused]", chip=chip,
                threshold_bytes=threshold_bytes, suppress=suppress))
    return reports
