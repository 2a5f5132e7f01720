# lint-tpu: disable-file=L004 -- serving-layer host-side control plane
# (like router.py); new backend code belongs under core/ ops/ kernels/
# static/ distributed/ (README: Repo lint)
"""Multi-tenant trace replay for the serving fleet router
(README "Serving fleet & router").

A *trace* is a seeded, deterministic arrival schedule over a few tenant
archetypes — the mixes a real fleet sees at once:

* **chat** — many short requests sharing one long system prompt (the
  prefix-affinity jackpot: after the first request lands, every
  follow-up re-prefills only its tail);
* **long** — few requests with long, mostly-unique prompts (prefill
  pressure; affinity helps only within the tenant's shared preamble);
* **burst** — a clump of near-simultaneous short arrivals (queueing
  pressure; load-term territory).

``build_trace`` materializes the schedule (all randomness from ONE
``numpy.random.RandomState(seed)`` — same seed, same trace, byte for
byte); ``replay_trace`` feeds it through a :class:`Router` step by
step and reports per-tenant goodput and TTFT tails plus fleet-level
placement/cache counters.  ONE trace through an affinity fleet and a
round-robin fleet (``tests/test_router.py``): the affinity fleet should
win on cached-token ratio.
"""
from __future__ import annotations

from dataclasses import dataclass, field
from typing import Dict, List, Optional, Sequence

import numpy as np

from .router import Router
from .scheduler import AdmissionError


@dataclass
class Tenant:
    """One workload archetype in the replayed mix."""

    name: str
    kind: str = "chat"                # "chat" | "long" | "burst"
    requests: int = 8
    shared_prefix_tokens: int = 48    # tokens every request shares
    tail_tokens: tuple = (4, 16)      # unique suffix length range
    max_new_tokens: int = 8
    deadline_s: Optional[float] = None
    priority: int = 0
    # sampled-tenant archetype (ISSUE 19): temperature > 0 routes the
    # tenant's requests through the seeded sampling path; each request
    # gets a trace-deterministic per-request seed so the same trace
    # replays the same token streams byte for byte
    temperature: float = 0.0
    top_k: int = 0
    top_p: float = 1.0


def default_tenants() -> List[Tenant]:
    """The stock four-tenant mix (module docstring): a chatty tenant
    with a big shared system prompt, a long-prompt tenant, a burst
    tenant that clumps its arrivals, and a sampled tenant exercising
    the seeded temperature/top-k/top-p decode path."""
    return [
        Tenant("chat", kind="chat", requests=10,
               shared_prefix_tokens=48, tail_tokens=(4, 12),
               max_new_tokens=8),
        Tenant("long", kind="long", requests=4,
               shared_prefix_tokens=16, tail_tokens=(40, 72),
               max_new_tokens=6),
        Tenant("burst", kind="burst", requests=8,
               shared_prefix_tokens=24, tail_tokens=(2, 8),
               max_new_tokens=4),
        Tenant("sampled", kind="chat", requests=4,
               shared_prefix_tokens=32, tail_tokens=(4, 10),
               max_new_tokens=6, temperature=0.8, top_k=16, top_p=0.95),
    ]


@dataclass
class Arrival:
    """One request of the trace: submit at router-iteration ``step``."""

    step: int
    tenant: str
    prompt: np.ndarray
    max_new_tokens: int
    deadline_s: Optional[float]
    priority: int
    request_id: str = ""
    # seeded sampling (0.0 temperature = greedy, seed ignored)
    temperature: float = 0.0
    top_k: int = 0
    top_p: float = 1.0
    seed: Optional[int] = None


def build_trace(tenants: Optional[Sequence[Tenant]] = None, *,
                seed: int = 0, horizon: int = 24, vocab: int = 256
                ) -> List[Arrival]:
    """Materialize the deterministic arrival schedule.

    Every tenant gets a seeded shared prefix; each of its requests is
    that prefix plus a seeded unique tail.  chat/long arrivals spread
    uniformly over ``horizon`` router iterations; a burst tenant clumps
    ALL its arrivals into a two-iteration window.  Token id 0 is
    avoided (tiny test models use 0 as pad/eos)."""
    tenants = list(tenants) if tenants is not None else default_tenants()
    rng = np.random.RandomState(seed)

    def toks(n):
        return rng.randint(1, vocab, size=n).astype(np.int32)

    arrivals: List[Arrival] = []
    for t in tenants:
        shared = toks(t.shared_prefix_tokens)
        if t.kind == "burst":
            start = int(rng.randint(0, max(1, horizon - 2)))
            steps = start + rng.randint(0, 2, size=t.requests)
        else:
            steps = rng.randint(0, horizon, size=t.requests)
        lo, hi = t.tail_tokens
        for i in range(t.requests):
            tail = toks(int(rng.randint(lo, hi + 1)))
            arrivals.append(Arrival(
                step=int(steps[i]), tenant=t.name,
                prompt=np.concatenate([shared, tail]),
                max_new_tokens=t.max_new_tokens,
                deadline_s=t.deadline_s, priority=t.priority,
                request_id=f"{t.name}-{i}",
                temperature=t.temperature, top_k=t.top_k, top_p=t.top_p,
                # per-request seed drawn from the trace rng: sampled
                # outputs are as reproducible as the schedule itself
                seed=(int(rng.randint(0, 2**31 - 1))
                      if t.temperature > 0 else None)))
    # stable order: by arrival step, tenant name, then index — NOT by
    # rng state, so the submit order is reproducible and readable
    arrivals.sort(key=lambda a: (a.step, a.tenant, a.request_id))
    return arrivals


def _pctl(values: List[float], q: float) -> Optional[float]:
    vals = sorted(v for v in values if v is not None)
    if not vals:
        return None
    return vals[min(len(vals) - 1, int(round(q * (len(vals) - 1))))]


@dataclass
class _TenantTally:
    submitted: int = 0
    finished: Dict[str, int] = field(default_factory=dict)
    goodput_tokens: int = 0
    ttfts: List[float] = field(default_factory=list)


def replay_trace(router: Router, trace: Sequence[Arrival]) -> dict:
    """Feed ``trace`` through ``router`` — each arrival submits at its
    scheduled iteration between ``router.step()`` calls, then the fleet
    drains — and report per-tenant outcomes plus fleet counters.

    Goodput follows metrics.py: tokens from requests finishing inside
    their SLO (eos/stop/length).  TTFTs come from the finishing
    replica's request timelines (compile excluded as long as the caller
    warmed the fleet first)."""
    pending = sorted(trace, key=lambda a: a.step)
    tallies: Dict[str, _TenantTally] = {}
    by_rid: Dict[str, str] = {}
    i = 0
    step = 0
    results: Dict[str, object] = {}
    timelines: Dict[str, dict] = {}
    retired: Dict[str, int] = {}    # replica -> metrics.completed read
    while i < len(pending) or router.has_work():
        while i < len(pending) and pending[i].step <= step:
            a = pending[i]
            i += 1
            tally = tallies.setdefault(a.tenant, _TenantTally())
            tally.submitted += 1
            by_rid[a.request_id] = a.tenant
            try:
                router.submit(a.prompt,
                              max_new_tokens=a.max_new_tokens,
                              deadline_s=a.deadline_s,
                              priority=a.priority,
                              request_id=a.request_id,
                              temperature=a.temperature,
                              do_sample=a.temperature > 0,
                              top_k=a.top_k, top_p=a.top_p,
                              seed=a.seed)
            except AdmissionError:
                # bounded-queue backpressure is a legitimate outcome of
                # an overload trace — tally it, don't crash the replay
                tally.finished["rejected"] = \
                    tally.finished.get("rejected", 0) + 1
        router.step()
        step += 1
        # a replica keeps the timelines of its last
        # ``metrics.FINISHED_REQUESTS`` retired requests, a replay may
        # hold more: read what each step retired as it goes (from
        # whichever replica finished the request)
        for rep in router.replicas:
            metrics = rep.engine.metrics
            new = metrics.completed - retired.get(rep.name, 0)
            retired[rep.name] = metrics.completed
            timelines.update(list(metrics.finished)[-new:] if new else ())
    results.update(router.run_until_complete())
    for rid, req in results.items():
        tenant = by_rid.get(rid)
        if tenant is None:
            continue
        tally = tallies[tenant]
        reason = req.finish_reason or "unknown"
        tally.finished[reason] = tally.finished.get(reason, 0) + 1
        if reason in ("eos", "stop", "length"):
            tally.goodput_tokens += req.num_generated
        tl = timelines.get(rid)
        if tl is not None and tl["ttft_s"] is not None:
            tally.ttfts.append(tl["ttft_s"])
    fleet_ttfts = [t for tally in tallies.values() for t in tally.ttfts]
    stats = router.stats()
    return {
        "tenants": {
            name: {
                "submitted": tally.submitted,
                "finished": dict(sorted(tally.finished.items())),
                "goodput_tokens": tally.goodput_tokens,
                "mean_ttft_s": (sum(tally.ttfts) / len(tally.ttfts)
                                if tally.ttfts else None),
                "p99_ttft_s": _pctl(tally.ttfts, 0.99),
            }
            for name, tally in sorted(tallies.items())
        },
        "fleet": {
            "policy": router.policy,
            "requests": len(results),
            "cached_token_ratio": stats["router"]["cached_token_ratio"],
            "placements": stats["router"]["placements"],
            "shed_global": stats["router"]["requests_shed_global"],
            "quarantines": stats["router"]["replica_quarantines"],
            "resubmits": stats["router"]["requests_resubmitted"],
            "p99_ttft_s": _pctl(fleet_ttfts, 0.99),
            "mean_ttft_s": (sum(fleet_ttfts) / len(fleet_ttfts)
                            if fleet_ttfts else None),
        },
    }


__all__ = ["Tenant", "Arrival", "default_tenants", "build_trace",
           "replay_trace"]
