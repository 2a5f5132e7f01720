"""Request lifecycle + scheduling policy (PAPERS.md: Orca's
iteration-level scheduling).

Policy, in one paragraph: admission is FCFS by arrival ordinal over a
BOUNDED wait queue (a full queue rejects at submit time — backpressure
instead of unbounded latency).  A request is admitted only when the
block pool can hold its prompt plus one decode block (capacity-based
admission control).  When a running sequence needs a block and the pool
is dry, the YOUNGEST running request is preempted — evict-and-requeue
at the queue head, keeping its original ordinal — so the oldest work
always finishes first and no request starves (the fairness half of
"FCFS + fairness").  Preemption drops the victim's generated tokens and
recomputes from the prompt on re-admission (vLLM's "recompute" mode);
under greedy decoding the final output is unchanged.

Termination is the SAME check ``generate()`` uses:
``models.generation.match_stop`` over the generated suffix, plus
eos_token_id and max_new_tokens.
"""
from __future__ import annotations

import itertools
import time
from collections import deque
from dataclasses import dataclass, field
from typing import Deque, List, Optional

import numpy as np

from ..models.generation import match_stop, normalize_stop_sequences


class AdmissionError(Exception):
    """Request rejected at submit time (backpressure or impossible fit)."""


class QueueFull(AdmissionError):
    """The bounded wait queue is at capacity.  Distinguished from the
    impossible-fit AdmissionError so the engine's overload layer can
    respond differently: a higher-priority arrival may shed the
    lowest-priority waiting request instead of being turned away."""


# request states
QUEUED = "queued"
PREFILLING = "prefilling"   # admitted; prompt chunks still being computed
RUNNING = "running"
PREEMPTED = "preempted"
FINISHED = "finished"

_ordinal = itertools.count()


@dataclass(eq=False)
class Request:
    """One generation request and its runtime state.  Identity equality
    (``eq=False``): requests are mutable runtime objects living in
    scheduler lists — field comparison over numpy prompts is both
    ambiguous and wrong."""

    prompt: np.ndarray                      # 1-D int32 token ids
    max_new_tokens: int = 32
    eos_token_id: Optional[int] = None
    stop_sequences: List[List[int]] = field(default_factory=list)
    request_id: str = ""
    # per-request SLO on the MONOTONIC clock (time.monotonic, immune to
    # wall-clock steps — hazard H111): the request is retired with
    # finish_reason "timeout" once deadline_s seconds have elapsed since
    # submission, whether it is still queued or mid-decode (partial
    # tokens kept)
    deadline_s: Optional[float] = None
    # priority class for overload control (serving/overload.py): higher
    # wins.  Admission prefers the highest-priority waiting request,
    # preemption and queue-full shedding take the LOWEST priority first
    # (youngest within a class).  All-default workloads reduce exactly
    # to the FCFS + fairness policy above.
    priority: int = 0
    # sampling spec (serving/sampling.SamplingParams) or None for
    # greedy; sampling_key is the request's base PRNG key ([2] uint32),
    # fixed at submit so preemption + recompute replays the exact token
    # stream (keys are derived from TOKEN INDEX, not step count)
    sampling: Optional[object] = None
    sampling_key: Optional[np.ndarray] = field(default=None, repr=False)
    # streaming (serving/stream.py): on_token fires once per ACCEPTED
    # token; token_deadline_s is a ROLLING inter-token SLO — the
    # monotonic token_deadline_t resets on every emitted token, and a
    # stream that stalls past it times out like a busted deadline_s
    # (it also bounds time-to-first-token, so the load shedder treats
    # it as an effective TTFT deadline)
    on_token: Optional[object] = field(default=None, repr=False)
    token_deadline_s: Optional[float] = None
    token_deadline_t: Optional[float] = field(default=None, repr=False)
    # runtime (engine-owned)
    ordinal: int = field(default_factory=lambda: next(_ordinal))
    state: str = QUEUED
    slot: Optional[int] = None
    blocks: List[int] = field(default_factory=list)
    # a model with window layers: the window group's pages this request
    # holds now, page index -> block (serving/cache.py advance_window)
    window_pages: dict = field(default_factory=dict)
    generated: List[int] = field(default_factory=list)
    # "eos" | "stop" | "length" | "timeout" | "error"
    finish_reason: Optional[str] = None
    error: Optional[str] = None             # set with finish_reason "error"
    preemptions: int = 0
    deadline_t: Optional[float] = field(default=None, repr=False)
    # chunked-prefill progress (engine-owned): tokens whose KV is
    # already in the pool, how many of those came from the prefix cache,
    # and how many prefill chunks this admission has run
    prefill_pos: int = 0
    cached_tokens: int = 0
    prefill_chunks: int = 0
    # where this admission's prefill ends: the prompt's length, or its
    # whole blocks for a model that generates by diffusion over blocks
    # (the tail then opens the first generated block)
    prefill_end: int = 0

    def __post_init__(self):
        self.prompt = np.asarray(self.prompt, np.int32).reshape(-1)
        if not self.request_id:
            self.request_id = f"req-{self.ordinal}"
        if self.prompt.size == 0:
            raise ValueError("empty prompt")
        if self.max_new_tokens < 1:
            raise ValueError("max_new_tokens must be >= 1")
        if self.deadline_s is not None:
            if self.deadline_s < 0:
                raise ValueError("deadline_s must be >= 0")
            self.deadline_t = time.monotonic() + self.deadline_s
        if self.token_deadline_s is not None:
            if self.token_deadline_s < 0:
                raise ValueError("token_deadline_s must be >= 0")
            self.token_deadline_t = time.monotonic() + self.token_deadline_s

    def expired(self) -> bool:
        """Past the per-request deadline or the rolling inter-token
        deadline (both on the monotonic clock)."""
        if self.deadline_t is not None \
                and time.monotonic() >= self.deadline_t:
            return True
        return self.token_deadline_t is not None \
            and time.monotonic() >= self.token_deadline_t

    @property
    def prompt_len(self) -> int:
        return int(self.prompt.size)

    @property
    def num_generated(self) -> int:
        return len(self.generated)

    @property
    def total_len(self) -> int:
        """Current cache frontier: prompt + tokens already written."""
        return self.prompt_len + self.num_generated

    def output_ids(self) -> np.ndarray:
        """prompt + generated tokens (terminator included)."""
        return np.concatenate(
            [self.prompt, np.asarray(self.generated, np.int32)])


class Scheduler:
    """FCFS + fairness policy over a bounded wait queue (module
    docstring).  The scheduler DECIDES (admit / victim / finished); the
    engine executes (prefill, decode, block moves)."""

    def __init__(self, pool, max_queue_len: int = 64):
        self.pool = pool
        self.max_queue_len = max_queue_len
        self.waiting: Deque[Request] = deque()
        self.running: List[Request] = []

    # -------------------------------------------------------- admission
    def enqueue(self, req: Request):
        """Accept into the wait queue, or raise AdmissionError.  A
        request whose full sequence can never fit the pool is rejected
        outright — queuing it would deadlock the head of the queue."""
        total = self.pool.blocks_for(req.prompt_len + req.max_new_tokens)
        if total > self.pool.capacity_blocks:
            raise AdmissionError(
                f"{req.request_id}: needs {total} blocks at full length, "
                f"pool capacity is {self.pool.capacity_blocks}")
        if len(self.waiting) >= self.max_queue_len:
            raise QueueFull(
                f"wait queue full ({self.max_queue_len}); retry later")
        self.waiting.append(req)

    def shed_candidate(self, priority: int) -> Optional[Request]:
        """Waiting request a ``priority``-class arrival may displace
        when the queue is full: the LOWEST-priority (youngest within
        the class) waiting request, and only when its priority is
        strictly below the arrival's.  None when nobody qualifies —
        same-priority traffic keeps the plain bounded-queue rejection."""
        if not self.waiting:
            return None
        victim = min(self.waiting, key=lambda r: (r.priority, -r.ordinal))
        return victim if victim.priority < priority else None

    def requeue_preempted(self, req: Request):
        """Victim goes to the HEAD of the queue with its original
        ordinal: it is the next admitted, so preemption never reorders
        completion past FCFS."""
        req.state = PREEMPTED
        req.slot = None
        req.blocks = []
        req.window_pages = {}
        req.generated = []
        req.prefill_pos = 0
        req.cached_tokens = 0
        req.prefill_chunks = 0
        self.waiting.appendleft(req)

    def next_admittable(self) -> Optional[Request]:
        """Head of the queue if the pool can hold its prompt + one
        decode block right now; None otherwise (strict FCFS: a blocked
        head blocks the tail, so completions stay in arrival order).
        Prefix-cache hits shrink the bill: blocks matched in the pool's
        content index need no fresh allocation (``admission_plan``
        accounts for matched blocks parked in the evictable LRU)."""
        if not self.waiting:
            return None
        # highest priority class first, FCFS ordinal within a class —
        # for all-default priorities this is exactly the old head-of-
        # deque pick (preempted requests re-queued at the head always
        # carry the smallest ordinals among waiting)
        head = min(self.waiting, key=lambda r: (-r.priority, r.ordinal))
        # uncached prompt blocks + room for the first generated token's
        # write position (a new block only when the prompt fills its
        # last one)
        _, _, feasible = self.pool.admission_plan(head.prompt,
                                                  extra_tokens=1)
        if not feasible:
            return None
        self.waiting.remove(head)
        return head

    # ------------------------------------------------------- preemption
    def pick_victim(self) -> Optional[Request]:
        """Lowest-priority running request, youngest within the class —
        the least completed work lost, and the last in FCFS order
        anyway.  The requester itself may be the victim (it self-
        preempts rather than evicting older work).  With all-default
        priorities this is exactly the old youngest-first pick."""
        if not self.running:
            return None
        return max(self.running, key=lambda r: (-r.priority, r.ordinal))

    # ------------------------------------------------------ termination
    @staticmethod
    def finish_reason(req: Request) -> Optional[str]:
        """Termination check over the request's generated tokens —
        shared semantics with ``generate()`` (same match_stop) — plus
        the monotonic-clock deadline (a hard SLO: it wins over eos/stop
        and fires even before the first token)."""
        if req.expired():
            return "timeout"
        if not req.generated:
            return None
        if req.eos_token_id is not None \
                and req.generated[-1] == req.eos_token_id:
            return "eos"
        if req.stop_sequences and match_stop(req.generated,
                                             req.stop_sequences):
            return "stop"
        if req.num_generated >= req.max_new_tokens:
            return "length"
        return None


__all__ = ["AdmissionError", "QueueFull", "Request", "Scheduler",
           "QUEUED", "PREFILLING", "RUNNING", "PREEMPTED", "FINISHED",
           "normalize_stop_sequences"]
