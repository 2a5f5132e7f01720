# lint-tpu: disable-file=L004 -- serving drives the compiled decode/
# prefill steps over raw device buffers (like models/); new backend code
# belongs under core/ ops/ kernels/ static/ distributed/ (README: Repo lint)
"""Continuous-batching inference engine (PAPERS.md: Orca's
iteration-level scheduling + vLLM's paged KV cache + Sarathi-style
chunked prefill) over the compiled steps of models/generation.py.

The engine keeps a fixed BUCKET of ``max_batch_size`` decode slots.
Every iteration it (1) retires finished sequences, (2) admits waiting
requests into free slots — attaching any prefix-cached blocks of the
prompt and allocating only the uncached suffix — (3) advances admitted
prompts by fixed-size prefill CHUNKS under a per-iteration token
budget, and (4) runs ONE compiled decode step over the whole bucket:
token ids [S, 1], the shared block pools, block tables [S, max_blocks]
and per-slot frontiers [S].  Because every array shape is fixed by the
config — including the prefill chunk's — the decode step AND the
prefill step each compile exactly once; idle slots decode into the
reserved garbage block instead of branching, and mid-prefill slots are
masked out of the decode view the same way.  Requests therefore enter
and leave at TOKEN granularity, and a long prompt no longer stalls
running requests for its whole prefill — it yields the iteration back
to decode after each chunk.

The greedy lane runs one step AHEAD of the host: step N+1 is dispatched
with step N's chosen ids as its input, still on the device, before the
host reads step N's ids; the host then reads, emits, retires and admits
while step N+1 runs.  A ``step()`` call therefore delivers the tokens of
the decode step dispatched by the call before it (and the first token of
a prompt its own chunk completed); the last step's tokens are delivered
by the call that finds nothing to run behind it.  What does not depend
on a token's value is applied at dispatch: lengths, blocks, and a slot
whose last token is in flight sits the next step out.  What only the
token tells (an EOS, a stop sequence, a raising ``on_token``, an expired
deadline) costs at most that slot's one in-flight step, discarded.  The
sampled, speculative and block lanes read each step before the next,
and entering one, a preemption, ``revive()`` or running out of work
reads the step in flight first (``_drain``).

Correctness contract: greedy outputs are token-exact with sequential
``generate()`` for the same prompts (tests/test_serving.py), including
across preemption (recompute-from-prompt is deterministic under
greedy), with the prefix cache on or off (shared blocks hold the exact
bits a fresh prefill would produce; copy-on-write keeps them immutable).
"""
from __future__ import annotations

import itertools
import time
from dataclasses import dataclass
from typing import Any, Dict, List, Optional

import jax
import numpy as np

from ..models.generation import (make_chunked_prefill_step,
                                 make_paged_block_step,
                                 make_paged_decode_step,
                                 normalize_stop_sequences, unmask_schedule)
from ..observability import warn_on_retrace
from .cache import BlockKVPool, PoolExhausted, describe_cache
from .metrics import ServingMetrics
from .overload import LADDER_LEVELS, EngineQuarantined, OverloadController
from .sampling import make_sampled_decode_step, resolve_sampling, sample_at
from .scheduler import (FINISHED, PREFILLING, RUNNING, AdmissionError,
                        QueueFull, Request, Scheduler)
from .speculative import (SpeculativeConfig, make_draft_propose_step,
                          make_spec_verify_step)


@dataclass
class ServingConfig:
    """Engine tuning knobs (README "Serving" documents each)."""

    # replica name (serving/router.py fleets): suffixes the watchdog /
    # chaos step labels as "serving::decode_step@<name>" so per-replica
    # fault injection and metrics can target ONE engine of a fleet.
    # Empty (the default) keeps the bare single-engine labels.
    name: str = ""
    max_batch_size: int = 8       # decode-bucket slots
    block_size: int = 16          # KV-cache tokens per block
    num_blocks: int = 128         # pool size incl. reserved block 0
    max_queue_len: int = 64       # bounded wait queue (backpressure)
    max_model_len: Optional[int] = None   # default: model max positions
    # prefill chunk size in tokens: every prompt prefills as fixed
    # [1, chunk_tokens] chunks, so prefill holds ONE compiled program
    # for all prompt lengths (clamped to max_model_len)
    chunk_tokens: int = 256
    # content-addressed KV block reuse across requests sharing a prompt
    # prefix (block-granular; LRU eviction of unreferenced blocks)
    enable_prefix_cache: bool = True
    # max prefill tokens computed per engine iteration before decode
    # runs again (Sarathi-style interleave); None = one chunk's worth
    prefill_token_budget: Optional[int] = None
    # raise (observability.RetraceError, a RuntimeError) if the compiled
    # decode step ever retraces after warmup — the H101-style jit
    # cache-key check via observability.warn_on_retrace; cheap, keep on.
    # When False, retraces are still counted (engine._decode_step.retraces)
    strict_no_retrace: bool = True
    # X-ray both compiled steps at startup (analysis.xray): static
    # FLOPs/bytes/peak-HBM land in engine.xray_reports and (when
    # telemetry is on) the observability gauges; ERROR-severity hazards
    # — f64 eqns, host callbacks, or peak HBM over hbm_budget_bytes —
    # raise before the engine serves a single token
    xray_on_start: bool = False
    hbm_budget_bytes: Optional[int] = None   # None: no H110 gate
    xray_chip: str = "v5e"                   # roofline ridge profile
    # static shard-plan audit at construction (analysis.shardplan):
    # an analysis.PlanRequest (or True for the default llama layout on
    # a simulated (data=2, fsdp=2, tp=2) mesh).  Propagates shardings
    # through the decode + chunked-prefill programs, mirrors per-chip
    # peak HBM and collective bytes into the observability gauges, and
    # aborts construction on S205/S207/H110-per-chip ERRORs — all on
    # CPU, no devices needed.
    shardplan: Any = None
    # RUNTIME mesh execution (distributed.MeshExecutor, or an
    # {axis: size} dict): weights are sharded per the canonical
    # SpecLayout and the paged KV pool PS(None, None, "tp", None), so
    # decode/prefill each run as ONE GSPMD program over the mesh.
    # Engine.reconcile_mesh() audits the compiled programs against the
    # static shard plan (diagnostic S209).
    mesh: Any = None
    # ---- overload control (serving/overload.py; README "Overload
    # control & graceful degradation") ----
    # deadline-aware load shedding at submit(): reject with
    # finish_reason="shed" when the estimated TTFT (queue depth +
    # pending prefill tokens over the chunk/decode latency EWMAs)
    # already busts deadline_s.  Never fires while the EWMAs are cold.
    enable_load_shedding: bool = True
    shed_safety_factor: float = 1.0   # shed when est > deadline * factor
    # KV memory-pressure watermarks (fraction of pool blocks referenced
    # by live requests) driving the degradation ladder, with hysteresis:
    # escalate one level per iteration STRICTLY above high, unwind one
    # below low.  The default high of 1.0 cannot be exceeded, so the
    # ladder is opt-in: set e.g. 0.9/0.7 to start degrading before the
    # pool is fully referenced (preemption still guards the full-pool
    # case either way)
    kv_high_watermark: float = 1.0
    kv_low_watermark: float = 0.75
    # hung-step watchdog: per-attempt budget = watchdog_budget_mult x
    # the step's EWMA latency, floored by watchdog_floor_s (generous:
    # the first call pays XLA compilation); a stall or transient step
    # exception gets step_max_retries retries with exponential backoff
    # from step_retry_backoff_s, then the engine quarantines DEGRADED
    # (stalls) or FAILED (exceptions, raising EngineQuarantined)
    watchdog_budget_mult: float = 20.0
    watchdog_floor_s: float = 30.0
    step_max_retries: int = 2
    step_retry_backoff_s: float = 0.05
    # consecutive in-budget steps before DEGRADED self-heals to SERVING
    health_recovery_steps: int = 3
    # fused serving kernels (kernels/fusion): None and True trace the
    # fused paged-attention decode + chunk kernels and the RMSNorm
    # epilogues (Pallas on a TPU, their XLA lowering elsewhere; the
    # gather path while a mesh is live); False pins the gather path,
    # the tests' oracle, on any backend.  Pinned at
    # step-build time, so it never flips inside a compiled program.
    fused_kernels: Optional[bool] = None
    # speculative decoding (serving/speculative.py): a SpeculativeConfig
    # (or a bare draft model, wrapped with the default K).  The draft's
    # KV layers live in the SAME BlockKVPool as the target's — one
    # block table per sequence, so the prefix cache serves both models
    # — and every decode iteration becomes draft-propose (K tokens, one
    # scanned program) + target-verify ([S, K+1], one chunked-shaped
    # program) with on-device acceptance and block-granular KV rollback.
    speculative: Any = None
    # ---- quantized serving (ISSUE 20; kernels/kv_quant) ----
    # KV-cache storage dtype: None serves full precision; "int8"/"fp8"
    # store the paged pools as int8 codes + per-(block, token)-row f32
    # absmax scales, quantizing at KV-write time inside the traced
    # steps and dequantizing at the attention kernels' DMA boundary.
    # The prefix-cache hash chain is namespaced by this dtype, so a
    # quantized pool never matches fp32-registered blocks.
    kv_cache_dtype: Optional[str] = None
    # weight-only quantization: "int8" converts every Column/Row-
    # parallel linear to absmax per-out-channel int8 codes dequantized
    # in the matmul prologue (paddle_tpu/quantization/serving.py) —
    # the paddle Int8Linear inference analog.  Applied IN PLACE to the
    # model at engine construction, before the steps trace.
    weight_dtype: Optional[str] = None
    # fixed KV HBM budget: when set, ``num_blocks`` is DERIVED as
    # kv_pool_bytes // pool-block-bytes (dtype-aware, scale sidecars
    # included).  The like-for-like capacity knob behind the int8-vs-
    # fp32 occupancy/goodput comparison: same bytes, ~4x the blocks at
    # int8, so the degradation ladder engages later under the same
    # burst.
    kv_pool_bytes: Optional[int] = None


# what a slot of a block-diffusion model is doing (the ``mode`` input of
# ``models/generation.py::make_paged_block_step``)
_BLOCK_IDLE, _BLOCK_DENOISE, _BLOCK_COMMIT = 0, 1, 2

# the ladder level from which its next tick may preempt
_LADDER_MAY_PREEMPT = LADDER_LEVELS.index("pause_admissions")

# The greedy decode step's token input made on the device: the ids the
# step before it chose, as a column (``_column``), with the first id of a
# prompt that completed behind that step set at its slot (``_merge``).
# One shape each per bucket size.
_column = jax.jit(lambda ids: ids[:, None])
_merge = jax.jit(lambda tok, first, slot: tok.at[slot, 0].set(first[0]))


class _DrainFirst(Exception):
    """``_ensure_blocks`` would preempt while a step is in flight."""


class _InFlight:
    """A greedy decode step dispatched and not yet read: its chosen ids
    (and a routed model's stats), still on the device, the requests it
    ran with their slots, and the routing stats of the chunks dispatched
    just before it, which its read waits for anyway."""

    __slots__ = ("ids", "stats", "active", "chunk_stats")

    def __init__(self, out, active, chunk_stats):
        self.ids, *self.stats = out if isinstance(out, tuple) else (out,)
        self.active = [(r, r.slot) for r in active]
        self.chunk_stats = chunk_stats


class _DeviceFed:
    """The greedy decode program's one executable, called where its
    token input is still on the device.  A device array and a numpy one
    are two entries of a jit's cache (one executable behind both), and
    the numpy one is what ``generate()``-style callers make; so the
    engine calls the executable itself, which it takes from the jit's
    own lowering of the numpy call (nothing compiles), and the jit's
    cache keeps its one entry.  Calls count on the step's tracker."""

    def __init__(self, tracked, tok, *args):
        self._tracked = tracked
        self._step = tracked.__wrapped__
        self._compiled = self._step.lower(
            np.zeros(tok.shape, np.int32), *args).compile()

    def __call__(self, *args):
        out, pools = self._compiled(self._step._weights(), *args)
        self._tracked.calls += 1
        # ``(logits, ids, *more)`` less the logits, as ``step.ids`` has it
        rest = out[1:]
        return (rest[0] if len(rest) == 1 else rest), pools


class Engine:
    """Continuous-batching engine for any causal LM following the
    cache contract of models/llama.py (StaticKVCache + PagedKVCache),
    for a model that generates by DIFFUSION OVER BLOCKS (one that
    has ``block_diffusion`` generation settings: models/sdar_moe.py),
    for a model with WINDOW layers beside full ones (one whose
    ``cache_layers()`` names a window: models/afmoe.py) and for a model
    that keeps LATENT records (one array a position, no heads: one whose
    ``cache_layers()`` names a ``value_dim``: models/glm4_moe_lite.py;
    one group, one table, the prefix cache on).  The pool is
    built from the model's own description of its cache, a record a
    layer (serving/cache.py).  A window model's pages live in two
    groups with a block table each; the same loop serves it, and the
    window group's pages go back to its free list in the very
    ``step()`` that moved a sequence's window past them
    (``_advance_window``: inside ``prefill_dispatch`` for a chunk,
    inside ``decode_prepare`` for a decode iteration).

    A block model is served by the same loop: admission, the pool, the
    chunked prefill lane (the prompt's WHOLE blocks of ``L =
    block_length`` tokens; what is left over becomes known positions of
    the first generated block), then ``_block_iteration`` in place of
    the one-token decode: a slot holds a block of ``L`` positions of
    which some are still masked; a step finalises 0..L of them, out of
    order, chosen on the device; when none is left the block is run
    once more with its final tokens and its K/V written (the commit
    pass, a step of its own in the same program), and the next block
    begins at the next multiple of ``L``.  A block's tokens reach
    ``on_token`` in order, as soon as they are final and contiguous.
    Greedy only; ``L``, the denoising steps, the remasking rule and its
    threshold are the MODEL's generation settings."""

    def __init__(self, model, config: Optional[ServingConfig] = None):
        from ..kernels.kv_quant import resolve_kv_cache_dtype

        self.model = model
        self.config = cfg = config or ServingConfig()
        self.kv_cache_dtype = resolve_kv_cache_dtype(cfg.kv_cache_dtype)
        if cfg.weight_dtype:
            # in place, idempotent, BEFORE the steps trace (they capture
            # the weights as jit constants)
            from ..quantization.serving import quantize_model_weights

            quantize_model_weights(model, cfg.weight_dtype)
        layer_caches = describe_cache(model)
        self.block = getattr(model, "block_diffusion", None)
        if self.block is not None:
            self._check_block_model()
        #: the window layers' span in keys (None: every layer is full)
        self.window = next((c.window for c in layer_caches
                            if c.window is not None), None)
        if self.window is not None:
            self._refuse_unsupported(
                "a model with window layers",
                "its pages live in two groups with a table each, and a "
                "draft's rollback, a quantized window page and the "
                "analysers' one-table programs are later work")
        #: the model keeps latent records (one array a position, no
        #: heads: ``LayerCache.value_dim``)
        self.latent = any(c.value_dim is not None for c in layer_caches)
        if self.latent:
            self._refuse_unsupported(
                "a model that keeps latent (compressed) cache records",
                "a draft's rollback over a latent pool, a quantized "
                "latent page, a sharded latent pool and the analysers' "
                "(k, v) programs are later work")
        model_max = getattr(model.config, "max_position_embeddings", None)
        self.max_model_len = min(
            cfg.max_model_len or model_max or 1 << 30,
            model_max or 1 << 30)
        self.max_blocks_per_seq = -(-self.max_model_len // cfg.block_size)
        self.chunk_tokens = max(1, min(cfg.chunk_tokens,
                                       self.max_model_len))
        # speculative decoding: one pool holds the target's layers
        # followed by the draft's, addressed by the same block tables
        spec = cfg.speculative
        if spec is not None and not isinstance(spec, SpeculativeConfig):
            spec = SpeculativeConfig(draft_model=spec)
        self.spec = spec
        self._n_target_layers = model.config.num_hidden_layers
        if spec is not None:
            spec.validate_against(model)
            if cfg.mesh is not None:
                raise ValueError(
                    "speculative decoding under a runtime mesh is not "
                    "supported yet (the draft's weights would stay "
                    "unsharded)")
            draft_max = getattr(spec.draft_model.config,
                                "max_position_embeddings", None)
            if draft_max is not None and draft_max < self.max_model_len:
                raise ValueError(
                    f"draft max_position_embeddings ({draft_max}) < "
                    f"max_model_len ({self.max_model_len})")
            layer_caches = layer_caches + describe_cache(spec.draft_model)
        if spec is not None and self.kv_cache_dtype is not None:
            raise ValueError(
                "speculative decoding with a quantized KV cache is not "
                "supported yet (the draft/verify rollback paths assume "
                "full-precision pool entries); drop kv_cache_dtype or "
                "speculative")
        # fixed-HBM sizing: a kv_pool_bytes budget derives num_blocks
        # from the per-dtype block bytes (quantized pools fit ~4x the
        # blocks in the same budget — the occupancy headline)
        self.num_blocks = cfg.num_blocks
        if cfg.kv_pool_bytes is not None:
            # (the budget is the full group's: a window group's size
            # follows from the window, below)
            per_block = sum(c.block_bytes(cfg.block_size,
                                          self.kv_cache_dtype)
                            for c in layer_caches)
            self.num_blocks = int(cfg.kv_pool_bytes) // per_block
            if self.num_blocks < 2:
                raise ValueError(
                    f"kv_pool_bytes={cfg.kv_pool_bytes} fits only "
                    f"{self.num_blocks} block(s) of {per_block} bytes; "
                    "need >= 2 (block 0 is the reserved garbage sink)")
        # the window group's size follows from the window, the chunk,
        # the block and the bucket: a sequence holds at most the pages
        # that (window + chunk) keys can span, whatever its length
        window_pages = 0 if self.window is None else \
            -(-(self.window + self.chunk_tokens) // cfg.block_size) + 1
        self.pool = BlockKVPool(
            len(layer_caches), self.num_blocks, cfg.block_size,
            # (every layer's geometry is its own record's)
            None, None, layer_caches[0].dtype,
            # which layers could reuse a block of a window model is a
            # later PR's: no registration, no match
            enable_prefix_cache=cfg.enable_prefix_cache
            and self.window is None,
            kv_cache_dtype=self.kv_cache_dtype,
            layer_caches=layer_caches,
            window_blocks=window_pages * cfg.max_batch_size + 1,
            window_pages_per_seq=window_pages)
        self.scheduler = Scheduler(self.pool,
                                   max_queue_len=cfg.max_queue_len)
        self.metrics = ServingMetrics()
        from ..kernels.kv_quant import (kv_pool_dtype_code,
                                        kv_scale_bytes_per_block)

        self.metrics.on_kv_cache_config(
            kv_pool_dtype_code(self.kv_cache_dtype),
            kv_scale_bytes_per_block(cfg.block_size, self.kv_cache_dtype))
        self.overload = OverloadController(cfg, self.metrics)
        S = cfg.max_batch_size
        self._slots: List[Optional[Request]] = [None] * S
        self._block_tables = np.zeros((S, self.max_blocks_per_seq),
                                      np.int32)
        # a window model's second table: the window group's pages, by
        # the same page index (a released page's entry names the garbage
        # block)
        self._window_tables = None if self.window is None else \
            np.zeros_like(self._block_tables)
        self._lengths = np.zeros((S,), np.int32)
        self._pending = np.zeros((S,), np.int32)  # next token to decode
        # per-slot sampling state, all fixed-shape device-step inputs:
        # greedy slots keep temperature 0 (the argmax lane inside the
        # sampled/verify steps) so a mixed bucket is still ONE program
        self._temps = np.zeros((S,), np.float32)
        self._top_ks = np.zeros((S,), np.int32)
        self._top_ps = np.ones((S,), np.float32)
        self._keys = np.zeros((S, 2), np.uint32)      # per-request base keys
        self._counters = np.zeros((S,), np.int32)     # next token index
        if self.block is not None:
            # a slot's block in flight: its tokens, which of them are
            # still masked (a state, not a comparison with the mask id:
            # a prompt may hold that id), what the slot does next and
            # how many denoise steps the block has had
            L = self.block.block_length
            self._blk_ids = np.zeros((S, L), np.int32)
            self._blk_masked = np.zeros((S, L), bool)
            self._blk_mode = np.zeros((S,), np.int32)
            self._blk_step = np.zeros((S,), np.int32)
        # routing stats of chunk programs, still on the device (a model
        # whose programs count what their routed layers read)
        self._route_stats = []
        # runtime SPMD: shard weights + KV pool BEFORE the steps first
        # run — they take the weights as arguments, so the rebind here
        # is what makes the compiled programs multi-device
        self.mesh_executor = None
        if cfg.mesh is not None:
            from ..distributed.executor import as_executor

            self.mesh_executor = as_executor(cfg.mesh)
            self.mesh_executor.install_serving(model, self.pool)
        # compile accounting wraps both compiled entry points, and BOTH
        # carry the no-retrace contract now: each one's single allowed
        # compile is this engine's warmup; any cache growth past it seen
        # through these wrappers is a retrace (the steps are cached on
        # the model, so another engine's entries never count against us).
        # Chunked prefill earns its wrapper by construction — one fixed
        # [1, chunk_tokens] shape for EVERY prompt length, where the old
        # bucketed prefill compiled one program per length bucket.
        self._decode_step = warn_on_retrace(
            make_paged_block_step(model, fused=cfg.fused_kernels)
            if self.block is not None else
            make_paged_decode_step(model, fused=cfg.fused_kernels,
                                   kv_cache_dtype=self.kv_cache_dtype),
            after=1, label="serving::decode_step",
            on_retrace="raise" if cfg.strict_no_retrace else "count")
        self._prefill_step = warn_on_retrace(
            make_chunked_prefill_step(model, fused=cfg.fused_kernels,
                                      kv_cache_dtype=self.kv_cache_dtype),
            after=1, label="serving::prefill_step",
            on_retrace="raise" if cfg.strict_no_retrace else "count")
        # the greedy lane's reader of the same two programs: the ids
        # they chose where their logits are (a block model's programs
        # choose inside the block step)
        self._decode_ids = self._prefill_ids = None
        if self.block is None:
            self._decode_ids = self._decode_step.sibling("ids")
            self._prefill_ids = self._prefill_step.sibling("ids")
        # the greedy lane runs a step ahead (module docstring) where the
        # host never needs a token to choose the next step's input: one
        # token a step, no draft, and one device (a mesh's programs lay
        # their inputs out across it)
        self._pipelined = self.block is None and spec is None \
            and self.mesh_executor is None
        self._ahead: Optional[_InFlight] = None
        # prompts completed behind the step in flight, each with the
        # first id its last chunk chose, still on the device
        self._firsts: List[tuple] = []
        self._device_fed: Optional[_DeviceFed] = None
        self._sampled_decode_step = None \
            if self.block is not None or self.window is not None \
            or self.latent else warn_on_retrace(
                make_sampled_decode_step(model, fused=cfg.fused_kernels,
                                         kv_cache_dtype=self.kv_cache_dtype),
                after=1, label="serving::sampled_decode_step",
                on_retrace="raise" if cfg.strict_no_retrace else "count")
        # every ADDITIONAL compiled step gets its own watchdog: the
        # per-EWMA compile_s carve-out only exempts ONE first call, so
        # sharing the decode/prefill watchdogs would record the second
        # program's compile as a real latency sample and poison the
        # budget + TTFT estimate (over-shedding) for good
        self._sampled_wd = self.overload.extra_watchdog(
            "sampled_decode_step")
        if spec is not None:
            draft = spec.draft_model
            self._draft_prefill_step = warn_on_retrace(
                make_chunked_prefill_step(draft, fused=cfg.fused_kernels),
                after=1, label="serving::draft_prefill_step",
                on_retrace="raise" if cfg.strict_no_retrace else "count")
            self._draft_propose_step = warn_on_retrace(
                make_draft_propose_step(draft, spec.num_draft_tokens,
                                        fused=cfg.fused_kernels),
                after=1, label="serving::draft_propose_step",
                on_retrace="raise" if cfg.strict_no_retrace else "count")
            self._spec_verify_step = warn_on_retrace(
                make_spec_verify_step(model, spec.num_draft_tokens,
                                      fused=cfg.fused_kernels),
                after=1, label="serving::spec_verify_step",
                on_retrace="raise" if cfg.strict_no_retrace else "count")
            self._draft_prefill_wd = self.overload.extra_watchdog(
                "draft_prefill_step")
            self._draft_propose_wd = self.overload.extra_watchdog(
                "draft_propose_step")
            self._spec_verify_wd = self.overload.extra_watchdog(
                "spec_verify_step")
        # the step's account reads their calls and compiles
        self.metrics.programs = tuple(
            step for step in (
                self._decode_step, self._prefill_step,
                self._sampled_decode_step,
                *((self._draft_prefill_step, self._draft_propose_step,
                   self._spec_verify_step) if spec is not None else ()))
            if step is not None)
        if self.block is not None:
            self._unmask_schedule = unmask_schedule(
                self.block.block_length, self.block.denoising_steps)
        self._finished: Dict[str, Request] = {}
        self._ids = itertools.count()
        self._evictions_seen = 0    # pool counter already mirrored
        self.xray_reports = self._xray_startup() if cfg.xray_on_start \
            else None
        self.shardplan_reports = self._shardplan_startup() \
            if cfg.shardplan is not None else None

    def _check_block_model(self):
        """What the block iteration does not do yet is refused here,
        with an error that says so."""
        cfg, L = self.config, self.block.block_length
        for what, given in (
                ("speculative decoding", cfg.speculative),
                ("a quantized KV cache", cfg.kv_cache_dtype),
                ("weight-only quantization", cfg.weight_dtype),
                ("a runtime mesh", cfg.mesh)):
            if given is not None:
                raise ValueError(
                    f"{what} is not supported for a model that generates "
                    "by diffusion over blocks (greedy block denoising "
                    "only in this engine)")
        if cfg.block_size % L or cfg.chunk_tokens % L:
            raise ValueError(
                f"block_size ({cfg.block_size}) and chunk_tokens "
                f"({cfg.chunk_tokens}) must be multiples of the model's "
                f"block_length ({L}): K/V blocks, chunks and generated "
                "blocks are aligned")

    def _refuse_unsupported(self, model_words: str, why: str):
        """What a model whose cache is not one table of (k, v) pages is
        not served with yet is refused here, with an error that says
        so."""
        cfg = self.config
        for what, given in (
                ("speculative decoding", cfg.speculative),
                ("a quantized KV cache", cfg.kv_cache_dtype),
                ("a runtime mesh", cfg.mesh),
                ("the start-up X-ray", cfg.xray_on_start or None),
                ("the static shard plan", cfg.shardplan)):
            if given is not None:
                raise ValueError(
                    f"{what} is not supported for {model_words}: {why}")

    def _shardplan_startup(self):
        """Statically plan the decode and chunked-prefill programs on
        this engine's exact shapes against an abstract mesh
        (analysis.shardplan) before serving: per-chip peak HBM and the
        collective inventory mirror into the observability gauges, and
        ERRORs — S205 resharding, S207 collective-bound, H110 per-chip
        budget — abort construction."""
        from ..analysis import PlanRequest, shardplan, xray

        cfg = self.config
        req = cfg.shardplan
        if req is True:
            req = PlanRequest(hbm_budget_bytes=cfg.hbm_budget_bytes)
        layout = req.resolved_layout()
        decode_args, prefill_args = xray._serving_abstract_args(
            self.model, batch=cfg.max_batch_size,
            num_blocks=self.num_blocks, block_size=cfg.block_size,
            max_blocks_per_seq=self.max_blocks_per_seq,
            chunk_tokens=self.chunk_tokens,
            kv_cache_dtype=self.kv_cache_dtype)
        decode_specs, prefill_specs = shardplan._serving_arg_specs(
            self.model, layout, decode_args, prefill_args)
        reports = [
            shardplan.plan_step(
                self._decode_step, decode_args, model=self.model,
                arg_specs=decode_specs, request=req,
                name="serving::decode_step",
                data_input_leaves=(("tokens", 0),),
                step_kind="paged_decode"),
            shardplan.plan_step(
                self._prefill_step, prefill_args, model=self.model,
                arg_specs=prefill_specs, request=req,
                name="serving::prefill_step",
                data_input_leaves=(("chunk_ids", 0),),
                step_kind="chunked_prefill"),
        ]
        errors = [d for r in reports for d in r.errors()]
        for r in reports:
            shardplan.export_plan_gauges(r)
        if errors and getattr(req, "raise_on_error", True):
            raise ValueError(
                "serving step shard plan found ERRORs:\n  " +
                "\n  ".join(str(d) for d in errors))
        return reports

    def reconcile_mesh(self):
        """Cross-check the COMPILED decode/prefill programs against the
        static shard plan (diagnostic S209: collective footprint,
        per-device memory, realized KV-pool output shards).  Returns
        ``{step_name: (PlanReport, [S209 diagnostics])}`` — empty
        diagnostic lists mean runtime and plan agree."""
        if self.mesh_executor is None:
            raise RuntimeError(
                "reconcile_mesh needs ServingConfig(mesh=...)")
        return self.mesh_executor.reconcile_serving(self)

    def _xray_startup(self):
        """X-ray the decode and prefill steps on this engine's exact
        shapes (analysis.xray) before serving: static FLOPs/bytes/peak-
        HBM mirror into the observability gauges, and ERROR hazards —
        f64, host callbacks, HBM budget (H110) — abort construction."""
        from ..analysis import xray

        cfg = self.config
        decode_args, prefill_args = xray._serving_abstract_args(
            self.model, batch=cfg.max_batch_size,
            num_blocks=self.num_blocks, block_size=cfg.block_size,
            max_blocks_per_seq=self.max_blocks_per_seq,
            chunk_tokens=self.chunk_tokens,
            kv_cache_dtype=self.kv_cache_dtype)
        reports = [
            xray.analyze(self._decode_step, decode_args,
                         name="serving::decode_step", chip=cfg.xray_chip,
                         hbm_budget_bytes=cfg.hbm_budget_bytes),
            xray.analyze(self._prefill_step, prefill_args,
                         name="serving::prefill_step", chip=cfg.xray_chip,
                         hbm_budget_bytes=cfg.hbm_budget_bytes),
        ]
        errors = [d for r in reports for d in r.errors()]
        for r in reports:
            xray.export_report_gauges(r)
        if errors:
            raise ValueError(
                "serving step X-ray found ERROR hazards:\n  " +
                "\n  ".join(str(d) for d in errors))
        return reports

    # ------------------------------------------------- pool layer slices
    # the combined pool lists the target's layers first, then the
    # draft's; every step consumes only its model's slice, and each
    # rebind reassembles the full list (non-speculative engines pass
    # through untouched)
    def _target_pools(self):
        if self.spec is None:
            return self.pool.layers
        return self.pool.layers[:self._n_target_layers]

    def _draft_pools(self):
        return self.pool.layers[self._n_target_layers:]

    def _rebind_target(self, new_pools):
        # entries are (k, v) or (k, v, k_scale, v_scale) — arity-agnostic
        new = [tuple(entry) for entry in new_pools]
        if self.spec is None:
            self.pool.layers = new
        else:
            self.pool.layers = new + self._draft_pools()

    def _rebind_draft(self, new_pools):
        self.pool.layers = self.pool.layers[:self._n_target_layers] \
            + [tuple(entry) for entry in new_pools]

    def _fetch(self, result):
        """A step program's ``result`` (an array, or a list of them) on
        the host, its bytes counted.  Waits for the device; a list's
        copies are all started before one is waited for, so a copy's
        latency is paid once."""
        out = jax.device_get(result)
        self.metrics.on_fetch(sum(
            a.nbytes for a in (out if isinstance(out, list) else [out])))
        return out

    # ----------------------------------------------------------- submit
    def submit(self, prompt, max_new_tokens: int = 32,
               eos_token_id: Optional[int] = None, stop_sequences=None,
               tokenizer=None, request_id: Optional[str] = None,
               temperature: float = 0.0, do_sample: bool = False,
               top_k: int = 0, top_p: float = 1.0,
               seed: Optional[int] = None, sampling=None,
               on_token=None, token_deadline_s: Optional[float] = None,
               deadline_s: Optional[float] = None, priority: int = 0
               ) -> Request:
        """Queue one request; returns its :class:`Request` handle.
        Raises :class:`AdmissionError` when the wait queue is full or
        the sequence can never fit the pool (backpressure: callers
        retry or shed load).

        ``deadline_s`` is a monotonic-clock SLO measured from
        submission (``time.monotonic``, so wall-clock steps/NTP slews
        never fire it — hazard H111): once exceeded the request is
        retired with ``finish_reason="timeout"`` (partial tokens kept)
        — whether it is still queued, mid-prefill, or mid-decode —
        instead of occupying a slot other requests could use.  When
        load shedding is enabled and the engine's latency EWMAs are
        warm, a request whose ESTIMATED time-to-first-token already
        busts the deadline is retired immediately with
        ``finish_reason="shed"`` (returned, not raised — cheap
        rejection beats a guaranteed timeout).

        ``priority`` (higher wins) orders overload decisions: admission
        prefers high, shedding and preemption take the lowest first.  A
        higher-priority arrival hitting a FULL queue sheds the
        lowest-priority waiting request instead of being rejected.

        Sampling: ``sampling=SamplingParams(...)`` (or a dict of its
        fields), or the ``generate()``-style spelling —
        ``temperature``/``do_sample``/``top_k``/``top_p``/``seed``.
        ``temperature=0`` stays the greedy special case and runs the
        unchanged greedy decode step; a sampled request carries a
        per-request PRNG key derived from its seed, folded with the
        token index ON DEVICE, so outputs are token-exact with
        ``generate()`` under the same seed regardless of batching or
        preemption (serving/sampling.py).

        Streaming: ``on_token`` fires once per ACCEPTED token (several
        per iteration under speculative decoding), in commit order.
        ``token_deadline_s`` is a rolling inter-token SLO: it resets on
        every emitted token and retires a stalled stream with
        ``finish_reason="timeout"``; the load shedder treats it as an
        effective TTFT bound."""
        if self.overload.health.failed:
            self.metrics.on_reject()
            raise AdmissionError(
                "engine quarantined FAILED "
                f"({self.overload.health.last_error}); revive() after "
                "operator intervention")
        params = resolve_sampling(sampling, temperature=temperature,
                                  do_sample=do_sample, top_k=top_k,
                                  top_p=top_p, seed=seed)
        if params is not None and self.block is not None:
            raise ValueError(
                "sampling is not supported for a model that generates by "
                "diffusion over blocks: its denoise step picks the argmax "
                "at every masked position (greedy only)")
        if params is not None and self.window is not None:
            raise ValueError(
                "sampling is not supported yet for a model with window "
                "layers: its decode program is the greedy one only")
        if params is not None and self.latent:
            raise ValueError(
                "sampling is not supported yet for a model that keeps "
                "latent cache records: its decode program is the greedy "
                "one only")
        prompt = np.asarray(
            prompt.numpy() if hasattr(prompt, "numpy") else prompt,
            np.int32).reshape(-1)
        req = Request(
            prompt=prompt, max_new_tokens=max_new_tokens,
            eos_token_id=eos_token_id,
            stop_sequences=normalize_stop_sequences(stop_sequences,
                                                    tokenizer),
            request_id=request_id or f"req-{next(self._ids)}",
            deadline_s=deadline_s, priority=priority,
            sampling=params,
            sampling_key=params.base_key() if params is not None else None,
            on_token=on_token, token_deadline_s=token_deadline_s)
        # speculation writes K draft positions past the frontier each
        # iteration; the admission bound keeps even the deepest
        # (immediately rolled back) write inside max_model_len
        limit = self.max_model_len - (
            self.spec.num_draft_tokens if self.spec is not None else 0)
        if req.prompt_len + req.max_new_tokens > limit:
            self.metrics.on_reject()
            raise AdmissionError(
                f"{req.request_id}: prompt ({req.prompt_len}) + "
                f"max_new_tokens ({req.max_new_tokens}) exceeds "
                f"max_model_len ({limit})")
        # deadline-aware load shedding (serving/overload.py): when even
        # an optimistic TTFT estimate busts the SLO, retire now — the
        # caller gets the handle back with finish_reason="shed"
        effective_deadline = deadline_s
        if token_deadline_s is not None:
            effective_deadline = token_deadline_s \
                if effective_deadline is None \
                else min(effective_deadline, token_deadline_s)
        if self.overload.should_shed(self, req.prompt, effective_deadline):
            self.metrics.on_submit(req.request_id)
            if req.on_token is not None:
                self.metrics.on_stream_start()
            self._retire(req, "shed")
            return req
        try:
            self.scheduler.enqueue(req)
        except QueueFull:
            victim = self.scheduler.shed_candidate(req.priority)
            if victim is None:
                self.metrics.on_reject()
                raise
            # full queue, higher-priority arrival: displace the lowest-
            # priority waiting request (shed) and take its place
            self.scheduler.waiting.remove(victim)
            self._retire(victim, "shed")
            self.scheduler.enqueue(req)
        except AdmissionError:
            self.metrics.on_reject()
            raise
        self.metrics.on_submit(req.request_id)
        if req.on_token is not None:
            self.metrics.on_stream_start()
        return req

    # ------------------------------------------------------------- step
    def step(self) -> bool:
        """One engine iteration: retire/admit at token granularity,
        advance admitted prompts by prefill chunks under the token
        budget, then one compiled decode step over the bucket.  Returns
        True while there is work left (running, prefilling or waiting).

        Raises :class:`EngineQuarantined` when the engine is FAILED
        (step watchdog out of retries, or a step failed holding the
        pool) — call :meth:`revive` after operator intervention."""
        if self.overload.health.failed:
            raise EngineQuarantined(
                f"engine quarantined FAILED "
                f"({self.overload.health.last_error}); revive() first")
        # One span, ``serving::step``, and one account a step
        # (``metrics.step``); inside it the phases (``metrics.phase``:
        # serving::admit, prefill_dispatch, first_token, decode_prepare,
        # decode_dispatch, decode_fetch, sample_emit, pool_sync), flat
        # among themselves, so that in a profiler trace every idle gap
        # of the device has one owner: a phase, or between two of them
        # the step.
        metrics = self.metrics
        with metrics.step():
            if self._ahead is not None \
                    and self.overload.ladder.level >= _LADDER_MAY_PREEMPT:
                self._drain()
            with metrics.phase("admit"):
                # one hysteresis step of the memory-pressure ladder
                # BEFORE admission, so pause_admissions takes effect this
                # iteration
                self.overload.ladder.tick(self)
                self._admit()
            self._prefill_tick()
            if any(r is not None and r.state == RUNNING
                   for r in self._slots):
                if self.block is not None:
                    self._block_iteration()
                else:
                    self._decode_iteration()
            elif self._ahead is not None:
                self._drain()
            with metrics.phase("pool_sync"):
                self._sync_pool_metrics()
            return self.has_work()

    def has_work(self) -> bool:
        return bool(self.scheduler.waiting) or self._ahead is not None \
            or any(r is not None for r in self._slots)

    def run_until_complete(self) -> Dict[str, Request]:
        """Drain queue + bucket; returns {request_id: Request} of every
        request finished during this drain."""
        while self.step():
            pass
        done, self._finished = self._finished, {}
        return done

    def generate(self, prompts, **submit_kwargs) -> List[np.ndarray]:
        """Batch convenience mirroring ``generate()``: submit every
        prompt, drain, return outputs (prompt + generated) in order."""
        reqs = [self.submit(p, **submit_kwargs) for p in prompts]
        self.run_until_complete()
        return [r.output_ids() for r in reqs]

    # -------------------------------------------------------- admission
    def _admit(self):
        # deadline sweep over the WAIT queue: an expired request must
        # not consume a prefill + slot it can no longer use
        for req in [r for r in self.scheduler.waiting if r.expired()]:
            self.scheduler.waiting.remove(req)
            self._retire(req, "timeout")
        if self.overload.ladder.admissions_paused:
            return
        free_slots = [i for i, r in enumerate(self._slots) if r is None]
        while free_slots:
            req = self.scheduler.next_admittable()
            if req is None:
                break
            if not self._begin_prefill(req, free_slots[0]):
                break
            free_slots.pop(0)

    def _begin_prefill(self, req: Request, slot: int) -> bool:
        """Admit ``req`` into ``slot``: attach prefix-cached blocks of
        its prompt (refcount bump, zero compute), allocate blocks for
        the uncached suffix, and mark it PREFILLING — chunks run in
        ``_prefill_tick``.  At least the prompt's LAST token is always
        recomputed, cached or not: its logits row is the first generated
        token, which cached k/v alone cannot produce."""
        matched, need, _ = self.pool.admission_plan(req.prompt,
                                                    extra_tokens=0)
        bs = self.config.block_size
        if self.block is not None:
            # the prompt's whole blocks are prefilled; nothing is
            # predicted from them, so all of a cached prefix is reused
            L = self.block.block_length
            req.prefill_end = req.prompt_len // L * L
            cached_len = min(len(matched) * bs, req.prefill_end)
        else:
            req.prefill_end = req.prompt_len
            cached_len = min(len(matched) * bs, req.prompt_len - 1)
        matched = matched[:self.pool.blocks_for(cached_len)] \
            if cached_len else []
        self.pool.acquire(req.request_id, matched)
        n = self.pool.blocks_for(req.prefill_end)
        try:
            suffix = self.pool.allocate(req.request_id, n - len(matched))
        except PoolExhausted:
            # defensive (admission_plan just said yes): hand the blocks
            # back and put the request at the head of the queue
            self.pool.free_request(req.request_id)
            self.scheduler.requeue_preempted(req)
            return False
        blocks = matched + suffix
        req.state = PREFILLING
        req.slot = slot
        req.blocks = blocks
        req.prefill_pos = cached_len
        req.cached_tokens = cached_len
        req.prefill_chunks = 0
        self.scheduler.running.append(req)
        self._slots[slot] = req
        self._block_tables[slot] = 0
        self._block_tables[slot, :len(blocks)] = blocks
        if self.window is not None:
            # window pages are taken chunk by chunk, as the window moves
            req.window_pages = {}
            self._window_tables[slot] = 0
        # frontier/pending stay 0 until the prompt completes: the decode
        # view masks this slot's block table to the garbage block
        self._lengths[slot] = 0
        self._pending[slot] = 0
        self.metrics.on_admit(req.request_id)
        self.metrics.on_prefix_lookup(req.request_id, cached_len,
                                      req.prompt_len)
        if self.block is not None and cached_len >= req.prefill_end:
            self._block_begin(req)      # nothing left to prefill
        return True

    def _prefill_tick(self):
        """Advance PREFILLING requests by fixed-shape chunks, oldest
        first, until the per-iteration token budget runs out (at least
        one chunk always runs so prefill can never stall).  A request
        whose final chunk completes gets its first token here and joins
        the decode bucket this same iteration."""
        budget = self.overload.ladder.effective_prefill_budget(
            self.config.prefill_token_budget or self.chunk_tokens)
        prefilling = sorted(
            (r for r in self.scheduler.running if r.state == PREFILLING),
            key=lambda r: r.ordinal)
        for req in prefilling:
            if budget <= 0:
                break
            while budget > 0 and req.state == PREFILLING:
                if req.expired():
                    self._retire(req, "timeout")
                    break
                try:
                    from ..resilience import chaos

                    chaos.maybe_fail_request(req.request_id)
                    self._prefill_chunk(req)
                except EngineQuarantined:
                    # an ENGINE-level failure (step watchdog out of
                    # retries) is not the request's fault — propagate
                    # instead of retiring it as poison
                    raise
                except Exception as e:  # noqa: BLE001 — poison isolation
                    # ONE malformed request must not kill the engine
                    # loop: fail and retire it, free its blocks, keep
                    # serving the rest
                    req.error = f"{type(e).__name__}: {e}"
                    self._retire(req, "error")
                    break
                budget -= self.chunk_tokens

    def _prefill_chunk(self, req: Request):
        """Run ONE [1, chunk_tokens] compiled prefill chunk for ``req``
        at its current prompt position, copy-on-write-protecting every
        block the chunk writes into; the prompt's last chunk yields the
        first token."""
        start = req.prefill_pos
        n_tok = min(self.chunk_tokens, req.prefill_end - start)
        with self.metrics.phase("prefill_dispatch",
                                request_id=req.request_id, start=start,
                                tokens=n_tok):
            last = self._dispatch_chunk(req, start, n_tok)
        if self.block is not None:
            # a block model's chunk returns its routing stats, which
            # stay on the device until the next block step is fetched
            self._route_stats.append(last)
        if req.prefill_pos < req.prefill_end:
            return
        with self.metrics.phase("first_token", request_id=req.request_id):
            if self.block is not None:
                self._block_begin(req)
            elif self._ahead is not None and req.sampling is None:
                # the first id stays on the device: the next step takes
                # it as its input, and it is read once the step in
                # flight has been read and emitted (``_emit_firsts``)
                self._prompt_done(req)
                self._firsts.append((req, last))
            else:
                self._first_token(req, last)

    def _dispatch_chunk(self, req: Request, start: int, n_tok: int):
        """Phase ``prefill_dispatch``: copy-on-write checks, the chunk's
        ids, and the call into the chunk program until it returns
        handles.  Returns, still on the device, what the chunk's last
        real token gives: the id the program chose (a greedy request),
        the logits row (a sampled one), a block model's routing
        stats."""
        bs = self.config.block_size
        C = self.chunk_tokens
        self.metrics.on_prefill_dispatch(req.request_id, start, n_tok,
                                         window=self.window)
        # blocks this chunk writes: CoW any that are shared/registered
        # (a cache hit whose last block the final recompute token lands
        # in, or blocks registered by a previous admission)
        for bi in range(start // bs,
                        self.pool.blocks_for(start + n_tok)):
            new = self.pool.ensure_writable(req.request_id,
                                            req.blocks[bi])
            if new != req.blocks[bi]:
                req.blocks[bi] = new
                self._block_tables[req.slot, bi] = new
        ids = np.zeros((1, C), np.int32)
        ids[0, :n_tok] = req.prompt[start:start + n_tok]
        # the host may write the table's row before it reads this chunk's
        # result: the program gets a copy, not a view changed under it
        bt = self._block_tables[req.slot:req.slot + 1].copy()
        if self.window is not None:
            # the chunk's first query sits at ``start``: what lies behind
            # its window goes back, the chunk's own pages are taken
            self._advance_window(req, start, start + n_tok)
            bt = (bt, self._window_tables[req.slot:req.slot + 1].copy())
        # watchdog-wrapped dispatch (serving/overload.py): monotonic
        # budget; the program consumes the pool it is handed (donated),
        # so what comes back is bound at once, and only a failure from
        # before the program took it is retried
        greedy = self.block is None and req.sampling is None
        last, new_pools = self.overload.prefill_watchdog.call(
            self._prefill_ids if greedy else self._prefill_step,
            ids, self._target_pools(), bt,
            np.asarray([start], np.int32), np.int32(n_tok - 1))
        self._rebind_target(new_pools)
        if isinstance(last, tuple):
            # a routed model's chunk also says what its experts read
            last, stats = last
            self._route_stats.append(stats)
        if self.spec is not None:
            # the draft prefills the same chunk into its own layer slice
            # of the SAME blocks (already CoW-protected above), so the
            # prefix cache serves both models from one block table
            _, new_draft = self._draft_prefill_wd.call(
                self._draft_prefill_step, ids, self._draft_pools(), bt,
                np.asarray([start], np.int32), np.int32(n_tok - 1))
            self._rebind_draft(new_draft)
        req.prefill_pos = start + n_tok
        req.prefill_chunks += 1
        return last

    def _first_token(self, req: Request, last):
        """Phase ``first_token``: the prompt is complete, and the last
        chunk gives the first token: the id its program chose (greedy:
        4 bytes to the host), or its logits row (token index 0 — sampled
        lanes fold the base key with 0, the same program generate()
        runs, so the streams agree from the very first token).  Reading
        ``last`` waits for the device."""
        params = req.sampling
        if params is not None:
            first_tok = int(np.asarray(sample_at(
                self._fetch(last).astype(np.float32),
                np.asarray([params.temperature], np.float32),
                np.asarray([params.top_k], np.int32),
                np.asarray([params.top_p], np.float32),
                req.sampling_key[None, :],
                np.asarray([0], np.int32)))[0])
        else:
            first_tok = int(self._fetch(last)[0])
        self._prompt_done(req)
        self._first_emit(req, first_tok)

    def _prompt_done(self, req: Request):
        """The prompt's last chunk is dispatched: the request joins the
        decode bucket at the prompt's end, and its full blocks are
        published for future prefix hits (they become immutable; the
        decode frontier CoWs out as needed).  The chunk writes them
        before any later program reads them: the device runs programs
        in order."""
        req.state = RUNNING
        slot, params = req.slot, req.sampling
        self._lengths[slot] = req.prompt_len
        if params is not None:
            self._temps[slot] = params.temperature
            self._top_ks[slot] = params.top_k
            self._top_ps[slot] = params.top_p
            self._keys[slot] = req.sampling_key
        self.metrics.on_prefill_complete(req.request_id,
                                         req.prefill_chunks)
        self.pool.register_prefix(req.request_id, req.prompt, req.blocks)

    def _first_emit(self, req: Request, first_tok: int):
        """The first token is on the host: it is emitted, and it may
        already terminate the request."""
        req.generated = [first_tok]
        self.metrics.tokens_generated += 1
        self._pending[req.slot] = first_tok
        self._counters[req.slot] = 1
        self.metrics.on_first_token(req.request_id)
        if not self._emit_token(req, first_tok):
            self._retire(req, "error")
            return
        self._maybe_retire(req)

    # ---------------------------------------------------------- decode
    def _ensure_blocks(self, horizon: int = 1, skip=(), preempt=True):
        """Every RUNNING slot (but those of ``skip``, whose last token is
        in flight) needs WRITABLE blocks for its next
        ``horizon`` write positions (1 for plain decode; K+1 under
        speculative decoding, where the verify step writes the pending
        token plus K draft positions): allocate when the frontier
        crosses into a new block, copy-on-write when a written block is
        one the prefix cache shares.  Allocation preempts
        YOUNGEST-first when the pool is dry — oldest first, so a
        starving old request evicts young ones, never the reverse (a
        young request that cannot get a block preempts ITSELF before
        touching older work).  Where a step is in flight (``preempt``
        False) a preemption raises ``_DrainFirst`` in its place: what is
        done so far stands, and the call may be made again."""
        for req in sorted(self.scheduler.running,
                          key=lambda r: r.ordinal):
            if req.slot is None or req.state != RUNNING or req in skip:
                continue
            pos = int(self._lengths[req.slot])
            need = self.pool.blocks_for(pos + horizon)
            preempted = False
            while len(req.blocks) < need:
                try:
                    new = self.pool.allocate(req.request_id, 1)
                except PoolExhausted:
                    victim = self.scheduler.pick_victim()
                    if victim is None:
                        # unreachable: enqueue() capacity check
                        # guarantees a sole-running request always fits
                        raise
                    if not preempt:
                        raise _DrainFirst from None
                    self._preempt(victim)
                    if victim is req:
                        preempted = True
                        break
                    continue
                self._block_tables[req.slot, len(req.blocks)] = new[0]
                req.blocks.extend(new)
            if preempted:
                continue
            if self.window is not None:
                # the next query sits at ``pos``: what lies behind its
                # window goes back, the page it writes is taken.  (The
                # group holds ``window_pages_per_seq`` a slot, so it
                # cannot run dry; were it to, the youngest goes as above)
                while req.slot is not None:
                    try:
                        self._advance_window(req, pos, pos + horizon)
                        break
                    except PoolExhausted:
                        if not preempt:
                            raise _DrainFirst from None
                        self._preempt(self.scheduler.pick_victim())
                if req.slot is None:
                    continue
            # a written block may be shared (prefix-cache hit on the
            # whole prompt, or a registered prompt tail): break the
            # share before decode writes into it.  Freshly allocated
            # blocks are singly-owned, so ensure_writable is a no-op
            # past the frontier block.
            for fi in range(pos // self.config.block_size, need):
                while True:
                    try:
                        new = self.pool.ensure_writable(req.request_id,
                                                        req.blocks[fi])
                    except PoolExhausted:
                        victim = self.scheduler.pick_victim()
                        if victim is None:
                            raise
                        if not preempt:
                            raise _DrainFirst from None
                        self._preempt(victim)
                        if victim is req:
                            preempted = True
                            break
                        continue
                    break
                if preempted:
                    break
                if new != req.blocks[fi]:
                    req.blocks[fi] = new
                    self._block_tables[req.slot, fi] = new
            if preempted:
                continue

    def _advance_window(self, req: Request, first_query: int, end: int):
        """The window group's pages of ``req`` for queries from
        ``first_query`` that write up to ``end``: released behind the
        window, taken ahead (``BlockKVPool.advance_window``)."""
        self.metrics.window_pages_released += self.pool.advance_window(
            req.request_id, req.window_pages,
            self._window_tables[req.slot], first_query, end)

    def _preempt(self, victim: Request):
        """Evict-and-requeue (recompute mode): free everything, head of
        the queue, original FCFS ordinal."""
        slot = victim.slot
        self.scheduler.running.remove(victim)
        self.pool.free_request(victim.request_id)
        victim.preemptions += 1
        self.metrics.on_preempt(victim.request_id, victim.num_generated)
        self._slots[slot] = None
        self._clear_tables(slot)
        self._lengths[slot] = 0
        self._pending[slot] = 0
        self._clear_sampling_slot(slot)
        self._clear_block_slot(slot)
        self.scheduler.requeue_preempted(victim)

    def _clear_tables(self, slot: int):
        self._block_tables[slot] = 0
        if self.window is not None:
            self._window_tables[slot] = 0

    def _clear_sampling_slot(self, slot: int):
        self._temps[slot] = 0.0
        self._top_ks[slot] = 0
        self._top_ps[slot] = 1.0
        self._keys[slot] = 0
        self._counters[slot] = 0

    def _decode_block_view(self, skip=(), copy=False):
        """Decode view of the block tables: slots still mid-prefill are
        masked to the garbage block so a bucket-wide step can never
        write into (possibly shared) blocks of an unfinished prompt, and
        so are the slots of ``skip``.  ``copy``: the view is a copy even
        where nothing is masked (a step left in flight while the host
        changes the tables).  A window model's view is the pair ``(full
        group's, window group's)``."""
        masked = [i for i, r in enumerate(self._slots)
                  if r is not None and (r.state == PREFILLING or r in skip)]

        def view(bt):
            if masked or copy:
                bt = bt.copy()
                bt[masked] = 0
            return bt

        if self.window is None:
            return view(self._block_tables)
        return view(self._block_tables), view(self._window_tables)

    def _emit_token(self, req: Request, tok: int) -> bool:
        """Per-accepted-token hooks: reset the rolling inter-token
        deadline and fire the streaming callback.  Returns False when
        the callback raised — the CONSUMER failed, so the caller
        retires the request as an error instead of crashing the engine
        loop (poison isolation, same policy as prefill)."""
        if req.token_deadline_s is not None:
            req.token_deadline_t = time.monotonic() + req.token_deadline_s
        if req.on_token is None:
            return True
        try:
            req.on_token(tok)
        except Exception as e:  # noqa: BLE001 — consumer isolation
            req.error = f"on_token callback: {type(e).__name__}: {e}"
            return False
        return True

    def _decode_iteration(self):
        if self.spec is not None:
            self._spec_iteration()
            return
        if self._pipelined and not any(
                r.sampling is not None for r in self.scheduler.running
                if r.state == RUNNING):
            self._ahead_iteration()
            return
        self._drain()
        active, bt = self._decode_prepare()
        if not active:
            return
        if any(r.sampling is not None for r in active):
            self._sampled_iteration(active, bt)
        else:
            self._greedy_iteration(active, bt)

    def _greedy_iteration(self, active, bt):
        """One greedy decode step over the bucket, read before this
        returns: a mesh's lane, whose programs lay their inputs out
        across the devices (``_DeviceFed`` is one device's)."""
        phase = self.metrics.phase
        chunk_stats = self._route_stats

        # the device→host sync (the ids the program chose: 4 bytes a
        # slot; the logits stay on the device) happens INSIDE the timed
        # closure so the watchdog budget covers device execution, not
        # just dispatch; a fault that surfaces in it finds the donated
        # pool consumed (the watchdog quarantines, ``revive`` rebuilds)
        def _timed_decode(tokens, layers, tables, lengths):
            with phase("decode_dispatch", slots=len(active)):
                out, pools = self._decode_ids(tokens, layers, tables,
                                              lengths)
            step = _InFlight(out, active, chunk_stats)
            return step, pools, self._read(step)

        step, new_pools, got = self.overload.decode_watchdog.call(
            _timed_decode, self._pending[:, None], self._target_pools(), bt,
            self._lengths)
        self._route_stats = []
        with phase("sample_emit"):
            self._rebind_target(new_pools)
            self._on_decode_iteration(active, self._lengths)
            # the pending token was written at position lengths[slot]
            for req in active:
                self._lengths[req.slot] += 1
            self._emit_tokens(step, got)

    def _ahead_iteration(self):
        """One greedy decode step over the bucket, run ahead: dispatched
        behind the step in flight, with that step's ids as its input on
        the device, and the step in flight read after it, while it runs
        (module docstring)."""
        prev = self._ahead
        try:
            active, bt, lengths, tok, program = self._ahead_prepare(prev)
        except _DrainFirst:
            self._drain()
            prev = None
            active, bt, lengths, tok, program = self._ahead_prepare(None)
        if not active:
            self._drain()
            return
        phase = self.metrics.phase
        chunk_stats = self._route_stats

        # the read of the step in flight happens INSIDE the timed closure
        # so the watchdog budget covers device execution, not just
        # dispatch; a fault that surfaces in it finds the donated pool
        # consumed by the step just dispatched (the watchdog quarantines,
        # ``revive`` rebuilds)
        def _timed_decode(tokens, layers, tables, lengths):
            with phase("decode_dispatch", slots=len(active)):
                out, pools = program(tokens, layers, tables, lengths)
            return (_InFlight(out, active, chunk_stats), pools,
                    prev and self._read(prev))

        self._ahead, new_pools, got = self.overload.decode_watchdog.call(
            _timed_decode, tok, self._target_pools(), bt, lengths)
        self._route_stats = []
        if prev is not None:
            self.metrics.decode_steps_ahead += 1
        with phase("sample_emit"):
            self._rebind_target(new_pools)
            self._on_decode_iteration(active, lengths)
            # the pending token is written at position lengths[slot]
            for req in active:
                self._lengths[req.slot] += 1
            if prev is not None:
                self._emit_tokens(prev, got)
        if self._firsts:
            # a read of their own, after the step in flight was emitted:
            # in the same batch it would wait for their chunks, queued
            # behind that step, and hold back every token it delivers
            firsts, self._firsts = self._firsts, []
            with phase("first_token"):
                self._emit_firsts(firsts, self._fetch(
                    [first for _, first in firsts]))

    def _ahead_prepare(self, prev):
        """Phase ``decode_prepare`` of a step dispatched ahead, behind
        ``prev`` (the step in flight, or None): ``(active, tables,
        lengths, tok, program)``, each array a copy the host may change
        while the step runs.  A request whose last token is in flight
        sits the step out, masked like a prefilling slot."""
        unread = [] if prev is None else [r for r, _ in prev.active] \
            + [r for r, _ in self._firsts]
        skip = {r for r in unread if r.state == RUNNING
                and r.num_generated + 1 >= r.max_new_tokens}
        with self.metrics.phase("decode_prepare"):
            self._ensure_blocks(1, skip, prev is None)
            active = [r for r in self._slots if r is not None
                      and r.state == RUNNING and r not in skip]
            if not active:
                return active, None, None, None, None
            lengths = self._lengths.copy()
            lengths[[r.slot for r in skip]] = 0
            return (active, self._decode_block_view(skip, copy=True), lengths,
                    *self._next_input(prev, active))

    def _next_input(self, prev, active):
        """``(tok, program)`` of the next greedy step: the host's pending
        tokens and the jitted program where nothing is in flight, else
        the ids of the step in flight, on the device, with the first id
        of each prompt completed behind it set at its slot, and the same
        program's executable (``_DeviceFed``)."""
        if prev is None:
            # (a copy: the host writes ``_pending`` while the step runs)
            return self._pending[:, None].copy(), self._decode_ids
        tok = _column(prev.ids)
        if self._device_fed is None:
            self._device_fed = _DeviceFed(
                self._decode_step, tok, self._target_pools(),
                self._block_tables if self.window is None else
                (self._block_tables, self._window_tables), self._lengths)
            # the merge's one compile, here and not where a prompt
            # first completes behind a step
            _merge(tok, jax.device_put(np.zeros((1,), np.int32)),
                   np.int32(0))
        running = set(active)
        for req, first in self._firsts:
            if req in running:
                tok = _merge(tok, first, np.int32(req.slot))
        return tok, self._device_fed

    def _read(self, step: _InFlight, firsts=()):
        """Phase ``decode_fetch``: ``step``'s ids and stats on the host,
        with the routing stats of the chunks dispatched before it and the
        first ids of ``firsts``, in one batch of copies (a copy's latency
        is paid once).  Waits for the device."""
        with self.metrics.phase("decode_fetch"):
            got = self._fetch([step.ids, *step.stats, *step.chunk_stats,
                               *(first for _, first in firsts)])
        n = 1 + len(step.stats)
        m = n + len(step.chunk_stats)
        return got[0], got[1:n], got[n:m], got[m:]

    def _drain(self):
        """Read the step in flight, if there is one, and the first ids
        of the prompts completed behind it, with nothing dispatched
        behind them (counter ``ahead_drains``): before a preemption, a
        switch to another lane, ``revive()``, or when the bucket has no
        next step to run."""
        step, firsts = self._ahead, self._firsts
        if step is None:
            return

        # under the decode watchdog, as every read of a decode step: its
        # budget and its retries (a fault before the read), and a fault
        # IN the read is one of the step it reads, whose output the pool
        # is: the pool is lost with it (its buffers are let go, so the
        # watchdog sees them consumed: ``pool_lost``, FAILED, and
        # ``revive`` rebuilds)
        def _timed_read(layers):
            try:
                return self._read(step, firsts)
            except Exception:
                for leaf in jax.tree_util.tree_leaves(layers):
                    leaf.delete()
                raise

        got = self.overload.decode_watchdog.call(
            _timed_read, self._target_pools())
        # (only once it is read: a quarantine with the pool whole leaves
        # the step in flight for ``revive`` to read)
        self._ahead, self._firsts = None, []
        self.metrics.ahead_drains += 1
        with self.metrics.phase("sample_emit"):
            self._emit_tokens(step, got)
        if firsts:
            with self.metrics.phase("first_token"):
                self._emit_firsts(firsts, got[3])

    def _drop_in_flight(self):
        """Forget the step in flight unread (its pool is gone, or its
        requests are served elsewhere)."""
        self._ahead = None
        self._firsts = []

    def _emit_tokens(self, step: _InFlight, got):
        """Emit each request's token from the read of ``step`` (``got``,
        as ``_read`` gives it); a request retired since ``step`` was
        dispatched has its token discarded."""
        ids, stats, chunk_stats, _ = got
        for chunk in chunk_stats:
            self.metrics.on_route_stats(*(int(v) for v in chunk))
        for decode in stats:
            self.metrics.on_route_stats(*(int(v) for v in decode),
                                        decode=True)
        for req, slot in step.active:
            if req.slot != slot or req.state != RUNNING:
                continue
            next_tok = int(ids[slot])
            self._append_token(req, next_tok)
            self._pending[slot] = next_tok
            self._counters[slot] = len(req.generated)
            if not self._emit_token(req, next_tok):
                self._retire(req, "error")
                continue
            self._maybe_retire(req)

    def _emit_firsts(self, firsts, ids):
        """The first tokens of ``firsts`` (``(request, id on the
        device)`` pairs), read as ``ids``."""
        for (req, _), first in zip(firsts, ids):
            if req.state == RUNNING:
                self._first_emit(req, int(first[0]))

    def _decode_prepare(self, horizon: int = 1):
        """Phase ``decode_prepare``: writable blocks for every running
        slot's next ``horizon`` positions, then the active requests and
        the decode view of the block tables."""
        with self.metrics.phase("decode_prepare"):
            self._ensure_blocks(horizon)
            active = [r for r in self._slots
                      if r is not None and r.state == RUNNING]
            return active, self._decode_block_view() if active else None

    def _on_decode_iteration(self, active, lengths):
        # every slot that does not run has length 0
        self.metrics.decode_context_tokens += int(lengths.sum())
        if self.window is not None:
            self.metrics.on_window_iteration(
                int(np.minimum(lengths, self.window).sum()),
                sum(len(r.window_pages) for r in active), len(active))
        self.metrics.on_decode_iteration(
            len(active), self.config.max_batch_size,
            self.pool.utilization())

    def _append_token(self, req: Request, tok: int):
        req.generated.append(tok)
        self.metrics.tokens_generated += 1

    def _sampled_iteration(self, active, bt):
        """One bucket-wide sampled decode step: identical forward pass
        to the greedy step plus the on-device fold + filter +
        categorical — runs whenever ANY active slot samples (greedy
        slots ride along on the temperature-0 argmax lane, so the
        bucket stays ONE compiled program with zero retraces)."""
        phase, fetch = self.metrics.phase, self._fetch

        def _timed_decode(tokens, layers, tables, lengths, temps,
                          tks, tps, keys, counters):
            with phase("decode_dispatch", slots=len(active)):
                out, pools = self._sampled_decode_step(
                    tokens, layers, tables, lengths, temps, tks, tps,
                    keys, counters)
            with phase("decode_fetch"):
                return fetch(out), pools

        toks, new_pools = self._sampled_wd.call(
            _timed_decode, self._pending[:, None],
            self._target_pools(), bt, self._lengths, self._temps,
            self._top_ks, self._top_ps, self._keys, self._counters)
        with phase("sample_emit"):
            self._rebind_target(new_pools)
            self._on_decode_iteration(active, self._lengths)
            for req in active:
                slot = req.slot
                self._lengths[slot] += 1
                next_tok = int(toks[slot])
                self._append_token(req, next_tok)
                self._pending[slot] = next_tok
                self._counters[slot] = len(req.generated)
                if not self._emit_token(req, next_tok):
                    self._retire(req, "error")
                    continue
                self._maybe_retire(req)

    def _spec_iteration(self):
        """One speculative iteration: draft-propose (K tokens, one
        scanned program over the draft's pool slice) → target-verify
        ([S, K+1] chunked-shaped program with on-device acceptance) →
        host commit of each slot's accepted tokens → block-granular KV
        rollback of the rejected tail.  Only the committed token ids
        and accepted lengths sync to host."""
        k_draft = self.spec.num_draft_tokens
        active, bt = self._decode_prepare(horizon=k_draft + 1)
        if not active:
            return
        phase, fetch = self.metrics.phase, self._fetch

        # draft proposals + distributions stay ON DEVICE between the
        # two steps; the verify closure's np.asarray is the only host
        # sync of the iteration
        def _timed_draft(tokens, layers, tables, lengths, temps,
                         tks, tps, keys, counters):
            with phase("decode_dispatch", slots=len(active)):
                return self._draft_propose_step(
                    tokens, layers, tables, lengths, temps, tks, tps,
                    keys, counters)

        props, dprobs, new_draft = self._draft_propose_wd.call(
            _timed_draft, self._pending[:, None], self._draft_pools(),
            bt, self._lengths, self._temps, self._top_ks,
            self._top_ps, self._keys, self._counters)
        self._rebind_draft(new_draft)

        def _timed_verify(pending, proposals, probs, layers, tables,
                          lengths, temps, tks, tps, keys, counters):
            with phase("decode_dispatch", slots=len(active)):
                committed, accepted, pools = self._spec_verify_step(
                    pending, proposals, probs, layers, tables, lengths,
                    temps, tks, tps, keys, counters)
            with phase("decode_fetch"):
                return fetch(committed), fetch(accepted), pools

        committed, accepted, new_target = self._spec_verify_wd.call(
            _timed_verify, self._pending, props, dprobs,
            self._target_pools(), bt, self._lengths, self._temps,
            self._top_ks, self._top_ps, self._keys, self._counters)
        with phase("sample_emit"):
            self._rebind_target(new_target)
            self._on_decode_iteration(active, self._lengths)
            accepted_drafts = 0
            for req in active:
                slot = req.slot
                n_new = int(accepted[slot])      # 1..K+1 committed tokens
                accepted_drafts += n_new - 1
                self.metrics.on_spec_commit(n_new)
                taken = 0
                finished = False
                for tok in committed[slot, :n_new]:
                    tok = int(tok)
                    self._append_token(req, tok)
                    taken += 1
                    if not self._emit_token(req, tok):
                        self._retire(req, "error")
                        finished = True
                        break
                    reason = self.scheduler.finish_reason(req)
                    if reason is not None:
                        # eos / stop / length may land mid-commit:
                        # trailing committed tokens are DROPPED, matching
                        # where sequential generate() stops — zero lost,
                        # zero duplicated (_retire frees every block)
                        self._retire(req, reason)
                        finished = True
                        break
                if finished:
                    continue
                self._lengths[slot] += taken
                self._pending[slot] = int(committed[slot, taken - 1])
                self._counters[slot] = len(req.generated)
                self._rollback_blocks(req)
            self.metrics.on_spec_step(k_draft * len(active),
                                      accepted_drafts)

    # ------------------------------------------- diffusion over blocks
    def _clear_block_slot(self, slot: int):
        if self.block is not None:
            self._blk_mode[slot] = _BLOCK_IDLE
            self._blk_step[slot] = 0
            self._blk_masked[slot] = False
            self._blk_ids[slot] = 0

    def _block_open(self, slot: int, known=()):
        """The slot's next block, at ``_lengths[slot]``: ``known``
        tokens (a prompt's tail) then masks."""
        self._blk_ids[slot] = self.block.mask_token_id
        self._blk_ids[slot, :len(known)] = known
        self._blk_masked[slot] = True
        self._blk_masked[slot, :len(known)] = False
        self._blk_mode[slot] = _BLOCK_DENOISE
        self._blk_step[slot] = 0

    def _block_begin(self, req: Request):
        """The prompt's whole blocks are in the pool: the request joins
        the bucket with its first block open, the prompt's tail as its
        known positions."""
        req.state = RUNNING
        req.generated = []
        self._lengths[req.slot] = req.prefill_end
        self._block_open(req.slot, req.prompt[req.prefill_end:])
        self.metrics.on_prefill_complete(req.request_id,
                                         req.prefill_chunks)
        self.pool.register_prefix(req.request_id,
                                  req.prompt[:req.prefill_end], req.blocks)

    def _block_iteration(self):
        """One step of the block program over the bucket: denoising
        slots get tokens unmasked (chosen on the device), committing
        slots get their block's K/V written.  Only the blocks' ids, their
        masks and three routing counts come back to the host."""
        blk, metrics = self.block, self.metrics
        L, S = blk.block_length, self.config.max_batch_size
        phase = metrics.phase
        # (block-aligned: a slot's frontier is a multiple of L)
        active, bt = self._decode_prepare(horizon=L)
        if not active:
            return
        with phase("decode_prepare"):
            mode, n_unmask, tau = self._block_step_inputs(active)

        def _timed_block(*args):
            with phase("decode_dispatch", slots=len(active)):
                small, _probe, pools = self._decode_step(*args)
            with phase("decode_fetch"):
                stats = [self._fetch(s) for s in self._route_stats]
                return self._fetch(small), stats, pools

        small, chunk_stats, new_pools = self.overload.decode_watchdog.call(
            _timed_block, self._blk_ids, self._blk_masked, self._lengths,
            mode, n_unmask, tau, self._target_pools(), bt)
        with phase("sample_emit"):
            self._rebind_target(new_pools)
            self._route_stats = []
            for stats in chunk_stats + [small[2 * S * L:]]:
                metrics.on_route_stats(*(int(v) for v in stats))
            new_ids = small[:S * L].reshape(S, L)
            new_masked = small[S * L:2 * S * L].reshape(S, L) != 0
            committing = sum(1 for r in active
                             if mode[r.slot] == _BLOCK_COMMIT)
            metrics.on_block_step(len(active), committing,
                                  int(self._lengths.sum()))
            metrics.on_decode_iteration(len(active), S,
                                        self.pool.utilization())
            for req in active:
                slot = req.slot
                if mode[slot] == _BLOCK_COMMIT:
                    # the block's K/V are in the pool: the next begins
                    self._lengths[slot] += L
                    self._block_open(slot)
                    continue
                metrics.tokens_unmasked += int(
                    self._blk_masked[slot].sum() - new_masked[slot].sum())
                self._blk_ids[slot] = new_ids[slot]
                self._blk_masked[slot] = new_masked[slot]
                self._blk_step[slot] += 1
                if not new_masked[slot].any():
                    self._blk_mode[slot] = _BLOCK_COMMIT
                self._emit_block_tokens(req)

    def _block_step_inputs(self, active):
        """``(mode, n_unmask, tau)`` of the next block step: what each
        slot does, how many positions a denoising slot unmasks at least
        (the static schedule; the last step a block may take unmasks
        what is left) and the confidence that unmasks more (the dynamic
        rule; 2.0, which no confidence passes, under the static one)."""
        blk, schedule = self.block, self._unmask_schedule
        S = self.config.max_batch_size
        n_unmask = np.zeros((S,), np.int32)
        tau = np.full((S,), 2.0, np.float32)
        for req in active:
            slot = req.slot
            if self._blk_mode[slot] != _BLOCK_DENOISE:
                continue
            step = int(self._blk_step[slot])
            n_unmask[slot] = blk.block_length \
                if step >= len(schedule) - 1 else schedule[step]
            if blk.remasking == "low_confidence_dynamic":
                tau[slot] = blk.confidence_threshold
        return self._blk_mode.copy(), n_unmask, tau

    def _emit_block_tokens(self, req: Request):
        """Hand the block's tokens that are final and contiguous with
        what was already emitted to ``on_token``, in order.  A request
        may end inside a block (``length``, ``eos``, a stop sequence):
        the block's trailing positions are dropped and ``_retire``
        returns every block."""
        slot = req.slot
        start = int(self._lengths[slot])
        at = req.prompt_len + req.num_generated - start
        while at < self.block.block_length and not self._blk_masked[slot, at]:
            tok = int(self._blk_ids[slot, at])
            if not req.generated:
                self.metrics.on_first_token(req.request_id)
            self._append_token(req, tok)
            if not self._emit_token(req, tok):
                self._retire(req, "error")
                return
            reason = self.scheduler.finish_reason(req)
            if reason is not None:
                self._retire(req, reason)
                return
            at += 1

    def _rollback_blocks(self, req: Request):
        """Truncate ``req``'s KV back to its accepted frontier: blocks
        wholly past the next write position were only ever filled with
        rejected draft KV — free them (refcount drop; they were made
        writable, hence singly-owned, by ``_ensure_blocks``).  Positions
        within kept blocks need no scrub: paged attention masks
        ``k_pos <= q_pos``, so KV past the frontier is never read and
        the next verify overwrites it."""
        keep = self.pool.blocks_for(int(self._lengths[req.slot]) + 1)
        if len(req.blocks) > keep:
            tail = req.blocks[keep:]
            del req.blocks[keep:]
            self.pool.free(tail, req.request_id)
            self._block_tables[req.slot, keep:] = 0

    # ----------------------------------------------------------- retire
    def _maybe_retire(self, req: Request):
        reason = self.scheduler.finish_reason(req)
        if reason is not None:
            self._retire(req, reason)

    def _retire(self, req: Request, reason: str):
        """Finish ``req`` for ``reason`` from ANY state — running in a
        slot, mid-prefill, or never admitted (queued timeout / failed
        prefill).  Releasing its references may PARK prompt blocks in
        the pool's prefix LRU rather than freeing them — that is the
        cache, not a leak."""
        slot = req.slot
        req.state = FINISHED
        req.finish_reason = reason
        if req in self.scheduler.running:
            self.scheduler.running.remove(req)
        self.pool.free_request(req.request_id)
        req.slot = None
        if slot is not None:
            self._slots[slot] = None
            self._clear_tables(slot)
            self._lengths[slot] = 0
            self._pending[slot] = 0
            self._clear_sampling_slot(slot)
            self._clear_block_slot(slot)
        self.metrics.on_finish(req.request_id, req.num_generated, reason)
        if req.on_token is not None:
            self.metrics.on_stream_end()
        self._finished[req.request_id] = req

    # ------------------------------------------------------------ misc
    def _sync_pool_metrics(self):
        """Mirror pool-owned prefix-cache counters into the metrics
        layer (delta-based: the pool counts, metrics accumulate)."""
        d = self.pool.evictions - self._evictions_seen
        if d:
            self._evictions_seen = self.pool.evictions
            self.metrics.on_evictions(d)

    def decode_cache_size(self) -> int:
        """Entries in the compiled decode step's jit cache — 1 after
        warmup, forever (the no-retrace contract)."""
        return self._decode_step._cache_size()

    def prefill_cache_size(self) -> int:
        """Entries in the compiled chunked-prefill step's jit cache — 1
        after warmup, for EVERY prompt length (the bucket-explosion
        fix)."""
        return self._prefill_step._cache_size()

    def sampled_decode_cache_size(self) -> int:
        """Jit-cache entries of the sampled decode step — 0 for a
        greedy-only workload (the step never runs), 1 after the first
        sampled iteration, forever (the same no-retrace contract)."""
        if self._sampled_decode_step is None:
            return 0
        return self._sampled_decode_step._cache_size()

    def spec_cache_sizes(self) -> Dict[str, int]:
        """Jit-cache entries of the speculative steps (each 1 after
        warmup) — empty dict when speculation is off."""
        if self.spec is None:
            return {}
        return {"draft_prefill": self._draft_prefill_step._cache_size(),
                "draft_propose": self._draft_propose_step._cache_size(),
                "spec_verify": self._spec_verify_step._cache_size()}

    def health(self) -> dict:
        """Engine health snapshot (serving/overload.py): state
        (``"serving"``/``"degraded"``/``"failed"``), degradation-ladder
        level, watchdog stall/retry totals, latency EWMAs, queue depth
        and KV pressure — host-side only, cheap to poll."""
        return self.overload.snapshot(self)

    def revive(self):
        """Operator override after a FAILED quarantine: clear health
        back to SERVING so ``submit`` and ``step`` accept work again.
        The caller owns deciding the underlying fault is gone.

        Where the quarantine came from a step that failed holding the
        donated pool (counter ``pool_lost``), the cached K/V of every
        request went with it: the pool gets fresh zeroed buffers and an
        empty prefix index, and every running or mid-prefill request
        goes back to the head of the queue through the recompute path
        of preemption, so it still ends with the tokens of a clean run
        (as after any preemption, ``on_token`` is handed the recomputed
        tokens from the first).  A greedy step still in flight is read
        first where the pool was not lost, and dropped where it was (or
        where that read loses it): its requests go back through
        recompute with the rest."""
        if not self.pool.lost():
            try:
                self._drain()
            except EngineQuarantined:
                if not self.pool.lost():
                    raise
        if self.pool.lost():
            self._drop_in_flight()
            for req in sorted(self.scheduler.running,
                              key=lambda r: r.ordinal, reverse=True):
                self._preempt(req)
            self.pool.reset()
            if self.mesh_executor is not None:
                self.pool.layers = self.mesh_executor.shard_kv_layers(
                    self.pool.layers)
            self._route_stats = []
        self.overload.health.revive()

    def pending_prefill_tokens(self) -> int:
        """Prompt tokens admitted-but-uncomputed plus everything still
        waiting in the queue — the prefill backlog a new arrival queues
        behind.  The router's load signal and the TTFT estimator's
        numerator (serving/overload.py) read the same number."""
        pending = sum(r.prompt_len - r.prefill_pos
                      for r in self.scheduler.running
                      if r.state == PREFILLING)
        pending += sum(r.prompt_len for r in self.scheduler.waiting)
        return pending

    def stats(self) -> dict:
        d = self.metrics.as_dict()
        d["pool"] = self.pool.stats()
        d["queue_depth"] = len(self.scheduler.waiting)
        d["pending_prefill_tokens"] = self.pending_prefill_tokens()
        d["prefix_index"] = self.pool.prefix_summary()
        d["health"] = self.health()
        return d
