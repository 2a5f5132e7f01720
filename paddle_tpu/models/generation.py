# lint-tpu: disable-file=L004 -- grandfathered direct jax use; new backend code belongs under core/ ops/ kernels/ static/ distributed/ (README: Repo lint)
"""Autoregressive decoding over KV caches (reference capability:
paddle/fluid/operators/fused/fused_multi_transformer_op.cu decode path +
the sampling ops top_k_op/top_p_sampling; the high-level loop lives in
PaddleNLP's GenerationMixin, whose API this mirrors).

Works with any causal LM exposing the cache contract
``model(input_ids, caches=..., position_offset=...) -> (logits, caches)``
with per-layer (k, v) tuples that grow by concat (models/llama.py).
The token loop runs on host (one compiled step per shape, like eager
serving); each step's math is jit-compiled by XLA.
"""
from __future__ import annotations

import functools
import inspect
import weakref

import jax
import jax.numpy as jnp
import numpy as np

from ..core.tensor import Tensor, to_tensor


def _cache_dims(model):
    """(kv_heads, head_dim, dtype) shared by both cache layouts."""
    cfg = model.config
    head_dim = getattr(cfg, "head_dim", None) \
        or cfg.hidden_size // cfg.num_attention_heads
    kv_heads = getattr(cfg, "num_key_value_heads", None) \
        or cfg.num_attention_heads
    dtype = jnp.bfloat16 if cfg.dtype == "bfloat16" else jnp.float32
    return kv_heads, head_dim, dtype


def _empty_caches(model, batch):
    kv_heads, head_dim, dtype = _cache_dims(model)
    empty = jnp.zeros((batch, 0, kv_heads, head_dim), dtype)
    return [(Tensor(empty), Tensor(empty))
            for _ in range(model.config.num_hidden_layers)]


def _static_caches(model, batch, max_len):
    """Fixed-size caches: every decode step reuses ONE set of op shapes
    (the concat-growing cache changes shapes per token, recompiling each
    step on TPU — see models/llama.py StaticKVCache).

    Under an ACTIVE mesh executor the [batch, max_len, kv_heads,
    head_dim] buffers are committed sharded on the tp axis over
    kv_heads — the same layout the serving path gives the paged pool
    (``MeshExecutor.kv_pool_spec``) — instead of replicating an entire
    max_len cache onto every chip.  ``clean_spec`` inside ``put`` falls
    back to replication when kv_heads does not divide tp."""
    from .llama import StaticKVCache

    kv_heads, head_dim, dtype = _cache_dims(model)
    caches = [StaticKVCache.empty(batch, max_len, kv_heads, head_dim,
                                  dtype)
              for _ in range(model.config.num_hidden_layers)]
    from ..distributed.executor import current_executor

    ex = current_executor()
    if ex is not None:
        spec = ex.static_kv_spec()
        for c in caches:
            c.k = ex.put(c.k, spec)
            c.v = ex.put(c.v, spec)
    return caches


def _select_token(logits, *, do_sample, temperature, top_k, top_p, key):
    """logits: [B, V] fp32 -> token ids [B]."""
    if not do_sample:
        return jnp.argmax(logits, axis=-1)
    if temperature and temperature != 1.0:
        logits = logits / temperature
    if top_k and top_k > 0:
        kth = jax.lax.top_k(logits, top_k)[0][..., -1:]
        logits = jnp.where(logits < kth, -jnp.inf, logits)
    if top_p and top_p < 1.0:
        sorted_logits = jnp.sort(logits, axis=-1)[..., ::-1]
        probs = jax.nn.softmax(sorted_logits, axis=-1)
        cum = jnp.cumsum(probs, axis=-1)
        # smallest set with cumulative prob >= top_p (keep the first token
        # crossing the threshold)
        cutoff_idx = jnp.sum(cum < top_p, axis=-1, keepdims=True)
        cutoff = jnp.take_along_axis(sorted_logits, cutoff_idx, axis=-1)
        logits = jnp.where(logits < cutoff, -jnp.inf, logits)
    return jax.random.categorical(key, logits, axis=-1)


def _gather_caches(caches, idx):
    return [(Tensor(c[0]._value[idx]), Tensor(c[1]._value[idx]))
            for c in caches]


# ---------------------------------------------------------------------------
# decode-step registry (serving hot loop + analysis H106)
# ---------------------------------------------------------------------------
# Every compiled step built here registers its raw (pre-jit) Python
# function so paddle_tpu.analysis.hazards can AST-audit the serving hot
# loop (H106: host syncs / python branching inside a decode step force a
# device→host round trip per token).  Weak refs: a registered step must
# not keep its model alive after the caller drops it.
_decode_step_registry: "list[tuple[weakref.ref, str]]" = []


def register_decode_step(fn, kind: str = "decode"):
    """Register ``fn`` (the raw Python function behind a compiled decode/
    prefill step) for hazard auditing and jaxpr X-ray (analysis.xray
    resolves abstract arg shapes per ``kind``).  Returns ``fn`` so it
    can be used as a decorator."""
    _decode_step_registry.append((weakref.ref(fn), kind))
    return fn


def registered_decode_steps():
    """Live registered decode-step functions (dead models pruned)."""
    return [fn for fn, _kind in registered_decode_step_entries()]


def registered_decode_step_entries():
    """Live ``(fn, kind)`` registry entries — the X-ray audit uses the
    kind to build each step's abstract argument shapes."""
    alive = []
    remaining = []
    for r, kind in _decode_step_registry:
        fn = r()
        if fn is not None:
            alive.append((fn, kind))
            remaining.append((r, kind))
    _decode_step_registry[:] = remaining
    return alive


# ---------------------------------------------------------------------------
# stop sequences (shared between generate() and serving.Scheduler)
# ---------------------------------------------------------------------------

def normalize_stop_sequences(stop_sequences, tokenizer=None):
    """Normalize user-facing stop specs to ``list[list[int]]``.

    Accepts None, a single token id, one token-id sequence, a list of
    either, or strings (requires ``tokenizer`` with an ``encode`` method
    or a callable returning token ids)."""
    if stop_sequences is None:
        return []
    if isinstance(stop_sequences, (int, np.integer, str)):
        stop_sequences = [stop_sequences]
    elif stop_sequences and all(
            isinstance(t, (int, np.integer)) for t in stop_sequences):
        # one bare token-id sequence
        stop_sequences = [list(stop_sequences)]
    out = []
    for s in stop_sequences:
        if isinstance(s, str):
            if tokenizer is None:
                raise ValueError(
                    "string stop sequences need a tokenizer= with an "
                    "encode method (generate works on token ids)")
            enc = getattr(tokenizer, "encode", tokenizer)
            s = enc(s)
            ids = getattr(s, "ids", s)  # tokenizers-style Encoding
            s = list(np.asarray(ids).reshape(-1))
        elif isinstance(s, (int, np.integer)):
            s = [s]
        s = [int(t) for t in s]
        if not s:
            raise ValueError("empty stop sequence")
        out.append(s)
    return out


def match_stop(generated, stop_sequences) -> bool:
    """True when ``generated`` (token ids, oldest→newest) ends with any
    of the normalized stop sequences.  The serving scheduler and
    ``generate()`` share this exact termination check."""
    for s in stop_sequences:
        n = len(s)
        if n <= len(generated) and list(generated[-n:]) == s:
            return True
    return False


def _weight_tensors(model):
    return [t for _, t in model.named_parameters()] + \
        [t for _, t in model.named_buffers()]


class jit_with_weights:
    """``jax.jit`` of a step whose model weights ride in as the leading
    ARGUMENT instead of being closed over.  A closed-over array is baked
    into the lowered program as a literal: harmless at toy size, but an
    8B-width layer is 436 MB of literals per program — compile time,
    the program text, the persistent-cache entry and a second HBM copy
    per compiled step all scale with it.

    Call signature, ``lower`` and ``_cache_size`` are the jitted raw
    step's own (minus the weights), so engines, ``warn_on_retrace`` and
    the analyzers use it as they would the ``jax.jit`` product; traced
    from outside (``jax.make_jaxpr(step)``) the live weights surface as
    the trace's top-level consts.  :func:`cached_step` builds every
    step of this package through it.

    ``donate`` is the name, among ``fn``'s arguments, of a paged KV
    pool that the step returns: the program takes it as a DONATED
    argument, so its output pool aliases its input and the step's
    scatter writes in place (undonated, the compiler copies the whole
    pool first, every step).  After a call the arrays handed in are
    deleted: the caller binds the returned pool and keeps no other.

    ``chooses``: ``fn`` gives ``((logits, ids, *more), pools)``, the ids
    being the tokens it chose from those logits.  The ONE program then
    has two readers.  Calling the step hands out ``(logits, pools)``
    (``((logits, *more), pools)``), as if no id were there; :meth:`ids`
    hands out ``(ids, pools)`` (``((ids, *more), pools)``) and drops the
    logits' handle unread, so they stay on the device.  Both run the
    same jitted function: one executable, one entry in its cache."""

    def __init__(self, model, fn, donate=None, chooses=False):
        # rebinding a tensor's ``_value`` (optimizer step, load, mesh
        # placement) is seen by the next call; a NEW parameter or buffer
        # is not, which ``holds`` tells ``cached_step``
        self._tensors = _weight_tensors(model)
        functools.update_wrapper(self, fn)

        def with_weights(weights, *args):
            live = [t._value for t in self._tensors]
            for t, w in zip(self._tensors, weights):
                t._value = w
            try:
                return fn(*args)
            finally:
                for t, v in zip(self._tensors, live):
                    t._value = v

        # the program takes the step's name: a profiler trace and the
        # HLO then read ``jit_paged_decode_step``, not ``jit_with_weights``
        # for every step of every model
        with_weights.__name__ = with_weights.__qualname__ = fn.__name__
        self._chooses = chooses
        # the weights shift the pool by one inside ``with_weights``
        self._jitted = jax.jit(
            with_weights,
            donate_argnums=() if donate is None else (
                1 + list(inspect.signature(fn).parameters).index(donate),))

    def holds(self, model) -> bool:
        """The model's parameters and buffers are still the very
        ``Tensor`` objects this step feeds its program."""
        now = _weight_tensors(model)
        return len(now) == len(self._tensors) and all(
            a is b for a, b in zip(now, self._tensors))

    def _weights(self):
        return [t._value for t in self._tensors]

    def __call__(self, *args):
        out = self._jitted(self._weights(), *args)
        return _without(out, 1) if self._chooses else out

    def ids(self, *args):
        """The same call into the same program, read for the ids it
        chose in place of the logits it chose them from."""
        if not self._chooses:
            raise TypeError(f"{self.__name__} chooses no token")
        return _without(self._jitted(self._weights(), *args), 0)

    def lower(self, *args):
        return self._jitted.lower(self._weights(), *args)

    def _cache_size(self):
        return self._jitted._cache_size()


def _without(out, i):
    """``((logits, ids, *more), pools)`` less the first result's
    ``i``-th part; one part left stands alone."""
    first, pools = out
    kept = first[:i] + first[i + 1:]
    return (kept[0] if len(kept) == 1 else kept), pools


def _chosen(logits):
    """The greedy token of each row of float32 ``logits``, int32: the
    lowest index among equal maxima, as ``np.argmax`` has it."""
    return jnp.argmax(logits, axis=-1).astype(jnp.int32)


def cached_step(model, key, fn, donate=None, chooses=False):
    """The one table of compiled steps, kept on the model: ``key`` is
    ``(kind, fused, kv_dtype, *extras)`` and ``fn`` the raw step, which
    is registered under its kind and compiled with
    :class:`jit_with_weights` the first time its key is asked for
    (``donate``: the name of ``fn``'s argument that is the paged pool
    it consumes and returns; every step over a paged pool names it;
    ``chooses``: the step also returns the ids it chose, read through
    its ``ids`` accessor).  The
    same key then returns the same object, with its executables, to
    every engine and every ``generate()`` call: a fresh wrapper per call
    would retrace and recompile the whole transformer per request.

    A step takes the weights as arguments, so a rebound weight needs no
    new step.  Only a model whose parameters or buffers are no longer
    the tensors the step holds (``quantize_model_weights`` registers new
    buffers) gets a fresh one, checked here, where a step is asked for,
    and never where it runs."""
    table = vars(model).setdefault("_compiled_steps", {})
    step = table.get(key)
    if step is None or not step.holds(model):
        step = table[key] = jit_with_weights(
            model, register_decode_step(fn, kind=key[0]), donate, chooses)
    return step


def make_decode_step(model):
    """One jit-compiled single-token decode step over static caches.

    Returns step(tok[B,1] int32, caches, offset int32 scalar) ->
    (last_logits[B,V] f32, new_caches).  The token position rides in as a
    TRACED scalar and the caches are fixed-size, so every decode step of
    every generation with the same (B, max_len) hits ONE executable —
    the TPU serving property the reference gets from
    fused_multi_transformer's decode kernel.  The weights ride in as
    arguments (:class:`jit_with_weights`), so after training or
    ``set_state_dict`` the same step, with the executables it has, serves
    the new weights; :func:`cached_step` keeps it on the model, and
    jax.jit's own cache then holds one executable per (B, max_len)
    across generate() calls."""
    from .llama import StaticKVCache

    from ..core.dispatch import no_grad_ctx

    def decode_step(tok, caches, offset):
        with no_grad_ctx():
            wrapped = [StaticKVCache(k, v) for k, v in caches]
            logits, new_caches = model(Tensor(tok), caches=wrapped,
                                       position_offset=offset)
            return (logits._value[:, -1].astype(jnp.float32),
                    [(c.k, c.v) for c in new_caches])

    return cached_step(model, ("decode", None, None), decode_step)


def make_beam_decode_step(model):
    """Beam-search decode step over static caches: re-indexes the
    preallocated caches by `parents` on the batch*beam axis INSIDE the
    compiled program, then decodes one token (reference semantics:
    BeamSearchDecoder's gather of cell states, fluid/layers/rnn.py, over
    fused_multi_transformer's fixed CacheKV).  step(tok[BV,1], caches,
    offset, parents[BV]) -> (logits[BV,V] f32, new_caches)."""
    from .llama import StaticKVCache

    from ..core.dispatch import no_grad_ctx

    def beam_decode_step(tok, caches, offset, parents):
        with no_grad_ctx():
            wrapped = [StaticKVCache(k[parents], v[parents])
                       for k, v in caches]
            logits, new_caches = model(Tensor(tok), caches=wrapped,
                                       position_offset=offset)
            return (logits._value[:, -1].astype(jnp.float32),
                    [(c.k, c.v) for c in new_caches])

    return cached_step(model, ("beam_decode", None, None),
                       beam_decode_step)


def make_prefill_step(model):
    """One jit-compiled prompt-prefill step over static caches, reusable
    at any padded prompt length (serving buckets prompts to block
    multiples, so the jit cache holds one executable per bucket, never
    per prompt).  step(ids[1, Lp] int32, caches, last_index int32 scalar)
    -> (last_real_logits[1, V] f32, new_caches): the logits are gathered
    at the TRACED index of the last REAL prompt token, so padding never
    changes which row is returned."""
    from .llama import StaticKVCache

    from ..core.dispatch import no_grad_ctx

    def prefill_step(ids, caches, last_index):
        with no_grad_ctx():
            wrapped = [StaticKVCache(k, v) for k, v in caches]
            logits, new_caches = model(Tensor(ids), caches=wrapped,
                                       position_offset=0)
            last = jax.lax.dynamic_index_in_dim(
                logits._value, last_index, axis=1, keepdims=False)
            return (last.astype(jnp.float32),
                    [(c.k, c.v) for c in new_caches])

    return cached_step(model, ("prefill", None, None), prefill_step)


def _wrap_paged(pools, block_tables, kv_dtype, model=None):
    """Pool entries -> PagedKVCache views: (k, v) tuples for full-
    precision pools, (k, v, k_scale, v_scale) for quantized ones
    (serving/cache.py BlockKVPool.layers); a model whose entries hold
    more than K and V (its ``cache_layers()`` name sidecars) builds its
    own views.  Called
    at TRACE time only — the branch is on the build-time kv_dtype
    constant, never a traced value, and lives outside the H106-audited
    step source."""
    from .llama import PagedKVCache

    if model is not None and hasattr(model, "paged_cache_views"):
        return model.paged_cache_views(pools, block_tables)

    if kv_dtype is not None:
        return [PagedKVCache(k, v, block_tables, ks, vs,
                             kv_dtype=kv_dtype)
                for k, v, ks, vs in pools]
    return [PagedKVCache(k, v, block_tables) for k, v in pools]


def _unwrap_paged(caches, kv_dtype, model=None):
    """Inverse of :func:`_wrap_paged`: repack updated cache views into
    pool-entry tuples for the engine to rebind."""
    if model is not None and hasattr(model, "paged_pool_entries"):
        return model.paged_pool_entries(caches)
    if kv_dtype is not None:
        return [(c.k, c.v, c.k_scale, c.v_scale) for c in caches]
    return [(c.k, c.v) for c in caches]


def make_paged_decode_step(model, fused=None, kv_cache_dtype=None):
    """The continuous-batching decode step: one token for a BUCKET of
    sequences, each at its own position, over the shared block-pool
    cache (models/llama.py PagedKVCache).  step(tok[B,1] int32, pools
    [(k, v)] per layer, block_tables[B, max_blocks] int32, lengths[B]
    int32) -> (last_logits[B, V] f32, new_pools).  Every input shape is
    fixed by the engine config, so after the first call this NEVER
    retraces — the property the serving engine asserts every step.
    ``pools`` is DONATED, here and in every step over a paged pool: the
    returned pool is the same buffers written in place, and the arrays
    handed in are deleted by the call, so the caller binds the result.

    The program also chooses each row's greedy token where its logits
    are (``argmax`` over the same float32 logits, int32 ``[B]``, the
    lowest index on ties): ``step.ids(...)``, the same call into the
    same executable, gives ``(ids, new_pools)`` and leaves the logits on
    the device.  The serving engine's greedy lane reads that; whoever
    wants logits calls the step.

    ``fused`` pins the serving-fusion mode (kernels/fusion) for the
    whole traced program: True forces the fused paged-attention decode
    kernel + RMSNorm epilogues (XLA fallback off-TPU), False forces the
    gather path, None is fused.  The mode is baked into the trace, so
    fused and gather steps are distinct cached executables.

    ``kv_cache_dtype`` (None / "int8" / "fp8") selects quantized pool
    entries: pools become [(k, v, k_scale, v_scale)] per layer, writes
    quantize in-trace and reads dequantize at the kernel DMA boundary
    (kernels/kv_quant.py).  Like ``fused``, the dtype is part of the
    step's key, so mixed-precision engines over one model never collide
    on a cached step (their pool treedefs differ: a shared step would
    retrace for the second engine)."""
    from ..kernels.fusion import resolve_serving_fusion, serving_fusion
    from ..kernels.kv_quant import resolve_kv_cache_dtype

    fused = resolve_serving_fusion(fused)
    kv_dtype = resolve_kv_cache_dtype(kv_cache_dtype)

    from ..core.dispatch import no_grad_ctx

    if hasattr(model, "decode_token"):
        return cached_step(model, ("grouped_paged_decode", fused, kv_dtype),
                           _grouped_decode_step(model, fused),
                           donate="pools", chooses=True)

    def paged_decode_step(tok, pools, block_tables, lengths):
        with no_grad_ctx(), serving_fusion(fused):
            wrapped = _wrap_paged(pools, block_tables, kv_dtype)
            logits, new_caches = model(Tensor(tok), caches=wrapped,
                                       position_offset=lengths)
            last = logits._value[:, -1].astype(jnp.float32)
            return ((last, _chosen(last)),
                    _unwrap_paged(new_caches, kv_dtype))

    return cached_step(model, ("paged_decode", fused, kv_dtype),
                       paged_decode_step, donate="pools", chooses=True)


def make_chunked_prefill_step(model, fused=None, kv_cache_dtype=None):
    """Chunked prefill straight into the paged block pool: ONE fixed
    chunk shape serves every prompt length, so prefill compiles O(1)
    programs instead of one per length bucket (each bucket was a new
    fused XLA program — the compile-cost term PAPERS.md's fusion
    analysis quantifies).  step(ids[1, C] int32, pools [(k, v)] per
    layer, block_table[1, max_blocks] int32, start[1] int32,
    last_index int32 scalar) -> (logits[1, V] f32, new_pools).

    The chunk's tokens occupy absolute positions ``start .. start+C-1``
    of the sequence; their k/v land in the pool at block offsets through
    the block table.  ``last_index`` is the TRACED index of the last
    REAL token within the chunk: positions past it are padding, whose
    pool writes the model redirects to the reserved garbage block via
    the validity mask, and whose logits are never returned — the
    gathered row is always the last real one, so the final chunk of a
    prompt yields the first generated token.  Both ``start`` and
    ``last_index`` are traced, so every chunk of every prompt hits the
    SAME executable (the serving engine asserts this via
    ``warn_on_retrace``).  As the decode step does, the program chooses
    that row's greedy token too: ``step.ids(...)`` gives ``(ids [1]
    int32, new_pools)``.

    ``fused`` (see make_paged_decode_step) pins the serving-fusion mode:
    fused prefill folds each RMSNorm into the following projections
    (kernels/fused_norm_linear) and runs the chunk attention through the
    fused block-gather + online-softmax kernel
    (kernels/chunked_prefill — mined by analysis/fusionminer as the #1
    remaining candidate); padded positions still scatter to the garbage
    block and mask off exactly as on the gather path.

    ``kv_cache_dtype`` selects quantized pool entries exactly as in
    :func:`make_paged_decode_step` (padded positions scatter their
    garbage CODES + scale into block 0 the same way)."""
    from ..kernels.fusion import resolve_serving_fusion, serving_fusion
    from ..kernels.kv_quant import resolve_kv_cache_dtype

    fused = resolve_serving_fusion(fused)
    kv_dtype = resolve_kv_cache_dtype(kv_cache_dtype)

    from ..core.dispatch import no_grad_ctx

    if getattr(model, "block_diffusion", None) is not None:
        return cached_step(model, ("block_chunked_prefill", fused, kv_dtype),
                           _block_chunk_step(model, fused), donate="pools")
    if hasattr(model, "decode_token"):
        return cached_step(
            model, ("grouped_chunked_prefill", fused, kv_dtype),
            _grouped_chunk_step(model, fused), donate="pools",
            chooses=True)

    def chunked_prefill_step(ids, pools, block_table, start, last_index):
        with no_grad_ctx(), serving_fusion(fused):
            wrapped = _wrap_paged(pools, block_table, kv_dtype)
            valid = (jnp.arange(ids.shape[1]) <= last_index)[None, :]
            logits, new_caches = model(Tensor(ids),
                                       attn_mask=Tensor(valid),
                                       caches=wrapped,
                                       position_offset=start)
            last = jax.lax.dynamic_index_in_dim(
                logits._value, last_index, axis=1,
                keepdims=False).astype(jnp.float32)
            return ((last, _chosen(last)),
                    _unwrap_paged(new_caches, kv_dtype))

    return cached_step(model, ("chunked_prefill", fused, kv_dtype),
                       chunked_prefill_step, donate="pools", chooses=True)


def _grouped_decode_step(model, fused):
    """The decode program of a model that runs its own served passes
    over the pool's entries (``model.decode_token``): one whose cache
    lies in two GROUPS of pages, a block table each (window layers
    beside full ones, models/afmoe.py), or whose entries are not ``(k,
    v)`` at all (one latent array a position, models/glm4_moe_lite.py).
    The same name, lane and call signature as
    :func:`make_paged_decode_step`'s, so the engine's decode iteration,
    the trace and the metrics read it as they read any decode step.
    ``block_tables`` is what the engine lays out for the model and the
    program hands on unread: the pair ``(full group's [S, max_blocks],
    window group's [S, max_blocks])`` of a window model, the one ``[S,
    max_blocks]`` table of a one-group model.  The first result is
    the pair ``(logits [S, V] f32, stats [3] int32)``: what the routed
    layers read (experts read, assignments, the busiest expert's
    assignments, summed over layers); through ``step.ids`` the pair
    ``(ids [S] int32, stats)``."""
    from ..core.dispatch import no_grad_ctx
    from ..kernels.fusion import serving_fusion

    def paged_decode_step(tok, pools, block_tables, lengths):
        with no_grad_ctx(), serving_fusion(fused):
            logits, stats, new_pools = model.decode_token(
                tok, pools, block_tables, lengths)
            return (logits, _chosen(logits), stats), new_pools

    return paged_decode_step


def _grouped_chunk_step(model, fused):
    """The chunk program of such a model (``model.prefill_chunk``), as
    :func:`make_chunked_prefill_step`'s is called: ``block_table`` is
    the pair of ``[1, max_blocks]`` tables or the one table, the first
    result ``(logits
    [1, V] f32 of the chunk's last real token, stats [3] int32)``
    (``(ids [1] int32, stats)`` through ``step.ids``)."""
    from ..core.dispatch import no_grad_ctx
    from ..kernels.fusion import serving_fusion

    def chunked_prefill_step(ids, pools, block_table, start, last_index):
        with no_grad_ctx(), serving_fusion(fused):
            valid = (jnp.arange(ids.shape[1]) <= last_index)[None, :]
            last, stats, new_pools = model.prefill_chunk(
                ids, valid, pools, block_table, start, last_index)
            return (last, _chosen(last), stats), new_pools

    return chunked_prefill_step


def unmask_schedule(block_length: int, denoising_steps: int):
    """Positions a denoise step unmasks under the static rule:
    ``block_length / denoising_steps`` each, the remainder to the first
    steps."""
    base, extra = divmod(block_length, denoising_steps)
    return [base + (1 if t < extra else 0) for t in range(denoising_steps)]


def unmask_select(logits, ids, masked, n_unmask, tau):
    """One denoise step's choice, on the device.  ``logits [S, L, V]``
    float32, ``ids [S, L]``, ``masked [S, L]`` bool, ``n_unmask [S]``,
    ``tau [S]``.  At every still-masked position the candidate is the
    argmax and its confidence the softmax probability of it; a position
    is unmasked where its confidence passes ``tau`` or it is among the
    ``n_unmask`` most confident masked positions (ties to the earlier
    position).  The static rule passes ``tau`` > 1.  Returns ``(ids,
    masked)`` after the step."""
    top = jnp.max(logits, axis=-1)
    cand = jnp.argmax(logits, axis=-1).astype(ids.dtype)
    conf = 1.0 / jnp.sum(jnp.exp(logits - top[..., None]), axis=-1)
    conf = jnp.where(masked, conf, -1.0)
    # rank among the block's positions, most confident first
    better = (conf[:, None, :] > conf[:, :, None]) | (
        (conf[:, None, :] == conf[:, :, None])
        & (jnp.arange(conf.shape[1])[None, None, :]
           < jnp.arange(conf.shape[1])[None, :, None]))
    rank = jnp.sum(better, axis=-1)
    take = masked & ((rank < n_unmask[:, None]) | (conf > tau[:, None]))
    return jnp.where(take, cand, ids), masked & ~take


def _block_chunk_step(model, fused):
    """The chunk program of a model that generates by diffusion over
    blocks (``model.block_diffusion``): the same name, lane and call
    signature as :func:`make_chunked_prefill_step`'s, so the engine's
    prefill lane, the trace and the metrics read it as they read any
    chunk.  A chunk holds WHOLE blocks of the prompt (``last_index + 1``
    a multiple of the block length), attends under the block-causal
    mask and writes K/V and the routing witness to the pool.  Nothing is
    predicted from a prompt's whole blocks, so no logits are formed: the
    first result is ``stats [3] int32`` (experts read, assignments, the
    busiest expert's assignments, summed over layers)."""
    from ..core.dispatch import no_grad_ctx
    from ..kernels.fusion import serving_fusion

    def chunked_prefill_step(ids, pools, block_table, start, last_index):
        with no_grad_ctx(), serving_fusion(fused):
            wrapped = _wrap_paged(pools, block_table, None, model)
            valid = (jnp.arange(ids.shape[1]) <= last_index)[None, :]
            stats, new_caches = model.prefill_chunk(ids, valid, wrapped,
                                                    start)
            return stats, _unwrap_paged(new_caches, None, model)

    return chunked_prefill_step


def make_paged_block_step(model, fused=None):
    """The step of a model that generates by diffusion over blocks: one
    block of ``L = block_length`` positions a slot, ONE compiled program
    for every state a slot can be in.

    step(ids[S, L] int32, masked[S, L] bool, start[S] int32, mode[S]
    int32, n_unmask[S] int32, tau[S] f32, pools, block_tables[S,
    max_blocks] int32) -> (small, probe, new_pools).

    ``mode``: 0 an idle slot (reads no expert, writes nothing), 1
    DENOISING (the block's positions, masks included, are run against
    the slot's cached positions ``< start`` and the block itself; the
    step unmasks the ``n_unmask`` most confident masked positions and
    every one whose confidence passes ``tau``; K/V dropped), 2
    COMMITTING (the block's tokens are final: the same forward, K/V and
    the routing witness written to the pool at ``start .. start+L-1``;
    nothing is unmasked).  The choice is made on the device.

    ``small`` is all that goes to the host, one int32 vector: the
    block's ids after the step ``[S*L]``, its mask ``[S*L]``, then the
    routing stats ``[3]`` (see :func:`_block_chunk_step`).
    ``probe`` stays on the device unless a check reads it: ``logits
    [L, V]`` float32 of slot 0 and ``chosen [layers, S, L, k]``, what
    the routers chose for the positions in flight."""
    from ..kernels.fusion import resolve_serving_fusion, serving_fusion

    fused = resolve_serving_fusion(fused)

    from ..core.dispatch import no_grad_ctx

    def paged_block_step(ids, masked, start, mode, n_unmask, tau, pools,
                         block_tables):
        with no_grad_ctx(), serving_fusion(fused):
            wrapped = _wrap_paged(pools, block_tables, None, model)
            logits, chosen, stats, new_caches = model.block_step(
                ids, wrapped, start, mode == 2, mode != 0)
            with jax.named_scope("unmask_select"):
                new_ids, new_masked = unmask_select(
                    logits, ids, masked & (mode == 1)[:, None], n_unmask,
                    tau)
                new_masked = new_masked | (masked & (mode != 1)[:, None])
                small = jnp.concatenate([
                    new_ids.reshape(-1).astype(jnp.int32),
                    new_masked.reshape(-1).astype(jnp.int32), stats])
            probe = {"logits": logits[0], "chosen": chosen}
            return small, probe, _unwrap_paged(new_caches, None, model)

    return cached_step(model, ("paged_block", fused, None),
                       paged_block_step, donate="pools")


def make_moe_block_step(model):
    """Full-sequence forward of a mixture-of-experts model
    (LlamaConfig.moe_num_experts > 0) — the traced workload behind the
    MoE static-analysis audits.  step(ids[B, T] int32) -> logits
    [B, T, V] f32.  Off-TPU the dispatch/combine kernels resolve to
    their XLA one-hot einsum fallback, so this exact program is what
    CPU tier-1 checks for parity and the analyzers price."""
    from ..core.dispatch import no_grad_ctx

    def step(ids):
        with no_grad_ctx():
            logits = model(Tensor(ids))
            return logits._value.astype(jnp.float32)

    return cached_step(model, ("moe_block", None, None), step)


def make_ring_sp_step(model, mesh=None):
    """Full-sequence forward through the sequence-parallel attention
    path (LlamaConfig.context_parallel = "ring"/"ulysses").  ``mesh``
    (real or abstract) is installed around the traced body via
    distributed.mesh.use_mesh so trace-time mesh resolution sees the
    ``sp`` axis; None keeps whatever mesh is globally active — no `sp`
    axis means the dense fallback, which IS the CPU parity path.
    step(ids[B, T] int32) -> logits[B, T, V] f32."""
    import contextlib

    from ..core.dispatch import no_grad_ctx
    from ..distributed.mesh import use_mesh

    def step(ids):
        ctx = (use_mesh(mesh) if mesh is not None
               else contextlib.nullcontext())
        with no_grad_ctx(), ctx:
            logits = model(Tensor(ids))
            return logits._value.astype(jnp.float32)

    return cached_step(model, ("ring_sp", None, None, mesh), step)


def generate(model, input_ids, max_new_tokens=32, do_sample=False,
             temperature=1.0, top_k=0, top_p=1.0, num_beams=1,
             eos_token_id=None, seed=None, use_static_cache=False,
             stop_sequences=None, tokenizer=None, sampling=None):
    """Decode continuations for a batch of prompts.

    Returns [B, T_prompt + T_new] token ids (beam search returns the best
    beam per batch element).  Greedy by default; ``do_sample`` enables
    temperature/top-k/top-p sampling (``sampling=SamplingParams(...)``
    is the equivalent explicit spelling, shared with ``Engine.submit``);
    ``num_beams > 1`` switches to beam search with length-agnostic
    log-prob scores.

    Sampled decoding uses the serving engine's key schedule — the seed's
    base key folded with each TOKEN INDEX (serving/sampling.py) — so the
    same prompt + seed is token-exact here and under the engine, which
    is what extends the engine-vs-generate parity oracle to sampled
    outputs.  All rows of a batch share the base key: identical prompts
    sample identical continuations (seed identity is per REQUEST, not
    per row — submit separate engine requests for diverse samples).

    Termination: a sequence finishes when it emits ``eos_token_id``, when
    its generated suffix matches any of ``stop_sequences`` (token-id
    list(s); strings need ``tokenizer``), or at ``max_new_tokens``.
    Finished sequences are padded with ``eos_token_id`` (0 when only stop
    sequences are given) and the loop exits early once EVERY sequence has
    finished — a mixed-length batch never pays full-length compute."""
    from ..core.dispatch import no_grad_ctx
    from ..ops import random as rnd

    if sampling is not None:
        # lazy: serving imports this module at load time
        from ..serving.sampling import resolve_sampling

        params = resolve_sampling(sampling)
        do_sample = params is not None
        if params is not None:
            temperature, top_k, top_p, seed = (params.temperature,
                                               params.top_k,
                                               params.top_p, params.seed)
    ids = np.asarray(input_ids.numpy() if hasattr(input_ids, "numpy")
                     else input_ids)
    if ids.ndim == 1:
        ids = ids[None]
    B, T0 = ids.shape
    max_pos = getattr(model.config, "max_position_embeddings", None)
    if max_pos is not None and T0 + max_new_tokens > max_pos:
        raise ValueError(
            f"prompt ({T0}) + max_new_tokens ({max_new_tokens}) exceeds "
            f"max_position_embeddings ({max_pos}) — the rope table has no "
            f"entries past it (dynamic_slice would silently clamp)")
    stops = normalize_stop_sequences(stop_sequences, tokenizer)
    with no_grad_ctx():
        if num_beams > 1:
            if stops:
                raise ValueError(
                    "stop_sequences are not supported with beam search; "
                    "use eos_token_id or greedy/sampling decoding")
            return _beam_generate(model, ids, max_new_tokens, num_beams,
                                  eos_token_id,
                                  use_static_cache=use_static_cache)
        # seed=None draws from the framework RNG stream (paddle.seed)
        key = rnd.next_key() if seed is None else jax.random.PRNGKey(seed)
        if do_sample:
            # serving/sampling key schedule: token i samples with
            # fold_in(base, i) on device — slot- and batch-independent,
            # so the engine reproduces these exact streams per seed
            from ..serving.sampling import sample_at

            base_keys = np.broadcast_to(
                np.asarray(key, np.uint32).reshape(-1)[:2], (B, 2))
            s_temps = np.full((B,), float(temperature or 0.0), np.float32)
            s_tks = np.full((B,), int(top_k or 0), np.int32)
            s_tps = np.full((B,), float(top_p if top_p else 1.0),
                            np.float32)
        caches = _static_caches(model, B, T0 + max_new_tokens) \
            if use_static_cache else _empty_caches(model, B)
        logits, caches = model(to_tensor(ids.astype(np.int32)),
                               caches=caches, position_offset=0)
        decode_step = None
        if use_static_cache:
            decode_step = make_decode_step(model)
            cache_arrays = [(c.k, c.v) for c in caches]
        out = [ids]
        finished = np.zeros((B,), bool)
        terminal = eos_token_id is not None or bool(stops)
        # finished rows are padded with eos (0 when only stop sequences
        # terminate) so a mixed-length batch stays rectangular
        pad_id = eos_token_id if eos_token_id is not None else 0
        max_stop = max((len(s) for s in stops), default=0)
        suffixes = [[] for _ in range(B)]   # per-row stop-match windows
        last = logits._value[:, -1].astype(jnp.float32)
        for step in range(max_new_tokens):
            if do_sample:
                tok = sample_at(last, s_temps, s_tks, s_tps, base_keys,
                                np.full((B,), step, np.int32))
            else:
                tok = jnp.argmax(last, axis=-1)
            tok_np = np.asarray(tok)
            if terminal:
                tok_np = np.where(finished, pad_id, tok_np)
                if eos_token_id is not None:
                    finished |= tok_np == eos_token_id
                for b in range(B):
                    if stops and not finished[b]:
                        suffixes[b].append(int(tok_np[b]))
                        if len(suffixes[b]) > max_stop:
                            del suffixes[b][:-max_stop]
                        finished[b] = match_stop(suffixes[b], stops)
            out.append(tok_np[:, None])
            if terminal and finished.all():
                break
            if step == max_new_tokens - 1:
                break  # the last token is chosen; don't pay one more step
            cur_raw = tok_np[:, None].astype(np.int32)
            if decode_step is not None:
                # one compiled program for the whole generation: the
                # position is a traced scalar, the caches fixed-size
                last, cache_arrays = decode_step(
                    cur_raw, cache_arrays, np.int32(T0 + step))
            else:
                logits, caches = model(to_tensor(cur_raw), caches=caches,
                                       position_offset=T0 + step)
                last = logits._value[:, -1].astype(jnp.float32)
        return to_tensor(np.concatenate(out, axis=1))


def _beam_generate(model, ids, max_new_tokens, beams, eos_token_id,
                   use_static_cache=False):
    B, T0 = ids.shape
    BV = B * beams
    # prefill once per prompt, then replicate caches across beams
    caches = _static_caches(model, B, T0 + max_new_tokens) \
        if use_static_cache else _empty_caches(model, B)
    logits, caches = model(to_tensor(ids.astype(np.int32)), caches=caches,
                           position_offset=0)
    rep = jnp.repeat(jnp.arange(B), beams)
    beam_step = None
    if use_static_cache:
        beam_step = make_beam_decode_step(model)
        # replicate the fixed-size buffers across beams; per-step gathers
        # then happen inside the compiled step
        cache_arrays = [(c.k[rep], c.v[rep]) for c in caches]
    else:
        caches = _gather_caches(caches, rep)
    last = jnp.repeat(logits._value[:, -1].astype(jnp.float32), beams,
                      axis=0)                      # [B*beams, V]
    scores = jnp.tile(jnp.asarray([0.0] + [-1e9] * (beams - 1)), (B,))
    tokens_acc = []     # list of [B*beams] arrays
    parents_acc = []
    finished = jnp.zeros((BV,), bool)
    V = last.shape[-1]
    end_only = None
    if eos_token_id is not None:
        end_only = jnp.full((V,), -1e9).at[eos_token_id].set(0.0)
    for step in range(max_new_tokens):
        logp = jax.nn.log_softmax(last, axis=-1)
        if end_only is not None:
            logp = jnp.where(finished[:, None], end_only, logp)
        total = (scores[:, None] + logp).reshape(B, beams * V)
        top_scores, top_idx = jax.lax.top_k(total, beams)   # [B, beams]
        parents = (top_idx // V + jnp.arange(B)[:, None] * beams).reshape(-1)
        toks = (top_idx % V).reshape(-1)
        scores = top_scores.reshape(-1)
        if beam_step is None:
            caches = _gather_caches(caches, parents)
        if eos_token_id is not None:
            finished = finished[parents] | (toks == eos_token_id)
        tokens_acc.append(np.asarray(toks))
        parents_acc.append(np.asarray(parents))
        if eos_token_id is not None and bool(finished.all()):
            break
        if step == max_new_tokens - 1:
            break  # the last token is chosen; don't pay one more step
        cur_raw = np.asarray(toks)[:, None].astype(np.int32)
        if beam_step is not None:
            # cache re-indexing by `parents` happens inside the compiled
            # step: one executable serves the whole beam generation
            last, cache_arrays = beam_step(
                cur_raw, cache_arrays, np.int32(T0 + step),
                np.asarray(parents))
        else:
            logits, caches = model(to_tensor(cur_raw), caches=caches,
                                   position_offset=T0 + step)
            last = logits._value[:, -1].astype(jnp.float32)
    # backtrace best beam (beam 0 holds the max score after top_k)
    T = len(tokens_acc)
    seq = np.zeros((BV, T), np.int64)
    cursor = np.arange(BV)
    for t in range(T - 1, -1, -1):
        seq[:, t] = tokens_acc[t][cursor]
        cursor = parents_acc[t][cursor]
    best = seq.reshape(B, beams, T)[:, 0]
    return to_tensor(np.concatenate([ids, best], axis=1))
