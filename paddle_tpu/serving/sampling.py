# lint-tpu: disable-file=L004 -- serving drives the compiled decode/
# prefill steps over raw device buffers (like models/); new backend code
# belongs under core/ ops/ kernels/ static/ distributed/ (README: Repo lint)
"""Traced per-request sampling for the serving engine (reference
capability: paddle/fluid/operators/top_k_op + top_p_sampling_op and
PaddleNLP's ``decode_strategy="sampling"``; here the whole transform is
part of the compiled decode step).

Design constraints (ISSUE 19 / H106):

- The bucket-wide decode step stays ONE compiled program: temperature /
  top-k / top-p are per-slot DEVICE arrays, not trace constants, so a
  bucket mixing greedy and sampled requests (or requests with different
  temperatures) never retraces.
- PRNG state never round-trips to host.  Each request carries a base
  key (``[2] uint32``, from its seed); the step folds the key with the
  request's token counter ON DEVICE (`fold_keys`), so the i-th generated
  token of a request always uses ``fold_in(base, i)`` — independent of
  slot placement, bucket composition, or preemption/recompute history.
  ``generate()`` uses the same schedule, which is what makes the
  engine-vs-generate parity oracle extend to sampled outputs (same seed
  → token-exact).
- Greedy stays the ``temperature == 0`` special case: those lanes take
  ``argmax`` of the raw logits via ``jnp.where``, bit-identical to the
  plain paged-decode step's selection, and an all-greedy engine never
  runs this step at all.

Dynamic per-row top-k: ``lax.top_k`` needs a static k, so rows are
sorted descending and thresholded at their own (clamped) k-th value —
O(V log V) per row, all shapes static.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from ..core.tensor import Tensor
from ..models.generation import cached_step

# key-derivation tags: the draft proposal, acceptance uniform and bonus/
# residual resample for token index i must be independent of the target
# sample for token index i (speculative.py folds these on top of the
# per-token fold), so each purpose gets a second fold with its own tag
DRAFT_TAG = 0x5D
ACCEPT_TAG = 0xAC
BONUS_TAG = 0xB0


@dataclass(frozen=True)
class SamplingParams:
    """Per-request sampling knobs (``Engine.submit(sampling=...)``).

    ``temperature == 0`` means greedy (argmax) — the engine keeps such
    requests on the plain greedy decode step.  ``top_k == 0`` and
    ``top_p == 1.0`` disable those filters.  ``seed=None`` draws the
    request's base key from the framework RNG (deterministic under
    ``paddle.seed``, unique per request); a fixed seed makes the token
    stream reproducible regardless of batching, slot placement or
    preemption."""

    temperature: float = 0.0
    top_k: int = 0
    top_p: float = 1.0
    seed: Optional[int] = None

    def __post_init__(self):
        if self.temperature < 0:
            raise ValueError(f"temperature must be >= 0, "
                             f"got {self.temperature}")
        if self.top_k < 0:
            raise ValueError(f"top_k must be >= 0, got {self.top_k}")
        if not 0.0 < self.top_p <= 1.0:
            raise ValueError(f"top_p must be in (0, 1], got {self.top_p}")

    @property
    def is_greedy(self) -> bool:
        return self.temperature == 0.0

    def base_key(self) -> np.ndarray:
        """The request's base PRNG key as raw ``[2] uint32``."""
        if self.seed is None:
            from ..ops import random as rnd
            return np.asarray(rnd.next_key(), np.uint32)
        return np.asarray(jax.random.PRNGKey(int(self.seed)), np.uint32)


def resolve_sampling(sampling=None, *, temperature=None, do_sample=False,
                     top_k=0, top_p=1.0, seed=None):
    """Normalize the legacy ``generate()``-style knobs and the explicit
    ``SamplingParams`` into one spec.  Returns ``None`` for greedy.

    Shared by ``Engine.submit`` and ``Router.submit`` so both front
    doors accept ``temperature=0.8`` / ``do_sample=True`` (reference
    ``decode_strategy="sampling"`` spelling) as well as
    ``sampling=SamplingParams(...)`` / ``sampling={"temperature": ...}``.
    """
    if sampling is not None:
        if isinstance(sampling, dict):
            sampling = SamplingParams(**sampling)
        if not isinstance(sampling, SamplingParams):
            raise TypeError("sampling= takes a SamplingParams or a dict "
                            f"of its fields, got {type(sampling).__name__}")
        return None if sampling.is_greedy else sampling
    temp = 0.0 if temperature is None else float(temperature)
    if do_sample and temp == 0.0:
        temp = 1.0          # reference default: do_sample alone means T=1
    if temp == 0.0:
        return None
    return SamplingParams(temperature=temp, top_k=int(top_k),
                          top_p=float(top_p), seed=seed)


# ---------------------------------------------------------------------------
# traced transform
# ---------------------------------------------------------------------------

def fold_keys(keys, data):
    """Vectorized ``jax.random.fold_in``: ``keys [..., 2] uint32`` folded
    elementwise with ``data`` (broadcast to the leading dims)."""
    keys = jnp.asarray(keys).astype(jnp.uint32)
    lead = keys.shape[:-1]
    data = jnp.broadcast_to(jnp.asarray(data, jnp.int32), lead)
    flat = jax.vmap(jax.random.fold_in)(keys.reshape(-1, 2),
                                        data.reshape(-1))
    return flat.reshape(lead + (2,))


def filter_logits(logits, temps, top_ks, top_ps):
    """Temperature-scale + per-row dynamic top-k + top-p mask.

    ``logits [N, V] f32``; ``temps [N]`` (rows with 0 pass through at
    scale 1 — their output is unused, greedy lanes argmax raw logits);
    ``top_ks [N] int32`` (0 = off); ``top_ps [N]`` (1.0 = off).
    Filtered entries become ``-inf``; at least the max survives."""
    v = logits.shape[-1]
    scale = jnp.where(temps > 0, temps, 1.0)[:, None]
    scaled = logits / scale
    # dynamic per-row top-k: threshold at each row's own k-th value
    order = -jnp.sort(-scaled, axis=-1)                     # descending
    k = jnp.clip(top_ks, 0, v)
    kth = jnp.take_along_axis(
        order, jnp.clip(k - 1, 0, v - 1)[:, None], axis=-1)
    scaled = jnp.where((k > 0)[:, None] & (scaled < kth),
                       -jnp.inf, scaled)
    # top-p over the top-k-filtered distribution
    order = -jnp.sort(-scaled, axis=-1)
    probs = jax.nn.softmax(order, axis=-1)
    cum = jnp.cumsum(probs, axis=-1)
    cut_idx = jnp.minimum(jnp.sum(cum < top_ps[:, None], axis=-1,
                                  keepdims=True), v - 1)
    cutoff = jnp.take_along_axis(order, cut_idx, axis=-1)
    scaled = jnp.where((top_ps < 1.0)[:, None] & (scaled < cutoff),
                       -jnp.inf, scaled)
    return scaled


def filtered_probs(logits, temps, top_ks, top_ps):
    """Softmax of :func:`filter_logits` — the per-row proposal /
    verification distribution (filtered entries have probability 0)."""
    return jax.nn.softmax(filter_logits(logits, temps, top_ks, top_ps),
                          axis=-1)


def sample_tokens(logits, temps, top_ks, top_ps, keys):
    """One token per row: categorical over the filtered distribution for
    ``temps > 0`` lanes, raw argmax for greedy lanes.  ``keys`` are the
    per-row PER-TOKEN keys (already folded with the token counter)."""
    filt = filter_logits(logits, temps, top_ks, top_ps)
    sampled = jax.vmap(jax.random.categorical)(
        jnp.asarray(keys).astype(jnp.uint32), filt)
    greedy = jnp.argmax(logits, axis=-1)
    return jnp.where(temps > 0, sampled, greedy).astype(jnp.int32)


@jax.jit
def sample_at(logits, temps, top_ks, top_ps, keys, counters):
    """Sample row tokens at explicit counters: the exact program both
    ``generate()`` and the engine's first-token path run, so a request's
    i-th token is bitwise reproducible across the two front ends."""
    return sample_tokens(logits, temps, top_ks, top_ps,
                         fold_keys(keys, counters))


# ---------------------------------------------------------------------------
# compiled step
# ---------------------------------------------------------------------------

def make_sampled_decode_step(model, fused=None, kv_cache_dtype=None):
    """Paged decode with the sampling transform fused into the program:
    step(tok[S, 1] int32, pools [(k, v)] per layer, block_tables
    [S, max_blocks] int32, lengths[S] int32, temps[S] f32, top_ks[S]
    int32, top_ps[S] f32, keys[S, 2] uint32, counters[S] int32) ->
    (next_tok[S] int32, new_pools).

    Identical forward pass to ``make_paged_decode_step``; the only
    addition is the on-device fold + filter + categorical on the last
    logits, so only the chosen token ids sync back (a [S] int32, as
    from the greedy step's ``ids`` reader).  All per-slot sampling state
    rides in fixed-shape device arrays — zero retraces, zero host
    round-trips in the token loop (H106).  Kept in the model's table of
    steps (``cached_step``), like every other step builder's."""
    from ..kernels.fusion import resolve_serving_fusion, serving_fusion
    from ..kernels.kv_quant import resolve_kv_cache_dtype
    from ..models.generation import _unwrap_paged, _wrap_paged

    fused = resolve_serving_fusion(fused)
    kv_dtype = resolve_kv_cache_dtype(kv_cache_dtype)

    from ..core.dispatch import no_grad_ctx

    def sampled_decode_step(tok, pools, block_tables, lengths, temps,
                            top_ks, top_ps, keys, counters):
        with no_grad_ctx(), serving_fusion(fused):
            wrapped = _wrap_paged(pools, block_tables, kv_dtype)
            logits, new_caches = model(Tensor(tok), caches=wrapped,
                                       position_offset=lengths)
            last = logits._value[:, -1].astype(jnp.float32)
            toks = sample_tokens(last, temps, top_ks, top_ps,
                                 fold_keys(keys, counters))
            return toks, _unwrap_paged(new_caches, kv_dtype)

    return cached_step(model, ("sampled_decode", fused, kv_dtype),
                       sampled_decode_step, donate="pools")
