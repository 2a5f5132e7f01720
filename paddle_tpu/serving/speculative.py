# lint-tpu: disable-file=L004 -- serving drives the compiled decode/
# prefill steps over raw device buffers (like models/); new backend code
# belongs under core/ ops/ kernels/ static/ distributed/ (README: Repo lint)
"""Speculative decoding: a small draft model proposes K tokens per
target step; the target verifies all K+1 positions in ONE
chunked-prefill-shaped program (Leviathan et al., "Fast Inference from
Transformers via Speculative Decoding"; reuse of the PR 5/14 chunked
program and the prefix cache is the point of ISSUE 19).

Two compiled steps, both in the decode-step registry:

- ``draft_propose``: K sequential draft forwards inside one
  ``lax.scan`` — ONE compiled program per engine config, writing the
  draft's KV into its own layer slice of the shared block pool, and
  emitting the proposals plus the draft's full filtered distributions
  (needed for rejection sampling).
- ``spec_verify``: one batched [S, K+1] target forward over
  ``[pending, d1..dK]`` at positions ``P..P+K`` (the chunked-prefill
  attention shape), then ON-DEVICE acceptance:

  * greedy lanes (``temperature == 0``): proposal ``d_{j+1}`` is
    accepted iff it equals the target argmax at position j; the first
    mismatch position contributes the target's own argmax as the
    correction token — so the committed tokens are exactly the greedy
    continuation, token-for-token what ``generate()`` emits.
  * sampled lanes: standard rejection sampling — accept ``d`` with
    probability ``min(1, p(d)/q(d))`` (target / draft filtered probs,
    uniforms keyed by the per-token fold + ACCEPT_TAG); on rejection
    resample from the residual ``normalize(max(p - q, 0))``; when all K
    drafts survive, a bonus token samples from the target distribution
    at position K.  Every key derives from the request's base key and
    TOKEN INDEX, so preemption + recompute replays identically.

  Only ``(committed [S, K+1], accepted_len [S])`` sync to host.

KV bookkeeping is the engine's job: the verify step writes target KV
for all K+1 positions; the engine truncates each slot back to its
accepted length (block-table tail positions are simply never attended —
the paged attention masks ``k_pos <= q_pos``) and frees whole blocks
past the new frontier, so rejected drafts leak nothing.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Any

import jax
import jax.numpy as jnp

from ..core.tensor import Tensor
from ..models.generation import _cache_dims, cached_step
from .sampling import (ACCEPT_TAG, BONUS_TAG, DRAFT_TAG, filtered_probs,
                       fold_keys, sample_tokens)


@dataclass
class SpeculativeConfig:
    """``ServingConfig.speculative``: the draft model (same
    ``LlamaConfig`` family — must share vocab, kv-head count, head_dim
    and cache dtype with the target so both live in one
    :class:`~paddle_tpu.serving.cache.BlockKVPool`) and the number of
    draft tokens proposed per target verify step."""

    draft_model: Any
    num_draft_tokens: int = 4

    def __post_init__(self):
        if self.num_draft_tokens < 1:
            raise ValueError("num_draft_tokens must be >= 1, got "
                             f"{self.num_draft_tokens}")

    def validate_against(self, model):
        """Both models' KV slices share one block pool (that is what
        lets the prefix cache serve draft and target from the same
        blocks), so the per-position cache geometry must match."""
        if _cache_dims(self.draft_model) != _cache_dims(model):
            raise ValueError(
                "draft/target cache layouts differ "
                f"(draft {_cache_dims(self.draft_model)} vs target "
                f"{_cache_dims(model)}): speculative decoding shares one "
                "BlockKVPool, so kv_heads, head_dim and dtype must match")
        dv = self.draft_model.config.vocab_size
        tv = model.config.vocab_size
        if dv != tv:
            raise ValueError(f"draft vocab {dv} != target vocab {tv}: "
                             "speculative decoding needs a shared "
                             "tokenizer")


def make_draft_propose_step(draft_model, num_draft, fused=None):
    """step(tok[S, 1] int32, pools, block_tables[S, max_blocks] int32,
    lengths[S] int32, temps[S] f32, top_ks[S] int32, top_ps[S] f32,
    keys[S, 2] uint32, counters[S] int32) -> (proposals[S, K] int32,
    draft_probs[S, K, V] f32, new_pools).

    K+1 sequential single-token draft decodes under one ``lax.scan`` —
    one fused program, no host syncs between draft tokens.  The scan
    runs one iteration PAST the last proposal: iteration K feeds
    ``d_K`` back in purely to write its KV into the draft's pool slice
    (its proposal is discarded).  Without that, a fully-accepted window
    commits ``d_K`` at position ``lengths + K`` while the draft cache
    has no entry there — every later draft forward would attend garbage
    at that hole and mispropose forever after.  Draft token j for a
    request whose next token index is i uses key
    ``fold(fold(base, i + j), DRAFT_TAG)``: greedy lanes argmax, so a
    weight-identical draft reproduces the target's greedy continuation
    exactly (the accept-rate ceiling the bench measures)."""
    from ..core.dispatch import no_grad_ctx
    from ..kernels.fusion import resolve_serving_fusion, serving_fusion
    from ..models.llama import PagedKVCache

    fused = resolve_serving_fusion(fused)

    def draft_propose_step(tok, pools, block_tables, lengths, temps,
                           top_ks, top_ps, keys, counters):
        with no_grad_ctx(), serving_fusion(fused):
            def propose(carry, i):
                cur, layers = carry
                wrapped = [PagedKVCache(k, v, block_tables)
                           for k, v in layers]
                logits, new_caches = draft_model(
                    Tensor(cur), caches=wrapped,
                    position_offset=lengths + i)
                last = logits._value[:, -1].astype(jnp.float32)
                step_keys = fold_keys(fold_keys(keys, counters + i),
                                      DRAFT_TAG)
                nxt = sample_tokens(last, temps, top_ks, top_ps,
                                    step_keys)
                probs = filtered_probs(last, temps, top_ks, top_ps)
                return ((nxt[:, None], [(c.k, c.v) for c in new_caches]),
                        (nxt, probs))

            (_, layers), (props, probs) = jax.lax.scan(
                propose, (tok, list(pools)), jnp.arange(num_draft + 1))
            return (jnp.transpose(props)[:, :num_draft],
                    jnp.transpose(probs, (1, 0, 2))[:, :num_draft], layers)

    return cached_step(draft_model,
                       ("draft_propose", fused, None, num_draft),
                       draft_propose_step, donate="pools")


def _spec_acceptance(lg, proposals, draft_probs, temps, top_ks, top_ps,
                     keys, counters):
    """On-device acceptance over the verify logits ``lg [S, K+1, V]``.

    Returns ``(committed [S, K+1] int32, accepted_len [S] int32)``:
    row s commits ``committed[s, :accepted_len[s]]`` (accepted drafts
    followed by one bonus/correction token, so ``accepted_len`` is in
    ``1..K+1``); later entries are zero padding."""
    s, k1, v = lg.shape
    k = k1 - 1
    tprobs = filtered_probs(
        lg.reshape(s * k1, v), jnp.repeat(temps, k1),
        jnp.repeat(top_ks, k1), jnp.repeat(top_ps, k1)).reshape(s, k1, v)
    greedy_choice = jnp.argmax(lg, axis=-1).astype(jnp.int32)
    greedy_ok = proposals == greedy_choice[:, :k]
    q = jnp.take_along_axis(draft_probs, proposals[..., None],
                            axis=-1)[..., 0]
    p = jnp.take_along_axis(tprobs[:, :k], proposals[..., None],
                            axis=-1)[..., 0]
    draft_idx = counters[:, None] + jnp.arange(k)[None, :]
    ukeys = fold_keys(fold_keys(
        jnp.broadcast_to(keys[:, None, :], (s, k, 2)), draft_idx),
        ACCEPT_TAG)
    u = jax.vmap(lambda kk: jax.random.uniform(kk, ()))(
        ukeys.reshape(-1, 2)).reshape(s, k)
    stochastic_ok = u * jnp.maximum(q, 1e-20) < p
    ok = jnp.where((temps > 0)[:, None], stochastic_ok, greedy_ok)
    acc = jnp.cumprod(ok.astype(jnp.int32), axis=1)
    n = jnp.sum(acc, axis=1)                        # accepted drafts 0..K
    # bonus token at position n: residual resample on rejection, fresh
    # target sample when every draft survived
    t_at = jnp.take_along_axis(tprobs, n[:, None, None], axis=1)[:, 0]
    dpad = jnp.concatenate(
        [draft_probs, jnp.zeros((s, 1, v), draft_probs.dtype)], axis=1)
    d_at = jnp.take_along_axis(dpad, n[:, None, None], axis=1)[:, 0]
    resid = jnp.maximum(t_at - d_at, 0.0)
    rsum = resid.sum(-1, keepdims=True)
    use_resid = (n < k)[:, None] & (rsum > 1e-12)
    dist = jnp.where(use_resid, resid / jnp.maximum(rsum, 1e-20), t_at)
    bkeys = fold_keys(fold_keys(keys, counters + n), BONUS_TAG)
    sampled_bonus = jax.vmap(jax.random.categorical)(
        bkeys, jnp.log(dist + 1e-30)).astype(jnp.int32)
    greedy_bonus = jnp.take_along_axis(greedy_choice, n[:, None],
                                       axis=1)[:, 0]
    bonus = jnp.where(temps > 0, sampled_bonus, greedy_bonus)
    pos = jnp.arange(k1)[None, :]
    padded = jnp.concatenate(
        [proposals, jnp.zeros((s, 1), proposals.dtype)], axis=1)
    committed = jnp.where(pos < n[:, None], padded,
                          jnp.where(pos == n[:, None], bonus[:, None], 0))
    return committed.astype(jnp.int32), (n + 1).astype(jnp.int32)


def make_spec_verify_step(model, num_draft, fused=None):
    """step(pending[S] int32, proposals[S, K] int32, draft_probs
    [S, K, V] f32, pools, block_tables[S, max_blocks] int32, lengths[S]
    int32, temps[S] f32, top_ks[S] int32, top_ps[S] f32, keys[S, 2]
    uint32, counters[S] int32) -> (committed[S, K+1] int32,
    accepted_len[S] int32, new_pools).

    The target forward is exactly the chunked-prefill attention shape
    batched over slots ([S, K+1] ids with vector position offsets);
    causal masking means junk KV past a slot's frontier is never read,
    which is what makes writing all K+1 positions and rolling back by
    length truncation safe.  Acceptance (:func:`_spec_acceptance`) stays
    on device; only committed tokens + accepted lengths sync back."""
    from ..core.dispatch import no_grad_ctx
    from ..kernels.fusion import resolve_serving_fusion, serving_fusion
    from ..models.llama import PagedKVCache

    fused = resolve_serving_fusion(fused)

    def spec_verify_step(pending, proposals, draft_probs, pools,
                         block_tables, lengths, temps, top_ks, top_ps,
                         keys, counters):
        with no_grad_ctx(), serving_fusion(fused):
            ids = jnp.concatenate(
                [pending[:, None], proposals.astype(pending.dtype)],
                axis=1)
            wrapped = [PagedKVCache(k, v, block_tables) for k, v in pools]
            logits, new_caches = model(Tensor(ids), caches=wrapped,
                                       position_offset=lengths)
            lg = logits._value.astype(jnp.float32)
            committed, accepted = _spec_acceptance(
                lg, proposals, draft_probs, temps, top_ks, top_ps,
                keys, counters)
            return committed, accepted, [(c.k, c.v) for c in new_caches]

    return cached_step(model, ("spec_verify", fused, None, num_draft),
                       spec_verify_step, donate="pools")
