"""Closed-loop serving of a model with WINDOW layers beside full ones:
``closed_loop_serve``'s clients, lengths, ramp, window and drain
(imported, not copied) against one ``serving.Engine`` whose pool holds
two groups of pages, and a logit check moved PAST the attention window.

Parameters of a mix (``benchmarks/traffic/<mix>.json``): those of
``closed_loop_serve`` (``clients``, ``prompt_tokens``, ``output_tokens``,
``pool``, ``round``, ``lengths_seed``, ``ramp_prompt_tokens``; its
docstring says what each does), and for the check
``check_prompt_tokens`` and ``check_decode_steps`` (below).

What it reports: ``serve_tok_s``, defined as ``closed_loop_serve``
defines it (tokens stamped by ``on_token`` inside the window over the
window's seconds).  No ``itl_p95_ms``: with prompts of up to 14 k tokens
in the queue a decode step waits behind a chunk in most iterations, and
the tail of the gaps swings with the order of arrivals; the gaps'
percentiles stay in the ``window`` line.

The check, outside the window (``compared`` holds every number):

- everything ``closed_loop_serve`` holds: every request ended
  ``stop``/``length`` with all its tokens generated and delivered
  (``requests_not_whole``), no leaked block IN EITHER GROUP, one program
  a step, no compile request in the window;
- one seeded prompt of ``check_prompt_tokens`` (2,590: ten whole chunks
  and a tail of 30, longer than the window) and ``check_decode_steps``
  fed tokens go through the engine's own compiled chunk and decode
  programs, at the engine's own shapes, under tables laid out by the
  engine's own manager for that length
  (``BlockKVPool.advance_window``: before every chunk and every decode
  step the pages behind the window go back to the window group and
  their table entries name its garbage block).  So that a page the
  program should no longer reach cannot be read unnoticed, the check
  first overwrites the window layers' pools, and then every page as it
  is released, with keys of 0 and values of ``POISON``: one such key
  inside a softmax moves the logits by far more than the limit, as the
  next sequence's keys would in a page taken again.  The first token's
  logits and those of every decode step are compared with the
  configuration's reference (one full forward of the same row, the
  window as a mask) within ``LOGIT_TOL`` (``harness/models.py``,
  unchanged);
- the reference runs under the WITNESS of what those very programs
  chose (``harness/models.py``: ``witness``, ``referee``), read back from
  the pool, and holds each choice to its margin (``choice_shortfall``);
- the check's own sequence never held more window pages than the
  manager's bound (``window_pages_held``).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from paddle_tpu.serving import Engine, ServingConfig

from benchmarks.harness import models
from benchmarks.harness.stats import clock, percentile
from benchmarks.kinds.closed_loop_serve import ClosedLoop

CHECK_PROMPT_TOKENS = 2590    # ten whole chunks and a tail (chunk 256)
CHECK_DECODE_STEPS = 4
POISON = 1.0e4


@functools.partial(jax.jit, donate_argnums=(0,),
                   static_argnames=("window_layers",))
def _poison(layers, blocks, window_layers):
    """Keys of 0 and values of ``POISON`` in the pages ``blocks`` (all
    pages where ``blocks`` is None) of the window layers' pools."""
    def spoil(pool, value):
        if blocks is None:
            return jnp.full_like(pool, value)
        return pool.at[blocks].set(jnp.asarray(value, pool.dtype))

    return [(spoil(entry[0], 0.0), spoil(entry[1], POISON)) + tuple(entry[2:])
            if is_window else tuple(entry)
            for entry, is_window in zip(layers, window_layers)]


def check_window_programs(eng, model, config, mix, seed):
    """The comparison of the module docstring; returns ``(logits
    report, choices report or None, most window pages held)``."""
    from paddle_tpu.models.generation import (make_chunked_prefill_step,
                                              make_paged_decode_step)

    cfg, pool = eng.config, eng.pool
    kwargs = dict(fused=cfg.fused_kernels, kv_cache_dtype=cfg.kv_cache_dtype)
    chunk = make_chunked_prefill_step(eng.model, **kwargs)
    decode = make_paged_decode_step(eng.model, **kwargs)
    C, S, nb = eng.chunk_tokens, cfg.max_batch_size, eng.max_blocks_per_seq
    steps = mix.get("check_decode_steps", CHECK_DECODE_STEPS)
    n_prompt = min(mix.get("check_prompt_tokens", CHECK_PROMPT_TOKENS),
                   eng.max_model_len - steps - 1)
    rng = np.random.default_rng([seed, 3])
    vocab = model.config.vocab_size
    prompt = rng.integers(1, vocab, size=n_prompt, dtype=np.int32)
    feed = rng.integers(1, vocab, size=steps, dtype=np.int32)
    window_layers = tuple(c.window is not None for c in pool.layer_caches)
    rid = "logit-check"
    full = np.zeros((S, nb), np.int32)
    window = np.zeros((S, nb), np.int32)
    pages, held = {}, 0

    def bind(pools):
        pool.layers = [tuple(entry) for entry in pools]

    def advance(first_query, end):
        """The manager's own move of the window, and poison in what it
        gave back."""
        nonlocal held
        before = dict(pages)
        pool.advance_window(rid, pages, window[0], first_query, end)
        held = max(held, len(pages))
        gone = [b for p, b in before.items() if p not in pages]
        if gone:
            bind(_poison(pool.layers, np.asarray(gone, np.int32),
                         window_layers))

    def grow(end):
        n = pool.blocks_for(end) - len(owned)
        if n > 0:
            new = pool.allocate(rid, n)
            full[0, len(owned):len(owned) + n] = new
            owned.extend(new)

    owned = []
    try:
        bind(_poison(pool.layers, None, window_layers))
        grow(n_prompt)
        for start in range(0, n_prompt, C):
            n_tok = min(C, n_prompt - start)
            advance(start, start + n_tok)
            ids = np.zeros((1, C), np.int32)
            ids[0, :n_tok] = prompt[start:start + n_tok]
            (last, _), pools = chunk(
                ids, pool.layers, (full[:1].copy(), window[:1].copy()),
                np.asarray([start], np.int32), np.int32(n_tok - 1))
            bind(pools)
        out = [np.asarray(last)[0]]
        lengths = np.zeros((S,), np.int32)
        lengths[0] = n_prompt
        tok = np.zeros((S, 1), np.int32)
        for t in feed:
            pos = int(lengths[0])
            grow(pos + 1)
            advance(pos, pos + 1)
            tok[0, 0] = t
            (logits, _), pools = decode(tok, pool.layers,
                                        (full.copy(), window.copy()),
                                        lengths)
            bind(pools)
            out.append(np.asarray(logits)[0])
            lengths[0] += 1
        got = np.stack(out)
        checked = np.concatenate([prompt, feed])
        # what those steps chose, read where they wrote it: the pools are
        # bound to the engine as the last step left them
        chose = models.witness(config, model=model, engine=eng,
                               tokens=checked, block_table=full[0],
                               prompt_tokens=n_prompt)
    finally:
        pool.free_request(rid)
    reference = models.load_reference(config)
    want, choices = models.referee(
        reference.logits, reference.weights_of(model), config, checked,
        last=1 + len(feed), witness=chose)
    report = models.compare_logits(got, np.asarray(want)[:len(got)])
    report["prompt_tokens"] = n_prompt
    return report, choices, held


def run(ctx) -> dict:
    config, mix = ctx.cell.config, ctx.cell.traffic
    model = models.build_model(config, ctx.seed)
    model.eval()
    ctx.say(phase="model", built_s=clock() - ctx.t_start)
    eng = Engine(model, ServingConfig(**config["serving"]))
    pool = eng.pool
    ctx.say(phase="engine", max_batch_size=eng.config.max_batch_size,
            num_blocks=eng.num_blocks, chunk_tokens=eng.chunk_tokens,
            fused_kernels=eng.config.fused_kernels,
            window=pool.window_size,
            window_blocks=pool.window.num_blocks,
            window_pages_per_seq=pool.window_pages_per_seq,
            layer_kinds=[c.kind for c in pool.layer_caches],
            built_s=clock() - ctx.t_start)
    loop = ClosedLoop(eng, mix, model.config.vocab_size, ctx.seed)

    # ramp: compiles (or loads) both step programs, fills every slot
    while not all(loop.ramped):
        loop.iterate()
    ctx.say(phase="ramp", ramped_s=clock() - ctx.t_start,
            iterations=len(loop.iters), requests_ended=len(loop.done))
    compiles_before = ctx.compiles.count
    counters0 = eng.metrics.as_dict()["counters"]
    w0 = clock()
    ctx.tracer.arm(w0, ctx.seconds)
    while True:
        loop.iterate()
        now = clock()
        if now - w0 >= ctx.seconds:
            break
        ctx.tracer.tick(now)
    w1 = now
    ctx.tracer.finish()
    counters1 = eng.metrics.as_dict()["counters"]
    compiles_in_window = ctx.compiles.count - compiles_before

    def in_window(t):
        return w0 <= t <= w1

    # the drain: every request in flight runs to its end
    loop.drain()
    drained_s = clock() - w1
    try:
        pool.check_leaks()
        leaks = None
    except AssertionError as e:
        leaks = str(e)[:500]

    sent = loop.everything()
    measured = [s for s in sent if in_window(s.submitted)]
    tokens = sum(1 for s in sent for t in s.stamps if in_window(t))
    first_tokens = sum(1 for s in sent
                       if s.stamps and in_window(s.stamps[0]))
    ttft_ms = [(s.stamps[0] - s.submitted) * 1e3
               for s in measured if s.stamps]
    gaps_ms = [(b - a) * 1e3 for s in sent
               for a, b in zip(s.stamps, s.stamps[1:]) if in_window(b)]
    iter_ms = [(e - s) * 1e3 for s, e in loop.iters if in_window(e)]
    seconds = w1 - w0
    ctx.say(phase="window", seconds=seconds, requests_submitted=len(measured),
            ttft_samples=len(ttft_ms), itl_samples=len(gaps_ms),
            engine_iterations=len(iter_ms), tokens=tokens,
            ttft_ms={q: percentile(ttft_ms, q) for q in (50, 80, 90, 95)},
            itl_ms={q: percentile(gaps_ms, q) for q in (50, 95, 99)},
            iter_ms=dict({q: percentile(iter_ms, q) for q in (5, 50, 95)},
                         mean=sum(iter_ms) / len(iter_ms)),
            # a stalled host shows here and in no percentile
            slowest_iterations=sorted(
                ([(e - s) * 1e3, e - w0] for s, e in loop.iters
                 if in_window(e)), reverse=True)[:3],
            requests_ended=sum(1 for s in loop.done
                               if s.stamps and in_window(s.stamps[-1])),
            compiles_in_window=compiles_in_window,
            drained_s=drained_s, still_running=sum(
                1 for s in loop.live if s is not None),
            leaked_blocks=leaks)

    # correctness, outside the window
    bad = [s.handle.request_id for s in sent if not s.ok()]
    one_program_each = (eng.decode_cache_size() == 1
                        and eng.prefill_cache_size() == 1)
    logits, choices, held = check_window_programs(eng, model, config, mix,
                                                  ctx.seed)
    still_one = (eng.decode_cache_size() == 1
                 and eng.prefill_cache_size() == 1)
    ctx.say(phase="check", failed_requests=bad[:20],
            requests_checked=len(sent), requests_ended=len(loop.done),
            one_program_each=one_program_each and still_one, logits=logits,
            window_pages_held=held,
            **({} if choices is None else {"choices": choices}),
            checked_s=clock() - ctx.t_start)

    compared = {
        "requests_not_whole": [len(bad), 0],
        "leaked_blocks": [int(leaks is not None), 0],
        "programs_a_step": [max(eng.decode_cache_size(),
                                eng.prefill_cache_size()), 1],
        "compiles_in_window": [compiles_in_window, 0],
        "logit_gap": [max(logits["max_abs_diff"]), logits["tolerance"]],
        "window_pages_held": [held, pool.window_pages_per_seq],
    }
    if choices is not None:
        compared["choice_shortfall"] = [choices["largest_shortfall"],
                                        choices["margin"]]
    return {
        "window_start": w0,
        "attempted": len(measured),
        "failed": sum(1 for s in measured if not s.ok()),
        "correct": bool(not bad and leaks is None and one_program_each
                        and still_one and compiles_in_window == 0
                        and logits["ok"]
                        and held <= pool.window_pages_per_seq
                        and models.chose_admissibly(choices)),
        "compared": compared,
        "end_to_end": {"serve_tok_s": tokens / seconds},
        "window": {
            "seconds": seconds,
            "tokens": tokens,
            "first_tokens": first_tokens,
            "iter_ms": iter_ms,
            "ttft_ms": ttft_ms,
            "gaps_ms": gaps_ms,
            "counters": {k: counters1[k] - counters0[k] for k in counters1},
            "max_batch_size": eng.config.max_batch_size,
            "compiles_in_window": compiles_in_window,
        },
    }
