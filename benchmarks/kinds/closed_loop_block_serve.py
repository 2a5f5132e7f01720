"""Closed-loop serving of a model that generates by DIFFUSION OVER
BLOCKS: ``closed_loop_serve``'s clients, lengths, ramp, window and drain
(imported, not copied) against one ``serving.Engine`` whose decode
iteration is the block step, and a check written for what such an
engine times.

Parameters of a mix (``benchmarks/traffic/<mix>.json``): those of
``closed_loop_serve`` (``clients``, ``prompt_tokens``, ``output_tokens``,
``pool``, ``round``, ``lengths_seed``, ``ramp_prompt_tokens``; its
docstring says what each does).  A mix for a block model usually gives
every request ONE output length (``sigma`` 0): generation of a set
length is how such a model is called.  The block length, the denoising
steps and the remasking rule are the MODEL's generation settings (the
configuration's), not the mix's.

What it reports: ``serve_tok_s``, defined as ``closed_loop_serve``
defines it (tokens stamped by ``on_token`` inside the window over the
window's seconds).  No ``itl_p95_ms``: a block's tokens reach
``on_token`` together, so most gaps are zero and the rest are a block's
whole denoising; that cadence is ``window["block_gaps_ms"]`` (the time
between two consecutive blocks of one request being complete at
``on_token``), read by a per-layer metric.

The drain is capped in BLOCK STEPS: a chunk an iteration for every
prompt, then for the longest output its blocks times the most steps a
block can take (its denoising steps and the commit pass).

The check, outside the window (``compared`` holds every number):

- everything ``closed_loop_serve`` holds: every request ended
  ``stop``/``length`` with all its tokens generated and delivered
  (``requests_not_whole``), no leaked block, one program a step, no
  compile request in the window;
- one seeded prompt (``LOGIT_PROMPT_TOKENS``: 90 whole blocks of 4 and a
  tail of 2) goes through the engine's own compiled chunk program, then
  ``LOGIT_BLOCKS`` blocks and the first step of one more through the
  engine's own compiled block program, TEACHER-FORCED: what a denoise
  step unmasks is given seeded tokens, not the program's own picks, so
  the row is the same whatever rounding does to an argmax.  At every
  denoise step the program's logits of the block's positions (masks
  included) are compared with the configuration's reference: one full
  forward of the same row under the block-causal mask, within
  ``LOGIT_TOL`` (``harness/models.py``, unchanged).  The step after a
  commit reads back what the commit wrote;
- the reference runs under the WITNESS of what those very programs
  chose (``harness/models.py``: ``witness``, ``referee``): the experts
  of cached positions from the pool entries the chunk and commit steps
  wrote, those of the block in flight from the step's own output; the
  reference replays them and holds each to its margin
  (``choice_shortfall``);
- which positions each step unmasked against the reference's rule on
  the reference's logits, wherever the rule's answer does not hang on a
  confidence gap smaller than four times the logit tolerance
  (``unmask_disagreements``; positions only: with random weights an
  argmax flips on rounding).
"""
from __future__ import annotations

import numpy as np

from paddle_tpu.serving import Engine, ServingConfig

from benchmarks.harness import cells, models
from benchmarks.harness.stats import clock, percentile
from benchmarks.kinds.closed_loop_serve import ClosedLoop

LOGIT_PROMPT_TOKENS = 362     # 90 whole blocks of 4 and a tail of 2
LOGIT_BLOCKS = 2


class BlockClosedLoop(ClosedLoop):
    def __init__(self, eng, mix, vocab, seed, steps_a_block, block_length):
        super().__init__(eng, mix, vocab, seed)
        self.steps_a_block, self.block_length = steps_a_block, block_length

    def drain(self):
        chunks = -(-self.mix["prompt_tokens"]["max"] // self.eng.chunk_tokens)
        blocks = -(-self.mix["output_tokens"]["max"] // self.block_length) + 1
        limit = self.mix["clients"] * chunks \
            + blocks * self.steps_a_block + 8
        while self.eng.has_work() and limit > 0:
            self.iterate(submit=False)
            limit -= 1


def block_gaps_ms(sent, block_length, in_window):
    """Milliseconds between two consecutive blocks of one request being
    complete at ``on_token`` (the stamp of a block's last token), over
    the gaps that end inside the window."""
    gaps = []
    for s in sent:
        first = s.handle.prompt_len
        done = {}
        for i, t in enumerate(s.stamps):
            done[(first + i) // block_length] = t
        times = [done[b] for b in sorted(done)]
        gaps += [(b - a) * 1e3 for a, b in zip(times, times[1:])
                 if in_window(b)]
    return gaps


def check_block_programs(eng, model, config, seed, say):
    """The teacher-forced comparison of the module docstring: one
    ``check_step`` line a compared step through ``say``; returns
    ``(logits report, choices report or None, unmask report)``."""
    from paddle_tpu.models.generation import (make_chunked_prefill_step,
                                              make_paged_block_step,
                                              unmask_schedule)

    cfg, gen = eng.config, model.config
    L, steps = gen.block_length, gen.denoising_steps
    static = gen.remasking == "low_confidence_static"
    chunk = make_chunked_prefill_step(eng.model, fused=cfg.fused_kernels,
                                      kv_cache_dtype=cfg.kv_cache_dtype)
    block = make_paged_block_step(eng.model, fused=cfg.fused_kernels)
    reference = models.load_reference(config)
    rule = cells.config_module(config, "reference").remask
    weights = reference.weights_of(model)
    C, S, nb = eng.chunk_tokens, cfg.max_batch_size, eng.max_blocks_per_seq
    rng = np.random.default_rng([seed, 3])
    room = eng.max_model_len - (LOGIT_BLOCKS + 1) * L
    n_prompt = min(LOGIT_PROMPT_TOKENS, room // L * L - L + L // 2)
    vocab = gen.vocab_size
    prompt = rng.integers(1, vocab, size=n_prompt, dtype=np.int32)
    whole = n_prompt // L * L
    table = np.zeros((S, nb), np.int32)
    n_blocks = -(-(whole + (LOGIT_BLOCKS + 1) * L) // cfg.block_size)
    table[0, :n_blocks] = np.arange(1, n_blocks + 1)
    pools = eng.pool.layers
    for at in range(0, whole, C):
        n_tok = min(C, whole - at)
        ids = np.zeros((1, C), np.int32)
        ids[0, :n_tok] = prompt[at:at + n_tok]
        _, pools = chunk(ids, pools, table[:1], np.asarray([at], np.int32),
                         np.int32(n_tok - 1))
        eng.pool.layers = pools = [tuple(entry) for entry in pools]

    ids = np.zeros((S, L), np.int32)
    masked = np.zeros((S, L), bool)
    start = np.zeros((S,), np.int32)
    mode = np.zeros((S,), np.int32)
    n_unmask = np.zeros((S,), np.int32)
    tau = np.full((S,), 2.0, np.float32)
    committed = list(prompt[:whole])
    known = list(prompt[whole:])
    schedule = unmask_schedule(L, steps)
    compared, reports, unmask = [], [], {"steps": 0, "decided": 0,
                                         "disagreements": 0}

    def run(slot_mode, n):
        nonlocal pools
        mode[0], n_unmask[0] = slot_mode, n
        tau[0] = 2.0 if static or slot_mode != 1 \
            else gen.confidence_threshold
        start[0] = len(committed)
        small, probe, pools = block(ids, masked, start, mode, n_unmask,
                                    tau, pools, table)
        eng.pool.layers = pools = [tuple(entry) for entry in pools]
        return np.asarray(small), probe

    for b in range(LOGIT_BLOCKS + 1):
        ids[0] = gen.mask_token_id
        ids[0, :len(known)] = known
        masked[0] = True
        masked[0, :len(known)] = False
        known = []
        for step in range(steps):
            if not masked[0].any():
                break
            n = L if step == steps - 1 else schedule[step]
            was = masked[0].copy()
            small, probe = run(1, n)
            got = np.asarray(probe["logits"])
            row = np.asarray(committed + list(ids[0]), np.int32)
            chose = models.witness(
                config, model=model, engine=eng, tokens=row,
                block_table=table[0], prompt_tokens=len(committed),
                in_flight=np.asarray(probe["chosen"])[:, 0])
            want, report = models.referee(
                reference.logits, weights, config, row, last=L,
                witness=chose)
            want = np.asarray(want)
            cmp = models.compare_logits(got, want)
            compared.append(cmp)
            if report is not None:
                reports.append(report)
            now_masked = small[S * L:S * L + L] != 0
            took = was & ~now_masked
            ref_took, _, conf = rule(want, was, n,
                                     None if static
                                     else gen.confidence_threshold)
            # decided: the rule's answer does not hang on a gap the
            # logit tolerance could close
            order = np.sort(np.log(conf[was]))[::-1]
            edge = min(n, len(order))
            decided = edge >= len(order) or \
                order[edge - 1] - order[edge] > 4 * cmp["tolerance"]
            if not static:
                near = np.abs(np.log(conf[was])
                              - np.log(gen.confidence_threshold))
                decided = decided and bool(
                    (near > 4 * cmp["tolerance"]).all())
            unmask["steps"] += 1
            unmask["decided"] += int(decided)
            unmask["disagreements"] += int(
                decided and bool((took != ref_took).any()))
            say(phase="check_step", block=b, step=step,
                context_tokens=len(committed), masked=int(was.sum()),
                unmasked=[int(i) for i in np.flatnonzero(took)],
                reference_unmasks=[int(i) for i in np.flatnonzero(ref_took)],
                decided=bool(decided),
                logit_gap=max(cmp["max_abs_diff"]),
                tolerance=cmp["tolerance"],
                **({} if report is None else {"choices": report}))
            # teacher-forced: the unmasked positions take seeded tokens
            fed = rng.integers(1, vocab, size=L, dtype=np.int32)
            ids[0] = np.where(took, fed, ids[0])
            masked[0] = now_masked
            if b == LOGIT_BLOCKS:
                break           # the step after the last commit is enough
        if b == LOGIT_BLOCKS:
            break
        # whatever is still masked (a threshold no confidence passed
        # within the steps) is forced too, then the block is committed
        fed = rng.integers(1, vocab, size=L, dtype=np.int32)
        ids[0] = np.where(masked[0], fed, ids[0])
        masked[0] = False
        run(2, 0)
        committed += list(ids[0])

    choices = None
    if reports:
        worst = max(reports, key=lambda r: r["largest_shortfall"]
                    / max(r["margin"], 1e-30))
        choices = {"ok": all(r["ok"] for r in reports),
                   "decisions": sum(r["decisions"] for r in reports),
                   "not_first_choice": sum(r["not_first_choice"]
                                           for r in reports),
                   "largest_shortfall": worst["largest_shortfall"],
                   "margin": worst["margin"]}
    worst = max(compared, key=lambda c: max(c["max_abs_diff"])
                / max(c["tolerance"], 1e-30))
    logits = {"ok": all(c["ok"] for c in compared),
              "steps_compared": len(compared),
              "max_abs_diff": [max(c["max_abs_diff"]) for c in compared],
              "tolerance": [c["tolerance"] for c in compared],
              "worst": [max(worst["max_abs_diff"]), worst["tolerance"]],
              "max_abs_reference_logit": [c["max_abs_reference_logit"]
                                          for c in compared],
              "prompt_tokens": n_prompt}
    return logits, choices, unmask


def run(ctx) -> dict:
    config, mix = ctx.cell.config, ctx.cell.traffic
    model = models.build_model(config, ctx.seed)
    model.eval()
    ctx.say(phase="model", built_s=clock() - ctx.t_start)
    eng = Engine(model, ServingConfig(**config["serving"]))
    gen = model.config
    ctx.say(phase="engine", max_batch_size=eng.config.max_batch_size,
            num_blocks=eng.num_blocks, chunk_tokens=eng.chunk_tokens,
            fused_kernels=eng.config.fused_kernels,
            block_length=gen.block_length,
            denoising_steps=gen.denoising_steps, remasking=gen.remasking,
            built_s=clock() - ctx.t_start)
    loop = BlockClosedLoop(eng, mix, gen.vocab_size, ctx.seed,
                           gen.denoising_steps + 1, gen.block_length)

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

    loop.drain()
    drained_s = clock() - w1
    try:
        eng.pool.check_leaks()
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
    block_ms = block_gaps_ms(sent, gen.block_length, in_window)
    iter_ms = [(e - s) * 1e3 for s, e in loop.iters if in_window(e)]
    seconds = w1 - w0
    ctx.say(phase="window", seconds=seconds, requests_submitted=len(measured),
            ttft_samples=len(ttft_ms), block_gap_samples=len(block_ms),
            engine_iterations=len(iter_ms), tokens=tokens,
            ttft_ms={q: percentile(ttft_ms, q) for q in (50, 80, 90, 95)},
            block_gap_ms={q: percentile(block_ms, q) for q in (50, 95, 99)},
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
    logits, choices, unmask = check_block_programs(
        eng, model, config, ctx.seed, ctx.say)
    still_one = (eng.decode_cache_size() == 1
                 and eng.prefill_cache_size() == 1)
    ctx.say(phase="check", failed_requests=bad[:20],
            requests_checked=len(sent), requests_ended=len(loop.done),
            one_program_each=one_program_each and still_one, logits=logits,
            unmask=unmask,
            **({} if choices is None else {"choices": choices}),
            checked_s=clock() - ctx.t_start)

    compared = {
        "requests_not_whole": [len(bad), 0],
        "leaked_blocks": [int(leaks is not None), 0],
        "programs_a_step": [max(eng.decode_cache_size(),
                                eng.prefill_cache_size()), 1],
        "compiles_in_window": [compiles_in_window, 0],
        "logit_gap": logits["worst"],
        "unmask_disagreements": [unmask["disagreements"], 0],
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
                        and unmask["disagreements"] == 0
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
            "block_gaps_ms": block_ms,
            "counters": {k: counters1[k] - counters0[k] for k in counters1},
            "max_batch_size": eng.config.max_batch_size,
            "compiles_in_window": compiles_in_window,
        },
    }
