"""Closed-loop serving: a fixed number of clients, each sending its next
request the moment its last one ends, against one ``serving.Engine``
driven from this thread.

Parameters of a mix (``benchmarks/traffic/<mix>.json``):

- ``clients``: concurrent callers (no think time);
- ``prompt_tokens`` / ``output_tokens``: ``{"median", "sigma", "min",
  "max"}`` of a lognormal, clipped;
- ``pool``, ``round``: ``pool`` (prompt, output) length pairs make the
  mix, dealt in rounds of ``round``.  The lengths are the lognormals'
  quantiles; each of the ``k = pool / round`` rounds holds one of every
  ``k`` neighbouring prompt lengths and one of every ``k`` neighbouring
  output lengths, so every round spans both distributions evenly and any
  run of consecutive requests asks for about the same work.  The pairing,
  the order of the rounds and the order inside each are drawn from
  ``lengths_seed``: every ``--seed`` is dealt the SAME lengths in the
  SAME order.  (Dealt in another order per seed, a window of some tens of
  requests read 5 % apart from seed to seed and 0.1-1 % apart on one
  seed: the order was changing the work.)  A cell's numbers therefore
  hold for ONE schedule; a second realisation of the same mix is a copy
  of the mix's file with another ``lengths_seed``, added as a cell of
  its own (data only).  ``--seed`` draws the weights
  and the token ids (uniform in [1, vocab), one stream per client,
  nothing shared between requests);
- ``ramp_prompt_tokens`` (optional): the longest prompt of a client's
  FIRST request, so that the ramp costs one chunk a client.

Requests are greedy and have no EOS: each runs to its ``max_new_tokens``.

The ramp (set-up): every client sends its first request and the loop runs
until each of those has its first token, so the window opens on a busy
decode bucket.  Each client's first request has its output cut to a different
share, so that the clients do not end, and send again, in lock step: the
first requests are replaced by whole ones as the window goes on.

The window: ``--seconds`` of ``submit / engine.step() / collect`` on the
benchmark's clock.  Tokens are counted and timed by ``on_token``.

The drain, after the window and outside every metric: nothing new is
sent and the loop goes on until the engine has no work, so that every
request is judged whole (ended ``stop``/``length`` with all its tokens)
and ``pool.check_leaks()`` can show that every block came back.  It is
capped at the iterations the requests in flight can need at most; a
request that has not ended by then counts as failed.

Then one seeded prompt and a few fed tokens go through the engine's own
step programs and their logits are compared with the configuration's
reference; where the configuration names a witness
(``harness/models.py``) it is fetched after those steps, from the pools
they left, and the reference replays and verifies what they chose.
"""
from __future__ import annotations

import statistics

import numpy as np

from paddle_tpu.serving import Engine, ServingConfig

from benchmarks.harness import models
from benchmarks.harness.stats import clock, percentile
from benchmarks.harness.tracing import span

LOGIT_PROMPT_TOKENS = 360     # one whole chunk and a part (chunk 256)
LOGIT_DECODE_STEPS = 4


def lognormal_quantiles(spec: dict, n: int):
    """``n`` lengths at the lognormal's mid-quantiles, clipped."""
    nd = statistics.NormalDist()
    z = np.array([nd.inv_cdf((i + 0.5) / n) for i in range(n)])
    raw = spec["median"] * np.exp(spec["sigma"] * z)
    return np.clip(np.rint(raw), spec["min"], spec["max"]).astype(int)


def length_rounds(mix: dict):
    """The mix's fixed rounds of (prompt tokens, output tokens)."""
    n, r = mix["pool"], mix["round"]
    if n % r:
        raise ValueError(f"pool {n} is not a whole number of rounds of {r}")
    k = n // r
    prompts = lognormal_quantiles(mix["prompt_tokens"], n)
    outputs = lognormal_quantiles(mix["output_tokens"], n)
    rng = np.random.default_rng(mix["lengths_seed"])
    rounds = []
    for j in range(k):
        # of each k neighbours one, taken from alternate ends, so that
        # no round gets the longer of every group
        pick = np.array([g * k + (j if g % 2 == 0 else k - 1 - j)
                         for g in range(r)])
        rounds.append([(int(p), int(o)) for p, o in zip(
            prompts[pick], outputs[pick][rng.permutation(r)])])
    return rounds


class _Sent:
    __slots__ = ("handle", "submitted", "stamps", "want")

    def __init__(self, want):
        self.want = want
        self.handle, self.submitted, self.stamps = None, None, []

    def ok(self) -> bool:
        """Ended ``stop``/``length`` with every token it was asked for
        generated and delivered."""
        h = self.handle
        return h.error is None and h.finish_reason in ("stop", "length") \
            and h.num_generated == len(self.stamps) == self.want


class ClosedLoop:
    def __init__(self, eng, mix, vocab, seed):
        self.eng, self.mix, self.vocab = eng, mix, vocab
        self.rounds = length_rounds(mix)
        self.order_rng = np.random.default_rng([mix["lengths_seed"], 1])
        self.round_order, self.dealt = [], []
        self.token_rngs = [np.random.default_rng([seed, 2, c])
                           for c in range(mix["clients"])]
        self.live = [None] * mix["clients"]
        self.first = [True] * mix["clients"]
        self.ramped = [False] * mix["clients"]   # had a first token
        self.done = []
        self.iters = []          # (start, end) of every engine.step()

    def _next_lengths(self, client):
        if not self.dealt:
            if not self.round_order:
                self.round_order = list(
                    self.order_rng.permutation(len(self.rounds)))
            pairs = self.rounds[self.round_order.pop()]
            self.dealt = [pairs[i]
                          for i in self.order_rng.permutation(len(pairs))]
        prompt, out = self.dealt.pop()
        if self.first[client]:
            self.first[client] = False
            lo = self.mix["output_tokens"]["min"]
            out = max(lo, out * (client + 1) // self.mix["clients"])
            prompt = min(prompt, self.mix.get("ramp_prompt_tokens", prompt))
        return prompt, out

    def _submit(self, client):
        n_prompt, n_out = self._next_lengths(client)
        prompt = self.token_rngs[client].integers(
            1, self.vocab, size=n_prompt, dtype=np.int32)
        sent = _Sent(n_out)
        stamps = sent.stamps
        sent.submitted = clock()
        sent.handle = self.eng.submit(
            prompt, max_new_tokens=n_out,
            on_token=lambda _tok: stamps.append(clock()))
        self.live[client] = sent

    def iterate(self, submit=True):
        with span("submit"):
            for c, sent in enumerate(self.live):
                if sent is None and submit:
                    self._submit(c)
        t0 = clock()
        with span("engine_step"):
            self.eng.step()
        self.iters.append((t0, clock()))
        with span("bookkeeping"):
            for c, sent in enumerate(self.live):
                if sent is None:
                    continue
                if sent.stamps:
                    self.ramped[c] = True
                if sent.handle.finish_reason is not None:
                    self.done.append(sent)
                    self.live[c] = None

    def drain(self):
        """Send nothing new and run what is in flight to its end; at
        most as many iterations as those requests can need (a chunk an
        iteration for every prompt, then the longest output)."""
        chunks = -(-self.mix["prompt_tokens"]["max"] // self.eng.chunk_tokens)
        limit = self.mix["clients"] * chunks \
            + self.mix["output_tokens"]["max"] + 8
        while self.eng.has_work() and limit > 0:
            self.iterate(submit=False)
            limit -= 1

    def everything(self):
        return self.done + [s for s in self.live if s is not None]


def run(ctx) -> dict:
    config, mix = ctx.cell.config, ctx.cell.traffic
    model = models.build_model(config, ctx.seed)
    model.eval()
    ctx.say(phase="model", built_s=clock() - ctx.t_start)
    eng = Engine(model, ServingConfig(**config["serving"]))
    ctx.say(phase="engine", max_batch_size=eng.config.max_batch_size,
            num_blocks=eng.num_blocks, chunk_tokens=eng.chunk_tokens,
            fused_kernels=eng.config.fused_kernels,
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
    rng = np.random.default_rng([ctx.seed, 3])
    n_prompt = min(LOGIT_PROMPT_TOKENS,
                   eng.max_model_len - LOGIT_DECODE_STEPS - 1)
    prompt = rng.integers(1, model.config.vocab_size, size=n_prompt,
                          dtype=np.int32)
    feed = rng.integers(1, model.config.vocab_size, size=LOGIT_DECODE_STEPS,
                        dtype=np.int32)
    got, table = models.engine_logits(eng, prompt, feed)
    checked = np.concatenate([prompt, feed])
    # what those steps chose, read where they wrote it: the pools are
    # bound to the engine as the last step left them
    chose = models.witness(config, model=model, engine=eng, tokens=checked,
                           block_table=table, prompt_tokens=len(prompt))
    reference = models.load_reference(config)
    want, choices = models.referee(
        reference.logits, reference.weights_of(model), config, checked,
        last=1 + len(feed), witness=chose)
    logits = models.compare_logits(got, np.asarray(want)[:len(got)])
    still_one = (eng.decode_cache_size() == 1
                 and eng.prefill_cache_size() == 1)
    ctx.say(phase="check", failed_requests=bad[:20],
            requests_checked=len(sent), requests_ended=len(loop.done),
            one_program_each=one_program_each and still_one, logits=logits,
            **({} if choices is None else {"choices": choices}),
            checked_s=clock() - ctx.t_start)

    compared = {
        "requests_not_whole": [len(bad), 0],
        "leaked_blocks": [int(leaks is not None), 0],
        "programs_a_step": [max(eng.decode_cache_size(),
                                eng.prefill_cache_size()), 1],
        "compiles_in_window": [compiles_in_window, 0],
        "logit_gap": [max(logits["max_abs_diff"]), logits["tolerance"]],
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
                        and models.chose_admissibly(choices)),
        "compared": compared,
        "end_to_end": {
            "serve_tok_s": tokens / seconds,
            "itl_p95_ms": percentile(gaps_ms, 95),
        },
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
