"""Closed-loop serving of DOCUMENTS ASKED SEVERAL TIMES: a fixed number
of clients, each of which loads one long document and sends ``document +
question_i`` for ``i = 1..asks``, each the moment the last one ended,
then takes its next document.  ``closed_loop_serve``'s loop, ramp,
window and drain (imported, not copied) against one ``serving.Engine``
with its prefix cache ON: the only reuse is a client's own document,
whose full blocks the engine registers at the first ask's first token
and finds again at admission of the next.

Parameters of a mix (``benchmarks/traffic/<mix>.json``):

- ``clients``: concurrent callers (no think time);
- ``document_tokens``, ``question_tokens``, ``output_tokens``:
  ``{"median", "sigma", "min", "max"}`` of a lognormal, clipped;
- ``asks``: questions a document is asked;
- ``pool``, ``round``, ``lengths_seed``: ``pool`` documents make the
  mix, each with its ``asks`` question and output lengths, dealt in
  balanced rounds of ``round`` as ``closed_loop_serve`` deals its pairs
  (the lengths are the lognormals' quantiles; the pairing and the
  orders are drawn from ``lengths_seed``: every ``--seed`` is dealt the
  SAME lengths in the SAME order).  ``--seed`` draws the weights and
  the token ids (uniform in [1, vocab), one stream a client; a
  document's ids are drawn once, a question's anew for every ask;
  nothing is shared between clients or between documents);
- ``ramp_document_tokens``: the longest FIRST document of a client;
- a request the engine could not admit is cut to what it admits: a
  question to a quarter of ``max_model_len``, then the document to what
  is left beside its question and answer (a rehearsal at tiny sizes
  needs it; the cell's own lengths never reach it);
- ``first_document_asks``: ``"staggered"``: client ``c``'s first
  document is asked ``1 + (c mod asks)`` times, so that the clients do
  not change documents in lock step;
- ``check_prompt_tokens``, ``check_decode_steps``,
  ``check_reask_tokens``, ``check_decode_row_tokens``,
  ``check_decode_row_steps``: the check's rows (below).

Requests are greedy and have no EOS: each runs to its ``max_new_tokens``.
The window opens when every client has its first token.

What it reports: ``serve_tok_s``, as ``closed_loop_serve`` defines it.
No ``itl_p95_ms``: with documents of up to 30 k tokens in the queue a
decode step waits behind a chunk in most iterations; the gaps'
percentiles stay in the ``window`` line, and the first tokens' apart for
first asks (the whole document prefilled) and repeated asks (its pages
found in the pool).  The ``window`` line also holds the two quotients of
the routing counters that the accepted readers would look up under
another family's config keys (``expert_load_max_over_mean``,
``experts_read_per_layer_decode``), and ``window`` carries
``kv_pool_bytes_per_token`` (the pool's own ``block_bytes()`` over its
``block_size``).

The check, outside the window (``compared`` holds every number):

- everything ``closed_loop_serve`` holds: every request ended
  ``stop``/``length`` with all its tokens generated and delivered, no
  leaked block, one program a step, no compile request in the window;
- **row A**: one seeded prompt of ``check_prompt_tokens`` (2,590: ten
  whole chunks and a tail of 30) and ``check_decode_steps`` fed tokens
  through the engine's own compiled chunk and decode programs, at the
  engine's own shapes, on blocks the pool's own allocator hands out:
  first-token logits and one row a decode step;
- **row B**, the pool left as row A's steps left it and row A's full
  blocks registered through the pool's own ``register_prefix``: row A's
  first whole blocks (2,576 tokens: 161 blocks, found by the pool's own
  ``match_prefix`` and taken by ``acquire``) and ``check_reask_tokens``
  (46) new ones: ONE chunk over cached latent pages, first-token logits
  and two decode steps;
- **row C**, the decode row: a fresh prompt of
  ``check_decode_row_tokens`` (480, a whole chunk and a part) and
  ``check_decode_row_steps`` (160) fed tokens, first-token logits and one
  row a decode step.  Rows A and B decode 4 and 2 tokens behind some
  2,590 sound prompt keys, where a key the DECODE program wrote holds
  about 1/19,000 of a softmax's weight and no limit can see it; by row
  C's last step a quarter of the context is what decode steps wrote;
- every row against the configuration's reference (one full forward of
  the row, no cache) within ``LOGIT_TOL`` (``harness/models.py``,
  unchanged), under the WITNESS of what those very programs chose, read
  back through each row's own table (row B's cached positions are row
  A's pages) and held to the reference's margin (``choice_shortfall``);
- the count of row B's matched blocks against what the lengths imply
  (``matched_blocks``).
"""
from __future__ import annotations

import numpy as np

from paddle_tpu.serving import Engine, ServingConfig

from benchmarks.harness import models
from benchmarks.harness.stats import clock, percentile
from benchmarks.kinds.closed_loop_serve import (ClosedLoop, _Sent,
                                                lognormal_quantiles)

CHECK_PROMPT_TOKENS = 2590    # ten whole chunks and a tail (chunk 256)
CHECK_DECODE_STEPS = 4
CHECK_REASK_TOKENS = 46
REASK_DECODE_STEPS = 2
DECODE_ROW_TOKENS = 480       # row C: a chunk and a part, then ...
DECODE_ROW_STEPS = 160        # ... a quarter of the context decode-written


def document_rounds(mix: dict):
    """The mix's fixed rounds of documents: ``(document tokens,
    [(question tokens, output tokens)] * asks)``."""
    n, r, asks = mix["pool"], mix["round"], mix["asks"]
    if n % r:
        raise ValueError(f"pool {n} is not a whole number of rounds of {r}")
    k = n // r
    documents = lognormal_quantiles(mix["document_tokens"], n)
    rng = np.random.default_rng(mix["lengths_seed"])
    # a question and an output length for every ask of every document,
    # the quantiles dealt at random: a document's asks span both
    questions = lognormal_quantiles(mix["question_tokens"], n * asks)[
        rng.permutation(n * asks)].reshape(n, asks)
    outputs = lognormal_quantiles(mix["output_tokens"], n * asks)[
        rng.permutation(n * asks)].reshape(n, asks)
    rounds = []
    for j in range(k):
        # of each k neighbours one, taken from alternate ends, so that
        # no round gets the longer of every group
        pick = [g * k + (j if g % 2 == 0 else k - 1 - j) for g in range(r)]
        rounds.append([
            (int(documents[d]),
             [(int(q), int(o)) for q, o in zip(questions[d], outputs[d])])
            for d in pick])
    return rounds


class _Asked(_Sent):
    __slots__ = ("repeat",)


class ReaskLoop(ClosedLoop):
    """``ClosedLoop`` whose clients ask a document ``asks`` times."""

    def __init__(self, eng, mix, vocab, seed):
        self.eng, self.vocab = eng, vocab
        # (``drain`` reads the longest prompt and output of the mix)
        self.mix = dict(mix, prompt_tokens={
            "max": mix["document_tokens"]["max"]
            + mix["question_tokens"]["max"]})
        self.rounds = document_rounds(mix)
        self.order_rng = np.random.default_rng([mix["lengths_seed"], 1])
        self.round_order, self.dealt = [], []
        self.token_rngs = [np.random.default_rng([seed, 2, c])
                           for c in range(mix["clients"])]
        self.live = [None] * mix["clients"]
        self.first = [True] * mix["clients"]
        self.ramped = [False] * mix["clients"]
        self.done = []
        self.iters = []
        # a client's document in hand: its ids and the asks still to send
        self.document = [None] * mix["clients"]
        self.to_ask = [[] for _ in range(mix["clients"])]
        self.documents_dealt = 0

    def _next_document(self, client):
        if not self.dealt:
            if not self.round_order:
                self.round_order = list(
                    self.order_rng.permutation(len(self.rounds)))
            docs = self.rounds[self.round_order.pop()]
            self.dealt = [docs[i]
                          for i in self.order_rng.permutation(len(docs))]
        tokens, asks = self.dealt.pop()
        if self.first[client]:
            self.first[client] = False
            tokens = min(tokens, self.mix.get("ramp_document_tokens",
                                              tokens))
            if self.mix.get("first_document_asks") == "staggered":
                asks = asks[:1 + client % len(asks)]
        self.documents_dealt += 1
        self.document[client] = self.token_rngs[client].integers(
            1, self.vocab, size=tokens, dtype=np.int32)
        self.to_ask[client] = list(reversed(asks))
        return len(asks)

    def _submit(self, client):
        repeat = bool(self.to_ask[client])
        if not repeat:
            self._next_document(client)
        n_question, n_out = self.to_ask[client].pop()
        limit = self.eng.max_model_len
        n_question = min(n_question, limit // 4)
        prompt = np.concatenate([
            self.document[client][:limit - n_question - n_out],
            self.token_rngs[client].integers(
                1, self.vocab, size=n_question, dtype=np.int32)])
        sent = _Asked(n_out)
        sent.repeat = repeat
        stamps = sent.stamps
        sent.submitted = clock()
        sent.handle = self.eng.submit(
            prompt, max_new_tokens=n_out,
            on_token=lambda _tok: stamps.append(clock()))
        self.live[client] = sent


def _row_logits(eng, chunk, decode, rid, prompt, feed, taken):
    """``(logits [1 + len(feed), V], table row)`` of one row through the
    engine's own step programs: ``taken`` are the blocks the row already
    holds (a matched prefix; its positions are NOT run again), the rest
    come from the pool's own allocator."""
    cfg, pool = eng.config, eng.pool
    C, S, nb = eng.chunk_tokens, cfg.max_batch_size, eng.max_blocks_per_seq
    blocks = list(taken)
    blocks += pool.allocate(
        rid, pool.blocks_for(len(prompt) + len(feed) + 1) - len(blocks))
    table = np.zeros((S, nb), np.int32)
    table[0, :len(blocks)] = blocks
    for start in range(len(taken) * cfg.block_size, len(prompt), C):
        n_tok = min(C, len(prompt) - start)
        ids = np.zeros((1, C), np.int32)
        ids[0, :n_tok] = prompt[start:start + n_tok]
        (last, _), pools = chunk(ids, pool.layers, table[:1].copy(),
                                 np.asarray([start], np.int32),
                                 np.int32(n_tok - 1))
        pool.layers = [tuple(entry) for entry in pools]
    out = [np.asarray(last)[0]]
    lengths = np.zeros((S,), np.int32)
    lengths[0] = len(prompt)
    tok = np.zeros((S, 1), np.int32)
    for t in feed:
        tok[0, 0] = t
        (logits, _), pools = decode(tok, pool.layers, table.copy(), lengths)
        pool.layers = [tuple(entry) for entry in pools]
        out.append(np.asarray(logits)[0])
        lengths[0] += 1
    return np.stack(out), table[0], blocks


def check_reask_programs(eng, model, config, mix, seed):
    """The comparison of the module docstring; returns ``(logits report
    of every row, choices report or None, blocks matched, blocks the
    lengths imply)``."""
    from paddle_tpu.models.generation import (make_chunked_prefill_step,
                                              make_paged_decode_step)

    cfg, pool = eng.config, eng.pool
    kwargs = dict(fused=cfg.fused_kernels, kv_cache_dtype=cfg.kv_cache_dtype)
    chunk = make_chunked_prefill_step(eng.model, **kwargs)
    decode = make_paged_decode_step(eng.model, **kwargs)
    steps = mix.get("check_decode_steps", CHECK_DECODE_STEPS)
    n_new = mix.get("check_reask_tokens", CHECK_REASK_TOKENS)
    room = eng.max_model_len - n_new - max(steps, REASK_DECODE_STEPS) - 1
    n_prompt = min(mix.get("check_prompt_tokens", CHECK_PROMPT_TOKENS), room)
    rng = np.random.default_rng([seed, 3])
    vocab = model.config.vocab_size
    prompt_a = rng.integers(1, vocab, size=n_prompt, dtype=np.int32)
    feed_a = rng.integers(1, vocab, size=steps, dtype=np.int32)
    shared = n_prompt // cfg.block_size
    prompt_b = np.concatenate([
        prompt_a[:shared * cfg.block_size],
        rng.integers(1, vocab, size=n_new, dtype=np.int32)])
    feed_b = rng.integers(1, vocab, size=REASK_DECODE_STEPS, dtype=np.int32)
    # (row C is cut to what the engine admits, as row A is: a rehearsal
    # at tiny sizes needs it, the cell's own lengths never reach it)
    steps_c = min(mix.get("check_decode_row_steps", DECODE_ROW_STEPS),
                  eng.max_model_len // 2)
    prompt_c = rng.integers(
        1, vocab, dtype=np.int32, size=min(
            mix.get("check_decode_row_tokens", DECODE_ROW_TOKENS),
            eng.max_model_len - steps_c - 1))
    feed_c = rng.integers(1, vocab, size=steps_c, dtype=np.int32)
    reports, choices, matched = [], [], []
    try:
        got, table, blocks = _row_logits(eng, chunk, decode, "check-a",
                                         prompt_a, feed_a, ())
        rows = [("a", prompt_a, feed_a, got, table)]
        # row A's full blocks go into the index as a served prompt's do
        pool.register_prefix("check-a", prompt_a, blocks)
        matched = pool.match_prefix(prompt_b)
        pool.acquire("check-b", matched)
        got, table, _ = _row_logits(eng, chunk, decode, "check-b",
                                    prompt_b, feed_b, matched)
        rows.append(("b", prompt_b, feed_b, got, table))
        got, table, _ = _row_logits(eng, chunk, decode, "check-c",
                                    prompt_c, feed_c, ())
        rows.append(("c", prompt_c, feed_c, got, table))
        # (the reference reads the weights only now, after the served
        # programs have run: a control may round them for it alone)
        reference = models.load_reference(config)
        weights = reference.weights_of(model)
        for name, prompt, feed, got, table in rows:
            checked = np.concatenate([prompt, feed])
            # what those steps chose, read where they wrote it: the
            # pools are bound to the engine as the last step left them
            chose = models.witness(config, model=model, engine=eng,
                                   tokens=checked, block_table=table,
                                   prompt_tokens=len(prompt))
            want, report = models.referee(
                reference.logits, weights, config, checked,
                last=1 + len(feed), witness=chose)
            logits = models.compare_logits(got, np.asarray(want)[:len(got)])
            logits["row"], logits["prompt_tokens"] = name, len(prompt)
            reports.append(logits)
            choices.append(report)
    finally:
        pool.free_request("check-c")
        pool.free_request("check-b")
        pool.free_request("check-a")
    return reports, _merge_choices(choices), len(matched), shared


def _merge_choices(reports):
    reports = [r for r in reports if r is not None]
    if not reports:
        return None
    return {"ok": all(r["ok"] for r in reports),
            "decisions": sum(r["decisions"] for r in reports),
            "not_first_choice": sum(r["not_first_choice"] for r in reports),
            "largest_shortfall": max(r["largest_shortfall"]
                                     for r in reports),
            "margin": reports[0]["margin"]}


def routing_quotients(config, counters):
    """The two quotients of the routing counters, under the config keys
    of this kind's configurations."""
    experts = config.get("n_routed_experts")
    routed = config.get("num_hidden_layers", 0) \
        - config.get("first_k_dense_replace", 0)
    out = {}
    if experts and counters.get("expert_assignments"):
        out["expert_load_max_over_mean"] = \
            counters["expert_assignments_max"] \
            / (counters["expert_assignments"] / experts)
    if routed > 0 and counters.get("decode_iterations"):
        out["experts_read_per_layer_decode"] = \
            counters.get("experts_read_decode", 0) \
            / counters["decode_iterations"] / routed
    return out


def run(ctx) -> dict:
    config, mix = ctx.cell.config, ctx.cell.traffic
    model = models.build_model(config, ctx.seed)
    model.eval()
    ctx.say(phase="model", built_s=clock() - ctx.t_start)
    eng = Engine(model, ServingConfig(**config["serving"]))
    pool = eng.pool
    bytes_per_token = pool.block_bytes() / pool.block_size
    ctx.say(phase="engine", max_batch_size=eng.config.max_batch_size,
            num_blocks=eng.num_blocks, chunk_tokens=eng.chunk_tokens,
            fused_kernels=eng.config.fused_kernels,
            prefix_cache=pool.enable_prefix_cache,
            layer_kinds=[c.kind for c in pool.layer_caches],
            pool_leaves=[[list(a.shape) for a in entry]
                         for entry in pool.layers[:2]],
            kv_pool_bytes_per_token=bytes_per_token,
            built_s=clock() - ctx.t_start)
    loop = ReaskLoop(eng, mix, model.config.vocab_size, ctx.seed)

    # ramp: compiles (or loads) both step programs, fills every slot
    while not all(loop.ramped):
        loop.iterate()
    ctx.say(phase="ramp", ramped_s=clock() - ctx.t_start,
            iterations=len(loop.iters), requests_ended=len(loop.done))
    compiles_before = ctx.compiles.count
    counters0 = eng.metrics.as_dict()["counters"]
    documents0 = loop.documents_dealt
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
    counters = {k: counters1[k] - counters0[k] for k in counters1}
    compiles_in_window = ctx.compiles.count - compiles_before
    documents = loop.documents_dealt - documents0

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
    ttft = {repeat: [(s.stamps[0] - s.submitted) * 1e3 for s in measured
                     if s.stamps and s.repeat == repeat]
            for repeat in (False, True)}
    ttft_ms = ttft[False] + ttft[True]
    gaps_ms = [(b - a) * 1e3 for s in sent
               for a, b in zip(s.stamps, s.stamps[1:]) if in_window(b)]
    iter_ms = [(e - s) * 1e3 for s, e in loop.iters if in_window(e)]
    seconds = w1 - w0
    ctx.say(phase="window", seconds=seconds, requests_submitted=len(measured),
            documents_begun=documents,
            ttft_samples=len(ttft_ms), itl_samples=len(gaps_ms),
            engine_iterations=len(iter_ms), tokens=tokens,
            ttft_first_ask_ms={q: percentile(ttft[False], q)
                               for q in (50, 95)} if ttft[False] else None,
            ttft_repeated_ask_ms={q: percentile(ttft[True], q)
                                  for q in (50, 95)} if ttft[True] else None,
            itl_ms={q: percentile(gaps_ms, q) for q in (50, 95, 99)},
            iter_ms=dict({q: percentile(iter_ms, q) for q in (5, 50, 95)},
                         mean=sum(iter_ms) / len(iter_ms)),
            # a stalled host shows here and in no percentile
            slowest_iterations=sorted(
                ([(e - s) * 1e3, e - w0] for s, e in loop.iters
                 if in_window(e)), reverse=True)[:3],
            requests_ended=sum(1 for s in loop.done
                               if s.stamps and in_window(s.stamps[-1])),
            prompt_tokens=counters.get("prompt_tokens"),
            cached_prompt_tokens=counters.get("cached_prompt_tokens"),
            pool_stats={k: pool.stats()[k] for k in (
                "used_blocks", "cached_blocks", "prefix_evictions",
                "cow_copies")},
            **routing_quotients(config, counters),
            compiles_in_window=compiles_in_window,
            drained_s=drained_s, still_running=sum(
                1 for s in loop.live if s is not None),
            leaked_blocks=leaks)

    # correctness, outside the window
    bad = [s.handle.request_id for s in sent if not s.ok()]
    one_program_each = (eng.decode_cache_size() == 1
                        and eng.prefill_cache_size() == 1)
    rows, choices, matched, shared = check_reask_programs(
        eng, model, config, mix, ctx.seed)
    still_one = (eng.decode_cache_size() == 1
                 and eng.prefill_cache_size() == 1)
    ctx.say(phase="check", failed_requests=bad[:20],
            requests_checked=len(sent), requests_ended=len(loop.done),
            one_program_each=one_program_each and still_one, logits=rows,
            matched_blocks=matched,
            **({} if choices is None else {"choices": choices}),
            checked_s=clock() - ctx.t_start)

    compared = {
        "requests_not_whole": [len(bad), 0],
        "leaked_blocks": [int(leaks is not None), 0],
        "programs_a_step": [max(eng.decode_cache_size(),
                                eng.prefill_cache_size()), 1],
        "compiles_in_window": [compiles_in_window, 0],
        "matched_blocks": [matched, shared],
    }
    for row in rows:
        compared[f"logit_gap_row_{row['row']}"] = [
            max(row["max_abs_diff"]), row["tolerance"]]
    if choices is not None:
        compared["choice_shortfall"] = [choices["largest_shortfall"],
                                        choices["margin"]]
    return {
        "window_start": w0,
        "attempted": len(measured),
        "failed": sum(1 for s in measured if not s.ok()),
        "correct": bool(not bad and leaks is None and one_program_each
                        and still_one and compiles_in_window == 0
                        and len(rows) == 3 and all(r["ok"] for r in rows)
                        and matched == shared
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
            "counters": counters,
            "max_batch_size": eng.config.max_batch_size,
            "compiles_in_window": compiles_in_window,
            "kv_pool_bytes_per_token": bytes_per_token,
        },
    }
