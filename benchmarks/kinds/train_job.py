"""A pre-training job: ``paddle.Model.fit`` over fresh seeded batches of
random token ids, as ``chip_smoke.fit_llama`` drives it (the network
returns the loss, AdamW, one jitted train step).

Parameters of a job (``benchmarks/traffic/<job>.json``): ``batch`` and
``sequence`` (tokens a step = batch x sequence), ``warmup_steps`` (the
first step creates the optimizer's slots and the second takes them as
inputs: two programs, then none).  The optimizer and its learning rate
are the configuration's (``"training"``).

The window: ``fit`` runs until the first step that ends ``--seconds``
after it began; a step ends when its loss has been read on the host.
Batch ``i`` is a function of ``(--seed, i)``, so the reference's loss for
the first measured batch is computed before the window, on the weights
the warm-up left.

``correct``: that loss against the first measured step's (a mean over
all tokens: it holds the step's forward pass to the reference, but
averages roundings away); after the window, the model's forward logits
at the last ``LOGIT_POSITIONS`` positions of that batch's first row
against the reference's, position by position, on the weights the window
left (a skipped layer or products a precision lower show here); every
loss finite; no compile request inside the window.  The backward pass
and the optimizer have no reference: see PERF.md, Open questions.

A configuration whose model makes discrete choices names a witness
(``harness/models.py``): the reference then replays, for the loss, what
the model's forward pass chooses on that batch and those weights (the
step returns only its loss) and, for the logits, what it chooses on the
row, and ``correct`` also needs every choice admissible.
"""
from __future__ import annotations

import math

import numpy as np

import paddle_tpu as paddle
from paddle_tpu import nn
from paddle_tpu.hapi.callbacks import Callback

from benchmarks.harness import models
from benchmarks.harness.stats import clock
from benchmarks.harness.tracing import span

LOGIT_POSITIONS = 256         # the longest contexts of one row


def batch_at(seed, index, job, vocab):
    rng = np.random.default_rng([seed, 4, index])
    return rng.integers(0, vocab, size=(job["batch"], job["sequence"]),
                        dtype=np.int32)


def batches(seed, start, job, vocab):
    index = start
    while True:
        yield (batch_at(seed, index, job, vocab),)
        index += 1


class LMWithLoss(nn.Layer):
    """``network(tokens) -> loss``: hapi's step calls
    ``network(*inputs)``; the loss is computed in the model's forward
    (copied from ``chip_smoke.py``)."""

    def __init__(self, lm):
        super().__init__()
        self.lm = lm

    def forward(self, tokens):
        return self.lm(tokens, labels=tokens)[0]


class Clock(Callback):
    """Stamps every step, opens and closes its host span, and stops
    ``fit`` after ``steps`` steps or ``seconds`` seconds."""

    def __init__(self, steps=None, seconds=None, tracer=None):
        super().__init__()
        self.steps, self.seconds, self.tracer = steps, seconds, tracer
        self.start, self.ends, self.losses = None, [], []
        self._span = self._gap = None

    def on_train_begin(self, logs=None):
        self.start = clock()
        self._gap = span("bookkeeping")
        self._gap.__enter__()

    def on_train_batch_begin(self, step, logs=None):
        self._gap.__exit__(None, None, None)
        self._span = span("train_step")
        self._span.__enter__()

    def on_train_batch_end(self, step, logs=None):
        self._span.__exit__(None, None, None)
        now = clock()
        self.ends.append(now)
        self.losses.append(float(logs["loss"]))
        self._gap = span("bookkeeping")
        self._gap.__enter__()
        if (self.steps is not None and len(self.ends) >= self.steps) \
                or (self.seconds is not None
                    and now - self.start >= self.seconds):
            self.model.stop_training = True
        elif self.tracer is not None:
            self.tracer.tick(now)

    def on_train_end(self, logs=None):
        self._gap.__exit__(None, None, None)


def run(ctx) -> dict:
    config, job = ctx.cell.config, ctx.cell.traffic
    training = config["training"]
    lm = models.build_model(config, ctx.seed)
    vocab = lm.config.vocab_size
    net = LMWithLoss(lm)
    model = paddle.Model(net)
    optimizer = getattr(paddle.optimizer, training["optimizer"])(
        training["learning_rate"], parameters=net.parameters())
    model.prepare(optimizer, loss=lambda loss: loss)
    ctx.say(phase="model", built_s=clock() - ctx.t_start,
            params=sum(int(np.prod(p.shape)) for p in net.parameters()))

    warm = Clock(steps=job["warmup_steps"])
    model.fit(batches(ctx.seed, 0, job, vocab), epochs=1, verbose=0,
              callbacks=[warm])
    first = job["warmup_steps"]
    reference = models.load_reference(config)
    batch = batch_at(ctx.seed, first, job, vocab)
    # (the step returns only its loss: what it chose is read from the
    # model's forward pass on the same batch and weights, and before the
    # weights, because a compiled call re-binds the model's arrays)
    chose = models.witness(config, model=lm, engine=None, tokens=batch,
                           block_table=None, prompt_tokens=None)
    want, loss_choices = models.referee(
        reference.causal_lm_loss, reference.weights_of(lm), config, batch,
        witness=chose)
    ctx.say(phase="warm", losses=warm.losses, reference_loss=want,
            **({} if loss_choices is None else {"choices": loss_choices}),
            warmed_s=clock() - ctx.t_start)

    compiles_before = ctx.compiles.count
    timed = Clock(seconds=ctx.seconds, tracer=ctx.tracer)
    ctx.tracer.arm(clock(), ctx.seconds)
    model.fit(batches(ctx.seed, first, job, vocab), epochs=1, verbose=0,
              callbacks=[timed])
    ctx.tracer.finish()
    compiles_in_window = ctx.compiles.count - compiles_before

    w0, w1 = timed.start, timed.ends[-1]
    seconds = w1 - w0
    tokens_per_step = job["batch"] * job["sequence"]
    step_ms = [(b - a) * 1e3
               for a, b in zip([w0] + timed.ends, timed.ends)]
    relative = abs(timed.losses[0] - want) / abs(want)
    non_finite = sum(1 for x in timed.losses if not math.isfinite(x))
    ctx.say(phase="window", seconds=seconds, steps=len(timed.ends),
            first_loss=timed.losses[0], last_loss=timed.losses[-1],
            reference_loss=want, relative_difference=relative,
            tolerance=models.LOSS_TOL,
            compiles_in_window=compiles_in_window)

    # correctness, outside the window, on the weights it left
    row = batch[0]
    last = min(LOGIT_POSITIONS, len(row))
    got = models.forward_logits(lm, row, last)
    chose = models.witness(config, model=lm, engine=None, tokens=row,
                           block_table=None, prompt_tokens=None)
    # (the compiled forward re-binds the model's arrays: read them after)
    want_logits, choices = models.referee(
        reference.logits, reference.weights_of(lm), config, row, last=last,
        witness=chose)
    logits = models.compare_logits(got, np.asarray(want_logits))
    logits["max_abs_diff"] = max(logits["max_abs_diff"])
    logits["argmax_agree"] = sum(logits["argmax_agree"]) / last
    ctx.say(phase="check", logits=logits,
            **({} if choices is None else {"choices": choices}),
            checked_s=clock() - ctx.t_start)

    compared = {
        "losses_not_finite": [non_finite, 0],
        "compiles_in_window": [compiles_in_window, 0],
        "loss_relative_difference": [relative, models.LOSS_TOL],
        "logit_gap": [logits["max_abs_diff"], logits["tolerance"]],
    }
    for name, report in (("loss_choice_shortfall", loss_choices),
                         ("choice_shortfall", choices)):
        if report is not None:
            compared[name] = [report["largest_shortfall"], report["margin"]]
    return {
        "window_start": w0,
        "attempted": len(timed.ends),
        "failed": non_finite,
        "correct": bool(non_finite == 0 and compiles_in_window == 0
                        and relative <= models.LOSS_TOL and logits["ok"]
                        and models.chose_admissibly(loss_choices)
                        and models.chose_admissibly(choices)),
        "compared": compared,
        "end_to_end": {
            "train_tok_s": len(timed.ends) * tokens_per_step / seconds,
        },
        "window": {
            "seconds": seconds,
            "steps": len(timed.ends),
            "tokens": len(timed.ends) * tokens_per_step,
            "step_ms": step_ms,
            "sequence": job["sequence"],
            "compiles_in_window": compiles_in_window,
        },
    }
