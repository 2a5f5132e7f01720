"""Builds the system under test from a configuration file and compares
its logits with the configuration's plain reference.

A model whose forward pass makes discrete choices (which experts a
token goes to) is not continuous in its roundings: where two scores lie
closer than the bf16 error of the residual stream, the timed path and a
float32 reference choose differently and the row moves by a whole
choice's worth.  Such a configuration names a ``"witness"``: a function
of the program that says what the timed path chose.  The harness
fetches it after that path has run and hands it, opaque, to the
reference, which replays the choices and holds each to its own
arithmetic (``witness`` and ``referee`` below; README, "A configuration
whose model chooses").

Adapted from ``chip_smoke.py`` (``build_engine``, ``step_logits``,
``check_logits``), which ran on the chip in PR 22.  The program is
reached only through what PERF.md lists under "symbols the benchmark
leans on".
"""
from __future__ import annotations

import dataclasses
import importlib

import numpy as np

from . import cells

# Largest difference allowed between the served logits and the float32
# reference, as a share of the largest reference logit: 8 bf16 epsilons
# (2^-8 each).  The served model rounds to bf16 at every layer boundary
# and keeps its rope tables in bf16; the reference rounds nowhere.  PR 22
# measured 0.96 % between two bf16 paths at 16 layers; PR 25 0.8 % against
# the reference at 16 layers served and 0.33 % at 3 layers trained.  A
# model whose weights or products are float8 (16 times coarser) fails it,
# also at tiny widths on the CPU (benchmarks/tests); int8 weights with
# per-channel scales round about as finely as bf16 and pass.  Greedy
# tokens flip on far less, so tokens are not the check.
LOGIT_TOL = 2.0 ** -5
# Train-step loss against the reference's forward loss, relative.  A mean
# over 8,188 tokens averages the roundings away: 20 runs on the chip read
# 9e-8 to 6.3e-6 (PR 25), and this is five times the largest.  It holds
# the train step's own forward pass to the reference, but a mean hides
# what a position shows (8-bit products would move it by about 1e-5), so
# ``forward_logits`` is compared position by position as well.
LOSS_TOL = 3e-5


def resolve(dotted: str):
    """``"package.module:Name"`` -> the object."""
    module, name = dotted.split(":")
    return getattr(importlib.import_module(module), name)


def model_config(config: dict):
    """The program's config object for a configuration file: every field
    of the config class that the file gives at its top level (the
    published ``config.json`` names), then ``model_config_kwargs``."""
    cls = resolve(config["model_config"])
    fields = {f.name for f in dataclasses.fields(cls)}
    kwargs = {k: v for k, v in config.items() if k in fields}
    kwargs.update(config.get("model_config_kwargs", {}))
    return cls(**kwargs)


def build_model(config: dict, seed: int):
    """The configuration's model with the program's own seeded
    initialisation (made on the device, in the served type)."""
    import paddle_tpu as paddle

    paddle.seed(seed)
    return resolve(config["model"])(model_config(config))


def load_reference(config: dict):
    return cells.config_module(config, "reference")


def witness(config: dict, **where):
    """What the path that has just run chose, from the configuration's
    ``"witness": "package.module:function"``, called as ``function(
    model=, engine=, tokens=, block_table=, prompt_tokens=)``: a pytree
    of arrays that only the configuration's reference reads.  ``None``
    for a configuration that names none."""
    if "witness" not in config:
        return None
    return resolve(config["witness"])(**where)


def referee(function, *args, witness=None, **kwargs):
    """``(want, report)`` of one of the reference's functions.  Without
    a witness it is called as ever and ``report`` is ``None``.  With one
    the reference gets it as ``witness=``, takes the witness's choice at
    every decision of its own float32 trajectory and returns the
    ``report`` beside what it computed: ``ok`` (every choice within the
    reference's stated ``margin`` of its own k-th best; one conjunct of
    ``correct``), ``decisions``, ``not_first_choice``,
    ``largest_shortfall``, ``margin``."""
    if witness is None:
        return function(*args, **kwargs), None
    return function(*args, witness=witness, **kwargs)


def chose_admissibly(report) -> bool:
    return report is None or bool(report["ok"])


def engine_logits(eng, prompt, feed):
    """``(logits, table)``: logits ``[1 + len(feed), V]`` of the first
    token and of one decode step per fed token for one sequence, through
    the step programs the engine itself compiled, at the engine's own
    shapes (so nothing compiles), on blocks 1.. of its pool, and the row
    of the block table those steps ran under.  The steps are pure; the new
    pools are bound back so that no third copy of the pool is held (and a
    witness can read what the steps wrote): call this only when the
    engine is idle and will serve nothing more."""
    from paddle_tpu.models.generation import (make_chunked_prefill_step,
                                              make_paged_decode_step)

    cfg = eng.config
    kwargs = dict(fused=cfg.fused_kernels, kv_cache_dtype=cfg.kv_cache_dtype)
    prefill = make_chunked_prefill_step(eng.model, **kwargs)
    decode = make_paged_decode_step(eng.model, **kwargs)
    C, S, nb = eng.chunk_tokens, cfg.max_batch_size, eng.max_blocks_per_seq
    table = np.zeros((S, nb), np.int32)
    n_blocks = -(-(len(prompt) + len(feed) + 1) // cfg.block_size)
    table[0, :n_blocks] = np.arange(1, n_blocks + 1)
    pools = eng.pool.layers
    for start in range(0, len(prompt), C):
        n_tok = min(C, len(prompt) - start)
        ids = np.zeros((1, C), np.int32)
        ids[0, :n_tok] = prompt[start:start + n_tok]
        last, pools = prefill(ids, pools, table[:1],
                              np.asarray([start], np.int32),
                              np.int32(n_tok - 1))
        eng.pool.layers = pools = [tuple(entry) for entry in pools]
    out = [np.asarray(last)[0]]
    lengths = np.zeros((S,), np.int32)
    lengths[0] = len(prompt)
    tok = np.zeros((S, 1), np.int32)
    for t in feed:
        tok[0, 0] = t
        logits, pools = decode(tok, pools, table, lengths)
        eng.pool.layers = pools = [tuple(entry) for entry in pools]
        out.append(np.asarray(logits)[0])
        lengths[0] += 1
    return np.stack(out), table[0]


def forward_logits(model, tokens, last):
    """float32 logits ``[last, V]`` of the final ``last`` positions of
    the 1-D sequence ``tokens`` through the model's own forward pass (the
    layers and kernels the train step differentiates), compiled as one
    program by the program's ``jit.to_static``, on the weights the model
    holds now."""
    import paddle_tpu as paddle

    forward = paddle.jit.to_static(lambda ids: model(ids))
    with paddle.no_grad():
        out = forward(paddle.to_tensor(np.asarray(tokens, np.int32)[None]))
    return np.asarray(out._value[0, -last:]).astype(np.float32)


def compare_logits(got, want, tol=LOGIT_TOL) -> dict:
    """What the comparison found; ``ok`` is the verdict."""
    finite = bool(np.isfinite(got).all() and np.isfinite(want).all())
    scale = float(np.abs(want).max())
    diffs = np.abs(got - want).max(axis=1)
    return {"ok": finite and float(diffs.max()) <= tol * scale,
            "finite": finite, "max_abs_reference_logit": scale,
            "max_abs_diff": [float(d) for d in diffs],
            "tolerance": tol * scale,
            "argmax_agree": [bool(a == b) for a, b in
                             zip(got.argmax(1), want.argmax(1))]}
