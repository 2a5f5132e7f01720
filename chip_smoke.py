"""Quickest proof that paddle_tpu still starts on the chip.

Drives the two main paths once, through the entry points a user calls, at
the full width of Llama-3-8B (vocabulary 128256, hidden 4096, MLP 14336,
32 heads, 8 KV heads, head 128, bf16) with depth cut to what one 16 GB
chip holds and weights drawn from a seed:

- serving: ``serving.Engine`` with its default kernel selection answers a
  handful of requests admitted at different steps; the compiled decode and
  chunked-prefill programs must hold the three Pallas serving kernels by
  name, every request must retire ``stop``/``length``, nothing may compile
  after warm-up, and first-token and decode logits must agree with the
  unfused XLA engine within a bf16 tolerance;
- training: ``hapi.Model.fit`` takes a few AdamW steps with the fused
  lm-head loss at sequence 2048; the loss must be finite and fall, and the
  compiled step must hold flash attention, rms_norm and fused_rope.

``--chips 4`` runs, instead, only what exists across chips: the same
serving configuration under tensor parallel 4 and a few train steps on a
``{"fsdp": 2, "tp": 2}`` mesh, each compared with its one-device
counterpart in this process, with parameters and KV pool checked to be
spread a quarter to each device.

Every earlier line of output is one JSON object worth reading; the last is
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": N}}``.
A phase that fails ends the run non-zero with ``"ok": false``.  Without an
accelerator the script fails before it prints any result: it never carries
on on the CPU.  One process, no children: the chip belongs to it.
"""
from __future__ import annotations

import argparse
import collections
import dataclasses
import gc
import json
import re
import sys
import time
import traceback

import jax
import jaxlib
import numpy as np

import paddle_tpu as paddle
from paddle_tpu import nn
from paddle_tpu.core.compile_cache import enable_compile_cache
from paddle_tpu.hapi.callbacks import Callback
from paddle_tpu.models import LlamaConfig, LlamaForCausalLM
from paddle_tpu.serving import Engine, ServingConfig

SEED = 0
# fused-vs-unfused and sharded-vs-one-device logits: largest difference
# allowed, as a share of the largest reference logit — 8 bf16 epsilons
# (2^-8 each).  Both sides round to bf16 at every layer boundary, in a
# different order; greedy tokens flip on less, so tokens are not the check.
LOGIT_TOL = 2.0 ** -5
# sharded-vs-one-device loss, relative: reductions reorder across devices
LOSS_TOL = 1e-2
SERVING_KERNELS = ("fused_paged_decode", "fused_chunked_prefill")
TRAINING_KERNELS = ("flash_attention_fwd", "flash_attention_bwd_dq",
                    "flash_attention_bwd_dkv", "rms_norm", "fused_rope")


@dataclasses.dataclass
class Sizes:
    """What the phases run at; the defaults are the chip's.  Depths were
    chosen from ``compiled.memory_analysis()`` of the whole step programs
    compiled for a described v5e before the first chip run (PERF.md)."""

    serve_layers: int = 16
    train_layers: int = 1
    max_model_len: int = 2048
    num_blocks: int = 512
    chunk_tokens: int = 256          # ServingConfig's default
    decode_checks: int = 4           # decode steps compared by logits
    train_batch: int = 1
    train_seq: int = 2048
    train_steps: int = 4
    lm_loss_chunk: int = 512
    learning_rate: float = 1e-4


def say(**fields):
    print(json.dumps(fields), flush=True)


class SmokeFailure(AssertionError):
    """A phase's check did not hold."""


def check(cond, what):
    if not cond:
        raise SmokeFailure(what)


# ---------------------------------------------------------------- device

def require_accelerator(chips: int) -> dict:
    """The device as JAX reports it; exits when there is no accelerator
    or fewer chips than asked for."""
    devices = jax.devices()
    if devices[0].platform == "cpu":
        raise SystemExit(
            "chip_smoke: JAX found no accelerator (platform 'cpu'); this "
            "script proves the chip path and does not fall back")
    if len(devices) < chips:
        raise SystemExit(
            f"chip_smoke: --chips {chips} needs {chips} devices, JAX "
            f"reports {len(devices)}")
    return {"platform": devices[0].platform,
            "kind": devices[0].device_kind, "count": len(devices)}


# ------------------------------------------------- compiled-program reads

_cache_events = collections.Counter()


def _on_jax_event(name, **_):
    if name.startswith("/jax/compilation_cache/cache_"):
        _cache_events[name.rsplit("/", 1)[1]] += 1


def cache_delta(before):
    return {k: _cache_events[k] - before.get(k, 0)
            for k in ("cache_hits", "cache_misses")}


# the scope a named pallas_call opens, as the last one before the call
# in the instruction's op_name; autodiff wraps it: transpose(jvp(name))
_KERNEL_RE = re.compile(
    r'custom_call_target="tpu_custom_call".*?'
    r'op_name="[^"]*?([A-Za-z0-9_]+)\)*/pallas_call')


def kernels_in(hlo_text: str) -> dict:
    """``{kernel name: count}`` of the Pallas kernels a compiled TPU
    program holds (``tpu_custom_call``s, named by their ``pallas_call``)."""
    found = collections.Counter()
    for line in hlo_text.splitlines():
        if "tpu_custom_call" in line:
            m = _KERNEL_RE.search(line)
            found[m.group(1) if m else "unnamed"] += 1
    return dict(found)


def describe_program(phase, program, compiled, *, seconds=None, cache=None):
    """Print what one compiled program holds; returns its kernels."""
    kernels = kernels_in(compiled.as_text())
    mem = compiled.memory_analysis()
    say(phase=phase, program=program, compile_seconds=seconds,
        persistent_cache=cache, kernels=kernels,
        memory_analysis_bytes=None if mem is None else {
            "arguments": mem.argument_size_in_bytes,
            "outputs": mem.output_size_in_bytes,
            "temporaries": mem.temp_size_in_bytes,
            "aliased": mem.alias_size_in_bytes,
            "code": mem.generated_code_size_in_bytes})
    return kernels


def compile_step(phase, program, step, args):
    """Lower and compile a serving step ahead of its first call (the
    call then reuses the executable), timing it and reading the cache."""
    before = dict(_cache_events)
    t0 = time.perf_counter()
    compiled = step.lower(*args).compile()
    seconds = round(time.perf_counter() - t0, 2)
    return describe_program(phase, program, compiled, seconds=seconds,
                            cache=cache_delta(before))


def device_bytes(arrays) -> dict:
    """Bytes each device holds of ``arrays`` (by their shards)."""
    held = collections.Counter()
    for a in arrays:
        for s in a.addressable_shards:
            held[str(s.device)] += s.data.nbytes
    return dict(held)


def check_spread(what, arrays, n_devices):
    held = device_bytes(arrays)
    total = sum(a.nbytes for a in arrays)
    shares = {d: round(b / total, 4) for d, b in sorted(held.items())}
    say(phase="four_chips", spread=what, total_bytes=total, shares=shares)
    check(len(held) == n_devices,
          f"{what}: on {len(held)} devices, expected {n_devices}")
    # replicated norm weights and scalars put each share a hair over 1/n
    check(max(shares.values()) <= 1.2 / n_devices,
          f"{what}: not spread evenly over {n_devices} devices: {shares}")


def peak_hbm():
    stats = {str(d): d.memory_stats() for d in jax.devices()}
    return {d: {"peak_bytes_in_use": s.get("peak_bytes_in_use"),
                "bytes_in_use": s.get("bytes_in_use"),
                "bytes_limit": s.get("bytes_limit")}
            for d, s in stats.items() if s}


# --------------------------------------------------------------- serving

def llama_config(layers, tiny=False, **overrides):
    if tiny:
        # the CPU rehearsal's: Llama-3-8B's 4:1 GQA, KV heads that four
        # devices divide
        return LlamaConfig.tiny(num_hidden_layers=layers,
                                num_attention_heads=16,
                                num_key_value_heads=4, **overrides)
    return LlamaConfig.llama3_8b(num_hidden_layers=layers, **overrides)


def build_engine(cfg, sizes, **serving_overrides):
    """A seeded model behind an Engine.  Kernel selection, batch size and
    block size are ServingConfig's defaults."""
    paddle.seed(SEED)
    model = LlamaForCausalLM(cfg)
    model.eval()
    return Engine(model, ServingConfig(
        max_model_len=sizes.max_model_len, num_blocks=sizes.num_blocks,
        chunk_tokens=sizes.chunk_tokens, **serving_overrides))


def step_args(eng):
    """Arguments shaped as the engine's own calls of its two steps."""
    S, nb = eng.config.max_batch_size, eng.max_blocks_per_seq
    decode = (np.zeros((S, 1), np.int32), eng._target_pools(),
              np.zeros((S, nb), np.int32), np.zeros((S,), np.int32))
    prefill = (np.zeros((1, eng.chunk_tokens), np.int32),
               eng._target_pools(), np.zeros((1, nb), np.int32),
               np.zeros((1,), np.int32), np.int32(0))
    return decode, prefill


def expect_kernels(phase, found, names):
    missing = [k for k in names if not found.get(k)]
    check(not missing, f"{phase}: kernels missing from the compiled "
                       f"programs: {missing} (found {dict(found)})")


def compile_engine(phase, eng, expect=()):
    """Compile both step programs, printing what each holds; fail unless
    every kernel named in ``expect`` is in one of them."""
    decode_args, prefill_args = step_args(eng)
    found = collections.Counter()
    found.update(compile_step(phase, "decode_step",
                              eng._decode_step._fn, decode_args))
    found.update(compile_step(phase, "chunked_prefill_step",
                              eng._prefill_step._fn, prefill_args))
    expect_kernels(phase, found, expect)


def make_requests(rng, vocab, chunk):
    """(submit at engine step, prompt, new tokens): prompts from under one
    chunk to several, the fourth sharing a many-block prefix with the
    second, admitted while earlier ones are mid-prefill or decoding."""
    def prompt(n):
        return rng.randint(1, vocab, size=n).astype(np.int32)

    long_prompt = prompt(int(2.6 * chunk))
    shared = np.concatenate([long_prompt[:int(1.5 * chunk)],
                             prompt(int(0.7 * chunk))])
    return [
        (0, prompt(int(0.35 * chunk)), 32),
        (0, long_prompt, 48),
        (3, prompt(int(1.1 * chunk)), 64),
        (6, shared, 40),
        (10, prompt(int(3.3 * chunk)), 32),
        (14, prompt(max(2, int(0.1 * chunk))), 56),
    ]


def serve_requests(phase, eng, requests):
    """Drive ``eng.step()`` submitting each request at its step; every
    request must retire ``stop`` or ``length`` with all its tokens."""
    todo = sorted(requests, key=lambda r: r[0])
    handles, step = [], 0
    while todo or eng.has_work():
        while todo and todo[0][0] <= step:
            _, prompt, new = todo.pop(0)
            handles.append(eng.submit(prompt, max_new_tokens=new))
        eng.step()
        step += 1
        check(step < 5000, f"{phase}: engine did not drain in 5000 steps")
    for r in handles:
        say(phase=phase, request=r.request_id, prompt_tokens=r.prompt_len,
            cached_tokens=r.cached_tokens, generated=r.num_generated,
            finish_reason=r.finish_reason, error=r.error,
            first_tokens=[int(t) for t in r.generated[:4]])
    bad = [r.request_id for r in handles
           if r.finish_reason not in ("stop", "length")
           or r.num_generated != r.max_new_tokens]
    check(not bad, f"{phase}: requests did not retire stop/length with "
                   f"all their tokens: {bad}")
    check(handles[3].cached_tokens >= 2 * eng.config.block_size,
          f"{phase}: the shared prefix was not served from the cache")
    compiles = {"decode": eng._decode_step.compiles,
                "prefill": eng._prefill_step.compiles}
    say(phase=phase, engine_steps=step, compiles=compiles,
        retraces={"decode": eng._decode_step.retraces,
                  "prefill": eng._prefill_step.retraces})
    check(compiles == {"decode": 1, "prefill": 1},
          f"{phase}: compiled after warm-up: {compiles}")
    eng.pool.check_leaks()
    return handles


def step_logits(eng, prompt, feed=None, n_decode=0):
    """Logits of the first token and of ``n_decode`` decode steps for one
    sequence, through ``eng``'s compiled steps at the engine's own shapes
    (so nothing compiles) on blocks 1.. of its idle pool (a step consumes
    the pool it is handed: the engine gets the last back).  Decode is fed
    ``feed`` where given, else its own greedy tokens; returns (logits
    [1 + n_decode, V], the tokens fed)."""
    cfg = eng.config
    C, S, nb = eng.chunk_tokens, cfg.max_batch_size, eng.max_blocks_per_seq
    pools = eng._target_pools()
    table = np.zeros((S, nb), np.int32)
    n_blocks = -(-(len(prompt) + n_decode + 1) // cfg.block_size)
    table[0, :n_blocks] = np.arange(1, n_blocks + 1)
    for start in range(0, len(prompt), C):
        n_tok = min(C, len(prompt) - start)
        ids = np.zeros((1, C), np.int32)
        ids[0, :n_tok] = prompt[start:start + n_tok]
        last, pools = eng._prefill_step(
            ids, pools, table[:1], np.asarray([start], np.int32),
            np.int32(n_tok - 1))
    out = [np.asarray(last)[0]]
    lengths = np.zeros((S,), np.int32)
    lengths[0] = len(prompt)
    tok = np.zeros((S, 1), np.int32)
    fed = []
    for i in range(n_decode):
        tok[0, 0] = feed[i] if feed is not None else int(np.argmax(out[-1]))
        fed.append(int(tok[0, 0]))
        logits, pools = eng._decode_step(tok, pools, table, lengths)
        out.append(np.asarray(logits)[0])
        lengths[0] += 1
    eng._rebind_target(pools)
    return np.stack(out), fed


def check_logits(phase, eng, prompt, want, fed):
    """``eng`` against a reference's logits ``want`` on one prompt, fed
    the tokens ``fed`` that the reference decoded."""
    got, _ = step_logits(eng, prompt, feed=fed, n_decode=len(fed))
    check(np.isfinite(got).all() and np.isfinite(want).all(),
          f"{phase}: non-finite logits")
    scale = float(np.abs(want).max())
    diffs = np.abs(got - want).max(axis=1)
    say(phase=phase, logits_shape=list(got.shape),
        max_abs_reference_logit=scale,
        max_abs_diff_first_token=float(diffs[0]),
        max_abs_diff_decode_steps=[float(d) for d in diffs[1:]],
        tolerance=LOGIT_TOL * scale,
        argmax_agree=[bool(a == b) for a, b in
                      zip(got.argmax(1), want.argmax(1))])
    check(float(diffs.max()) <= LOGIT_TOL * scale,
          f"{phase}: logits differ by {float(diffs.max()):.4g}, over the "
          f"tolerance {LOGIT_TOL * scale:.4g}")


def serving_phase(sizes, tiny=False, check_kernels=True):
    phase = "serving"
    cfg = llama_config(sizes.serve_layers, tiny,
                       max_position_embeddings=sizes.max_model_len)
    say(phase=phase, model=dataclasses.asdict(cfg), sizes={
        "layers": cfg.num_hidden_layers,
        "max_model_len": sizes.max_model_len,
        "num_blocks": sizes.num_blocks, "chunk_tokens": sizes.chunk_tokens})
    eng = build_engine(cfg, sizes)
    say(phase=phase, max_batch_size=eng.config.max_batch_size,
        block_size=eng.config.block_size,
        fused_kernels=eng.config.fused_kernels,
        params=sum(int(np.prod(p.shape)) for p in eng.model.parameters()))
    compile_engine(phase, eng, SERVING_KERNELS if check_kernels else ())
    rng = np.random.RandomState(SEED)
    serve_requests(phase, eng, make_requests(
        rng, cfg.vocab_size, eng.chunk_tokens))
    # the unfused XLA path over the SAME weights, on the same device
    ref = Engine(eng.model, ServingConfig(
        max_model_len=sizes.max_model_len, num_blocks=sizes.num_blocks,
        chunk_tokens=sizes.chunk_tokens, fused_kernels=False))
    compile_engine(phase + "_unfused_reference", ref)
    prompt = rng.randint(1, cfg.vocab_size,
                         size=int(1.4 * eng.chunk_tokens)).astype(np.int32)
    want, fed = step_logits(ref, prompt, n_decode=sizes.decode_checks)
    check_logits(phase, eng, prompt, want, fed)
    check(eng._decode_step.compiles == 1 and eng._prefill_step.compiles == 1,
          f"{phase}: the logit check compiled a new program")
    say(phase=phase, peak_hbm=peak_hbm())


# -------------------------------------------------------------- training

class LMWithLoss(nn.Layer):
    """``network(tokens) -> loss``: hapi's step calls ``network(*inputs)``,
    and the fused lm-head loss is computed inside the model's forward."""

    def __init__(self, lm):
        super().__init__()
        self.lm = lm

    def forward(self, tokens):
        return self.lm(tokens, labels=tokens)[0]


class _Losses(Callback):
    def __init__(self):
        super().__init__()
        self.losses, self.cache = [], []
        self._before = dict(_cache_events)

    def on_train_batch_end(self, step, logs=None):
        self.losses.append(float(logs["loss"]))
        self.cache.append(cache_delta(self._before))
        self._before = dict(_cache_events)


def fit_llama(phase, cfg, sizes, mesh=None):
    """A few AdamW steps of the Llama train step through ``Model.fit`` on
    one seeded batch; returns (losses, hapi model)."""
    paddle.seed(SEED)
    net = LMWithLoss(LlamaForCausalLM(cfg))
    model = paddle.Model(net)
    opt = paddle.optimizer.AdamW(sizes.learning_rate,
                                 parameters=net.parameters())
    model.prepare(opt, loss=lambda loss: loss, mesh=mesh)
    tokens = np.random.RandomState(SEED).randint(
        0, cfg.vocab_size,
        (sizes.train_batch, sizes.train_seq)).astype(np.int32)
    rec = _Losses()
    model.fit([(tokens,)] * sizes.train_steps, epochs=1, verbose=0,
              callbacks=[rec])
    step = model._train_step_fn
    say(phase=phase, losses=rec.losses, compiles=step.compiles,
        compile_seconds=round(step.compile_seconds, 2),
        persistent_cache_by_step=rec.cache)
    check(all(np.isfinite(rec.losses)), f"{phase}: non-finite loss")
    check(rec.losses[-1] < rec.losses[0],
          f"{phase}: loss did not fall: {rec.losses}")
    # the first step creates the optimizer's slots, the second takes
    # them as inputs: two programs, then none
    check(step.compiles <= 2, f"{phase}: compiled after warm-up "
                              f"({step.compiles} programs)")
    return rec.losses, model


def train_kernels(phase, model, expect=()):
    """Print what the compiled train-step programs hold; fail unless
    every kernel named in ``expect`` is in one of them."""
    found = collections.Counter()
    for i, compiled in enumerate(model._train_step_fn._fn
                                 .compiled_programs()):
        found.update(describe_program(phase, f"train_step[{i}]", compiled))
    expect_kernels(phase, found, expect)


def train_config(sizes, tiny=False):
    return llama_config(
        sizes.train_layers, tiny, fused_lm_loss=True,
        lm_loss_chunk=sizes.lm_loss_chunk,
        max_position_embeddings=sizes.train_seq)


def training_phase(sizes, tiny=False, check_kernels=True):
    phase = "training"
    cfg = train_config(sizes, tiny)
    say(phase=phase, model=dataclasses.asdict(cfg), sizes={
        "layers": cfg.num_hidden_layers, "batch": sizes.train_batch,
        "seq": sizes.train_seq, "steps": sizes.train_steps,
        "optimizer": "AdamW", "learning_rate": sizes.learning_rate})
    _, model = fit_llama(phase, cfg, sizes)
    train_kernels(phase, model, TRAINING_KERNELS if check_kernels else ())
    say(phase=phase, peak_hbm=peak_hbm())


# ------------------------------------------------------------ four chips

def four_chip_phases(sizes, tiny=False, n_devices=4):
    """Only what exists across chips, each against one device."""
    phase = "four_chips_serving"
    cfg = llama_config(sizes.serve_layers, tiny,
                       max_position_embeddings=sizes.max_model_len)
    say(phase=phase, model=dataclasses.asdict(cfg),
        mesh={"tp": n_devices})
    # the one-device engine comes and goes first: the sharded model is
    # built whole on device 0 before the executor spreads it, and the
    # executor registers itself process-wide when built
    one = build_engine(cfg, sizes)
    compile_engine(phase + "_one_device", one)
    rng = np.random.RandomState(SEED)
    requests = make_requests(rng, cfg.vocab_size, one.chunk_tokens)
    prompt = rng.randint(1, cfg.vocab_size,
                         size=int(1.4 * one.chunk_tokens)).astype(np.int32)
    want, fed = step_logits(one, prompt, n_decode=sizes.decode_checks)
    del one
    gc.collect()
    tp = build_engine(cfg, sizes, mesh={"tp": n_devices})
    # under a mesh the model drops to the unfused program (ROADMAP
    # Speed 5): the lines this prints say which kernels, if any, the
    # sharded programs really hold
    compile_engine(phase, tp)
    serve_requests(phase, tp, requests)
    check_logits(phase, tp, prompt, want, fed)
    check_spread("serving parameters",
                 [p._value for p in tp.model.parameters()], n_devices)
    check_spread("kv pool", [a for layer in tp.pool.layers
                             for a in layer], n_devices)
    say(phase=phase, peak_hbm=peak_hbm())
    tp.mesh_executor.close()
    del tp
    gc.collect()

    phase = "four_chips_training"
    mesh = {"fsdp": 2, "tp": n_devices // 2}
    cfg = train_config(sizes, tiny)
    say(phase=phase, model=dataclasses.asdict(cfg), mesh=mesh)
    one_losses, one_model = fit_llama(phase + "_one_device", cfg, sizes)
    train_kernels(phase + "_one_device", one_model)
    del one_model
    gc.collect()
    losses, model = fit_llama(phase, cfg, sizes, mesh=mesh)
    train_kernels(phase, model)
    rel = [abs(a - b) / abs(b) for a, b in zip(losses, one_losses)]
    say(phase=phase, sharded_losses=losses, one_device_losses=one_losses,
        relative_differences=rel, tolerance=LOSS_TOL)
    check(max(rel) <= LOSS_TOL,
          f"{phase}: sharded loss departs from one device: {rel}")
    opt = model._optimizer
    check_spread("training parameters",
                 [p._value for p in model.network.parameters()], n_devices)
    check_spread("optimizer moments",
                 [a for name in ("moment1", "moment2")
                  for a in opt._accumulators[name].values()], n_devices)
    say(phase=phase, peak_hbm=peak_hbm())
    model._mesh_executor.close()


# ------------------------------------------------------------------ main

def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the four-chip phases")
    args = ap.parse_args(argv)
    device = require_accelerator(args.chips)
    cache_dir = enable_compile_cache()
    jax.monitoring.register_event_listener(_on_jax_event)
    say(jax=jax.__version__, jaxlib=jaxlib.__version__,
        backend=jax.extend.backend.get_backend().platform_version,
        device=device, compile_cache_dir=cache_dir, seed=SEED)
    sizes = Sizes()
    t0 = time.perf_counter()
    try:
        if args.chips == 4:
            four_chip_phases(sizes)
        else:
            serving_phase(sizes)
            gc.collect()
            training_phase(sizes)
    except Exception as e:  # noqa: BLE001 — report, then fail the run
        traceback.print_exc()
        say(failed=f"{type(e).__name__}: {e}"[:2000],
            seconds=round(time.perf_counter() - t0, 1))
        print(json.dumps({"ok": False, "device": device}), flush=True)
        return 1
    say(seconds=round(time.perf_counter() - t0, 1),
        persistent_cache_total=dict(_cache_events))
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
