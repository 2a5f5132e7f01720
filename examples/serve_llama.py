"""Serving example: continuous-batching inference over the block-pool
KV cache (paddle_tpu/serving/).

Eight requests with different prompt lengths arrive STAGGERED — new ones
are submitted while earlier ones are mid-decode — and the engine admits
and retires them at every decode iteration over one fixed-shape compiled
step.  Compare the engine's total decode iterations with what serving
the requests one at a time would cost.

With ``--prefix-cache`` the demo switches to a shared-system-prompt
workload: every request carries the same long prefix, the first
admission seeds the pool's content-addressed block index, and every
later admission reuses those blocks — prefilling only its unique tail
in fixed-shape chunks (ONE compiled prefill program for all lengths).

With ``--overload-chaos`` (the CI overload stage) the demo replays a
seeded traffic burst with per-request deadlines under an injected
sustained slowdown — hopeless requests are SHED at admission instead
of timing out after burning prefill — then injects a hung decode step
the watchdog detects and retries, and asserts the engine recovers to
``SERVING`` with zero retraces.

With ``--fused`` (the CI fused-kernels stage) the demo runs the same
staggered workload through TWO engines — fused serving kernels forced
on (``ServingConfig(fused_kernels=True)``: fused paged-attention decode
+ RMSNorm→matmul epilogues, the XLA fallback off-TPU) and forced off —
and asserts token-for-token identical outputs, agreement with plain
``generate()``, and zero retraces on the fused steps.

With ``--router`` (the CI router-chaos stage) the demo fronts TWO named
engine replicas with a ``serving.Router``: a shared-prefix burst shows
prefix-affinity placement consolidating a prompt family on one replica,
then a replica-scoped ``FaultPlan`` kills one replica mid-burst — the
router quarantines it, drains its stranded requests, and resubmits them
to the survivor with ZERO lost requests and token parity against a
single-engine run.

With ``--speculative`` (the CI spec-decode stage) a small random draft
model proposes K tokens per target step and the target verifies all K+1
positions in one chunked-shaped program: greedy outputs stay token-for-
token identical to ``generate()`` AND to the non-speculative engine
across accept/reject boundaries, a weight-identical draft hits the 1.0
accept-rate ceiling, rejected drafts roll their KV blocks back leak-
free, and both new steps compile exactly once.

With ``--quantized`` (the CI quantized-serving stage) the demo serves
the same staggered workload from an int8 paged KV cache (per-block-row
absmax scales, dequant at the attention kernels' block boundary) and a
weight-only int8 engine, asserting greedy token parity with the fp32
engine, zero retraces, zero pool leaks — then re-sizes both engines
from one FIXED ``kv_pool_bytes`` HBM budget to show the quantized pool
holding >= 1.5x the resident KV blocks.

With ``--stream`` the demo drains one SSE response from the
``Endpoint`` front door — ``data: <json>`` frames in token order,
terminated by ``data: [DONE]`` — and asserts the streamed tokens match
the request's final generated list, greedy and sampled.

Run:  python examples/serve_llama.py
          [--prefix-cache | --overload-chaos | --fused | --router |
           --speculative | --quantized | --stream]
"""
import argparse

import numpy as np

import paddle_tpu as paddle
from paddle_tpu.core.compile_cache import enable_compile_cache
from paddle_tpu.models import LlamaConfig, LlamaForCausalLM
from paddle_tpu.serving import Engine, ServingConfig


def staggered_demo(model):
    rng = np.random.RandomState(0)
    prompts = [rng.randint(1, 256, size=(L,)).astype(np.int32)
               for L in (3, 8, 5, 12, 4, 9, 6, 7)]
    max_new = 16

    eng = Engine(model, ServingConfig(max_batch_size=4, block_size=8,
                                      num_blocks=64))
    reqs = []
    for prompt in prompts:                  # staggered arrivals
        reqs.append(eng.submit(prompt, max_new_tokens=max_new))
        eng.step()                          # decode while others queue
    eng.run_until_complete()

    for req in reqs:
        out = req.output_ids()
        print(f"{req.request_id}: prompt={req.prompt_len:2d} tokens -> "
              f"{out[req.prompt_len:].tolist()} ({req.finish_reason})")

    stats = eng.stats()
    iters = stats["counters"]["decode_iterations"]
    sequential = len(prompts) * (max_new - 1)
    print(f"\ndecode iterations: {iters} continuous-batched vs "
          f"{sequential} sequential")
    print(f"avg batch occupancy: "
          f"{stats['gauges']['batch_occupancy_avg']:.2f}, "
          f"avg cache utilization: "
          f"{stats['gauges']['cache_utilization_avg']:.2f}")
    print(f"compiled decode executables: {eng.decode_cache_size()} "
          f"(never retraces)")
    assert iters < sequential
    assert eng.decode_cache_size() == 1


def prefix_cache_demo(model):
    rng = np.random.RandomState(0)
    system = rng.randint(1, 256, size=(48,)).astype(np.int32)
    tails = [rng.randint(1, 256, size=(L,)).astype(np.int32)
             for L in (5, 3, 7, 4, 6, 2)]
    prompts = [np.concatenate([system, t]) for t in tails]

    eng = Engine(model, ServingConfig(max_batch_size=2, block_size=8,
                                      num_blocks=64, chunk_tokens=16,
                                      enable_prefix_cache=True))
    for prompt in prompts:      # sequential: each sees the warm cache
        req = eng.submit(prompt, max_new_tokens=8)
        eng.run_until_complete()
        print(f"{req.request_id}: prompt={req.prompt_len:2d} "
              f"cached={req.cached_tokens:2d} "
              f"prefill_chunks={req.prefill_chunks} "
              f"-> {req.output_ids()[req.prompt_len:].tolist()}")

    eng.pool.check_leaks()
    c = eng.stats()["counters"]
    g = eng.stats()["gauges"]
    print(f"\nprefix cache: {c['prefix_cache_hits']} hits / "
          f"{c['prefix_cache_misses']} miss, "
          f"cached-token ratio {g['prefix_cached_token_ratio']:.2f}, "
          f"{c['prefill_chunks']} prefill chunks total")
    print(f"compiled prefill executables: {eng.prefill_cache_size()} "
          f"(one fixed chunk shape for every prompt length)")
    # the first request seeds the cache; every other one hits it and
    # prefills only its tail (48 shared tokens = 6 blocks reused)
    assert c["prefix_cache_hits"] == len(prompts) - 1
    assert c["prefix_cache_misses"] == 1
    assert eng.prefill_cache_size() == 1
    assert eng._prefill_step.retraces == 0


def overload_chaos_demo(model):
    from paddle_tpu.resilience.chaos import FaultPlan, burst_prompts
    from paddle_tpu.serving import SERVING

    eng = Engine(model, ServingConfig(max_batch_size=4, block_size=4,
                                      num_blocks=64, chunk_tokens=4,
                                      max_queue_len=32))

    # --- phase 1: seeded burst + sustained slowdown -> load shedding
    with FaultPlan(seed=11, step_delay_s=0.03):
        warm = eng.submit(burst_prompts(seed=1, n=1, min_len=8,
                                        max_len=8)[0], max_new_tokens=4)
        eng.run_until_complete()          # warms the latency EWMAs
        assert warm.finish_reason == "length"
        burst = burst_prompts(seed=11, n=4, min_len=96, max_len=96)
        feasible = eng.submit(
            burst_prompts(seed=2, n=1, min_len=8, max_len=8)[0],
            max_new_tokens=4, deadline_s=0.7)
        doomed = [eng.submit(p, max_new_tokens=4, deadline_s=0.7)
                  for p in burst]
        eng.run_until_complete()

    c = eng.stats()["counters"]
    print(f"burst: {c['requests_shed']} shed at admission, "
          f"{c['requests_timed_out']} timed out, "
          f"goodput {c['goodput_tokens']} tokens")
    assert feasible.finish_reason == "length"
    assert all(r.finish_reason == "shed" for r in doomed)
    assert c["requests_timed_out"] == 0   # shed beats a timeout

    # --- phase 2: injected hung step -> watchdog detects the stall and
    # keeps the late result (the step consumed its donated pool: nothing
    # is dispatched again), engine returns to SERVING
    eng2 = Engine(model, ServingConfig(
        max_batch_size=4, block_size=4, num_blocks=64, chunk_tokens=4,
        watchdog_floor_s=0.25, watchdog_budget_mult=50.0,
        step_max_retries=1, health_recovery_steps=2))
    req = eng2.submit(burst_prompts(seed=3, n=1, min_len=4,
                                    max_len=4)[0], max_new_tokens=6)
    with FaultPlan(step_delay_s={3: 0.6}):   # hang one decode attempt
        eng2.run_until_complete()
    h = eng2.health()
    print(f"watchdog: {h['watchdog_stalls']} stall detected, "
          f"{h['step_retries']} retries, health={h['state']}")
    assert req.finish_reason == "length"
    assert h["watchdog_stalls"] == 1 and h["step_retries"] == 0
    assert h["state"] == SERVING          # recovered after clean steps

    for e in (eng, eng2):
        assert e._decode_step.retraces == 0
        assert e._prefill_step.retraces == 0
        e.pool.check_leaks()
    print("overload chaos: shed + stall recovery OK, zero retraces")


def fused_demo(model):
    from paddle_tpu.models.generation import generate

    rng = np.random.RandomState(0)
    prompts = [rng.randint(1, 256, size=(L,)).astype(np.int32)
               for L in (3, 8, 5, 12, 4, 9, 6, 7)]
    max_new = 16

    outs = {}
    engines = {}
    for label, fused in (("fused", True), ("unfused", False)):
        eng = Engine(model, ServingConfig(max_batch_size=4, block_size=8,
                                          num_blocks=64,
                                          fused_kernels=fused))
        reqs = [eng.submit(p, max_new_tokens=max_new) for p in prompts]
        eng.run_until_complete()
        outs[label] = [r.output_ids()[r.prompt_len:].tolist()
                       for r in reqs]
        engines[label] = eng

    for i, (f, u) in enumerate(zip(outs["fused"], outs["unfused"])):
        assert f == u, f"request {i}: fused {f} != unfused {u}"
    print(f"token parity: {len(prompts)} requests, fused == unfused")

    # the fused engine must also agree with plain generate() — the
    # whole-sequence reference path with no paging at all
    for i, prompt in enumerate(prompts[:3]):
        ref = generate(model, paddle.to_tensor(prompt[None, :]),
                       max_new_tokens=max_new)
        ref_new = np.asarray(ref.numpy() if hasattr(ref, "numpy")
                             else ref)[0, len(prompt):].tolist()
        assert outs["fused"][i] == ref_new, \
            f"request {i}: fused {outs['fused'][i]} != generate {ref_new}"
    print("token parity: fused engine == generate() reference")

    for label, eng in engines.items():
        assert eng._decode_step.retraces == 0, label
        assert eng._prefill_step.retraces == 0, label
        assert eng.decode_cache_size() == 1, label
        eng.pool.check_leaks()
    print("fused serving: zero retraces, one compiled decode "
          "executable per engine")


def router_demo(model):
    from paddle_tpu.resilience.chaos import FaultPlan, burst_prompts
    from paddle_tpu.serving import Router

    def make(name):
        return Engine(model, ServingConfig(
            name=name, max_batch_size=4, block_size=4, num_blocks=64,
            chunk_tokens=16, max_queue_len=32, step_max_retries=1,
            step_retry_backoff_s=0.0))

    # --- phase 1: prefix-affinity placement on a shared-prefix burst
    router = Router([make("replica-0"), make("replica-1")], seed=0)
    rng = np.random.RandomState(0)
    system = rng.randint(1, 256, size=(32,)).astype(np.int32)
    family = [np.concatenate([system, rng.randint(
        1, 256, size=(L,)).astype(np.int32)]) for L in (5, 3, 7, 4)]
    solo = [rng.randint(1, 256, size=(L,)).astype(np.int32)
            for L in (9, 6)]
    reqs = [router.submit(p, max_new_tokens=6) for p in family + solo]
    done = router.run_until_complete()
    for line in router.placement_log:
        print(f"  {line}")
    st = router.stats()["router"]
    print(f"placements: {st['placements']}, expected-cached ratio "
          f"{st['affinity_token_ratio']:.2f}")
    # the shared-prefix family consolidates on ONE replica (first
    # placement is load-based; affinity pins the follow-ups to it)
    family_rids = {r.request_id for r in reqs[:len(family)]}
    homes = {line.split(" -> ")[1].split()[0]
             for line in router.placement_log
             if line.split(" -> ")[0] in family_rids}
    assert len(homes) == 1, f"family scattered across {homes}"
    assert len(done) == len(reqs)

    # --- phase 2: replica-scoped chaos kill mid-burst -> quarantine,
    # drain, resubmit; zero lost requests, token parity with 1 engine
    e0, e1 = make("replica-0"), make("replica-1")
    fleet = Router([e0, e1], seed=0)
    prompts = burst_prompts(seed=3, n=6, min_len=6, max_len=14)
    ref = Engine(model, ServingConfig(max_batch_size=4, block_size=4,
                                      num_blocks=64, chunk_tokens=16)
                 ).generate(list(prompts), max_new_tokens=5)
    reqs = [fleet.submit(p, max_new_tokens=5) for p in prompts]
    with FaultPlan(step_fault_scope="@replica-1", fail_step_at={1, 2}):
        done = fleet.run_until_complete()
    st = fleet.stats()["router"]
    h = fleet.health()
    print(f"chaos: {st['replica_quarantines']} replica quarantined, "
          f"{st['requests_resubmitted']} resubmitted, "
          f"{h['serving_replicas']}/{len(fleet.replicas)} serving")
    assert st["replica_quarantines"] == 1
    assert st["requests_resubmitted"] > 0
    assert len(done) == len(reqs)           # zero lost requests
    for rq, expect in zip(reqs, ref):
        out = done[rq.request_id]
        assert out.finish_reason == "length", out.finish_reason
        assert np.array_equal(out.output_ids(), expect)
    for e in (e0, e1):
        assert e._decode_step.retraces == 0
        assert e._prefill_step.retraces == 0
        e.pool.check_leaks()
    print("router chaos: replica killed mid-burst, zero lost requests, "
          "token parity across failover, zero retraces")


def speculative_demo(model):
    import dataclasses

    from paddle_tpu.models.generation import generate
    from paddle_tpu.serving import SpeculativeConfig

    # a real (weight-divergent) draft: same cache geometry and vocab,
    # one layer, different seed — proposals get REJECTED, exercising
    # the rollback path
    paddle.seed(123)
    draft = LlamaForCausalLM(dataclasses.replace(
        LlamaConfig.tiny(), num_hidden_layers=1))
    draft.eval()

    rng = np.random.RandomState(0)
    prompts = [rng.randint(1, 256, size=(L,)).astype(np.int32)
               for L in (3, 8, 5, 12, 4, 9)]
    max_new = 12

    ref = [np.asarray(generate(model, paddle.to_tensor(p[None, :]),
                               max_new_tokens=max_new).numpy())[0]
           for p in prompts]
    plain = Engine(model, ServingConfig(max_batch_size=4, block_size=8,
                                        num_blocks=96))
    plain_outs = plain.generate(list(prompts), max_new_tokens=max_new)

    eng = Engine(model, ServingConfig(
        max_batch_size=4, block_size=8, num_blocks=96,
        speculative=SpeculativeConfig(draft_model=draft,
                                      num_draft_tokens=3)))
    outs = eng.generate(list(prompts), max_new_tokens=max_new)
    for i, (o, r, p) in enumerate(zip(outs, ref, plain_outs)):
        assert np.array_equal(o, r), f"request {i}: spec != generate"
        assert np.array_equal(o, p), f"request {i}: spec != plain engine"
    m = eng.stats()["counters"]
    print(f"token parity: {len(prompts)} requests, speculative == "
          f"generate() == non-speculative engine")
    print(f"random draft: {m['spec_tokens_drafted']} drafted, "
          f"{m['spec_tokens_accepted']} accepted "
          f"(rate {eng.metrics.spec_accept_rate():.2f})")
    eng.pool.check_leaks()     # rejected drafts leaked nothing

    # weight-identical draft: every greedy proposal matches the target
    # argmax — the accept-rate ceiling a distilled draft approaches
    ceil = Engine(model, ServingConfig(
        max_batch_size=4, block_size=8, num_blocks=96,
        speculative=SpeculativeConfig(draft_model=model,
                                      num_draft_tokens=3)))
    couts = ceil.generate(list(prompts), max_new_tokens=max_new)
    assert all(np.array_equal(o, r) for o, r in zip(couts, ref))
    assert ceil.metrics.spec_accept_rate() == 1.0
    print(f"self-draft ceiling: accept rate "
          f"{ceil.metrics.spec_accept_rate():.2f}")

    for e in (eng, ceil):
        caches = e.spec_cache_sizes()
        assert all(v == 1 for v in caches.values()), caches
        assert e._draft_propose_step.retraces == 0
        assert e._spec_verify_step.retraces == 0
        assert e._draft_prefill_step.retraces == 0
        e.pool.check_leaks()
    print("speculative decoding: zero retraces, one executable per "
          "step kind, zero KV leaks after rejected drafts")


def quantized_demo(model):
    from paddle_tpu.serving.cache import BlockKVPool

    rng = np.random.RandomState(0)
    prompts = [rng.randint(1, 256, size=(L,)).astype(np.int32)
               for L in (3, 8, 5, 12, 4, 9, 6, 7)]
    max_new = 16

    # --- phase 1: int8 KV (and int8 weights) vs fp32, token parity
    outs = {}
    engines = {}
    configs = {
        "fp32": {},
        "int8-kv": dict(kv_cache_dtype="int8"),
        "int8-kv+w8": dict(kv_cache_dtype="int8", weight_dtype="int8"),
    }
    for label, extra in configs.items():
        eng = Engine(model, ServingConfig(max_batch_size=4, block_size=8,
                                          num_blocks=64, **extra))
        reqs = [eng.submit(p, max_new_tokens=max_new) for p in prompts]
        eng.run_until_complete()
        outs[label] = [r.output_ids()[r.prompt_len:].tolist()
                       for r in reqs]
        engines[label] = eng
    for label in ("int8-kv", "int8-kv+w8"):
        for i, (q, f) in enumerate(zip(outs[label], outs["fp32"])):
            assert q == f, f"request {i}: {label} {q} != fp32 {f}"
    print(f"token parity: {len(prompts)} requests, int8 KV == "
          f"int8 KV + int8 weights == fp32")

    for label, eng in engines.items():
        assert eng._decode_step.retraces == 0, label
        assert eng._prefill_step.retraces == 0, label
        eng.pool.check_leaks()
        st = eng.pool.stats()
        g = eng.stats()["gauges"]
        print(f"  {label:>11}: block={st['block_bytes']}B "
              f"pool={st['capacity_bytes'] / 2**10:.0f}KiB "
              f"kv_dtype_gauge={g['serving_kv_cache_dtype']:.0f} "
              f"scale_bytes={g['kv_quant_scale_bytes']:.0f}")
    print("quantized serving: zero retraces, zero pool leaks")

    # --- phase 2: one fixed HBM budget, dtype-aware block derivation
    cfg = model.config
    budget = 48 * BlockKVPool.block_bytes_for(
        cfg.num_hidden_layers, 8, cfg.num_key_value_heads,
        cfg.hidden_size // cfg.num_attention_heads, cfg.dtype, None)
    resident = {}
    for label, kv_dtype in (("fp32", None), ("int8", "int8")):
        eng = Engine(model, ServingConfig(max_batch_size=4, block_size=8,
                                          num_blocks=None,
                                          kv_pool_bytes=budget,
                                          kv_cache_dtype=kv_dtype))
        resident[label] = eng.num_blocks
    ratio = resident["int8"] / resident["fp32"]
    print(f"fixed {budget / 2**10:.0f}KiB KV budget: "
          f"{resident['fp32']} fp32 blocks vs {resident['int8']} int8 "
          f"blocks ({ratio:.2f}x resident)")
    assert ratio >= 1.5, ratio


def stream_demo(model):
    import json

    from paddle_tpu.serving import Endpoint

    rng = np.random.RandomState(0)
    prompt = rng.randint(1, 256, size=(6,)).astype(np.int32)
    ep = Endpoint(model, ServingConfig(max_batch_size=4, block_size=8,
                                       num_blocks=64))

    frames = list(ep.stream(prompt, max_new_tokens=8))
    assert frames[-1] == "data: [DONE]\n\n"
    events = []
    for f in frames[:-1]:
        assert f.startswith("data: ") and f.endswith("\n\n"), repr(f)
        events.append(json.loads(f[len("data: "):]))
    toks = [e["token"] for e in events[:-1]]
    summary = events[-1]
    print(f"streamed {len(toks)} tokens: {toks}")
    print(f"summary: {summary}")
    assert summary["finish_reason"] == "length"
    assert summary["num_tokens"] == len(toks) == 8
    assert [e["index"] for e in events[:-1]] == list(range(8))

    # the streamed tokens ARE the request's generated list — and they
    # match a plain (non-streaming) run of the same prompt
    ref = ep.run([prompt], max_new_tokens=8)[0][len(prompt):].tolist()
    assert toks == ref, (toks, ref)

    # one sampled stream: same seed twice -> identical streamed tokens
    def stream_tokens(**kw):
        fs = list(ep.stream(prompt, max_new_tokens=8, **kw))
        return [json.loads(f[len("data: "):])["token"] for f in fs[:-2]]

    sampled = dict(do_sample=True, temperature=0.8, top_k=16, seed=7)
    a, b = stream_tokens(**sampled), stream_tokens(**sampled)
    assert a == b, (a, b)
    print(f"sampled stream (seed 7, replayed identically): {b}")
    print("SSE round-trip OK: framed, ordered, [DONE]-terminated, "
          "token parity with the non-streaming path")


def main():
    enable_compile_cache()      # before the first jit
    ap = argparse.ArgumentParser()
    ap.add_argument("--prefix-cache", action="store_true",
                    help="shared-system-prompt workload exercising the "
                         "content-addressed prefix cache")
    ap.add_argument("--overload-chaos", action="store_true",
                    help="seeded burst + injected stall: load shedding, "
                         "watchdog retry, recovery to SERVING")
    ap.add_argument("--fused", action="store_true",
                    help="fused serving kernels forced on vs off: "
                         "token parity, generate() agreement, zero "
                         "retraces")
    ap.add_argument("--router", action="store_true",
                    help="two-replica fleet router: prefix-affinity "
                         "placement, then a chaos-killed replica with "
                         "drain + resubmit and zero lost requests")
    ap.add_argument("--speculative", action="store_true",
                    help="draft-propose/target-verify speculative "
                         "decoding: greedy token parity with generate() "
                         "and the plain engine, leak-free rollback, "
                         "self-draft accept-rate ceiling")
    ap.add_argument("--quantized", action="store_true",
                    help="int8 paged KV + weight-only int8 engines: "
                         "greedy token parity with fp32, zero retraces "
                         "and leaks, >=1.5x resident blocks at a fixed "
                         "kv_pool_bytes budget")
    ap.add_argument("--stream", action="store_true",
                    help="SSE streaming front door: per-token data: "
                         "frames in order, summary event, [DONE] "
                         "terminator, parity with the batch path")
    args = ap.parse_args()

    paddle.seed(0)
    model = LlamaForCausalLM(LlamaConfig.tiny())
    model.eval()
    if args.prefix_cache:
        prefix_cache_demo(model)
    elif args.overload_chaos:
        overload_chaos_demo(model)
    elif args.fused:
        fused_demo(model)
    elif args.router:
        router_demo(model)
    elif args.speculative:
        speculative_demo(model)
    elif args.quantized:
        quantized_demo(model)
    elif args.stream:
        stream_demo(model)
    else:
        staggered_demo(model)


if __name__ == "__main__":
    main()
