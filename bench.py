"""Benchmark: Llama pretrain tokens/sec/chip on the available accelerator.

Prints ONE JSON line: {"metric", "value", "unit", "vs_baseline"}.
The reference publishes no absolute numbers (BASELINE.md) — vs_baseline
reports achieved MFU (model flops utilization) as the comparable scalar.

JAX is initialised once, in this process, on whatever backend it finds;
every line names that backend and device.  Nothing falls back: a backend
that does not come up, a kernel the compiler refuses, an out-of-memory
batch or an unknown chip ends the run non-zero with the error.
"""
from __future__ import annotations

import json
import sys
import time

import numpy as np

# bf16 peak FLOP/s per chip by PJRT device_kind substring (public specs).
# Checked in order; first match wins.
_PEAK_FLOPS = (
    ("v6e", 918e12), ("v6 lite", 918e12),
    ("v5p", 459e12),
    ("v5e", 197e12), ("v5 lite", 197e12), ("v5litepod", 197e12),
    ("v4", 275e12),
    ("v3", 123e12),
    ("v2", 45e12),
)


def _peak_flops(device_kind: str):
    kind = device_kind.lower()
    for key, peak in _PEAK_FLOPS:
        if key in kind:
            return peak
    raise ValueError(
        f"no peak FLOP/s on record for device_kind {device_kind!r}; add "
        "it to _PEAK_FLOPS with its source — a utilization against a "
        "guessed peak is not a measurement")


def _emit(result: dict):
    # every artifact line names the device it ran on
    import jax

    d = jax.devices()[0]
    result.setdefault("device", {"platform": d.platform,
                                 "kind": d.device_kind,
                                 "count": len(jax.devices())})
    print(json.dumps(result))
    sys.stdout.flush()


def _n_chips() -> int:
    """Device count for per-chip normalization of serving headlines."""
    import jax

    return len(jax.devices())


def _eager_overhead_us(n_ops: int = 1000):
    """Per-op eager-dispatch overhead: Tensor-path chained adds vs raw jnp
    (SURVEY §7 'eager-mode performance' hard part; the reference's hot
    loop is TraceOpImpl, SURVEY §3.1).  Returns (overhead_us_per_op,
    tensor_us_per_op, jnp_us_per_op)."""
    import jax.numpy as jnp

    import paddle_tpu as paddle

    x_t = paddle.to_tensor(np.ones((64, 64), np.float32))
    x_j = jnp.ones((64, 64), jnp.float32)

    def chain_tensor(n):
        acc = x_t
        for _ in range(n):
            acc = acc + x_t
        acc._value.block_until_ready()

    def chain_jnp(n):
        acc = x_j
        for _ in range(n):
            acc = acc + x_j
        acc.block_until_ready()

    chain_tensor(50)  # warm caches
    chain_jnp(50)
    t0 = time.perf_counter()
    chain_tensor(n_ops)
    t_tensor = time.perf_counter() - t0
    t0 = time.perf_counter()
    chain_jnp(n_ops)
    t_jnp = time.perf_counter() - t0
    per_op = (t_tensor - t_jnp) / n_ops * 1e6
    return round(per_op, 3), round(t_tensor / n_ops * 1e6, 3), \
        round(t_jnp / n_ops * 1e6, 3)


def _moe_bench(on_tpu: bool):
    """Second BASELINE config (expert-parallel MoE proxy, single chip):
    tokens/s through a jitted fwd+bwd of an 8-expert top-2 MoE block
    (BASELINE.md config 4; reference MoE path python/paddle/incubate/
    distributed/models/moe/moe_layer.py)."""
    import jax

    import paddle_tpu as paddle
    from paddle_tpu import jit
    from paddle_tpu.distributed.moe import MoELayer
    from paddle_tpu.optimizer import AdamW

    if on_tpu:
        d_model, d_hidden, experts = 1024, 4096, 8
        batch, seq, steps, warmup = 8, 512, 10, 3
    else:
        d_model, d_hidden, experts = 32, 64, 4
        batch, seq, steps, warmup = 2, 16, 25, 3
    moe = MoELayer(d_model=d_model, d_hidden=d_hidden, num_experts=experts,
                   top_k=2)
    opt = AdamW(1e-4, parameters=moe.parameters())

    @jit.to_static
    def step(x):
        out = moe(x)
        loss = (out * out).mean() + 0.01 * moe.aux_loss
        loss.backward()
        opt.step()
        opt.clear_grad()
        return loss

    rng = np.random.RandomState(0)
    x = paddle.to_tensor(rng.randn(batch, seq, d_model).astype(np.float32))
    for _ in range(warmup):
        loss = step(x)
    loss._value.block_until_ready()
    t0 = time.perf_counter()
    for _ in range(steps):
        loss = step(x)
        loss._value.block_until_ready()
    dt = time.perf_counter() - t0
    return round(batch * seq * steps / dt, 1)


def _unet_bench(on_tpu: bool):
    """Third BASELINE config (SDXL-UNet inference proxy, config 5):
    denoise-step latency (ms) of a jitted UNet2DConditionModel forward —
    the reference serves this through Paddle Inference's predictor
    (ppdiffusers + inference/api.cc); here the predictor path IS jit."""
    import jax.numpy as jnp

    import paddle_tpu as paddle
    from paddle_tpu import jit
    from paddle_tpu.models.unet import UNet2DConditionModel, UNetConfig

    if on_tpu:
        cfg = UNetConfig(dtype="bfloat16")  # SDXL channel plan
        B, HW, T = 1, 64, 77
    else:
        cfg = UNetConfig.tiny()
        B, HW, T = 1, 8, 4
    model = UNet2DConditionModel(cfg)
    model.eval()

    rng = np.random.RandomState(0)
    dt = jnp.bfloat16 if cfg.dtype == "bfloat16" else jnp.float32
    lat = paddle.to_tensor(jnp.asarray(
        rng.randn(B, cfg.in_channels, HW, HW), dt))
    ts = paddle.to_tensor(np.asarray([500], np.int32))
    ctx = paddle.to_tensor(jnp.asarray(
        rng.randn(B, T, cfg.cross_attention_dim), dt))

    @jit.to_static
    def denoise(lat, ts, ctx):
        return model(lat, ts, ctx)

    steps, warmup = (10, 3) if on_tpu else (10, 2)
    for _ in range(warmup):
        out = denoise(lat, ts, ctx)
    out._value.block_until_ready()
    t0 = time.perf_counter()
    for _ in range(steps):
        out = denoise(lat, ts, ctx)
        out._value.block_until_ready()
    return round((time.perf_counter() - t0) / steps * 1000, 2)


def _resnet_bench(on_tpu: bool):
    """BASELINE config 1 (ResNet-50 ImageNet, single-device dygraph+AMP):
    images/s through a jitted train step of paddle.vision resnet50
    (reference: python/paddle/vision/models/resnet.py + the dygraph AMP
    path)."""
    import paddle_tpu as paddle
    from paddle_tpu import jit
    from paddle_tpu.optimizer import Momentum
    from paddle_tpu.vision.models import resnet50

    if on_tpu:
        batch, hw, steps, warmup = 64, 224, 10, 3
    else:
        batch, hw, steps, warmup = 2, 64, 8, 2
    model = resnet50(num_classes=100)
    opt = Momentum(learning_rate=0.1, momentum=0.9,
                   parameters=model.parameters())

    @jit.to_static
    def step(img, lab):
        with paddle.amp.auto_cast(level="O1"):
            loss = paddle.nn.functional.cross_entropy(model(img), lab)
        loss.backward()
        opt.step()
        opt.clear_grad()
        return loss

    rng = np.random.RandomState(0)
    img = paddle.to_tensor(rng.randn(batch, 3, hw, hw).astype(np.float32))
    lab = paddle.to_tensor(rng.randint(0, 100, (batch,)).astype(np.int64))
    for _ in range(warmup):
        loss = step(img, lab)
    loss._value.block_until_ready()
    t0 = time.perf_counter()
    for _ in range(steps):
        loss = step(img, lab)
        loss._value.block_until_ready()
    return round(batch * steps / (time.perf_counter() - t0), 1)


def _bert_dp_bench(on_tpu: bool):
    """BASELINE config 2 (BERT-base pretraining, Fleet data-parallel):
    tokens/s through the fleet DP path — dp=2 over the host mesh when >1
    device is visible (the virtual-CPU case), single-chip otherwise
    (reference: fleet DDP over ProcessGroupNCCL; here SPMD dp sharding)."""
    import jax

    import paddle_tpu as paddle
    from paddle_tpu import jit
    from paddle_tpu.distributed import fleet
    from paddle_tpu.distributed.fleet import DistributedStrategy
    from paddle_tpu.distributed.sharding import shard_tensor
    from paddle_tpu.models.bert import BertConfig, BertForPretraining
    from paddle_tpu.optimizer import AdamW

    n_dev = len(jax.devices())
    dp = n_dev if n_dev > 1 else 1  # fleet meshes over all visible devices
    if on_tpu:
        cfg = BertConfig.base()
        batch, seq, steps, warmup = 16 * dp, 128, 10, 3
    else:
        cfg = BertConfig.tiny()
        # batch must divide over dp whatever the virtual device count is
        batch, seq, steps, warmup = dp * max(1, 8 // dp), 16, 25, 3

    strategy = DistributedStrategy()
    strategy.hybrid_configs = {"dp_degree": dp}
    fleet.init(is_collective=True, strategy=strategy)
    try:
        model = fleet.distributed_model(BertForPretraining(cfg))
        opt = fleet.distributed_optimizer(
            AdamW(1e-4, parameters=model.parameters()))

        @jit.to_static
        def step(ids, mlm_labels, nsp):
            loss, _, _ = model(ids, masked_lm_labels=mlm_labels,
                               next_sentence_labels=nsp)
            loss.backward()
            opt.step()
            opt.clear_grad()
            return loss

        rng = np.random.RandomState(0)
        ids = rng.randint(0, cfg.vocab_size, (batch, seq)).astype(np.int32)
        lab = np.where(rng.rand(batch, seq) < 0.15, ids, -100).astype(
            np.int64)
        nsp = rng.randint(0, 2, (batch,)).astype(np.int64)

        def mk(a):
            t = paddle.to_tensor(a)
            return shard_tensor(t, placements=["dp"]) if dp > 1 else t

        ids_t, lab_t, nsp_t = mk(ids), mk(lab), mk(nsp)
        for _ in range(warmup):
            loss = step(ids_t, lab_t, nsp_t)
        loss._value.block_until_ready()
        t0 = time.perf_counter()
        for _ in range(steps):
            loss = step(ids_t, lab_t, nsp_t)
            loss._value.block_until_ready()
        # per-chip so artifacts stay comparable when the visible device
        # count differs between rounds (the headline metric's convention)
        return round(batch * seq * steps
                     / (time.perf_counter() - t0) / dp, 1)
    finally:
        fleet.shutdown()


def _serving_bench(on_tpu: bool):
    """Serving throughput (paddle_tpu/serving): generated tokens/s
    through the continuous-batching engine on a staggered workload —
    requests arrive while earlier ones are mid-decode, the compiled
    paged decode step never retraces (asserted by the engine itself
    under strict_no_retrace)."""
    import paddle_tpu as paddle
    from paddle_tpu.models import LlamaConfig, LlamaForCausalLM
    from paddle_tpu.serving import Engine, ServingConfig

    if on_tpu:
        cfg = LlamaConfig.tiny(
            vocab_size=32000, hidden_size=1024, intermediate_size=2816,
            num_hidden_layers=8, num_attention_heads=16,
            num_key_value_heads=8, max_position_embeddings=2048,
            dtype="bfloat16")
        scfg = ServingConfig(max_batch_size=16, block_size=32,
                             num_blocks=512)
        n_req, max_new, lens = 48, 128, (16, 48, 96, 192)
    else:
        cfg = LlamaConfig.tiny()
        scfg = ServingConfig(max_batch_size=4, block_size=8,
                             num_blocks=64)
        n_req, max_new, lens = 8, 16, (3, 8, 5, 12)
    paddle.seed(0)
    model = LlamaForCausalLM(cfg)
    model.eval()
    rng = np.random.RandomState(0)
    prompts = [rng.randint(1, cfg.vocab_size,
                           size=(lens[i % len(lens)],)).astype(np.int32)
               for i in range(n_req)]

    # warmup: compile prefill buckets + the one decode executable
    eng = Engine(model, scfg)
    eng.generate(prompts[:len(lens)], max_new_tokens=2)

    eng = Engine(model, scfg)
    t0 = time.perf_counter()
    for p in prompts:       # staggered arrivals, decode between submits
        eng.submit(p, max_new_tokens=max_new)
        eng.step()
    eng.run_until_complete()
    dt = time.perf_counter() - t0
    tokens = eng.stats()["counters"]["tokens_generated"]
    tps = tokens / dt
    return round(tps, 1), {
        "tokens_per_sec_per_chip": round(tps / _n_chips(), 1)}


def _prefix_cache_bench(on_tpu: bool):
    """BENCH_ONLY=prefix_cache: TTFT on a shared-prefix workload
    (ISSUE 5) — N requests share a long system prompt; after the first
    request seeds the cache, every later admission reuses its prefix
    blocks and prefills only the short unique tail.  Reported value is
    the cache-off/cache-on median-TTFT ratio (> 1 means the cache wins);
    prefill compile counts and both TTFTs print to stderr.  Both modes
    run the SAME chunked prefill, so the delta is pure block reuse."""
    import paddle_tpu as paddle
    from paddle_tpu.models import LlamaConfig, LlamaForCausalLM
    from paddle_tpu.serving import Engine, ServingConfig

    if on_tpu:
        cfg = LlamaConfig.tiny(
            vocab_size=32000, hidden_size=1024, intermediate_size=2816,
            num_hidden_layers=8, num_attention_heads=16,
            num_key_value_heads=8, max_position_embeddings=2048,
            dtype="bfloat16")
        sys_len, tail_len, n_req, max_new = 1024, 64, 12, 8
        blocks, bsz, chunk = 512, 32, 256
    else:
        cfg = LlamaConfig.tiny(max_position_embeddings=512)
        sys_len, tail_len, n_req, max_new = 192, 16, 8, 4
        blocks, bsz, chunk = 128, 16, 64
    paddle.seed(0)
    model = LlamaForCausalLM(cfg)
    model.eval()
    rng = np.random.RandomState(0)
    system = rng.randint(1, cfg.vocab_size,
                         size=(sys_len,)).astype(np.int32)
    prompts = [np.concatenate([
        system,
        rng.randint(1, cfg.vocab_size, size=(tail_len,)).astype(np.int32)])
        for _ in range(n_req)]

    def run(enable):
        eng = Engine(model, ServingConfig(
            max_batch_size=4, block_size=bsz, num_blocks=blocks,
            chunk_tokens=chunk, enable_prefix_cache=enable))
        # warmup: compile both steps; with the cache on, this also
        # seeds the shared prefix (request 0's production role)
        eng.generate([prompts[0]], max_new_tokens=2)
        ttfts = []
        t0 = time.perf_counter()
        for p in prompts[1:]:   # sequential: TTFT unpolluted by batching
            req = eng.submit(p, max_new_tokens=max_new)
            eng.run_until_complete()
            ttfts.append(
                eng.metrics.requests[req.request_id].to_dict()["ttft_s"])
        dt = time.perf_counter() - t0
        eng.pool.check_leaks()  # zero leak failures is part of the bar
        tokens = eng.stats()["counters"]["tokens_generated"]
        return (float(np.median(ttfts)), eng._prefill_step.compiles,
                tokens / dt)

    off_t, off_c, _ = run(False)
    on_t, on_c, on_tps = run(True)
    ratio = off_t / on_t if on_t > 0 else float("inf")
    print(f"# prefix_cache: ttft_off={off_t * 1e3:.2f}ms "
          f"ttft_on={on_t * 1e3:.2f}ms speedup={ratio:.2f}x "
          f"prefill_compiles off={off_c} on={on_c} "
          f"(chunked: constant across all prompt lengths)",
          file=sys.stderr)
    return round(ratio, 3), {
        "tokens_per_sec_per_chip": round(on_tps / _n_chips(), 1)}


def _resilience_bench(on_tpu: bool):
    """Atomic-checkpoint roundtrip (save + verified restore) for a
    llama-sized model+optimizer state — the per-checkpoint overhead a
    ResilienceCallback adds to training.  The save path hashes and
    fsyncs every payload, so this measures the real durability cost,
    not just pickle time."""
    import shutil
    import tempfile

    import paddle_tpu as paddle
    from paddle_tpu.models import LlamaConfig, LlamaForCausalLM
    from paddle_tpu.optimizer import AdamW
    from paddle_tpu.resilience import ResilientCheckpointer, collect_state

    if on_tpu:
        cfg = LlamaConfig.tiny(
            vocab_size=32000, hidden_size=1024, intermediate_size=2816,
            num_hidden_layers=8, num_attention_heads=16,
            num_key_value_heads=8, max_position_embeddings=2048,
            dtype="bfloat16")
        rounds = 5
    else:
        cfg = LlamaConfig.tiny()
        rounds = 8
    paddle.seed(0)
    model = LlamaForCausalLM(cfg)
    opt = AdamW(1e-4, parameters=model.parameters())
    state = collect_state(model, opt)

    d = tempfile.mkdtemp(prefix="bench-resilience-")
    try:
        ck = ResilientCheckpointer(d, max_to_keep=2)
        ck.save(0, state)                      # warm page cache / dirs
        times = []
        for i in range(1, rounds + 1):
            t0 = time.perf_counter()
            ck.save(i, state)
            step, restored = ck.restore_latest()
            times.append(time.perf_counter() - t0)
            assert step == i and restored is not None
    finally:
        shutil.rmtree(d, ignore_errors=True)
    return round(float(np.median(times)) * 1000, 2)


def _elastic_ckpt_bench(on_tpu: bool):
    """BENCH_ONLY=elastic_ckpt: sharded elastic-checkpoint roundtrip —
    a 2-process save through the owned-shard protocol (each process
    stages only its shards, the coordinator merges the per-process file
    lists and commits) followed by a verified 1-process restore that
    reassembles the global arrays (restore-with-reshard).  The
    single-file atomic roundtrip of the SAME state rides along so the
    artifact shows the protocol's overhead vs the legacy format."""
    import shutil
    import tempfile

    import paddle_tpu as paddle
    from paddle_tpu.distributed import bootstrap
    from paddle_tpu.models import LlamaConfig, LlamaForCausalLM
    from paddle_tpu.optimizer import AdamW
    from paddle_tpu.resilience import ResilientCheckpointer, collect_state

    if on_tpu:
        cfg = LlamaConfig.tiny(
            vocab_size=32000, hidden_size=1024, intermediate_size=2816,
            num_hidden_layers=8, num_attention_heads=16,
            num_key_value_heads=8, max_position_embeddings=2048,
            dtype="bfloat16")
        rounds = 5
    else:
        cfg = LlamaConfig.tiny()
        rounds = 8
    paddle.seed(0)
    model = LlamaForCausalLM(cfg)
    opt = AdamW(1e-4, parameters=model.parameters())
    state = collect_state(model, opt)

    def sharded_roundtrip(d, step):
        # per-process saves, coordinator LAST (it merges + commits);
        # under emulation the protocol runs sequentially in-process,
        # so the measured cost is the full fleet's I/O, not one host's
        for idx in (1, 0):
            with bootstrap.emulated_process_context(idx, 2):
                ResilientCheckpointer(d, max_to_keep=2).save(step, state)
        ck = ResilientCheckpointer(d, max_to_keep=2)
        got, restored = ck.restore_latest()
        assert got == step and restored is not None
        assert ck.reshard_restores == 1   # 2-process ckpt, 1-process read

    d_shard = tempfile.mkdtemp(prefix="bench-elastic-")
    d_single = tempfile.mkdtemp(prefix="bench-elastic-single-")
    try:
        sharded_roundtrip(d_shard, 0)          # warm page cache / dirs
        times = []
        for i in range(1, rounds + 1):
            t0 = time.perf_counter()
            sharded_roundtrip(d_shard, i)
            times.append(time.perf_counter() - t0)
        ck = ResilientCheckpointer(d_single, max_to_keep=2, sharded=False)
        ck.save(0, state)
        single = []
        for i in range(1, rounds + 1):
            t0 = time.perf_counter()
            ck.save(i, state)
            got, restored = ck.restore_latest()
            assert got == i and restored is not None
            single.append(time.perf_counter() - t0)
    finally:
        shutil.rmtree(d_shard, ignore_errors=True)
        shutil.rmtree(d_single, ignore_errors=True)
    return (round(float(np.median(times)) * 1000, 2),
            {"single_file_roundtrip_ms":
             round(float(np.median(single)) * 1000, 2)})


def _observe_overhead_bench(on_tpu: bool):
    """Per-step cost of the observability registry: the same compiled
    training loop timed with telemetry OFF (the no-op fast path every
    untelemetered run takes) and ON (StepTimer + compile tracking +
    registry mirrors), alternating passes for noise robustness.  Returns
    the on-vs-off overhead in percent — the ISSUE 4 acceptance gate is
    < 2%."""
    import paddle_tpu as paddle
    from paddle_tpu import nn, observability
    from paddle_tpu.models import LlamaConfig, LlamaForCausalLM

    steps, batch, seq = (30, 4, 64) if on_tpu else (30, 2, 16)
    paddle.seed(0)
    net = LlamaForCausalLM(LlamaConfig.tiny(max_position_embeddings=seq))
    model = paddle.Model(net)
    model.prepare(paddle.optimizer.AdamW(1e-3,
                                         parameters=net.parameters()),
                  nn.CrossEntropyLoss())
    rng = np.random.RandomState(1)
    ids = rng.randint(1, 256, size=(steps, batch, seq + 1)).astype(np.int64)
    batches = [(a[:, :-1], a[:, 1:]) for a in ids]

    def one_pass():
        t0 = time.perf_counter()
        model.fit(train_data=batches, epochs=1, verbose=0)
        return (time.perf_counter() - t0) / steps

    one_pass()                                   # compile + warm caches
    prev = observability.enable(False)
    ratios = []
    try:
        # paired passes, alternating which side runs first each round:
        # adjacent runs see the same machine state, so clock drift and
        # cache effects cancel inside each per-round ratio, and the
        # median of ratios shrugs off outlier rounds entirely
        for i in range(9):
            on_first = bool(i % 2)
            observability.enable(on_first)
            first = one_pass()
            observability.enable(not on_first)
            second = one_pass()
            on_t, off_t = (first, second) if on_first else (second, first)
            ratios.append((on_t - off_t) / off_t * 100)
    finally:
        observability.enable(prev)
    return round(float(np.median(ratios)), 2)


def _mesh_train_bench(on_tpu: bool):
    """BENCH_ONLY=mesh_train: per-chip training throughput under the
    runtime MeshExecutor — the same tiny-llama hapi loop on a (1,1,1)
    mesh and on (data=2,fsdp=2,tp=2).  Returns tokens/sec/chip for the
    sharded run (the number that should hold as the mesh grows); the
    single-chip figure and the achieved scaling ratio go to stderr.
    Needs 8 devices (CPU runs want XLA_FLAGS=
    --xla_force_host_platform_device_count=8, as tools/ci.sh sets)."""
    import paddle_tpu as paddle
    from paddle_tpu import nn
    from paddle_tpu.models import LlamaConfig, LlamaForCausalLM

    steps, batch, seq = (30, 8, 128) if on_tpu else (20, 4, 16)
    rng = np.random.RandomState(1)
    ids = rng.randint(1, 256, size=(batch, seq + 1)).astype(np.int64)
    x, y = ids[:, :-1], ids[:, 1:]

    def run(axes):
        paddle.seed(0)
        net = LlamaForCausalLM(
            LlamaConfig.tiny(max_position_embeddings=seq))
        model = paddle.Model(net)
        model.prepare(
            paddle.optimizer.AdamW(1e-3, parameters=net.parameters()),
            nn.CrossEntropyLoss(), mesh=axes)
        ex = model._mesh_executor
        for _ in range(3):                     # compile both entries
            model.train_batch([x], [y])
        t0 = time.perf_counter()
        for _ in range(steps):
            model.train_batch([x], [y])        # loss .numpy() syncs
        dt = time.perf_counter() - t0
        chips = max(1, ex.mesh.size)
        tps_chip = steps * batch * seq / dt / chips
        ex.close()
        return tps_chip, chips

    single_tps, _ = run({"data": 1, "fsdp": 1, "tp": 1})
    mesh_tps, chips = run({"data": 2, "fsdp": 2, "tp": 2})
    print(f"mesh_train: single-chip {single_tps:.1f} tok/s, "
          f"{chips}-chip mesh {mesh_tps:.1f} tok/s/chip "
          f"(scaling {mesh_tps / single_tps:.2f}x per chip)",
          file=sys.stderr)
    return round(float(mesh_tps), 2)


def _overload_bench(on_tpu: bool):
    """BENCH_ONLY=overload: goodput under a seeded overload burst with
    load shedding on vs off (README: Overload control).  The same burst
    runs twice under an injected per-step slowdown: four 96-token
    requests whose deadline the slowdown makes hopeless (the injected
    sleeps alone exceed it, so the outcome is machine-independent),
    two short feasible requests with the same deadline, and two
    deadline-free requests whose TTFT measures queueing delay.  With
    shedding OFF the hopeless work occupies every decode slot until it
    times out, so the feasible requests bust their own deadline waiting;
    with shedding ON it is rejected at admission and they complete.
    Reported value is the on/off goodput ratio (> 1 means shedding
    converts wasted work into met deadlines); shed rate and p99 TTFT
    for both modes print to stderr."""
    import paddle_tpu as paddle
    from paddle_tpu.models import LlamaConfig, LlamaForCausalLM
    from paddle_tpu.resilience.chaos import FaultPlan, burst_prompts
    from paddle_tpu.serving import Engine, ServingConfig

    delay_s, deadline_s = 0.03, 0.7
    paddle.seed(0)
    model = LlamaForCausalLM(LlamaConfig.tiny())
    model.eval()

    def run(shed_on):
        eng = Engine(model, ServingConfig(
            max_batch_size=4, block_size=4, num_blocks=64,
            chunk_tokens=4, max_queue_len=32,
            enable_load_shedding=shed_on))
        with FaultPlan(seed=11, step_delay_s=delay_s):
            # warm under the slowdown so the latency EWMAs (and thus
            # the shed estimate) reflect the conditions of the burst
            eng.submit(burst_prompts(seed=1, n=1, min_len=8,
                                     max_len=8)[0], max_new_tokens=4)
            eng.run_until_complete()
            reqs = []
            for p in burst_prompts(seed=11, n=4, min_len=96,
                                   max_len=96):    # hopeless vs deadline
                reqs.append(eng.submit(p, max_new_tokens=4,
                                       deadline_s=deadline_s))
            for p in burst_prompts(seed=2, n=2, min_len=8, max_len=8):
                reqs.append(eng.submit(p, max_new_tokens=4,
                                       deadline_s=deadline_s))
            for p in burst_prompts(seed=3, n=2, min_len=8, max_len=8):
                reqs.append(eng.submit(p, max_new_tokens=4))
            eng.run_until_complete()
        eng.pool.check_leaks()
        c = eng.stats()["counters"]
        ttfts = [m.to_dict()["ttft_s"]
                 for m in eng.metrics.requests.values()
                 if m.to_dict()["ttft_s"] is not None]
        p99 = float(np.percentile(ttfts, 99)) if ttfts else float("nan")
        return (c["goodput_tokens"], c["requests_shed"],
                c["requests_shed"] / len(reqs), p99,
                c["tokens_generated"])

    g_off, shed_off, rate_off, p99_off, _ = run(False)
    t_mid = time.perf_counter()
    g_on, shed_on, rate_on, p99_on, tok_on = run(True)
    dt_on = time.perf_counter() - t_mid
    assert shed_off == 0                 # nothing sheds with it off
    ratio = g_on / g_off if g_off > 0 else float("inf")
    print(f"# overload: goodput off={g_off} on={g_on} tokens "
          f"(ratio {ratio:.2f}x), shed rate off={rate_off:.2f} "
          f"on={rate_on:.2f}, p99 ttft off={p99_off * 1e3:.1f}ms "
          f"on={p99_on * 1e3:.1f}ms", file=sys.stderr)

    # --- fixed-HBM int8-vs-fp32: same kv_pool_bytes budget, same
    # KV-limited burst.  The quantized pool fits ~3.5x the blocks, so
    # more requests decode CONCURRENTLY: occupancy = generated tokens
    # per decode iteration, goodput = tokens per second under an
    # injected per-step delay that dominates wall-clock (so the ratio
    # tracks the iteration count, not host speed).  ISSUE 20's
    # headline: both strictly higher at int8, occupancy >= 1.5x.
    from paddle_tpu.serving.cache import BlockKVPool

    hbm = 12 * BlockKVPool.block_bytes_for(
        model.config.num_hidden_layers, 4,
        model.config.num_key_value_heads,
        model.config.hidden_size // model.config.num_attention_heads,
        model.config.dtype, None)
    quant = {}
    for kv_dtype in (None, "int8"):
        eng = Engine(model, ServingConfig(
            max_batch_size=8, block_size=4, num_blocks=None,
            kv_pool_bytes=hbm, kv_cache_dtype=kv_dtype,
            chunk_tokens=16, max_queue_len=64))
        burst = burst_prompts(seed=7, n=12, min_len=10, max_len=14)
        # warm OUTSIDE the timed region: the int8 step kinds compile
        # fresh here while the fp32 kinds were compiled by the shed
        # phase above — timing compiles would swamp the serve loop
        eng.submit(burst_prompts(seed=1, n=1, min_len=8, max_len=8)[0],
                   max_new_tokens=2)
        eng.run_until_complete()
        warm = eng.stats()["counters"]
        base = (warm["tokens_generated"], warm["decode_iterations"])
        t0 = time.perf_counter()
        # delay large enough to dominate the host-side step cost, so
        # the goodput ratio tracks iteration count (machine-independent)
        with FaultPlan(seed=7, step_delay_s=0.01):
            for p in burst:
                eng.submit(p, max_new_tokens=8)
            eng.run_until_complete()
        dt = time.perf_counter() - t0
        eng.pool.check_leaks()
        c = eng.stats()["counters"]
        toks = c["tokens_generated"] - base[0]
        iters = c["decode_iterations"] - base[1]
        quant[kv_dtype] = {
            "blocks": eng.num_blocks,
            "occupancy": toks / iters,
            "goodput_tps": toks / dt,
        }
    occ_ratio = quant["int8"]["occupancy"] / quant[None]["occupancy"]
    gp_ratio = quant["int8"]["goodput_tps"] / quant[None]["goodput_tps"]
    print(f"# overload fixed-HBM ({hbm} B): fp32 "
          f"{quant[None]['blocks']} blocks occ="
          f"{quant[None]['occupancy']:.2f} vs int8 "
          f"{quant['int8']['blocks']} blocks occ="
          f"{quant['int8']['occupancy']:.2f} "
          f"(occupancy {occ_ratio:.2f}x, goodput {gp_ratio:.2f}x)",
          file=sys.stderr)
    return round(float(ratio), 3), {
        "tokens_per_sec_per_chip": round(
            tok_on / dt_on / _n_chips(), 1),
        "int8_occupancy_ratio_fixed_hbm": round(occ_ratio, 3),
        "int8_goodput_ratio_fixed_hbm": round(gp_ratio, 3),
        "fixed_hbm_blocks_fp32": quant[None]["blocks"],
        "fixed_hbm_blocks_int8": quant["int8"]["blocks"]}


def _spec_decode_bench(on_tpu: bool):
    """BENCH_ONLY=spec_decode: goodput under deadline pressure with
    speculative decoding on vs off (README: Sampling, speculative
    decoding & streaming).  The same requests run twice under an
    injected per-step slowdown (FaultPlan step_delay_s, so the outcome
    is machine-independent): the plain engine pays one delayed decode
    step per token, while the speculative engine pays two delayed steps
    (draft scan + verify) per K+1 committed tokens — with K=3 and a
    weight-identical draft (accept rate 1.0, the CEILING a real distilled
    draft approaches; reported as such) that is 2 steps per 4 tokens,
    a 2x wall-clock win the deadline is tuned to detect.  Deadline-bound
    requests finish inside their SLO only with speculation on, so PR
    10's goodput counter moves; a deadline-free request keeps the OFF
    goodput nonzero so the ratio stays finite.  Reported value is the
    on/off goodput ratio (> 1 means speculation converts busted
    deadlines into met ones); accept rate, TPOT speedup and
    tokens/sec/chip ride in the JSON line."""
    import paddle_tpu as paddle
    from paddle_tpu.models import LlamaConfig, LlamaForCausalLM
    from paddle_tpu.resilience.chaos import FaultPlan, burst_prompts
    from paddle_tpu.serving import (Engine, ServingConfig,
                                    SpeculativeConfig)

    k_draft, delay_s, deadline_s = 4, 0.03, 0.9
    paddle.seed(0)
    model = LlamaForCausalLM(LlamaConfig.tiny())
    model.eval()

    def run(spec_on):
        eng = Engine(model, ServingConfig(
            max_batch_size=4, block_size=4, num_blocks=96,
            chunk_tokens=16, max_queue_len=32,
            speculative=(SpeculativeConfig(draft_model=model,
                                           num_draft_tokens=k_draft)
                         if spec_on else None)))
        # warm OUTSIDE the fault plan: compile time must not eat into
        # the deadline comparison
        eng.generate(burst_prompts(seed=1, n=1, min_len=8, max_len=8),
                     max_new_tokens=k_draft + 2)
        reqs = []
        with FaultPlan(seed=11, step_delay_s=delay_s):
            t0 = time.perf_counter()
            # 41 tokens of injected sleep: ~42 delayed steps (1.26s)
            # off; on, ~ceil(40/5)=8 verify iterations at TWO delayed
            # steps each (draft scan + verify) plus two delayed prefill
            # pairs — ~0.6s, comfortably inside the 0.9s deadline
            reqs.append(eng.submit(
                burst_prompts(seed=5, n=1, min_len=8, max_len=8)[0],
                max_new_tokens=41, deadline_s=deadline_s))
            reqs.append(eng.submit(
                burst_prompts(seed=6, n=1, min_len=8, max_len=8)[0],
                max_new_tokens=5))
            eng.run_until_complete()
            dt = time.perf_counter() - t0
        eng.pool.check_leaks()
        c = eng.stats()["counters"]
        tok = sum(len(r.generated) for r in reqs)
        return (c["goodput_tokens"], tok, dt,
                eng.metrics.spec_accept_rate())

    g_off, tok_off, dt_off, _ = run(False)
    g_on, tok_on, dt_on, accept = run(True)
    ratio = g_on / g_off if g_off > 0 else float("inf")
    tpot_speedup = (tok_on / dt_on) / (tok_off / dt_off)
    print(f"# spec_decode: goodput off={g_off} on={g_on} tokens "
          f"(ratio {ratio:.2f}x), accept_rate={accept:.3f} "
          f"(weight-identical draft ceiling), K={k_draft}, "
          f"tpot speedup {tpot_speedup:.2f}x", file=sys.stderr)
    return round(float(ratio), 3), {
        "spec_accept_rate": round(float(accept), 4),
        "spec_tpot_speedup": round(float(tpot_speedup), 3),
        "tokens_per_sec_per_chip": round(
            tok_on / dt_on / _n_chips(), 1)}


def _router_replay_bench(on_tpu: bool):
    """BENCH_ONLY=router_replay: the serving fleet router on a seeded
    multi-tenant trace (serving/replay.py), prefix-affinity placement
    vs round-robin on IDENTICAL fleets and the IDENTICAL trace (README:
    Serving fleet & router).  The trace mixes a chatty tenant sharing a
    long system prompt, a long-prompt tenant, and a burst tenant.
    Reported value is the affinity fleet's realized cached-token ratio
    (prompt tokens served from replica prefix caches); the round-robin
    ratio, both p99 TTFTs, and per-tenant goodput ride in the JSON line
    and print to stderr.  Affinity must beat round-robin on the ratio —
    round-robin scatters a tenant's requests across replicas, so each
    replica re-prefills the shared prefix — and not lose on p99 TTFT."""
    import paddle_tpu as paddle
    from paddle_tpu.models import LlamaConfig, LlamaForCausalLM
    from paddle_tpu.serving import (Engine, Router, ServingConfig,
                                    Tenant, build_trace, replay_trace)

    if on_tpu:
        cfg = LlamaConfig.tiny(max_position_embeddings=1024)
        tenants = [
            Tenant("chat", kind="chat", requests=16,
                   shared_prefix_tokens=192, tail_tokens=(8, 32),
                   max_new_tokens=8),
            Tenant("long", kind="long", requests=6,
                   shared_prefix_tokens=32, tail_tokens=(128, 256),
                   max_new_tokens=6),
            Tenant("burst", kind="burst", requests=12,
                   shared_prefix_tokens=64, tail_tokens=(4, 16),
                   max_new_tokens=4),
        ]
        blocks, bsz, chunk, horizon = 256, 16, 64, 24
    else:
        cfg = LlamaConfig.tiny()
        # shared prefixes dominate each prompt, so consolidation (one
        # prefix copy fleet-wide) vs duplication (one per replica) is
        # the measured difference, well clear of timing noise
        tenants = [
            Tenant("chat", kind="chat", requests=12,
                   shared_prefix_tokens=96, tail_tokens=(4, 12),
                   max_new_tokens=6),
            Tenant("long", kind="long", requests=4,
                   shared_prefix_tokens=16, tail_tokens=(48, 80),
                   max_new_tokens=4),
            Tenant("burst", kind="burst", requests=10,
                   shared_prefix_tokens=48, tail_tokens=(2, 8),
                   max_new_tokens=4),
        ]
        blocks, bsz, chunk, horizon = 128, 4, 32, 16
    paddle.seed(0)
    model = LlamaForCausalLM(cfg)
    model.eval()

    def fleet(policy):
        def rcfg(name):
            return ServingConfig(
                name=name, max_batch_size=4, block_size=bsz,
                num_blocks=blocks, chunk_tokens=chunk, max_queue_len=48)

        # weight high enough that transient queue imbalance never
        # unsticks a tenant from its prefix replica mid-trace
        return Router([Engine(model, rcfg(f"{policy[:2]}-0")),
                       Engine(model, rcfg(f"{policy[:2]}-1"))],
                      policy=policy, seed=0, affinity_weight=8.0)

    # warm ONCE: the compiled steps cache on the MODEL keyed by the
    # weights fingerprint, so every replica below reuses them and the
    # replayed TTFTs are compile-free
    warm = Engine(model, ServingConfig(max_batch_size=4, block_size=bsz,
                                       num_blocks=blocks,
                                       chunk_tokens=chunk))
    warm.generate([np.arange(1, chunk + 2, dtype=np.int32)],
                  max_new_tokens=2)

    trace = build_trace(tenants, seed=7, horizon=horizon,
                        vocab=cfg.vocab_size)
    # placement is deterministic per policy (identical logs every
    # repeat) but the fleet p99 TTFT is a max over ~a dozen wall-clock
    # samples — replay each fleet three times on FRESH replicas and
    # take the median p99 so scheduler jitter can't flip the headline
    # comparison either way
    reps = {"affinity": [], "round_robin": []}
    t0 = dt = None
    for _ in range(3):
        for policy in reps:
            if policy == "affinity":
                t0 = time.perf_counter()
            reps[policy].append(replay_trace(fleet(policy), trace))
            if policy == "affinity" and dt is None:
                dt = time.perf_counter() - t0
    aff, rr = reps["affinity"][0], reps["round_robin"][0]

    def med(runs, key):
        vals = sorted(r["fleet"][key] or 0 for r in runs)
        return vals[len(vals) // 2]

    # the ratio is NEARLY deterministic (cold placements are; once the
    # EWMAs warm a rare load spill can move one request), so the median
    # smooths both headline numbers the same way
    a_ratio = med(reps["affinity"], "cached_token_ratio")
    r_ratio = med(reps["round_robin"], "cached_token_ratio")
    a_p99 = med(reps["affinity"], "p99_ttft_s")
    r_p99 = med(reps["round_robin"], "p99_ttft_s")
    goodput = sum(t["goodput_tokens"] for t in aff["tenants"].values())
    assert a_ratio >= r_ratio, (a_ratio, r_ratio)
    per_tenant = " ".join(
        f"{name}:{t['goodput_tokens']}tok/p99="
        f"{(t['p99_ttft_s'] or 0) * 1e3:.1f}ms"
        for name, t in aff["tenants"].items())
    print(f"# router_replay: cached_ratio affinity={a_ratio:.3f} "
          f"round_robin={r_ratio:.3f}, p99 ttft affinity="
          f"{(a_p99 or 0) * 1e3:.1f}ms round_robin="
          f"{(r_p99 or 0) * 1e3:.1f}ms, placements="
          f"{aff['fleet']['placements']}, {per_tenant}",
          file=sys.stderr)
    return round(float(a_ratio), 4), {
        "round_robin_cached_token_ratio": round(float(r_ratio), 4),
        "affinity_p99_ttft_ms": a_p99 and round(a_p99 * 1e3, 2),
        "round_robin_p99_ttft_ms": r_p99 and round(r_p99 * 1e3, 2),
        "goodput_tokens": goodput,
        "tokens_per_sec_per_chip": round(goodput / dt / _n_chips(), 1)}


def _paged_attn_bench(on_tpu: bool):
    """BENCH_ONLY=paged_attn: fused vs scatter/gather paged-attention
    decode (kernels/paged_attention).  Times the COMPILED paged decode
    step — the whole serving TPOT unit — with the fused kernel pinned
    on vs off, on identical shapes and pool state: same model, same
    block tables, same mid-stream frontiers.  Reported value is the
    fused decode step time (TPOT) in ms; the unfused time and the
    speedup ride in the JSON line and print to stderr."""
    import jax
    import jax.numpy as jnp

    import paddle_tpu as paddle
    from paddle_tpu.models import LlamaConfig, LlamaForCausalLM
    from paddle_tpu.models.generation import make_paged_decode_step

    if on_tpu:
        cfg = LlamaConfig.tiny(
            vocab_size=32000, hidden_size=1024, intermediate_size=2816,
            num_hidden_layers=8, num_attention_heads=16,
            num_key_value_heads=8, max_position_embeddings=2048,
            dtype="bfloat16")
        B, bs, nbs, steps, warmup = 16, 32, 64, 50, 8
    else:
        cfg = LlamaConfig.tiny()
        B, bs, nbs, steps, warmup = 4, 8, 8, 10, 2
    nb = 1 + B * nbs        # block 0 reserved as the garbage block
    paddle.seed(0)
    model = LlamaForCausalLM(cfg)
    model.eval()
    kvh = cfg.num_key_value_heads
    hd = cfg.hidden_size // cfg.num_attention_heads
    dt_kv = jnp.bfloat16 if cfg.dtype == "bfloat16" else jnp.float32
    pools = [(jnp.zeros((nb, bs, kvh, hd), dt_kv),
              jnp.zeros((nb, bs, kvh, hd), dt_kv))
             for _ in range(cfg.num_hidden_layers)]
    bt = jnp.asarray(1 + np.arange(B * nbs).reshape(B, nbs), jnp.int32)
    # mid-stream frontiers at 3/4 of max context: the gather/split-K
    # sweep has real work, matching steady-state decode
    ctx = (bs * nbs * 3) // 4
    lengths = jnp.asarray(np.full(B, ctx), jnp.int32)
    rng = np.random.RandomState(0)
    tok = jnp.asarray(rng.randint(1, cfg.vocab_size, (B, 1)), jnp.int32)

    def time_step(step, p=pools):
        jax.block_until_ready(step(tok, p, bt, lengths)[0])  # compile
        for _ in range(warmup):
            jax.block_until_ready(step(tok, p, bt, lengths)[0])
        t0 = time.perf_counter()
        for _ in range(steps):
            jax.block_until_ready(step(tok, p, bt, lengths)[0])
        return (time.perf_counter() - t0) / steps

    t_unfused = time_step(make_paged_decode_step(model, fused=False))
    t_fused = time_step(make_paged_decode_step(model, fused=True))
    # quantized TPOT: the same step over an int8 pool (codes + per-row
    # scale sidecars) — the DMA-boundary dequant path, 4x fewer KV
    # bytes per decode step than fp32 (2x vs bf16)
    pools_q = [(jnp.zeros((nb, bs, kvh, hd), jnp.int8),
                jnp.zeros((nb, bs, kvh, hd), jnp.int8),
                jnp.ones((nb, bs), jnp.float32),
                jnp.ones((nb, bs), jnp.float32))
               for _ in range(cfg.num_hidden_layers)]
    t_int8 = time_step(make_paged_decode_step(model, fused=True,
                                              kv_cache_dtype="int8"),
                       p=pools_q)
    speedup = t_unfused / t_fused if t_fused > 0 else float("inf")
    q_speedup = t_fused / t_int8 if t_int8 > 0 else float("inf")
    print(f"# paged_attn: decode step unfused={t_unfused * 1e3:.3f}ms "
          f"fused={t_fused * 1e3:.3f}ms speedup={speedup:.2f}x "
          f"int8={t_int8 * 1e3:.3f}ms ({q_speedup:.2f}x vs fused) "
          f"(B={B}, ctx={ctx}, block_size={bs})", file=sys.stderr)
    return round(t_fused * 1e3, 3), {
        "unfused_tpot_ms": round(t_unfused * 1e3, 3),
        "fused_vs_unfused_speedup": round(speedup, 3),
        "int8_kv_tpot_ms": round(t_int8 * 1e3, 3),
        "int8_vs_fused_speedup": round(q_speedup, 3),
        "tokens_per_sec_per_chip": round(B / t_fused / _n_chips(), 1)}


def _fusion_miner_bench(on_tpu: bool):
    """BENCH_ONLY=fusion_miner: predicted-vs-measured HBM-byte savings
    of the mined chunked-prefill fusion — a standing test of the
    miner's cost model.  Predicted = the fusion miner's bytes-saved for
    the above-threshold candidates on the UNFUSED prefill trace;
    measured = the xray-priced byte delta between the unfused and fused
    prefill programs (fused traced under force_pallas_interpret so the
    pallas kernels price through kernels/costs).  The ratio must stay
    within 2x in either direction, or the byte model has drifted from
    what fusing actually buys.  Wall-clock of the compiled fused vs
    unfused prefill step rides along in the JSON line."""
    import jax
    import jax.numpy as jnp

    import paddle_tpu as paddle
    from paddle_tpu.analysis import fusionminer, xray
    from paddle_tpu.kernels.fusion import force_pallas_interpret
    from paddle_tpu.models import LlamaConfig, LlamaForCausalLM
    from paddle_tpu.models.generation import make_chunked_prefill_step

    paddle.seed(0)
    cfg = LlamaConfig.tiny()
    net = LlamaForCausalLM(cfg)
    net.eval()
    _, prefill_args = xray._serving_abstract_args(
        net, batch=4, num_blocks=32, block_size=8, max_blocks_per_seq=8,
        chunk_tokens=32)

    rep = fusionminer.mine(
        make_chunked_prefill_step(net, fused=False), prefill_args,
        name="serving::prefill_step", chip="v5e",
        threshold_bytes=fusionminer.DEFAULT_THRESHOLD_BYTES)
    predicted = sum(c.bytes_saved for c in rep.above_threshold())
    unfused_rep = xray.analyze(
        make_chunked_prefill_step(net, fused=False), prefill_args,
        name="u", chip="v5e")
    with force_pallas_interpret():
        fused_rep = xray.analyze(
            make_chunked_prefill_step(net, fused=True), prefill_args,
            name="f", chip="v5e")
    measured = unfused_rep.bytes - fused_rep.bytes
    ratio = predicted / measured if measured else float("inf")
    assert 0.5 <= ratio <= 2.0, (
        f"miner predicted {predicted:.0f}B but fusing removed "
        f"{measured:.0f}B of priced traffic (ratio {ratio:.2f})")

    # compiled-step wall clock, fused vs unfused, same shapes/state
    B, bs, nbs, C = 1, 8, 8, 32
    nb = 1 + B * nbs
    kvh = cfg.num_key_value_heads
    hd = cfg.hidden_size // cfg.num_attention_heads
    steps, warmup = (50, 8) if on_tpu else (10, 2)
    pools = [(jnp.zeros((nb, bs, kvh, hd), jnp.float32),
              jnp.zeros((nb, bs, kvh, hd), jnp.float32))
             for _ in range(cfg.num_hidden_layers)]
    bt = jnp.asarray(1 + np.arange(B * nbs).reshape(B, nbs), jnp.int32)
    rng = np.random.RandomState(0)
    ids = jnp.asarray(rng.randint(1, cfg.vocab_size, (B, C)), jnp.int32)
    start = jnp.zeros((B,), jnp.int32)
    last = jnp.asarray(C - 1, jnp.int32)

    def time_step(step):
        jax.block_until_ready(step(ids, pools, bt, start, last)[0])
        for _ in range(warmup):
            jax.block_until_ready(step(ids, pools, bt, start, last)[0])
        t0 = time.perf_counter()
        for _ in range(steps):
            jax.block_until_ready(step(ids, pools, bt, start, last)[0])
        return (time.perf_counter() - t0) / steps

    t_unfused = time_step(make_chunked_prefill_step(net, fused=False))
    t_fused = time_step(make_chunked_prefill_step(net, fused=True))
    speedup = t_unfused / t_fused if t_fused > 0 else float("inf")
    print(f"# fusion_miner: predicted={predicted / 1024.0:.1f}KiB "
          f"measured={measured / 1024.0:.1f}KiB ratio={ratio:.2f} "
          f"(top: {rep.candidates[0].code} rank 1), prefill chunk "
          f"unfused={t_unfused * 1e3:.3f}ms fused={t_fused * 1e3:.3f}ms "
          f"speedup={speedup:.2f}x", file=sys.stderr)
    return round(float(ratio), 3), {
        "predicted_kib": round(predicted / 1024.0, 1),
        "measured_kib": round(measured / 1024.0, 1),
        "unfused_prefill_ms": round(t_unfused * 1e3, 3),
        "fused_prefill_ms": round(t_fused * 1e3, 3),
        "fused_vs_unfused_speedup": round(speedup, 3)}


def _moe_plan_bench(on_tpu):
    """BENCH_ONLY=moe_plan: static shard-plan metrics for the MoE block
    on the canonical expert mesh — no devices touched, the number is the
    analyzer's wire-byte estimate, so a routing/propagation regression
    (a2a pair stops firing, an unplanned gather appears) moves the
    artifact even on CPU-only rounds."""
    del on_tpu  # the plan is abstract: same answer on every backend
    from paddle_tpu.analysis.shardplan import audit_shardplan

    (rep,) = audit_shardplan(steps=("moe",))
    unplanned = sum(1 for c in rep.collectives if not c.planned)
    a2a = sum(1 for c in rep.collectives if c.kind == "all_to_all")
    by_dtype = {k: int(v) for k, v in
                sorted(rep.per_chip_peak_hbm_by_dtype.items())}
    print(f"# moe_plan: comm={int(rep.comm_bytes)}B on wire, "
          f"{len(rep.collectives)} collectives ({a2a} all_to_all, "
          f"{unplanned} unplanned), per-chip peak HBM "
          f"{rep.per_chip_peak_hbm_bytes}B by dtype {by_dtype}, "
          f"{len(rep.errors())} error(s)", file=sys.stderr)
    assert unplanned == 0 and not rep.errors()
    return round(rep.comm_bytes / 1024.0, 3)


def _dcn_plan_bench(on_tpu):
    """BENCH_ONLY=dcn_plan: multi-host shard-plan metrics — the five
    registered steps priced on an emulated 2-host x (2,2) topology.  No
    devices touched; the number is the analyzer's DCN wire-byte
    estimate, so a decomposition regression (a host-crossing collective
    stops splitting into ICI + DCN phases, an axis silently lands on
    the wrong link level) moves the artifact even on CPU-only rounds."""
    del on_tpu  # the plan is abstract: same answer on every backend
    from paddle_tpu.analysis.shardplan import (Topology, audit_shardplan,
                                               recommend_layouts)

    topo = Topology(hosts=2, chips_per_host=(2, 2))
    reports = audit_shardplan(topology=topo)
    unplanned = sum(1 for r in reports for c in r.collectives
                    if not c.planned)
    n_err = sum(len(r.errors()) for r in reports)
    ici = sum(r.ici_comm_bytes for r in reports)
    dcn = sum(r.dcn_comm_bytes for r in reports)
    host_hbm = max(r.per_host_peak_hbm_bytes for r in reports)
    train = next(r for r in reports if "train" in r.name)
    top = recommend_layouts(train)[0]
    print(f"# dcn_plan: {len(reports)} step(s) on 2 host(s) x (2,2), "
          f"wire ICI={ici / 1024.0:.1f}KiB DCN={dcn / 1024.0:.1f}KiB, "
          f"per-host peak HBM {host_hbm}B, {unplanned} unplanned, "
          f"{n_err} error(s), train top layout: {top.describe()}",
          file=sys.stderr)
    assert unplanned == 0 and n_err == 0
    return round(dcn / 1024.0, 3)


def _run_single(which: str, on_tpu: bool):
    """BENCH_ONLY=<name>: run ONE secondary workload as its own artifact
    (VERDICT r4 weak #2 — 'extras timed out' zeroed resnet/bert/unet for
    four rounds; individually they get their own process + time budget)."""
    fns = {"moe": _moe_bench, "unet": _unet_bench, "resnet": _resnet_bench,
           "bert": _bert_dp_bench, "serve_llama": _serving_bench,
           "prefix_cache": _prefix_cache_bench,
           "resilient_train": _resilience_bench,
           "elastic_ckpt": _elastic_ckpt_bench,
           "observe_overhead": _observe_overhead_bench,
           "mesh_train": _mesh_train_bench,
           "overload": _overload_bench,
           "spec_decode": _spec_decode_bench,
           "router_replay": _router_replay_bench,
           "moe_plan": _moe_plan_bench,
           "dcn_plan": _dcn_plan_bench,
           "paged_attn": _paged_attn_bench,
           "fusion_miner": _fusion_miner_bench}
    metric, unit = _ONLY_METRICS[which]
    value = fns[which](on_tpu)
    extras = {}
    if isinstance(value, tuple):
        value, extras = value
    out = {"metric": metric, "value": value, "unit": unit,
           "vs_baseline": None}
    out.update(extras)   # serving headlines: tokens_per_sec_per_chip &c.
    _emit(out)


def run_bench():
    import os

    import jax

    from paddle_tpu.core.compile_cache import enable_compile_cache

    enable_compile_cache()
    devices, backend = jax.devices(), jax.default_backend()
    on_tpu = backend == "tpu"
    device_kind = devices[0].device_kind

    which = os.environ.get("BENCH_ONLY", "")
    if which:
        _run_single(which, on_tpu)
        return

    import paddle_tpu as paddle
    from paddle_tpu import jit
    from paddle_tpu.models import LlamaConfig, LlamaForCausalLM
    from paddle_tpu.optimizer import AdamW

    bench_config = os.environ.get("BENCH_CONFIG", "")
    if on_tpu and bench_config == "llama1b_s4096":
        # North-star-shaped memory proof (VERDICT r5 item 3): ~1.10B-param
        # Llama (TinyLlama-1.1B plan: h2048/i5632/22L/32h/4kv) at s4096,
        # bf16, per-layer remat + donated train state + chunked fused
        # lm-head loss.  Validates the remat/donation/HBM story the 8B
        # extrapolation rests on, on one 16 GB v5e.
        cfg = LlamaConfig(
            vocab_size=32000, hidden_size=2048, intermediate_size=5632,
            num_hidden_layers=22, num_attention_heads=32,
            num_key_value_heads=4, max_position_embeddings=4096,
            dtype="bfloat16", recompute=True)
        batch, seq, steps, warmup = 4, 4096, 10, 3
        batch = int(os.environ.get("BENCH_BATCH", batch))
    elif on_tpu:
        # 603M-param Llama (hidden 2048 → 128-lane-aligned matmuls that
        # saturate the MXU).  Fits one v5e chip with the chunked fused
        # lm-head loss; measured MFU ~0.47 vs 0.22 for the old h1024 config.
        cfg = LlamaConfig(
            vocab_size=32000, hidden_size=2048, intermediate_size=5632,
            num_hidden_layers=10, num_attention_heads=16,
            num_key_value_heads=8, max_position_embeddings=2048,
            dtype="bfloat16")
        batch, seq, steps, warmup = 8, 2048, 20, 5
        # experiment knob (tools/run_tpu_experiments.sh): batch override
        batch = int(os.environ.get("BENCH_BATCH", batch))
    else:  # smoke path for CPU dev runs
        cfg = LlamaConfig.tiny()
        if bench_config == "llama1b_s4096":
            cfg.recompute = True  # exercise the remat path on CPU too
        batch, seq, steps, warmup = 2, 64, 5, 2
    cfg.fused_lm_loss = True  # opt-in: bench never consumes the logits

    rng = np.random.RandomState(0)
    model = LlamaForCausalLM(cfg)
    opt = AdamW(1e-4, parameters=model.parameters())

    @jit.to_static
    def train_step(tokens):
        loss, _ = model(tokens, labels=tokens)
        loss.backward()
        opt.step()
        opt.clear_grad()
        return loss

    tokens = paddle.to_tensor(
        rng.randint(0, cfg.vocab_size, (batch, seq)).astype(np.int32))
    for _ in range(warmup):
        loss = train_step(tokens)
    np.asarray(loss.numpy())  # hard sync

    # tail sync (standard XLA benching: dispatch all steps, block once) —
    # each step's loss depends on the previous step's donated state, so
    # the final block covers the whole chain.
    t0 = time.perf_counter()
    for _ in range(steps):
        loss = train_step(tokens)
    loss._value.block_until_ready()
    dt = time.perf_counter() - t0

    tokens_per_step = batch * seq
    tokens_per_sec = tokens_per_step * steps / dt

    # params (embedding counted once) for 6N flops/token + attention term
    n_params = sum(int(np.prod(p.shape)) for p in model.parameters())
    flops_per_token = (6.0 * n_params
                       + 12.0 * cfg.num_hidden_layers * cfg.hidden_size * seq)
    achieved_flops = tokens_per_sec * flops_per_token
    peak = _peak_flops(device_kind) if on_tpu else None
    mfu = achieved_flops / peak if peak else None

    headline = {
        "metric": "llama_pretrain_tokens_per_sec_per_chip",
        "value": round(tokens_per_sec, 1),
        "unit": "tokens/s/chip",
        "vs_baseline": round(mfu, 4) if mfu is not None else None,
    }
    skip_extras = os.environ.get("BENCH_EXTRAS", "1") == "0"
    extra = {"batch": batch, "seq": seq}
    if bench_config:
        # tag smoke runs distinctly: a CPU run under
        # BENCH_CONFIG=llama1b_s4096 measures the tiny model, and must
        # not be filterable as 1B evidence
        extra["config"] = (bench_config if on_tpu
                           else f"smoke_{bench_config}")
    if skip_extras:
        extra["extras_skipped"] = True
    # HBM high-water (PJRT peak_bytes_in_use): the memory-proof datum
    # for the llama1b_s4096 config; cheap, so reported for every run
    from paddle_tpu import device as _pdev

    hbm_peak = _pdev.max_memory_allocated()
    if hbm_peak:
        extra["hbm_high_water_bytes"] = int(hbm_peak)
        print(f"# HBM high-water: {hbm_peak / 2**30:.2f} GiB",
              file=sys.stderr)
    # second timed pass, per-step sync (the host waits on every step)
    t0 = time.perf_counter()
    for _ in range(steps):
        loss = train_step(tokens)
        loss._value.block_until_ready()
    extra["per_step_sync_tokens_per_sec"] = round(
        tokens_per_step * steps / (time.perf_counter() - t0), 1)
    if skip_extras:
        _emit({**headline, "extra": dict(extra)})
        print(f"# extras skipped (BENCH_EXTRAS=0); model="
              f"{n_params/1e6:.1f}M batch={batch} seq={seq} "
              f"step_time={dt/steps*1000:.1f}ms backend={backend}",
              file=sys.stderr)
        return
    # secondary workloads; one that fails fails the run
    extra["moe_tokens_per_sec"] = _moe_bench(on_tpu)
    ov, t_us, j_us = _eager_overhead_us()
    extra["eager_op_overhead_us"] = ov
    print(f"# eager dispatch: tensor={t_us}us/op jnp={j_us}us/op "
          f"overhead={ov}us/op", file=sys.stderr)
    extra["unet_denoise_ms"] = _unet_bench(on_tpu)
    extra["resnet50_images_per_sec"] = _resnet_bench(on_tpu)
    extra["bert_dp_tokens_per_sec"] = _bert_dp_bench(on_tpu)
    serve_tps, serve_extras = _serving_bench(on_tpu)
    extra["serve_llama_tokens_per_sec"] = serve_tps
    extra["serve_llama_tokens_per_sec_per_chip"] = \
        serve_extras["tokens_per_sec_per_chip"]

    _emit({**headline, "extra": extra})
    print(f"# model={n_params/1e6:.1f}M params, batch={batch}, seq={seq}, "
          f"steps={steps}, step_time={dt/steps*1000:.1f}ms, "
          f"loss={float(np.asarray(loss.numpy())):.4f}, "
          f"backend={backend}, device_kind={device_kind}, "
          f"peak={peak and peak/1e12 or 0:.0f}TF", file=sys.stderr)


_ONLY_METRICS = {
    "moe": ("moe_tokens_per_sec", "tokens/s"),
    "unet": ("unet_denoise_ms", "ms"),
    "resnet": ("resnet50_images_per_sec", "images/s"),
    "bert": ("bert_dp_tokens_per_sec", "tokens/s/chip"),
    "serve_llama": ("serve_llama_tokens_per_sec", "tokens/s"),
    "prefix_cache": ("prefix_cache_ttft_speedup", "x"),
    "resilient_train": ("resilient_ckpt_roundtrip_ms", "ms"),
    "elastic_ckpt": ("elastic_ckpt_roundtrip_ms", "ms"),
    "observe_overhead": ("observe_overhead_pct", "%"),
    "mesh_train": ("mesh_train_tokens_per_sec_per_chip", "tokens/s/chip"),
    "overload": ("overload_goodput_ratio", "x"),
    "spec_decode": ("spec_decode_goodput_ratio", "x"),
    "router_replay": ("router_replay_cached_token_ratio", "ratio"),
    "moe_plan": ("moe_plan_comm_kib", "KiB"),
    "dcn_plan": ("dcn_plan_dcn_wire_kib", "KiB"),
    "paged_attn": ("paged_attn_fused_tpot_ms", "ms"),
    "fusion_miner": ("fusion_miner_pred_vs_measured", "x"),
}


def main():
    import os

    if "--retune" in sys.argv[1:] or \
            os.environ.get("BENCH_RETUNE", "") in ("1", "true", "True"):
        # autotune escape hatch: ignore cached tile winners and
        # re-measure once (kernels/autotune reads this env switch)
        os.environ["PADDLE_TPU_RETUNE"] = "1"
    run_bench()     # any failure propagates: traceback, non-zero exit


if __name__ == "__main__":
    main()
