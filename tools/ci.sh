#!/usr/bin/env bash
# CI driver (reference: paddle/scripts/paddle_build.sh + tools/ci_* gates).
# Runs the test suite, the API-freeze gate and the examples as smoke tests.
set -euo pipefail
cd "$(dirname "$0")/.."

export PYTHONPATH="$PWD${PYTHONPATH:+:$PYTHONPATH}"
# CI validates on the CPU backend (the TPU is exercised by chip_smoke.py);
# the ambient env often pins an accelerator platform, so override it.
export JAX_PLATFORMS=${CI_JAX_PLATFORMS:-cpu}
export XLA_FLAGS=${XLA_FLAGS:---xla_force_host_platform_device_count=8}

echo "== lint =="
# repo AST lint: op-schema parity, inplace-alias pairing, jax-import
# boundaries, mutable defaults.  Exit 1 on any ERROR finding; suppress
# intentional exceptions with `# lint-tpu: disable[-file]=CODE` (README).
python tools/lint_tpu.py paddle_tpu/

echo "== program x-ray (jaxpr hazards + HBM budget) =="
# traces the registered train/paged-decode/chunked-prefill steps on the
# CPU (1,1) config: ERROR hazards (f64 eqns, host callbacks H109) or a
# peak-live-HBM over the chip budget (H110) fail CI (README: Program X-ray)
python tools/lint_tpu.py --xray

echo "== shard plan (SPMD layout + per-chip HBM + collectives) =="
# propagates the canonical llama SpecLayout through the registered
# train/decode/chunked-prefill jaxprs on a simulated (data=2,fsdp=2,tp=2)
# mesh: resharding conflicts (S205), comm-bound steps (S207), or a
# per-chip HBM budget breach fail CI (README: Sharding plan analyzer)
python tools/lint_tpu.py --shardplan

echo "== shard plan: MoE + sequence-parallel workloads =="
# the MoE block on an expert mesh and the ring-attention block on an sp
# mesh must land fully planned: every collective layout-implied (the
# a2a dispatch/combine pair, the per-hop ppermutes), zero unplanned,
# zero unpriced primitives (S210), no capacity overflow (S211)
# (README: Planning new workloads)
python tools/lint_tpu.py --shardplan --steps moe \
  --mesh data=2,fsdp=2,expert=2 --fail-on-unplanned
python tools/lint_tpu.py --shardplan --steps ring \
  --mesh data=2,sp=2,tp=2 --fail-on-unplanned

echo "== dcn plan (multi-host topology: hierarchical ICI/DCN pricing) =="
# all five registered steps priced on an emulated 2-host x (2,2)
# topology: host-crossing collectives decompose into ICI + DCN phases;
# a DCN edge in a latency-critical step (S213), an avoidably-DCN hot
# axis (S214), or an unhideable DCN phase (S215) at ERROR fails CI
# (README: Multi-host planning)
python tools/lint_tpu.py --shardplan --hosts 2 --chips-per-host 2,2 \
  --fail-on-unplanned
python tools/lint_tpu.py --shardplan --steps moe \
  --mesh data=2,fsdp=2,expert=2 --hosts 2 --fail-on-unplanned
python tools/lint_tpu.py --shardplan --steps ring \
  --mesh data=2,sp=2,tp=2 --hosts 2 --fail-on-unplanned
# the machine-readable report must stay parseable (consumed by fleet
# dashboards); validate the JSON shape end to end
python tools/lint_tpu.py --shardplan --hosts 2 --steps train --json \
  | python -c "import json,sys; r=json.load(sys.stdin)[0]; \
assert r['hosts'] == 2 and 'dcn' in r['wire_bytes'], r"

echo "== hazard scan (H112 device-count + H113 process-write races) =="
# H112: jax.device_count()/len(jax.devices()) in per-process code paths
# and hardcoded chip counts in mesh constructors break under multi-host
# launch.  H113: ungated checkpoint-path writes — under jax.distributed
# EVERY host runs the line, so N processes race on the same file.
# ERROR findings fail CI (README: Hazards)
python tools/lint_tpu.py --hazards

echo "== mesh execution (2x2x2 SPMD on forced host devices) =="
# runtime MeshExecutor over an emulated 8-device host: train-loss parity
# (2,2,2) vs (1,1,1), serving token parity vs generate() with tp=2, zero
# retraces, and S209 plan-vs-runtime reconciliation (README: Mesh
# execution).  Env already forces JAX_PLATFORMS=cpu + 8 host devices
# above; run the module on its own so the mesh path gates every PR even
# when the main suite is filtered.
python -m pytest tests/test_mesh_executor.py -q

echo "== unit + integration tests =="
python -m pytest tests/ -q

echo "== example smoke runs =="
python examples/train_mnist.py --steps 3 --batch 8
python examples/pretrain_llama.py --steps 2 --batch 2 --seq 32
python examples/generate_text.py
python examples/serve_llama.py
python examples/serve_llama.py --prefix-cache

echo "== speculative decoding + SSE streaming =="
# draft-propose/target-verify speculation: greedy token parity with
# generate() AND the non-speculative engine across accept/reject
# boundaries (random small draft) plus the weight-identical-draft
# accept-rate ceiling, zero retraces after warmup, zero KV-pool leaks
# after rejected drafts; then one SSE round-trip over the streaming
# front door — per-token events in callback order, [DONE]-terminated
# (README: Sampling, speculative decoding & streaming)
python examples/serve_llama.py --speculative
python examples/serve_llama.py --stream

echo "== overload chaos (shed + hung-step recovery) =="
# seeded burst under an injected sustained slowdown: hopeless requests
# are shed at admission (zero timeouts), then an injected hung decode
# step is detected and retried by the watchdog and the engine recovers
# to SERVING — all with zero retraces (README: Overload control)
python examples/serve_llama.py --overload-chaos

echo "== fused serving kernels (forced on; XLA fallback on CPU) =="
# the fused paged-decode + RMSNorm-epilogue path forced on via
# ServingConfig(fused_kernels=True): token-for-token parity with the
# unfused engine AND with generate(), zero retraces on the fused steps;
# then the analysis gates over the fused programs — the x-ray must
# price the pallas kernel (no unpriced pallas_call) and the shard plan
# must land with zero S210 on the fused decode/prefill steps
# (README: Fused serving kernels)
python examples/serve_llama.py --fused
python tools/lint_tpu.py --xray --fused
python tools/lint_tpu.py --shardplan --steps fused_decode,fused_prefill \
  --fail-on-unplanned

echo "== quantized serving (int8 KV + weight-only int8) =="
# the int8 paged-KV engine (per-block-row absmax scales, dequant at the
# attention kernels' DMA boundary) and the weight-only-int8 engine must
# be greedy-token-exact with fp32 at zero retraces and zero pool leaks,
# and a fixed kv_pool_bytes budget must fit >= 1.5x the resident blocks
# at int8; the --xray --fused gate above already audits the QUANTIZED
# fused decode/prefill steps and the int8 fused kernel pricing
# (README: Quantized serving)
python examples/serve_llama.py --quantized

echo "== fusion miner (ranked F-series candidates + fused coverage) =="
# the fusion-candidate miner over the registered serving steps: the
# unfused traces must rank the hand-fused chains as candidates, and the
# FUSED steps (mined under force_pallas_interpret so the pallas leaves
# show up as F004 coverage) must leave zero unsuppressed non-F004
# candidates above the bytes-saved threshold — a mined chain that big
# should have become a kernel (README: Fusion-candidate miner)
python tools/lint_tpu.py --xray --fusion --fused --fail-on-candidates
# the machine-readable report must stay parseable (same consumer as the
# shardplan JSON); validate the fusion attachment shape end to end
python tools/lint_tpu.py --xray --fusion --json \
  | python -c "import json,sys; rs=json.load(sys.stdin); \
f=[r['fusion'] for r in rs if r['name'] == 'serving::prefill_step'][0]; \
assert f['n_above_threshold'] >= 1 and f['candidates'][0]['rank'] == 1, f"
python examples/export_and_serve.py
python examples/compat_journeys.py
python examples/hybrid_parallel_llama.py
python examples/resilient_train.py --steps 8 --kill-at 5
python examples/observe_train.py --steps 20

echo "== elastic multi-process (sharded ckpt + process-death chaos) =="
# four REAL spawned jax clusters (bootstrap.spawn_local: gloo
# collectives, genuine multi-controller runtime): uninterrupted
# reference run; 2-process run whose process 1 is hard-killed mid-save
# (partial step left uncommitted); 1-process restart from the same dir
# reassembling both hosts' shards (restore-with-reshard) — post-resume
# losses and final weights must be BIT-IDENTICAL to the reference; and
# S209 plan-vs-runtime reconciliation across a real 2-process mesh with
# Topology(hosts=2) (README: Elastic multi-host checkpointing)
timeout -k 10 600 python examples/elastic_train.py

echo "== serving fleet router (affinity placement + replica chaos) =="
# two named replicas behind serving.Router: a shared-prefix burst must
# consolidate on one replica (prefix-affinity placement), then a
# replica-scoped FaultPlan kills replica-1 mid-burst — the router
# quarantines it, drains the stranded requests and resubmits them to
# the survivor with zero lost requests and token parity against a
# single-engine run (README: Serving fleet & router)
python examples/serve_llama.py --router

echo "== multichip dryrun =="
python -c "import __graft_entry__ as g; g.dryrun_multichip(8)"

echo "CI OK"
