"""CPU rehearsal of ``run.py``'s code path: the real BENCHMARK.json, kinds,
layer metrics and reference, with each configuration shrunk to
``LlamaConfig.tiny()`` widths and each mix to a few short requests, in a
temporary copy of the benchmark's directories.  Imported and called,
never a child process.  Nothing it measures is a speed."""
from __future__ import annotations

import json
import os
import shutil

import jax

from benchmarks.harness import cells, runner
from benchmarks.harness.stats import clock

TINY_MODEL = {"vocab_size": 256, "hidden_size": 64, "intermediate_size": 128,
              "num_hidden_layers": 2, "num_attention_heads": 4,
              "num_key_value_heads": 2, "head_dim": 16,
              "max_position_embeddings": 128}
TINY_SERVING = {"max_batch_size": 4, "num_blocks": 64, "max_queue_len": 8,
                "max_model_len": 128}
TINY_LENGTHS = {
    "prompt_tokens": {"median": 24, "sigma": 0.5, "min": 4, "max": 64},
    "output_tokens": {"median": 8, "sigma": 0.5, "min": 2, "max": 16},
    "clients": 4, "pool": 8, "round": 4, "ramp_prompt_tokens": 16,
    "batch": 2, "sequence": 32}


def _rewrite(path, change):
    with open(path) as f:
        data = json.load(f)
    change(data)
    with open(path, "w") as f:
        json.dump(data, f)


def tiny_root(tmp_path) -> str:
    """A copy of BENCHMARK.json and the benchmark's directories under
    ``tmp_path`` with every configuration and mix made tiny."""
    root = str(tmp_path)
    bench = cells.load_benchmark()
    shutil.copy(os.path.join(cells.REPO_ROOT, "BENCHMARK.json"), root)
    for path in bench["paths"]:
        shutil.copytree(os.path.join(cells.REPO_ROOT, path),
                        os.path.join(root, path),
                        ignore=shutil.ignore_patterns("__pycache__", "tests"))

    def shrink_config(c):
        c.update(TINY_MODEL)
        c["model_config_kwargs"]["dtype"] = "float32"
        if "serving" in c:
            c["serving"].update(TINY_SERVING)

    def shrink_mix(m):
        m.update({k: v for k, v in TINY_LENGTHS.items() if k in m})

    for c in bench["configs"]:
        _rewrite(os.path.join(root, c["file"]), shrink_config)
    traffic = os.path.join(root, bench["paths"][0], "traffic")
    for name in os.listdir(traffic):
        _rewrite(os.path.join(traffic, name), shrink_mix)
    return root


TOY_ROUTED = {
    "source": "https://example.org/the-tests-own-routed-toy",
    "vocab_size": 256, "hidden_size": 64, "num_hidden_layers": 4,
    "num_experts": 16, "num_experts_per_tok": 4,
    "moe_intermediate_size": 32, "conv_L_cache": 3, "norm_eps": 1e-5,
    "model": "benchmarks.tests.toy_routed:ToyRoutedLM",
    "model_config": "benchmarks.tests.toy_routed:ToyRoutedConfig",
    "witness": "benchmarks.tests.toy_routed:witness",
    "reference": "benchmarks/reference/toy_routed.py",
    "costs": "benchmarks/costs/toy_routed.py",
    "model_config_kwargs": {"dtype": "bfloat16"},
    "training": {"optimizer": "AdamW", "learning_rate": 1e-4}}
TOY_JOB = {"kind": "train_job", "batch": 16, "sequence": 64,
           "warmup_steps": 2}
ROUTED_CELL = "toy-routed.toy-pretrain"
SERVED_CELL = "toy-served.toy-closed4"


def add_cell(root, name, config, traffic, like):
    """One more cell in the copy under ``root``, as a later PR brings
    it: a configuration file, a mix's file and entries in
    BENCHMARK.json, where it reports what the cell ``like`` reports.
    ``config`` and ``traffic`` are ``(name, content)``."""
    bench_dir = os.path.join(root, cells.load_benchmark(root)["paths"][0])
    for folder, (stem, content) in (("configs", config),
                                    ("traffic", traffic)):
        with open(os.path.join(bench_dir, folder, stem + ".json"),
                  "w") as f:
            json.dump(content, f)

    def enter(bench):
        rel = os.path.relpath(
            os.path.join(bench_dir, "configs", config[0] + ".json"), root)
        if config[0] not in [c["name"] for c in bench["configs"]]:
            bench["configs"].append({
                "name": config[0], "source": config[1]["source"],
                "file": rel, "reduced": [], "why": "a test's"})
        bench["workloads"].append({
            "name": name, "config": config[0], "traffic": traffic[0],
            "chips": 1, "why": "a test's"})
        for m in bench["end_to_end"] + bench["per_layer"]:
            if like in m.get("workloads", ()):
                m["workloads"].append(name)

    _rewrite(os.path.join(root, "BENCHMARK.json"), enter)


def add_witnessed_cells(root):
    """The two cells of the tests' own whose configurations name a model
    class, a witness, a reference and cost counts: files under ``root``
    and entries in its BENCHMARK.json, nothing edited."""
    bench = cells.load_benchmark(root)
    bench_dir = os.path.join(root, bench["paths"][0])
    here = os.path.dirname(os.path.abspath(__file__))
    os.makedirs(os.path.join(bench_dir, "costs"), exist_ok=True)
    for toy in ("toy_routed", "toy_served"):
        shutil.copy(os.path.join(here, toy + "_reference.py"),
                    os.path.join(bench_dir, "reference", toy + ".py"))
        shutil.copy(os.path.join(here, toy + "_costs.py"),
                    os.path.join(bench_dir, "costs", toy + ".py"))
    kinds = {}
    for w in bench["workloads"]:
        mix = cells.load_json(os.path.join(
            bench_dir, "traffic", w["traffic"] + ".json"))
        kinds.setdefault(mix["kind"], (w, mix))
    trained, _ = kinds["train_job"]
    add_cell(root, ROUTED_CELL, ("toy-routed", TOY_ROUTED),
             ("toy-pretrain", TOY_JOB), like=trained["name"])
    served, mix = kinds["closed_loop_serve"]
    config = dict(cells.load_json(os.path.join(
        bench_dir, "configs", served["config"] + ".json")),
        model="benchmarks.tests.toy_served:ServedLM",
        witness="benchmarks.tests.toy_served:witness",
        reference="benchmarks/reference/toy_served.py",
        costs="benchmarks/costs/toy_served.py")
    add_cell(root, SERVED_CELL, ("toy-served", config),
             ("toy-closed4", mix), like=served["name"])


def add_variant(root, cell, suffix, change):
    """A cell like ``cell`` (one of the two above) whose configuration
    ``change(config)`` has altered; returns its name."""
    bench = cells.load_benchmark(root)
    entry = [w for w in bench["workloads"] if w["name"] == cell][0]
    folder = os.path.join(root, bench["paths"][0])
    config = cells.load_json(os.path.join(
        folder, "configs", entry["config"] + ".json"))
    change(config)
    name = f"{entry['config']}-{suffix}.{entry['traffic']}"
    add_cell(root, name, (f"{entry['config']}-{suffix}", config),
             (entry["traffic"], cells.load_json(os.path.join(
                 folder, "traffic", entry["traffic"] + ".json"))),
             like=cell)
    return name


def cpu_device(chips: int) -> dict:
    """The rehearsal's device check: whatever JAX has, the CPU included."""
    d = jax.devices()[0]
    return {"platform": d.platform, "kind": d.device_kind,
            "count": len(jax.devices())}


def rehearse(workload, root, *, seconds=1.0, trace=False, seed=0,
             allow_cpu=True) -> dict:
    """``run.py``'s path for one cell; ``allow_cpu`` is the rehearsal
    flag (``run.py`` has no such option)."""
    check = cpu_device if allow_cpu else runner.device.require_accelerator
    return runner.run_cell(workload, seed, seconds, trace, t_start=clock(),
                           root=root, require_device=check)
