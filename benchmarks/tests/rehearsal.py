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
