"""Finds everything a cell is made of by the names in ``BENCHMARK.json``.

A cell names a ``config`` and a ``traffic``.  The loader resolves

- ``config``  -> ``<bench>/configs/<config>.json``
- ``traffic`` -> ``<bench>/traffic/<traffic>.json`` -> its ``"kind"``
  -> ``<bench>/kinds/<kind>.py`` (a module with ``run(ctx) -> dict``)
- each per-layer metric of the cell -> ``<bench>/layer_metrics/<name>.py``
  (a module with ``read(run) -> number or None``)

by ``json`` and ``importlib`` alone.  There is no registry: a later PR
adds a file and an entry in ``BENCHMARK.json`` and edits nothing here.
``<bench>`` is the first of ``BENCHMARK.json``'s ``paths``.

A configuration names further files of its own by their paths from the
checkout's root (``"reference"``, ``"costs"``): ``config_module`` loads
them from the checkout the cell was loaded from, which ``load_cell``
leaves in the configuration under ``"root"``.
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
import os

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config_name: str
    config: dict
    traffic_name: str
    traffic: dict
    kind: object             # module: run(ctx) -> dict
    end_to_end: list         # BENCHMARK.json entries this cell reports
    per_layer: list
    readers: dict            # per-layer metric name -> module with read()


def load_module(path: str, name: str):
    """Import the file at ``path`` (its name may hold dots and dashes)."""
    if not os.path.isfile(path):
        raise FileNotFoundError(f"{name}: no file {path}")
    spec = importlib.util.spec_from_file_location(
        "bench_" + "".join(c if c.isalnum() else "_" for c in name), path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def load_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def config_module(config: dict, key: str):
    """The module whose path ``config[key]`` gives, ``None`` where the
    configuration has no such key."""
    path = config.get(key)
    if path is None:
        return None
    return load_module(os.path.join(config.get("root", REPO_ROOT), path),
                       os.path.basename(path))


def load_benchmark(root: str = REPO_ROOT) -> dict:
    return load_json(os.path.join(root, "BENCHMARK.json"))


def _of_cell(metrics, cell_name):
    return [m for m in metrics
            if "workloads" not in m or cell_name in m["workloads"]]


def load_cell(name: str, root: str = REPO_ROOT) -> Cell:
    bench = load_benchmark(root)
    entries = [w for w in bench["workloads"] if w["name"] == name]
    if not entries:
        raise KeyError(f"BENCHMARK.json has no workload {name!r}")
    entry = entries[0]
    bench_dir = os.path.join(root, bench["paths"][0])
    config_entry = [c for c in bench["configs"]
                    if c["name"] == entry["config"]][0]
    config = dict(load_json(os.path.join(root, config_entry["file"])),
                  root=root)
    traffic = load_json(os.path.join(
        bench_dir, "traffic", entry["traffic"] + ".json"))
    kind = load_module(os.path.join(
        bench_dir, "kinds", traffic["kind"] + ".py"), traffic["kind"])
    per_layer = _of_cell(bench["per_layer"], name)
    readers = {m["name"]: load_module(os.path.join(
        bench_dir, "layer_metrics", m["name"] + ".py"), m["name"])
        for m in per_layer}
    return Cell(name=name, chips=entry["chips"],
                config_name=entry["config"], config=config,
                traffic_name=entry["traffic"], traffic=traffic, kind=kind,
                end_to_end=_of_cell(bench["end_to_end"], name),
                per_layer=per_layer, readers=readers)
