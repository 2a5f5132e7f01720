"""The add-by-file loader and the schema of BENCHMARK.json."""
import json
import os
import re
import shutil

import pytest

from benchmarks.harness import cells

BENCH = cells.load_benchmark()
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")


def _metrics():
    return BENCH["end_to_end"] + BENCH["per_layer"]


def _cells_of(metric):
    return metric.get("workloads", [w["name"] for w in BENCH["workloads"]])


def test_a_later_pr_adds_files_and_edits_none(tmp_path):
    """A new configuration, mix and layer metric are dropped into a copy
    of the directories beside a new BENCHMARK.json entry; the loader
    finds them with no harness file changed."""
    root = str(tmp_path)
    shutil.copytree(os.path.join(cells.REPO_ROOT, "benchmarks"),
                    os.path.join(root, "benchmarks"),
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    bench_dir = os.path.join(root, "benchmarks")
    with open(os.path.join(bench_dir, "configs", "newmodel-1b.json"),
              "w") as f:
        json.dump({"source": "https://example.org/newmodel",
                   "hidden_size": 2048}, f)
    with open(os.path.join(bench_dir, "traffic", "closed4-new.json"),
              "w") as f:
        json.dump({"kind": "newkind", "clients": 4}, f)
    with open(os.path.join(bench_dir, "kinds", "newkind.py"), "w") as f:
        f.write("def run(ctx):\n    return {'ran': ctx}\n")
    with open(os.path.join(bench_dir, "layer_metrics", "new_count.x.py"),
              "w") as f:
        f.write("def read(run):\n    return run.get('n')\n")
    bench = json.loads(json.dumps(BENCH))
    bench["configs"].append({
        "name": "newmodel-1b", "source": "https://example.org/newmodel",
        "file": "benchmarks/configs/newmodel-1b.json", "reduced": [],
        "why": "test"})
    bench["workloads"].append({
        "name": "newmodel.closed4-new", "config": "newmodel-1b",
        "traffic": "closed4-new", "chips": 1, "why": "test"})
    bench["per_layer"].append({
        "name": "new_count.x", "unit": "count", "better": "lower",
        "source": "program_counter", "layer": "scheduler",
        "moves": "setup_s", "workloads": ["newmodel.closed4-new"]})
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(bench, f)

    cell = cells.load_cell("newmodel.closed4-new", root)
    assert cell.config["hidden_size"] == 2048
    assert cell.traffic["clients"] == 4
    assert cell.kind.run("ctx") == {"ran": "ctx"}
    assert list(cell.readers) == ["new_count.x"]
    assert cell.readers["new_count.x"].read({"n": 3}) == 3
    assert cell.readers["new_count.x"].read({}) is None
    assert [m["name"] for m in cell.end_to_end] == ["setup_s"]
    # and the cells that were there still load, from the same files
    old = cells.load_cell(BENCH["workloads"][0]["name"], root)
    assert "new_count.x" not in old.readers


def _harness_sources(root):
    bench = os.path.join(root, "benchmarks")
    paths = [os.path.join(bench, "run.py")] + [
        os.path.join(bench, folder, name)
        for folder in ("harness", "kinds")
        for name in sorted(os.listdir(os.path.join(bench, folder)))
        if name.endswith(".py")]
    out = {}
    for path in paths:
        with open(path, "rb") as f:
            out[os.path.relpath(path, root)] = f.read()
    return out


def test_a_later_pr_s_model_witness_reference_and_costs_run_as_files(
        tmp_path, capsys, monkeypatch):
    """A configuration that names a model class, a witness, a reference
    and cost counts of its own comes in as files and runs through both
    kinds (at tiny widths, on the CPU) with ``run.py``, ``harness/`` and
    ``kinds/`` as they are."""
    from benchmarks.harness import device
    from benchmarks.tests import rehearsal

    monkeypatch.setitem(device.PEAKS, "cpu", {"bf16_flops_per_s": 1e12})
    root = rehearsal.tiny_root(tmp_path)
    rehearsal.add_witnessed_cells(root)
    assert _harness_sources(root) == _harness_sources(cells.REPO_ROOT)
    for cell, kind in ((rehearsal.SERVED_CELL, "closed_loop_serve"),
                       (rehearsal.ROUTED_CELL, "train_job")):
        loaded = cells.load_cell(cell, root)
        assert loaded.traffic["kind"] == kind
        assert {"model", "witness", "reference", "costs"} <= set(
            loaded.config)
        line = rehearsal.rehearse(cell, root, seconds=0.3, trace=True)
        assert line["correct"] is True and line["failed"] == 0
        out = capsys.readouterr().out
        assert '"choices": {"ok": true' in out
    # the one utilization metric read the routed configuration's own
    # count of operations (the dense formula would find no such keys)
    assert "train_mfu_pct" in line["metrics"]


def test_no_harness_file_names_a_cell_or_its_parts():
    """No registry: the harness and ``run.py`` hold no name of a cell,
    configuration, mix, kind or layer metric."""
    bench_dir = os.path.join(cells.REPO_ROOT, "benchmarks")
    names = {w["name"] for w in BENCH["workloads"]} \
        | {w["traffic"] for w in BENCH["workloads"]} \
        | {c["name"] for c in BENCH["configs"]} \
        | {m["name"] for m in BENCH["per_layer"]} \
        | {os.path.splitext(f)[0]
           for f in os.listdir(os.path.join(bench_dir, "kinds"))
           if f.endswith(".py")}
    files = [os.path.join(bench_dir, "run.py")] + [
        os.path.join(bench_dir, "harness", f)
        for f in os.listdir(os.path.join(bench_dir, "harness"))
        if f.endswith(".py")]
    for path in files:
        with open(path) as f:
            text = f.read()
        found = [n for n in names if re.search(
            r"(?<![A-Za-z0-9_.\-])" + re.escape(n) + r"(?![A-Za-z0-9_\-])",
            text)]
        assert not found, (path, found)


@pytest.mark.parametrize("cell", [w["name"] for w in BENCH["workloads"]])
def test_every_cell_resolves_to_files_that_exist(cell):
    loaded = cells.load_cell(cell)
    assert callable(loaded.kind.run)
    assert all(callable(r.read) for r in loaded.readers.values())
    assert loaded.config["source"].startswith("http")
    assert len(loaded.config["source"]) <= 200
    assert os.path.isfile(os.path.join(cells.REPO_ROOT,
                                       loaded.config["reference"]))
    assert loaded.per_layer, "a cell reports at least one per-layer metric"
    assert {m["name"] for m in loaded.end_to_end} > {"setup_s"}


def test_names_and_units_are_well_formed():
    names = [m["name"] for m in _metrics()] \
        + [w["name"] for w in BENCH["workloads"]] \
        + [w["traffic"] for w in BENCH["workloads"]] \
        + [c["name"] for c in BENCH["configs"]] \
        + [k for c in BENCH["configs"] for k in c["reduced"]]
    for n in names:
        assert NAME.match(n), n
    for kind in ("end_to_end", "per_layer", "workloads", "configs"):
        got = [x["name"] for x in BENCH[kind]]
        assert len(got) == len(set(got)), kind
    for m in _metrics():
        assert UNIT.match(m["unit"]), m
        assert m["better"] in ("lower", "higher")
        assert m["source"] in ("device_trace", "program_span",
                               "program_counter", "host_clock")
    for m in BENCH["end_to_end"]:
        assert m["source"] in ("host_clock", "device_trace")
        assert 0 < m["bound"] <= 0.1
    for w in BENCH["workloads"]:
        assert w["chips"] in (1, 4) and 1 <= len(w["why"]) <= 200
    pairs = [(w["config"], w["traffic"]) for w in BENCH["workloads"]]
    assert len(pairs) == len(set(pairs))


def test_every_layer_metric_moves_a_metric_its_cells_report():
    reported = {w["name"]: {m["name"] for m in BENCH["end_to_end"]
                            if w["name"] in _cells_of(m)}
                for w in BENCH["workloads"]}
    for m in BENCH["per_layer"]:
        for cell in _cells_of(m):
            assert m["moves"] in reported[cell], (m["name"], cell)


def test_reduced_lists_what_the_file_changed_and_no_width():
    widths = re.compile(r"(hidden|intermediate|head|latent|state|proj).*size"
                        r"|_dim$|_rank$|experts_per_tok|expansion")
    for c in BENCH["configs"]:
        data = cells.load_json(os.path.join(cells.REPO_ROOT, c["file"]))
        changed = {k for k, v in data["published"].items()
                   if k in data and data[k] != v}
        assert changed == set(c["reduced"]) == set(data["reduced"])
        assert not [k for k in c["reduced"] if widths.search(k)]
