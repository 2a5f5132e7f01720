"""All cells of BENCHMARK.json through ``run.py``'s code path on the CPU
at tiny widths, and the run that finds no accelerator."""
import json
import math
import types

import jax
import jax.numpy as jnp
import pytest

from benchmarks.harness import cells, device, models
from benchmarks.tests import rehearsal

BENCH = cells.load_benchmark()
CELLS = [w["name"] for w in BENCH["workloads"]]


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return rehearsal.tiny_root(tmp_path_factory.mktemp("bench"))


def _declared(kind, cell):
    return [m for m in BENCH[kind]
            if "workloads" not in m or cell in m["workloads"]]


@pytest.mark.parametrize("cell", CELLS)
@pytest.mark.parametrize("trace", [False, True])
def test_cell_rehearsal(root, cell, trace, capsys, monkeypatch):
    # a made-up peak, so that the utilization's arithmetic runs; the real
    # table has no CPU and a run on one is an error
    monkeypatch.setitem(device.PEAKS, "cpu", {"bf16_flops_per_s": 1e12})
    line = rehearsal.rehearse(cell, root, trace=trace, seed=2**31 + 7)
    json.dumps(line)
    assert set(line) >= {"correct", "attempted", "failed", "metrics",
                         "device"}
    assert line["correct"] is True
    assert line["attempted"] > 0 and line["failed"] == 0
    assert line["device"]["platform"] == "cpu"
    declared = _declared("per_layer" if trace else "end_to_end", cell)
    for m in declared:
        if m["source"] == "device_trace":
            continue        # the CPU has no device plane to read
        got = line["metrics"][m["name"]]
        assert got["unit"] == m["unit"]
        assert math.isfinite(got["value"]), m["name"]
    assert set(line["metrics"]) <= {m["name"] for m in declared}
    # the kind's own earlier line says nothing compiled in the window
    window = [json.loads(out) for out in capsys.readouterr().out.splitlines()
              if '"phase": "window"' in out]
    assert window and window[-1]["compiles_in_window"] == 0


def test_no_accelerator_exits_before_any_result(root, capsys):
    with pytest.raises(SystemExit) as e:
        rehearsal.rehearse(CELLS[0], root, allow_cpu=False)
    assert e.value.code not in (0, None)
    assert '"metrics"' not in capsys.readouterr().out


def _layer_skipped(weights):
    return dict(weights, layers=weights["layers"][1:])


def _weights_in_8_bits(weights):
    return jax.tree_util.tree_map(
        lambda w: w.astype(jnp.float8_e4m3fn).astype(w.dtype), weights)


@pytest.mark.parametrize("cell", CELLS)
@pytest.mark.parametrize("fault", [_layer_skipped, _weights_in_8_bits])
def test_correct_is_false_when_model_and_reference_differ(
        root, cell, fault, monkeypatch):
    """The system and its reference are made to differ by a whole layer,
    or by products of weights held in 8 bits: ``correct`` must say so."""
    load = models.load_reference

    def faulty(config, *a, **kw):
        ref = load(config, *a, **kw)
        return types.SimpleNamespace(
            weights_of=lambda model: fault(ref.weights_of(model)),
            logits=ref.logits, causal_lm_loss=ref.causal_lm_loss)

    monkeypatch.setattr(models, "load_reference", faulty)
    line = rehearsal.rehearse(cell, root)
    assert line["correct"] is False
    assert line["attempted"] > 0 and line["failed"] == 0


def test_correct_is_false_when_a_block_leaks(root, monkeypatch, capsys):
    from paddle_tpu.serving.cache import BlockKVPool

    def leaked(self):
        raise AssertionError("leaked blocks: [(7, ['r1'])]")

    monkeypatch.setattr(BlockKVPool, "check_leaks", leaked)
    line = rehearsal.rehearse(CELLS[0], root)
    assert line["correct"] is False and line["failed"] == 0
    window = [json.loads(out) for out in capsys.readouterr().out.splitlines()
              if '"phase": "window"' in out][-1]
    assert "leaked blocks" in window["leaked_blocks"]
    assert window["still_running"] == 0 and window["drained_s"] > 0


def test_a_request_that_never_ends_fails(root, monkeypatch):
    """The drain is capped: a request still running at its end is a
    failed request, not a hung run."""
    kind = cells.load_cell(CELLS[0], root).kind
    monkeypatch.setattr(kind.ClosedLoop, "drain", lambda self: None)
    monkeypatch.setattr(cells, "load_module",
                        lambda path, name, _load=cells.load_module:
                        kind if path.endswith("closed_loop_serve.py")
                        else _load(path, name))
    line = rehearsal.rehearse(CELLS[0], root)
    assert line["correct"] is False
