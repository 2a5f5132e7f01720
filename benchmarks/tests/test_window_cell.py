"""The cell of a model with window layers through ``run.py``'s path and
the new kind, on the CPU at tiny widths with a window of 32 keys under
contexts several windows long: ``correct`` is true for the program as it
is and false for each planted fault (``window_faults.py``)."""
import json
import os

import jax.numpy as jnp
import pytest

from benchmarks.harness import cells
from benchmarks.tests import rehearsal, window_faults as faults

CELL = "trinity-mini-serve.closed16-longctx"
SEED = 2**31 + 11


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    """``rehearsal.tiny_root``, then this configuration given layers of
    every kind, a window its contexts outgrow and a bias that matters."""
    root = rehearsal.tiny_root(tmp_path_factory.mktemp("bench"))
    bench = os.path.join(root, "benchmarks")

    def shrink(c):
        c.update(num_hidden_layers=3, num_dense_layers=1, layer_types=[
            "sliding_attention", "sliding_attention", "full_attention"],
            sliding_window=32, moe_intermediate_size=32, num_experts=16,
            num_experts_per_tok=4)
        c["model_config_kwargs"]["expert_bias_std"] = 0.3
        c["serving"].update(block_size=16, chunk_tokens=16)

    rehearsal._rewrite(
        os.path.join(bench, "configs", "trinity-mini-serve.json"), shrink)
    rehearsal._rewrite(
        os.path.join(bench, "traffic", "closed16-longctx.json"),
        lambda m: m.update(
            check_prompt_tokens=100,
            prompt_tokens={"median": 48, "sigma": 0.5, "min": 8,
                           "max": 100}))
    return root


def _check_line(capsys):
    return [json.loads(out) for out in capsys.readouterr().out.splitlines()
            if '"phase": "check"' in out][-1]


def test_the_cell_as_it_is(root, capsys):
    line = rehearsal.rehearse(CELL, root, seed=SEED, trace=True)
    assert line["correct"] is True and line["failed"] == 0
    compared = line["compared"]
    assert set(compared) == {
        "requests_not_whole", "leaked_blocks", "programs_a_step",
        "compiles_in_window", "logit_gap", "window_pages_held",
        "choice_shortfall"}
    assert compared["logit_gap"]["value"] < 1e-3 * compared[
        "logit_gap"]["limit"]
    held = compared["window_pages_held"]
    assert 0 < held["value"] <= held["limit"] == (32 + 16) // 16 + 1
    check = _check_line(capsys)
    assert check["logits"]["prompt_tokens"] == 100
    # every decision of every position: the witness outlives the pages
    assert check["choices"]["ok"] and \
        check["choices"]["decisions"] == 2 * 104 * 4
    m = line["metrics"]
    assert 0 < m["window_kv_share_pct"]["value"] < 100
    assert 0 < m["window_pages_per_seq"]["value"] <= 32 // 16 + 1
    assert 0 < m["experts_read_per_layer_decode"]["value"] <= 16
    assert m["expert_load_max_over_mean"]["value"] >= 1.0
    assert m["preemptions"]["value"] == 0
    assert "itl_p95_ms" not in m and "experts_read_per_layer_step" not in m


@pytest.mark.parametrize("fault", [
    faults.the_window_left_out_of_one_layer,
    faults.window_layers_run_as_full_layers,
    faults.a_walk_that_starts_one_page_early,
    faults.rope_on_the_full_layer,
    faults.the_attention_gate_left_out,
    faults.the_shared_expert_dropped,
    faults.the_bias_added_to_the_weights,
    faults.expert_weights_in(jnp.float8_e5m2),
    faults.reference_weights_in(jnp.float8_e4m3fn)],
    ids=lambda f: f.__name__)
def test_correct_is_false_for_a_planted_fault(root, fault, monkeypatch):
    fault(monkeypatch.setattr)
    line = rehearsal.rehearse(CELL, root, seed=SEED)
    assert line["correct"] is False
    assert line["attempted"] > 0 and line["failed"] == 0
    gap = line["compared"]["logit_gap"]
    assert gap["value"] > gap["limit"]


def test_correct_is_false_for_a_witness_that_lies(root, monkeypatch):
    faults.a_witness_that_lies(monkeypatch.setattr)
    line = rehearsal.rehearse(CELL, root, seed=SEED)
    assert line["correct"] is False
    short = line["compared"]["choice_shortfall"]
    assert short["value"] > short["limit"]
    gap = line["compared"]["logit_gap"]
    assert gap["value"] <= gap["limit"] or True     # one choice of 832


def test_the_new_kind_and_readers_load_by_name():
    cell = cells.load_cell(CELL)
    assert cell.traffic["kind"] == "closed_loop_window_serve"
    assert [m["name"] for m in cell.end_to_end] == ["serve_tok_s",
                                                    "setup_s"]
    for name in ("window_kv_share_pct", "window_pages_per_seq",
                 "paged_decode_kv_roofline_pct",
                 "moe_experts_decode_roofline_pct",
                 "experts_read_per_layer_decode", "attn_time_pct.serve"):
        # nothing to read (a program without the counters, no trace):
        # the reader says so and does not raise
        assert cell.readers[name].read(
            {"config": cell.config, "counters": {}, "trace_path": None,
             "device": {"kind": "cpu"}}) is None
    assert all(cell.config[k] == v for k, v in cell.config["published"]
               .items() if k not in cell.config["reduced"])
    assert set(cell.config["reduced"]) == {
        "num_hidden_layers", "num_dense_layers", "layer_types"}
    assert cell.config["global_attn_every_n_layers"] == 4
    mix = cell.traffic
    assert (mix["clients"], mix["pool"], mix["round"]) == (16, 64, 16)
    assert mix["check_prompt_tokens"] == 2590 == 10 * 256 + 30


def test_costs_count_what_a_decode_run_read_and_walked():
    cell = cells.load_cell(CELL)
    costs = cells.config_module(cell.config, "costs")
    cfg = cell.config
    assert costs.attention_params(cfg) == 27262976        # with the gate
    assert costs.expert_bytes(cfg) == 3 * 2048 * 1024 * 2
    held = costs.resident_params(cfg) + 2048 * 200192 \
        + 4 * 128 * costs.expert_params(cfg)
    assert round(held / 1e9, 2) == 4.24                   # 8.48 GB in bf16
    assert (costs.window_layers(cfg), costs.full_layers(cfg),
            costs.routed_layers(cfg)) == (4, 1, 4)
    counters = {"decode_iterations": 10, "decode_context_tokens": 1200000,
                "decode_window_tokens": 320000,
                "experts_read_decode": 10 * 4 * 55,
                "expert_assignments_decode": 10 * 4 * 128}
    kv = costs.decode_kv_bytes(cfg, counters)
    assert kv == (120000 + 4 * 32000) * 2048
    # a program without the window's counter: every layer walks it all
    every = costs.decode_kv_bytes(cfg, {k: v for k, v in counters.items()
                                        if k != "decode_window_tokens"})
    assert every == 5 * 120000 * 2048
    step = costs.decode_step_bytes(cfg, 120000, counters)
    assert step == costs.resident_params(cfg) * 2 \
        + 4 * 55 * costs.expert_bytes(cfg) + kv
    assert 4.4e9 < step < 4.6e9
    assert costs.expert_kernel_call_bytes(cfg, counters) == \
        55 * costs.expert_bytes(cfg) + 128 * 2048 * 6
    assert costs.decode_step_bytes(cfg, 0, {}) == \
        costs.resident_params(cfg) * 2


def test_the_controls_run_on_the_chip_rehearsed(root, monkeypatch, capsys):
    """``window_controls.py`` as the chip runs it, at tiny widths: the
    program as it is passes, each of its three faults fails."""
    from benchmarks.harness import device
    from benchmarks.tests import window_controls

    load = cells.load_cell
    monkeypatch.setattr(cells, "load_cell", lambda name: load(name, root))
    monkeypatch.setattr(device, "require_accelerator", rehearsal.cpu_device)
    assert window_controls.main(["--seed", str(SEED)]) == 0
    lines = [json.loads(out) for out in capsys.readouterr().out.splitlines()
             if out.startswith('{"control"')]
    assert [c["passed"] for c in lines] == [True, False, False, False]
    assert all(c["as_expected"] and c["window_pages_held"] <= 4
               for c in lines)
