"""``correct`` for a model that makes discrete choices: the tests' own
routed toy (``toy_routed.py``: bf16, normalised-sigmoid top-k experts)
through ``train_job``'s code path, and the witness's way through
``closed_loop_serve`` on the tiny Llama.  Configurations, references and
cost counts come in as files a configuration names; no file of the
harness knows them."""
import json
import types

import jax
import jax.numpy as jnp
import pytest

from benchmarks.harness import bytes as step_bytes
from benchmarks.harness import cells, device, flops, models
from benchmarks.tests import rehearsal, toy_served

SEEDS = [2**31 + 11, 3, 77, 1234, 40961, 650001, 2**30 + 5, 99991]


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    root = rehearsal.tiny_root(tmp_path_factory.mktemp("witness"))
    rehearsal.add_witnessed_cells(root)
    return root


@pytest.fixture(autouse=True)
def _a_made_up_peak(monkeypatch):
    monkeypatch.setitem(device.PEAKS, "cpu", {"bf16_flops_per_s": 1e12})


def _lines(capsys, phase):
    return [json.loads(out) for out in capsys.readouterr().out.splitlines()
            if f'"phase": "{phase}"' in out]


@pytest.fixture(scope="module")
def unwitnessed(root):
    return rehearsal.add_variant(root, rehearsal.ROUTED_CELL, "unwitnessed",
                                 lambda c: c.pop("witness"))


@pytest.mark.parametrize("seed", SEEDS)
def test_the_routed_toy_is_correct_with_its_witness_on_every_seed(
        root, seed, capsys):
    line = rehearsal.rehearse(rehearsal.ROUTED_CELL, root, seconds=0.2,
                              seed=seed)
    check = _lines(capsys, "check")[-1]
    assert line["correct"] is True, (check, line["compared"])
    # the reference replayed choices of the timed path that were not its
    # own first ones, and every one of them was within its margin
    report = check["choices"]
    assert report["ok"] and report["decisions"] == 4 * 64 * 4
    assert 0 <= report["largest_shortfall"] <= report["margin"]
    assert set(line["compared"]) == {
        "losses_not_finite", "compiles_in_window",
        "loss_relative_difference", "logit_gap", "loss_choice_shortfall",
        "choice_shortfall"}
    for pair in line["compared"].values():
        assert pair["value"] <= pair["limit"]


def test_without_the_witness_the_same_toy_fails_on_most_seeds(
        root, unwitnessed, capsys):
    """The problem the witness answers, kept measured: an honest bf16
    model and its float32 reference choose differently somewhere in
    nearly every run, and a row moves by a whole expert's output (7 to
    26 % of the largest reference logit, against the 3.1 % allowed)."""
    failed = []
    for seed in SEEDS:
        line = rehearsal.rehearse(unwitnessed, root, seconds=0.2, seed=seed)
        assert "choices" not in _lines(capsys, "check")[-1]
        assert set(line["compared"]) == {
            "losses_not_finite", "compiles_in_window",
            "loss_relative_difference", "logit_gap"}
        gap = line["compared"]["logit_gap"]
        failed.append(line["correct"] is False
                      and gap["value"] > gap["limit"])
    assert sum(failed) >= 0.75 * len(SEEDS), failed


def _variant(root, suffix, change):
    return rehearsal.add_variant(root, rehearsal.ROUTED_CELL, suffix, change)


def _over_the_limit(line):
    return {name for name, pair in line["compared"].items()
            if not pair["value"] <= pair["limit"]}


def test_a_witness_naming_an_expert_far_below_the_kth_is_inadmissible(
        root, capsys):
    cell = _variant(root, "lying", lambda c: c.update(
        witness="benchmarks.tests.toy_routed:witness_naming_the_last"))
    line = rehearsal.rehearse(cell, root, seconds=0.2, seed=SEEDS[0])
    report = _lines(capsys, "check")[-1]["choices"]
    assert line["correct"] is False and report["ok"] is False
    assert report["largest_shortfall"] > 4 * report["margin"]
    assert {"choice_shortfall", "loss_choice_shortfall"} \
        <= _over_the_limit(line)


def test_a_model_that_applies_another_expert_than_it_reports_fails_the_logits(
        root, capsys):
    """It routes admissibly (every reported choice is within the
    margin) and multiplies by the wrong weights."""
    cell = _variant(root, "misapplied", lambda c: c[
        "model_config_kwargs"].update(apply_shift=1))
    line = rehearsal.rehearse(cell, root, seconds=0.2, seed=SEEDS[0])
    assert line["correct"] is False
    assert _lines(capsys, "check")[-1]["choices"]["ok"] is True
    assert "logit_gap" in _over_the_limit(line)
    assert not {"choice_shortfall", "loss_choice_shortfall"} \
        & _over_the_limit(line)


def _layer_skipped(weights):
    return dict(weights, layers=weights["layers"][1:])


def _weights_in_8_bits(weights):
    return jax.tree_util.tree_map(
        lambda w: w.astype(jnp.float8_e4m3fn).astype(w.dtype), weights)


@pytest.mark.parametrize("fault", [_layer_skipped, _weights_in_8_bits])
def test_the_routed_toy_fails_for_a_skipped_layer_and_for_float8_weights(
        root, fault, monkeypatch):
    load = models.load_reference

    def faulty(config):
        ref = load(config)
        return types.SimpleNamespace(
            weights_of=lambda model: fault(ref.weights_of(model)),
            logits=ref.logits, causal_lm_loss=ref.causal_lm_loss)

    monkeypatch.setattr(models, "load_reference", faulty)
    line = rehearsal.rehearse(rehearsal.ROUTED_CELL, root, seconds=0.2,
                              seed=SEEDS[1])
    assert line["correct"] is False
    assert "logit_gap" in _over_the_limit(line)


def test_the_serving_witness_reaches_the_reference_through_the_table(
        root, capsys, monkeypatch):
    """The witness function is called after the step programs ran, with
    the tokens and the table row ``engine_logits`` used and the pools as
    those steps left them; what it read through the table is what the
    reference computes for those tokens."""
    seen = {}
    real = toy_served.witness

    def spy(**kw):
        seen.update(kw)
        return real(**kw)

    monkeypatch.setattr(toy_served, "witness", spy)
    line = rehearsal.rehearse(rehearsal.SERVED_CELL, root, seed=2**31 + 3)
    check = _lines(capsys, "check")[-1]
    assert line["correct"] is True, check
    n = len(seen["tokens"])
    assert seen["prompt_tokens"] == n - 4 and seen["model"] is not None
    blocks = -(-(n + 1) // seen["engine"].config.block_size)
    assert list(seen["block_table"][:blocks]) == list(range(1, blocks + 1))
    assert not seen["block_table"][blocks:].any()
    report = check["choices"]
    assert report["ok"] and report["decisions"] == n
    assert report["largest_shortfall"] < 1e-4        # float32 here
    assert line["compared"]["choice_shortfall"]["limit"] == report["margin"]


def test_an_inadmissible_report_alone_makes_serving_incorrect(root, capsys):
    cell = rehearsal.add_variant(
        root, rehearsal.SERVED_CELL, "emptied", lambda c: c.update(
            witness="benchmarks.tests.toy_served:witness_of_an_empty_cache"))
    line = rehearsal.rehearse(cell, root, seed=5)
    check = _lines(capsys, "check")[-1]
    assert check["logits"]["ok"] is True and check["choices"]["ok"] is False
    assert line["correct"] is False and line["failed"] == 0
    assert _over_the_limit(line) == {"choice_shortfall"}


def test_a_configuration_without_a_witness_prints_what_it_printed(
        root, capsys):
    """The dense cells' ``check`` lines keep their keys."""
    bench = cells.load_benchmark(root)
    keys = {}
    for w in bench["workloads"][:3]:
        rehearsal.rehearse(w["name"], root, seconds=0.5)
        keys[w["traffic"]] = set(_lines(capsys, "check")[-1])
    serving = {"phase", "failed_requests", "requests_checked",
               "requests_ended", "one_program_each", "logits", "checked_s"}
    assert sorted(keys.values(), key=len) == sorted(
        [serving, serving, {"phase", "logits", "checked_s"}], key=len)


def test_costs_come_from_the_file_a_configuration_names(root):
    routed = cells.load_cell(rehearsal.ROUTED_CELL, root).config
    layer = 64 * 64 + 64 * 16 + 4 * 3 * 64 * 32      # gate, router, 4 experts
    assert flops.matmul_params(routed) == 4 * layer + 64 * 256 == 135168
    assert flops.model_flops_per_token(routed, 64) \
        == 6.0 * (135168 + 4 * 3 * 64)
    # a routed decode step reads the experts the program counted
    counters = {"experts_read": 60, "decode_iterations": 10}
    assert step_bytes.decode_step_bytes(routed, 123.0, counters) \
        == 2.0 * (4 * (64 * 64 + 64 * 16) + 6 * 3 * 64 * 32 + 64 * 256)
    served = cells.load_cell(rehearsal.SERVED_CELL, root).config
    counters = {"decode_context_tokens": 500, "decode_iterations": 10}
    assert step_bytes.decode_step_bytes(served, 0.0, counters) \
        == 2.0 * (flops.matmul_params(served) + 50 * 2 * 2 * 2 * 16)


@pytest.mark.parametrize("name, params, per_token, step", [
    ("mistral-7b-v0.3-serve", 3623878656, 22548578304.0, 8599977984.0),
    ("mistral-7b-v0.3-train", 788529152, 4882169856.0, 1830599680.0)])
def test_a_configuration_that_names_no_costs_reads_as_it_did(
        name, params, per_token, step):
    """The dense formulas, to the last digit (the parent's values)."""
    config = cells.load_cell(
        [w["name"] for w in cells.load_benchmark()["workloads"]
         if w["config"] == name][0]).config
    assert "costs" not in config
    assert flops.matmul_params(config) == params
    assert flops.model_flops_per_token(config, 2048) == per_token
    assert step_bytes.decode_step_bytes(config, 20633.25) == step
    assert step_bytes.decode_step_bytes(config, 20633.25, {"x": 1}) == step
