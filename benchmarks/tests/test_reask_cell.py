"""The cell of a model with latent (MLA) pages through ``run.py``'s path
and the new kind, on the CPU at tiny widths with documents asked four
times: ``correct`` is true for the program as it is and false for each
planted fault (``reask_faults.py``); the kind's schedule asks each
document ``asks`` times and the cached share reads what the lengths
imply."""
import json
import os

import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.harness import cells
from benchmarks.tests import reask_faults as faults
from benchmarks.tests import rehearsal

CELL = "glm47flash-serve.closed8-docreask"
SEED = 2**31 + 11


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    """``rehearsal.tiny_root``, then this configuration given small
    ranks, two routed layers and a bias that matters, and documents a
    few blocks long."""
    root = rehearsal.tiny_root(tmp_path_factory.mktemp("bench"))
    bench = os.path.join(root, "benchmarks")

    def shrink(c):
        c.update(num_hidden_layers=3, num_key_value_heads=4,
                 q_lora_rank=32, kv_lora_rank=48, qk_nope_head_dim=24,
                 qk_rope_head_dim=16, v_head_dim=32,
                 moe_intermediate_size=32, n_routed_experts=16,
                 max_position_embeddings=256)
        c["model_config_kwargs"]["expert_bias_std"] = 0.3
        c["serving"].update(block_size=16, chunk_tokens=16,
                            max_model_len=256, num_blocks=96)

    rehearsal._rewrite(
        os.path.join(bench, "configs", "glm-4.7-flash-serve.json"), shrink)
    rehearsal._rewrite(
        os.path.join(bench, "traffic", "closed8-docreask.json"),
        lambda m: m.update(
            check_prompt_tokens=100, check_reask_tokens=14,
            check_decode_row_tokens=40, check_decode_row_steps=24,
            ramp_document_tokens=64,
            document_tokens={"median": 96, "sigma": 0.35, "min": 40,
                             "max": 160},
            question_tokens={"median": 10, "sigma": 0.5, "min": 4,
                             "max": 24}))
    return root


def _lines(capsys, phase):
    return [json.loads(out) for out in capsys.readouterr().out.splitlines()
            if f'"phase": "{phase}"' in out]


def test_the_cell_as_it_is(root, capsys):
    line = rehearsal.rehearse(CELL, root, seed=SEED, trace=True,
                              seconds=3.0)
    assert line["correct"] is True and line["failed"] == 0
    compared = line["compared"]
    assert set(compared) == {
        "requests_not_whole", "leaked_blocks", "programs_a_step",
        "compiles_in_window", "matched_blocks", "logit_gap_row_a",
        "logit_gap_row_b", "logit_gap_row_c", "choice_shortfall"}
    for row in "abc":
        gap = compared[f"logit_gap_row_{row}"]
        assert gap["value"] < 1e-3 * gap["limit"]
    # 100 tokens: six whole blocks of 16 found again for row B
    assert compared["matched_blocks"] == {"value": 6, "limit": 6}
    out = capsys.readouterr().out.splitlines()
    check = [json.loads(o) for o in out if '"phase": "check"' in o][-1]
    assert [r["prompt_tokens"] for r in check["logits"]] == [100, 96 + 14,
                                                             40]
    # one row of logits a decode step behind the first token's
    assert [len(r["max_abs_diff"]) for r in check["logits"]] == [5, 3, 25]
    # every decision of every position of every row, cached ones too
    assert check["choices"]["ok"] and check["choices"]["decisions"] == \
        2 * 4 * (104 + 112 + 64)
    window = [json.loads(o) for o in out if '"phase": "window"' in o][-1]
    assert window["expert_load_max_over_mean"] >= 1.0
    assert 0 < window["experts_read_per_layer_decode"] <= 16
    assert window["ttft_repeated_ask_ms"] is not None
    m = line["metrics"]
    # 3 layers of 128 lanes in float32 and two witnesses of 4 int32
    assert m["kv_pool_bytes_per_token"]["value"] == 3 * 128 * 4 + 2 * 16
    share = m["cached_prompt_share_pct"]["value"]
    assert share == pytest.approx(
        100.0 * window["cached_prompt_tokens"] / window["prompt_tokens"])
    assert 30 < share < 80
    assert m["preemptions"]["value"] == 0
    assert "itl_p95_ms" not in m and "expert_load_max_over_mean" not in m


@pytest.mark.parametrize("fault", [
    faults.the_latent_norm_left_out,
    faults.the_rotary_key_not_rotated,
    faults.the_rotary_key_not_rotated_in_the_decode_program,
    faults.the_value_read_from_the_wrong_lanes,
    faults.routed_scaling_factor_dropped,
    faults.the_shared_expert_dropped,
    faults.the_bias_added_to_the_weights,
    faults.a_stale_page_matched,
    faults.expert_weights_in(jnp.float8_e5m2),
    faults.reference_weights_in(jnp.float8_e4m3fn)],
    ids=lambda f: f.__name__)
def test_correct_is_false_for_a_planted_fault(root, fault, monkeypatch):
    fault(monkeypatch.setattr)
    line = rehearsal.rehearse(CELL, root, seed=SEED)
    assert line["correct"] is False
    assert line["attempted"] > 0 and line["failed"] == 0
    compared = line["compared"]
    assert any(compared[f"logit_gap_row_{row}"]["value"]
               > compared[f"logit_gap_row_{row}"]["limit"] for row in "abc")
    if fault is faults.the_rotary_key_not_rotated_in_the_decode_program:
        # the decode row is the one that sees what decode steps wrote:
        # by its last step 24 of 64 keys are theirs
        gap = compared["logit_gap_row_c"]
        assert gap["value"] > 4 * gap["limit"]
    if fault is faults.a_stale_page_matched:
        # row A is sound; only the row over the matched pages fails
        gap = compared["logit_gap_row_a"]
        assert gap["value"] <= gap["limit"]
        assert compared["matched_blocks"] == {"value": 6, "limit": 6}


def test_correct_is_false_for_a_witness_that_lies(root, monkeypatch):
    faults.a_witness_that_lies(monkeypatch.setattr)
    line = rehearsal.rehearse(CELL, root, seed=SEED)
    assert line["correct"] is False
    short = line["compared"]["choice_shortfall"]
    assert short["value"] > short["limit"]


def test_the_schedule_asks_each_document_asks_times():
    cell = cells.load_cell(CELL)
    kind, mix = cell.kind, cell.traffic
    rounds = kind.document_rounds(mix)
    assert len(rounds) == 4 and all(len(r) == 8 for r in rounds)
    docs = [d for r in rounds for d in r]
    assert all(len(asks) == mix["asks"] == 4 for _, asks in docs)
    lengths = np.array([t for t, _ in docs])
    assert lengths.min() >= 4096 and lengths.max() <= 30720
    assert 15000 < np.median(lengths) < 18000
    # the longest request the mix can send fits the engine
    assert 30720 + 384 + 384 <= cell.config["serving"]["max_model_len"]
    # every round spans the distribution: one of every four neighbours
    means = [np.mean([t for t, _ in r]) for r in rounds]
    assert max(means) / min(means) < 1.1
    assert kind.document_rounds(mix) == rounds        # one schedule

    class Engine:
        max_model_len = 32768

        def __init__(self):
            self.sent = []

        def submit(self, prompt, max_new_tokens, on_token):
            self.sent.append((np.array(prompt), max_new_tokens))

    eng = Engine()
    loop = kind.ReaskLoop(eng, mix, 1000, seed=5)
    for _ in range(3):                  # three requests a client
        for c in range(mix["clients"]):
            loop._submit(c)
    first = {c: eng.sent[c][0] for c in range(8)}
    # a client's first document is one ramp long and is asked 1 + c % 4
    # times; what is asked again shares the document and not the question
    for c in range(8):
        asked = [eng.sent[i * 8 + c][0] for i in range(3)]
        assert len(first[c]) <= 4096 + 384
        same = [np.array_equal(a[:4096], first[c][:4096])
                for a in asked if len(a) >= 4096]
        assert sum(same) == min(3, 1 + c % 4)
    # (clients 0, 1, 4 and 5 have taken their second document)
    assert loop.documents_dealt == 8 + 4
    # over whole documents the cached share is what the lengths imply:
    # three asks in four find the document's whole blocks
    doc, cached, total = 16384, 0, 0
    for i, (q, _) in enumerate(docs[0][1]):
        total += doc + q
        cached += (doc // 16) * 16 if i else 0
    assert 100.0 * cached / total == pytest.approx(74.6, abs=0.3)


def test_the_new_kind_and_readers_load_by_name():
    cell = cells.load_cell(CELL)
    assert cell.traffic["kind"] == "closed_loop_reask_serve"
    assert [m["name"] for m in cell.end_to_end] == ["serve_tok_s",
                                                    "setup_s"]
    names = {m["name"] for m in cell.per_layer}
    assert {"latent_decode_kv_roofline_pct", "latent_chunk_roofline_pct",
            "latent_absorb_time_pct.serve", "kv_pool_bytes_per_token",
            "cached_prompt_share_pct", "decode_hbm_roofline_pct",
            "moe_experts_decode_roofline_pct"} <= names
    assert not {"expert_load_max_over_mean",
                "experts_read_per_layer_decode"} & names
    for name in ("latent_decode_kv_roofline_pct",
                 "latent_chunk_roofline_pct", "latent_absorb_time_pct.serve",
                 "kv_pool_bytes_per_token", "cached_prompt_share_pct",
                 "moe_experts_decode_roofline_pct", "attn_time_pct.serve"):
        # nothing to read (a program without the counters, no trace):
        # the reader says so and does not raise
        assert cell.readers[name].read(
            {"config": cell.config, "counters": {}, "trace_path": None,
             "trace": None, "iter_ms": [], "ttft_ms": [],
             "device": {"kind": "cpu"}}) is None, name
    assert all(cell.config[k] == v for k, v in cell.config["published"]
               .items() if k not in cell.config["reduced"])
    assert set(cell.config["reduced"]) == {"num_hidden_layers"}
    assert cell.config["num_hidden_layers"] == 6
    mix = cell.traffic
    assert (mix["clients"], mix["pool"], mix["round"], mix["asks"]) == \
        (8, 32, 8, 4)
    assert mix["check_prompt_tokens"] == 2590 == 10 * 256 + 30
    assert (2590 // 16, 2590 // 16 * 16 + mix["check_reask_tokens"]) == \
        (161, 2622)
    assert (mix["check_decode_row_tokens"],
            mix["check_decode_row_steps"]) == (480, 160)


def test_costs_count_the_published_entry_and_the_attended_pairs():
    cell = cells.load_cell(CELL)
    costs = cells.config_module(cell.config, "costs")
    cfg = cell.config
    assert costs.attention_params(cfg) == 21757952
    assert costs.expert_params(cfg) == 9437184
    assert costs.routed_layers(cfg) == 5
    held = costs.resident_params(cfg) + 2048 * 154880 \
        + 5 * 64 * costs.expert_params(cfg)
    assert round(held * 2 / 1e9, 2) == 7.79
    assert costs.kv_bytes_per_token_layer(cfg) == 1152
    counters = {"decode_iterations": 10, "decode_context_tokens": 1200000,
                "experts_read_decode": 10 * 5 * 20,
                "expert_assignments_decode": 10 * 5 * 32,
                "prefill_chunks_run": 4, "prefill_context_tokens": 4 * 8192,
                "prefill_attended_pairs": 4 * 256 * 8000}
    kv = costs.decode_kv_bytes(cfg, counters)
    assert kv == 120000 * 6 * 1152
    step = costs.decode_step_bytes(cfg, 120000, counters)
    assert step == costs.resident_params(cfg) * 2 \
        + 5 * 20 * costs.expert_bytes(cfg) + kv
    assert costs.expert_kernel_call_bytes(cfg, counters) == \
        20 * costs.expert_bytes(cfg) + 32 * 2048 * 6
    flops, size = costs.chunk_attention_cost(cfg, counters)
    assert flops == 256 * 8000 * 6 * 2 * 20 * (576 + 512)
    assert size == 8192 * 6 * 1152
    assert costs.chunk_attention_cost(cfg, {}) == (0.0, 0.0)
    assert costs.decode_step_bytes(cfg, 0, {}) == \
        costs.resident_params(cfg) * 2


def test_the_controls_run_on_the_chip_rehearsed(root, monkeypatch, capsys):
    """``reask_controls.py`` as the chip runs it, at tiny widths: the
    program as it is passes, each of its three faults fails."""
    from benchmarks.harness import device
    from benchmarks.tests import reask_controls

    load = cells.load_cell
    monkeypatch.setattr(cells, "load_cell", lambda name: load(name, root))
    monkeypatch.setattr(device, "require_accelerator", rehearsal.cpu_device)
    assert reask_controls.main(["--seed", str(SEED)]) == 0
    lines = [json.loads(out) for out in capsys.readouterr().out.splitlines()
             if out.startswith('{"control"')]
    assert [c["passed"] for c in lines] == [True, False, False, False]
    assert all(c["as_expected"] for c in lines)
