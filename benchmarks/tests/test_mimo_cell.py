"""The cell of a model with window and full layers of different head
counts through ``run.py``'s path, on the CPU at tiny widths with a window
of 32 keys under contexts several windows long and a decode check longer
than the window: ``correct`` is true for the program as it is and false
for each planted fault (``mimo_faults.py``)."""
import json
import os

import jax.numpy as jnp
import pytest

from benchmarks.tests import mimo_faults as faults, rehearsal

CELL = "mimo-v25-serve.closed32-8kin1kout"
SEED = 2**31 + 13


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    """``rehearsal.tiny_root``, then this configuration given layers of
    every kind at widths of their own, a window its contexts outgrow and
    a share of the experts."""
    root = rehearsal.tiny_root(tmp_path_factory.mktemp("bench"))
    bench = os.path.join(root, "benchmarks")

    def shrink(c):
        c.update(num_hidden_layers=3, hybrid_layer_pattern=[0, 1, 0],
                 moe_layer_freq=[0, 1, 1], sliding_window=32,
                 swa_num_attention_heads=8, swa_num_key_value_heads=2,
                 swa_head_dim=24, swa_v_head_dim=16, head_dim=24,
                 v_head_dim=16, num_key_value_heads=1,
                 moe_intermediate_size=32, n_routed_experts=8,
                 num_experts_per_tok=4, max_position_embeddings=256)
        c["model_config_kwargs"].update(n_routed_experts=16,
                                        held_experts=list(range(8)),
                                        sink_mean=3.5)
        c["serving"].update(block_size=16, chunk_tokens=16,
                            max_model_len=256)

    rehearsal._rewrite(
        os.path.join(bench, "configs", "mimo-v2.5-serve.json"), shrink)
    rehearsal._rewrite(
        os.path.join(bench, "traffic", "closed32-8kin1kout.json"),
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
    assert compared["logit_gap"]["value"] < 1e-3 * compared[
        "logit_gap"]["limit"]
    check = _check_line(capsys)
    # 100 prompt tokens and the capped 128 decode steps: half the
    # engine's max_model_len, four windows
    assert check["logits"]["prompt_tokens"] == 100
    assert len(check["logits"]["max_abs_diff"]) == 1 + 128
    assert check["choices"]["ok"] and \
        check["choices"]["decisions"] == 2 * (100 + 128) * 4
    m = line["metrics"]
    assert 0 < m["held_expert_load_pct"]["value"] < 100
    assert 0 < m["window_pages_per_seq"]["value"] <= 32 // 16 + 1
    assert m["preemptions"]["value"] == 0
    assert "itl_p95_ms" not in m and "window_kv_share_pct" not in m


@pytest.mark.parametrize("fault", [
    faults.the_sink_left_out,
    faults.window_layers_run_as_full_layers,
    faults.window_keys_unrotated_in_the_decode_program,
    faults.reference_weights_in(jnp.float8_e4m3fn)],
    ids=lambda f: f.__name__)
def test_a_planted_fault_turns_correct_false(root, fault, monkeypatch):
    fault(monkeypatch.setattr)
    line = rehearsal.rehearse(CELL, root, seed=SEED)
    assert line["correct"] is False
    assert line["attempted"] > 0 and line["failed"] == 0


@pytest.mark.parametrize("fault", [
    faults.the_sink_left_out,
    faults.window_layers_run_as_full_layers,
    faults.window_keys_unrotated_in_the_decode_program],
    ids=lambda f: f.__name__)
def test_a_fault_planted_for_the_controls_is_taken_out_whole(fault):
    """``mimo_controls.py`` plants each fault with ``planted`` and runs
    the sound program after it: the program must then be as it was."""
    import numpy as np

    import paddle_tpu as paddle
    from paddle_tpu.models import MimoV2Config, MimoV2ForCausalLM

    paddle.seed(0)
    model = MimoV2ForCausalLM(MimoV2Config.tiny())
    model.eval()
    ids = np.arange(1, 41, dtype=np.int32)[None]
    before = np.asarray(model(ids)._value)
    with faults.planted(fault):
        pass
    np.testing.assert_array_equal(np.asarray(model(ids)._value), before)
