"""CPU rehearsal of chip_smoke.py: its phase functions at
``LlamaConfig.tiny()`` (imported, never run as a child), its device check,
and its reader of compiled programs.  What only the chip can show — the
kernels in the compiled programs — is switched off here by the test, not by
an option of the script."""
import json

import numpy as np
import pytest

import chip_smoke


def _tiny_sizes():
    return chip_smoke.Sizes(
        serve_layers=2, train_layers=2, max_model_len=192, num_blocks=64,
        chunk_tokens=32, decode_checks=3, train_batch=2, train_seq=32,
        train_steps=3, lm_loss_chunk=16, learning_rate=1e-2)


def _lines(capsys):
    return [json.loads(l) for l in capsys.readouterr().out.splitlines()]


def test_serving_phase_rehearsal(capsys):
    chip_smoke.serving_phase(_tiny_sizes(), tiny=True, check_kernels=False)
    out = _lines(capsys)
    reqs = [l for l in out if "request" in l]
    assert len(reqs) == 6
    assert {r["finish_reason"] for r in reqs} == {"length"}
    assert reqs[3]["cached_tokens"] >= 32      # the shared prefix
    logits = next(l for l in out if "max_abs_diff_first_token" in l)
    assert logits["logits_shape"] == [4, 256]
    assert len(logits["max_abs_diff_decode_steps"]) == 3
    counts = next(l for l in out if "engine_steps" in l)
    assert counts["compiles"] == {"decode": 1, "prefill": 1}
    assert counts["retraces"] == {"decode": 0, "prefill": 0}
    programs = [l["program"] for l in out if "program" in l]
    assert programs == ["decode_step", "chunked_prefill_step"] * 2


def test_training_phase_rehearsal(capsys):
    chip_smoke.training_phase(_tiny_sizes(), tiny=True, check_kernels=False)
    out = _lines(capsys)
    fit = next(l for l in out if "losses" in l)
    assert len(fit["losses"]) == 3
    assert fit["losses"][-1] < fit["losses"][0]
    assert fit["compiles"] == 2
    assert [l["program"] for l in out if "program" in l] == \
        ["train_step[0]", "train_step[1]"]


def test_four_chip_phases_rehearsal(capsys):
    # four of conftest's eight virtual devices
    chip_smoke.four_chip_phases(_tiny_sizes(), tiny=True)
    out = _lines(capsys)
    spread = {l["spread"]: l["shares"] for l in out if "spread" in l}
    assert set(spread) == {"serving parameters", "kv pool",
                           "training parameters", "optimizer moments"}
    for shares in spread.values():
        assert len(shares) == 4 and max(shares.values()) <= 0.3
    losses = next(l for l in out if "sharded_losses" in l)
    assert max(losses["relative_differences"]) <= chip_smoke.LOSS_TOL
    tp = next(l for l in out if l.get("phase") == "four_chips_serving"
              and "max_abs_diff_first_token" in l)
    assert tp["max_abs_diff_first_token"] <= tp["tolerance"]


def test_failed_check_fails_the_phase():
    with pytest.raises(chip_smoke.SmokeFailure, match="kernels missing"):
        # on the CPU no program holds a Pallas kernel
        chip_smoke.serving_phase(_tiny_sizes(), tiny=True)


def test_device_check_fails_on_cpu(capsys):
    with pytest.raises(SystemExit) as e:
        chip_smoke.require_accelerator(1)
    assert e.value.code not in (0, None)
    with pytest.raises(SystemExit) as e:
        chip_smoke.main([])
    assert e.value.code not in (0, None)
    assert capsys.readouterr().out == ""        # no result line


def test_kernels_in_reads_names_from_tpu_hlo():
    hlo = "\n".join([
        '  %rms_norm.1 = bf16[8,128]{1,0} custom-call(%x, %w), '
        'custom_call_target="tpu_custom_call", metadata={op_name='
        '"jit(step)/jit(main)/rms_norm/pallas_call" stack_frame_id=5}, '
        'backend_config={"custom_call_config": {"body": "abc"}}',
        '  %f.2 = f32[8]{0} custom-call(%q), custom_call_target='
        '"tpu_custom_call", metadata={op_name="jit(step)/while/body/'
        'fused_paged_decode/pallas_call"}',
        '  %f.3 = f32[8]{0} custom-call(%q), custom_call_target='
        '"tpu_custom_call", metadata={op_name="jit(step)/'
        'fused_paged_decode/pallas_call"}',
        '  %t = f32[8]{0} custom-call(%q), custom_call_target="TopK"',
        '  %r.2 = bf16[8]{0} custom-call(%g), custom_call_target='
        '"tpu_custom_call", metadata={op_name="jit(jax_fn)/'
        'transpose(jvp(fused_rope))/pallas_call" stack_frame_id=279}',
        '  %u = f32[8]{0} custom-call(%q), custom_call_target='
        '"tpu_custom_call", metadata={op_name="pallas_call"}',
    ])
    assert chip_smoke.kernels_in(hlo) == {
        "rms_norm": 1, "fused_paged_decode": 2, "fused_rope": 1,
        "unnamed": 1}
    assert chip_smoke.kernels_in("ROOT %a = f32[] add(%b, %c)") == {}


def test_spread_check_rejects_everything_on_one_device():
    import jax

    devs = jax.devices()[:4]
    lone = [jax.device_put(np.ones((64, 64), np.float32), devs[0])]
    with pytest.raises(chip_smoke.SmokeFailure, match="expected 4"):
        chip_smoke.check_spread("lone", lone, 4)
