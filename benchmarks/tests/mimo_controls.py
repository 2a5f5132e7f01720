"""The controls of the cell of a model with window and full layers of
different head counts and a sink in the window softmax, at the cell's
own sizes on the chip: the check that decides ``correct`` (the kind's
``check_window_programs``: a 2,590-token prompt and 160 decode steps, more
than the window, through the engine's own programs against the
reference) is run on the program as it is, once a seed, which must pass,
and then with each of four faults planted (``mimo_faults.py``), each of
which must FAIL by the logits:

    python benchmarks/tests/mimo_controls.py [--seeds N ...] [--workload CELL]

One JSON line a control, then ``{"controls_ok": ...}``; the exit code is
0 only where every sound check passed and every fault failed.  Each line
gives the largest logit gap of all the check's rows and of its first
five (the first token and four decode steps) beside the limit.  Not a
measurement: nothing is timed."""
import argparse
import gc
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

CELL = "mimo-v25-serve.closed32-8kin1kout"


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", default=CELL)
    ap.add_argument("--seeds", type=int, nargs="+", default=[2**31 + 5])
    args = ap.parse_args(argv)

    import jax.numpy as jnp

    from paddle_tpu.core.compile_cache import enable_compile_cache
    from paddle_tpu.serving import Engine, ServingConfig

    from benchmarks.harness import cells, device, models
    from benchmarks.kinds import closed_loop_window_serve as window_serve
    from benchmarks.tests import mimo_faults as faults

    cell = cells.load_cell(args.workload)
    dev = device.require_accelerator(cell.chips)
    enable_compile_cache()
    config, mix = cell.config, cell.traffic
    check = window_serve.check_window_programs

    def control(name, fault, must_pass, model, eng, seed):
        # the step programs are kept on the model: a planted fault is
        # traced into programs of its own, and taken out with them
        vars(model).pop("_compiled_steps", None)
        if fault is None:
            logits, choices, held = check(eng, model, config, mix, seed)
        else:
            with faults.planted(fault):
                logits, choices, held = check(eng, model, config, mix, seed)
            vars(model).pop("_compiled_steps", None)
        passed = bool(logits["ok"] and models.chose_admissibly(choices))
        print(json.dumps({
            "control": name, "seed": seed, "must_pass": must_pass,
            "passed": passed, "as_expected": passed == must_pass,
            "logit_gap": max(logits["max_abs_diff"]),
            "logit_gap_first_5": max(logits["max_abs_diff"][:5]),
            "limit": logits["tolerance"],
            "max_abs_reference_logit": logits["max_abs_reference_logit"],
            "rows": len(logits["max_abs_diff"]),
            "choices": choices, "window_pages_held": held,
            "device": dev}), flush=True)
        return passed == must_pass

    ok = []
    for i, seed in enumerate(args.seeds):
        model = models.build_model(config, seed)
        model.eval()
        eng = Engine(model, ServingConfig(**config["serving"]))
        ok.append(control("the program as it is", None, True, model, eng,
                          seed))
        if i == len(args.seeds) - 1:
            # (the float8 control rounds the model's own arrays: last)
            for name, fault in (
                    ("the sink left out", faults.the_sink_left_out),
                    ("window layers run as full layers",
                     faults.window_layers_run_as_full_layers),
                    ("window keys unrotated in the decode program",
                     faults.window_keys_unrotated_in_the_decode_program),
                    ("reference weights in float8 e4m3",
                     faults.reference_weights_in(jnp.float8_e4m3fn,
                                                 in_place=True))):
                ok.append(control(name, fault, False, model, eng, seed))
        del model, eng
        gc.collect()
    print(json.dumps({"controls_ok": all(ok)}), flush=True)
    return 0 if all(ok) else 1


if __name__ == "__main__":
    sys.exit(main())
