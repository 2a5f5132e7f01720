"""The controls of the cell of a model with window layers, at the cell's
own sizes on the chip: the check that decides ``correct`` (the kind's
``check_window_programs``: a 2,590-token prompt and 4 decode steps
through the engine's own programs against the reference) is run on the
program as it is, which must pass, and then with each of three faults
planted (``window_faults.py``), each of which must FAIL by the logits:

    python benchmarks/tests/window_controls.py [--seed N] [--workload CELL]

One JSON line a control, then ``{"controls_ok": ...}``; the exit code is
0 only where the sound check passed and every fault failed.  Not a
measurement: nothing is timed."""
import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload",
                    default="trinity-mini-serve.closed16-longctx")
    ap.add_argument("--seed", type=int, default=2**31 + 5)
    args = ap.parse_args(argv)

    import jax.numpy as jnp

    from paddle_tpu.core.compile_cache import enable_compile_cache
    from paddle_tpu.serving import Engine, ServingConfig

    from benchmarks.harness import cells, device, models
    from benchmarks.tests import window_faults as faults

    cell = cells.load_cell(args.workload)
    dev = device.require_accelerator(cell.chips)
    enable_compile_cache()
    config, mix = cell.config, cell.traffic
    model = models.build_model(config, args.seed)
    model.eval()
    eng = Engine(model, ServingConfig(**config["serving"]))
    check = cell.kind.check_window_programs

    def control(name, fault, must_pass):
        # the step programs are kept on the model: a planted fault is
        # traced into programs of its own, and taken out with them
        vars(model).pop("_compiled_steps", None)
        if fault is None:
            logits, choices, held = check(eng, model, config, mix, args.seed)
        else:
            with faults.planted(fault):
                logits, choices, held = check(eng, model, config, mix,
                                              args.seed)
            vars(model).pop("_compiled_steps", None)
        passed = bool(logits["ok"] and models.chose_admissibly(choices))
        print(json.dumps({
            "control": name, "must_pass": must_pass, "passed": passed,
            "as_expected": passed == must_pass,
            "logit_gap": max(logits["max_abs_diff"]),
            "limit": logits["tolerance"],
            "max_abs_reference_logit": logits["max_abs_reference_logit"],
            "choices": choices, "window_pages_held": held,
            "device": dev}), flush=True)
        return passed == must_pass

    # (the float8 control rounds the model's own arrays: it comes last)
    ok = [control("the program as it is", None, True),
          control("window layers run as full layers",
                  faults.window_layers_run_as_full_layers, False),
          control("a walk that starts one page early",
                  faults.a_walk_that_starts_one_page_early, False),
          control("reference weights in float8 e4m3",
                  faults.reference_weights_in(jnp.float8_e4m3fn,
                                              in_place=True), False)]
    print(json.dumps({"controls_ok": all(ok)}), flush=True)
    return 0 if all(ok) else 1


if __name__ == "__main__":
    sys.exit(main())
