"""The controls of the cell of a model with latent (MLA) pages, at the
cell's own sizes on the chip: the check that decides ``correct`` (the
kind's ``check_reask_programs``: row A, 2,590 tokens and 4 decode steps;
row B, 161 of its blocks found in the pool and 46 new tokens; row C, 480
tokens and 160 decode steps; all through the engine's own programs
against the reference) is run on the
program as it is, which must pass, and then with each of three faults
planted (``reask_faults.py``), each of which must FAIL:

    python benchmarks/tests/reask_controls.py [--seed N] [--workload CELL]

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
                    default="glm47flash-serve.closed8-docreask")
    ap.add_argument("--seed", type=int, default=2**31 + 5)
    args = ap.parse_args(argv)

    import jax.numpy as jnp

    from paddle_tpu.core.compile_cache import enable_compile_cache
    from paddle_tpu.serving import Engine, ServingConfig

    from benchmarks.harness import cells, device, models
    from benchmarks.tests import reask_faults as faults

    cell = cells.load_cell(args.workload)
    dev = device.require_accelerator(cell.chips)
    enable_compile_cache()
    config, mix = cell.config, cell.traffic
    model = models.build_model(config, args.seed)
    model.eval()
    eng = Engine(model, ServingConfig(**config["serving"]))
    check = cell.kind.check_reask_programs

    def control(name, fault, must_pass):
        # the step programs are kept on the model: a planted fault is
        # traced into programs of its own, and taken out with them; the
        # prefix index is emptied, so that every control's row A is run
        vars(model).pop("_compiled_steps", None)
        eng.pool.reset()
        if fault is None:
            rows, choices, matched, shared = check(eng, model, config, mix,
                                                   args.seed)
        else:
            with faults.planted(fault):
                rows, choices, matched, shared = check(
                    eng, model, config, mix, args.seed)
            vars(model).pop("_compiled_steps", None)
        passed = bool(all(r["ok"] for r in rows) and matched == shared
                      and models.chose_admissibly(choices))
        print(json.dumps({
            "control": name, "must_pass": must_pass, "passed": passed,
            "as_expected": passed == must_pass,
            "logit_gap": {r["row"]: max(r["max_abs_diff"]) for r in rows},
            "limit": {r["row"]: r["tolerance"] for r in rows},
            "matched_blocks": [matched, shared], "choices": choices,
            "device": dev}), flush=True)
        return passed == must_pass

    # (the float8 control rounds the model's own arrays: it comes last)
    ok = [control("the program as it is", None, True),
          control("the rotary key not rotated in the decode program",
                  faults.the_rotary_key_not_rotated_in_the_decode_program,
                  False),
          control("a stale page matched for row B",
                  faults.a_stale_page_matched, False),
          control("reference weights in float8 e4m3",
                  faults.reference_weights_in(jnp.float8_e4m3fn,
                                              in_place=True), False)]
    print(json.dumps({"controls_ok": all(ok)}), flush=True)
    return 0 if all(ok) else 1


if __name__ == "__main__":
    sys.exit(main())
