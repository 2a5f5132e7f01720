"""The benchmark's one command:

    python benchmarks/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Runs the cell ``<name>`` of ``BENCHMARK.json`` on the machine it is
started on and prints, last, one JSON object: ``correct``, ``attempted``,
``failed``, ``metrics`` (the cell's end-to-end metrics, or with
``--trace 1`` its per-layer metrics), ``device`` and, traced,
``breakdown``.  Without an accelerator it exits non-zero before it prints
a result.  See ``benchmarks/README.md``.
"""
import time

T_START = time.perf_counter()   # set-up counts from here: imports included

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    from benchmarks.harness import runner

    line = runner.run_cell(args.workload, args.seed, args.seconds,
                           bool(args.trace), t_start=T_START)
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
