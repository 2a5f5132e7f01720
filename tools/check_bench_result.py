#!/usr/bin/env python
"""Perf-regression gate (reference: tools/check_op_benchmark_result.py:106
compare_benchmark_result — PR-vs-develop op benchmark diffing).

Compares two bench JSON artifacts (the driver's BENCH_r{N}.json format or
bench.py's raw line) and fails when throughput regresses beyond the
threshold:

    python tools/check_bench_result.py BENCH_r01.json BENCH_r02.json \
        --threshold 0.05

Exit codes: 0 ok / 3 regression / 4 missing-or-errored artifact.
"""
from __future__ import annotations

import argparse
import json
import sys


def load_node(path: str):
    with open(path) as f:
        data = json.load(f)
    # driver format wraps the bench line under "parsed"; accept both
    node = data.get("parsed") if isinstance(data, dict) and "parsed" in data \
        else data
    return node if isinstance(node, dict) else {}, data


def load_value(path: str):
    node, data = load_node(path)
    if node.get("value") is None:
        return None, node.get("error") or (
            data.get("tail", "")[-200:] if isinstance(data, dict) else "")
    return float(node["value"]), None


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("baseline")
    ap.add_argument("candidate")
    ap.add_argument("--threshold", type=float, default=0.05,
                    help="max allowed fractional slowdown (default 5%)")
    args = ap.parse_args(argv)

    base, base_err = load_value(args.baseline)
    cand, cand_err = load_value(args.candidate)
    if cand is None:
        print(f"FAIL: candidate bench produced no number ({cand_err})")
        return 4
    if base is None:
        # nothing to compare against: candidate having a number is a pass
        print(f"OK: candidate={cand:.1f}; baseline had no number "
              f"({base_err}) — treating as initial measurement")
        return 0
    # methodology alignment: the headline switched from per-step sync to
    # tail sync (tail-sync era artifacts carry a per_step_sync extra).
    # Comparing a tail-sync candidate against a per-step-sync baseline
    # would inflate the candidate by one host round trip per step and
    # mask real regressions — substitute the matching-methodology number.
    bx = load_node(args.baseline)[0].get("extra") or {}
    cx = load_node(args.candidate)[0].get("extra") or {}
    b_ss, c_ss = (bx.get("per_step_sync_tokens_per_sec"),
                  cx.get("per_step_sync_tokens_per_sec"))
    if c_ss and not b_ss:
        print(f"# note: per-step-sync candidate value {c_ss} used against "
              "legacy per-step-sync baseline")
        cand = float(c_ss)
    elif b_ss and not c_ss:
        print(f"# note: per-step-sync baseline value {b_ss} used against "
              "legacy per-step-sync candidate")
        base = float(b_ss)
    ratio = cand / base
    if ratio < 1.0 - args.threshold:
        print(f"FAIL: {cand:.1f} vs baseline {base:.1f} "
              f"({(1 - ratio) * 100:.1f}% slower > {args.threshold * 100:.0f}% "
              f"threshold)")
        return 3
    print(f"OK: {cand:.1f} vs baseline {base:.1f} ({(ratio - 1) * 100:+.1f}%)")

    # secondary gates over bench.py's extra fields (VERDICT r2 #7/#8):
    # one loop, per-metric direction + headroom + missing-value severity
    base_x = load_node(args.baseline)[0].get("extra") or {}
    cand_x = load_node(args.candidate)[0].get("extra") or {}
    rc = 0
    # (field, lower_is_better, allowed fractional slip, fail_when_missing)
    gates = [
        ("moe_tokens_per_sec", False, args.threshold, True),
        ("unet_denoise_ms", True, args.threshold, True),
        # the two full-model extras: a missing value WARNS instead of
        # sinking the round, a present-but-worse value FAILS
        ("resnet50_images_per_sec", False, args.threshold, False),
        ("bert_dp_tokens_per_sec", False, args.threshold, False),
        # eager overhead is host-side Python: allow 50% headroom, and a
        # missing value only warns (it never gated a round's number)
        ("eager_op_overhead_us", True, 0.5, False),
    ]
    # a candidate that deliberately ran headline-only (BENCH_EXTRAS=0
    # sweep experiment) marks itself; its absent extras warn, not fail
    cand_skipped = bool(cand_x.get("extras_skipped"))
    for field, lower_better, slip, fail_missing in gates:
        b, c = base_x.get(field), cand_x.get(field)
        if b is None or b == 0:
            continue
        if c is None:
            msg = (f"baseline has {field}={b} but the candidate bench "
                   "produced none")
            if fail_missing and not cand_skipped:
                print(f"FAIL: {msg}")
                rc = 3
            else:
                print(f"WARN: {msg}")
            continue
        ratio = (c / b) if lower_better else (b / c)
        if ratio > 1.0 + slip:
            print(f"FAIL: {field} {c} vs {b} "
                  f"({(ratio - 1) * 100:.1f}% worse > {slip * 100:.0f}%)")
            rc = 3
        else:
            print(f"OK: {field} {c} vs {b}")
    return rc


if __name__ == "__main__":
    sys.exit(main())
