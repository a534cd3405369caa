#!/usr/bin/env python3
"""A cell's controls, on the chip, at the cell's own size: the cell run
once under each named fault of its driver.  Each has to come out
`correct: false`.

    python3 benchmark/tests/controls_on_chip.py \\
        --workload catchup.mixedcurve --faults yes_verifier,yes_ed25519 \\
        --seed 11 --seconds 20

`catchup.mixedcurve`'s two: the yes-verifier in the device peer's place,
and a verifier whose Ed25519 answers alone are yes (`yes_ed25519`: the
P-256 kernel still says no, so only the endorsements broken on the
other curve go through).  Run by hand (the benchmark's own runs never
run it); `test_mixedcurve_cell.py` keeps both at a tiny size on the CPU.
"""

from __future__ import annotations

import argparse
import os
import sys

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [os.path.dirname(BENCH), BENCH]

import harness  # noqa: E402
import run as launcher  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--faults", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)
    manifest = launcher.load_json(launcher.REPO, "BENCHMARK.json")
    harness.adopt_orphans()
    outcomes = []
    try:
        for fault in args.faults.split(","):
            workload, config = launcher.load_cell(manifest, args.workload)
            ctx = harness.Context(workload=workload, config=config,
                                  seed=args.seed, seconds=args.seconds,
                                  trace=False, faults=frozenset([fault]))
            line = launcher.run_cell(ctx, manifest)
            broken = [c["name"] for c in ctx.checks if not c["ok"]]
            print(f"control {fault} seed {args.seed}: "
                  f"correct={line['correct']} on {line['device']}; failed "
                  f"comparisons: {broken}", flush=True)
            outcomes.append(line["correct"])
    finally:
        harness.reap_descendants()
    return 1 if any(outcomes) else 0


if __name__ == "__main__":
    sys.exit(main())
