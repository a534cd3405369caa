#!/usr/bin/env python3
"""The controls of `catchup.sbe.owned`, on the chip, at the cell's own
size: the yes-verifier in the device peer's place (a tampered envelope
goes unflagged) and a device peer whose validator is built without its
look-up of committed validation parameters (`sbe_blind`: every signature
is judged as it is, and every owner-endorsed update then fails AND of
three — the one control the verifier's answers cannot satisfy).  Each
has to come out `correct: false`.

    python3 benchmark/tests/sbe_control_on_chip.py --seed 11 --seconds 20

Run by hand (the benchmark's own runs never run it);
`test_sbe_cell.py` keeps both at a tiny size on the CPU.
"""

from __future__ import annotations

import argparse
import os
import sys

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [os.path.dirname(BENCH), BENCH]

import harness  # noqa: E402
import run as launcher  # noqa: E402

CELL = "catchup.sbe.owned"
FAULTS = ("yes_verifier", "sbe_blind")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)
    manifest = launcher.load_json(launcher.REPO, "BENCHMARK.json")
    harness.adopt_orphans()
    outcomes = []
    try:
        for fault in FAULTS:
            workload, config = launcher.load_cell(manifest, CELL)
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
