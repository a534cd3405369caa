#!/usr/bin/env python3
"""The control, on the chip, at the cell's own size: the cell with a
verifier that answers yes to everything in the device peer's place
(`yes_verifier`), which breaks the guarantee that a tampered envelope is
flagged.  Every seed has to come out `correct: false`.

    python3 benchmark/tests/control_on_chip.py --workload catchup.cut10k \\
        --seeds 11,12,13 --seconds 20

Run by hand by a `benchmark` PR (the benchmark's own runs never run it);
`test_run_cells.py` keeps the same control at a tiny size on the CPU.
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
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)
    manifest = launcher.load_json(launcher.REPO, "BENCHMARK.json")
    outcomes = []
    for seed in (int(s) for s in args.seeds.split(",")):
        workload, config = launcher.load_cell(manifest, args.workload)
        ctx = harness.Context(workload=workload, config=config, seed=seed,
                              seconds=args.seconds, trace=False,
                              faults=frozenset(["yes_verifier"]))
        line = launcher.run_cell(ctx, manifest)
        broken = [c["name"] for c in ctx.checks if not c["ok"]]
        print(f"control seed {seed}: correct={line['correct']} on "
              f"{line['device']}; failed comparisons: {broken}", flush=True)
        outcomes.append(line["correct"])
    return 1 if any(outcomes) else 0


if __name__ == "__main__":
    sys.exit(main())
