#!/usr/bin/env python3
"""Find a served cell's knee again: the same driver, the same
deployment, with a ramp of offered load in place of the cell's fixed
rate.

    python3 benchmark/sweep.py --workload served.steady --seed 7 \\
        --start 3 --end 18 --seconds 120 [--bin 10]

Prints, for each bin of due times: requests due, the offered rate, how
many of them were answered right and the median and worst time from due
time to answer.  The knee is the offered rate at which the time to
answer stops being flat and the answers fall behind the offers.  Not
part of any benchmark run; a `benchmark` PR reads the knee from it and
writes four fifths of it into the cell's file.
"""

from __future__ import annotations

import argparse
import statistics
import sys

import run as launcher
import harness


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(prog="benchmark/sweep.py")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--start", type=float, required=True)
    ap.add_argument("--end", type=float, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--bin", type=float, default=10.0)
    args = ap.parse_args(argv)
    workload, config = launcher.load_cell(
        launcher.load_json(launcher.REPO, "BENCHMARK.json"), args.workload)
    workload.update(drain_s=120.0,
                    arrivals={"kind": "ramp", "start_rate": args.start,
                              "end_rate": args.end, "ramp_s": args.seconds})
    ctx = harness.Context(workload=workload, config=config, seed=args.seed,
                          seconds=args.seconds, trace=False)
    try:
        out = launcher.load_module("drivers", workload["driver"]).run(ctx)
    except harness.BenchFailure as exc:
        sys.stderr.write(f"sweep FAILED: {exc}\n")
        return 1
    print(f"correct: {all(c['ok'] for c in ctx.checks)}")
    print("bin_start_s due offered_tps answered_right median_ms max_ms "
          "answered_by_bin_end")
    reqs = out["obs"]["requests"]
    t = 0.0
    while t < args.seconds:
        here = [r for r in reqs if t <= r[0] < t + args.bin]
        lat = [1e3 * (done - due) for due, done, ok in here
               if ok and done is not None]
        in_time = sum(1 for due, done, ok in here
                      if ok and done is not None and done < t + args.bin)
        print(t, len(here), len(here) / args.bin, len(lat),
              statistics.median(lat) if lat else None,
              max(lat) if lat else None, in_time)
        t += args.bin
    return 0


if __name__ == "__main__":
    sys.exit(main())
