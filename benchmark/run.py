#!/usr/bin/env python3
"""The benchmark's one command.

    python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s>
                             --trace <0|1>

A launcher that never imports jax (a parent that touched JAX would hold
the chip).  Everything that belongs to one cell is data it finds by the
name in BENCHMARK.json: `workloads/<cell>.json` names the cell's
configuration (`configs/<config>.json`) and its driver
(`drivers/<driver>.py`); the per-layer metrics BENCHMARK.json lists for
the cell are read by `layer_metrics/<name>.py`.  It fails, with no
result line, where JAX finds no accelerator.

The last line of stdout is the result: `correct`, `attempted`, `failed`,
`metrics`, `device` and, traced, `breakdown`.  With `--trace 0` the
metrics are the cell's end-to-end metrics; with `--trace 1` its
per-layer metrics.
"""

from __future__ import annotations

import argparse
import importlib.util
import json
import os
import signal
import sys

BENCH = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(BENCH)
for _p in (REPO, BENCH):
    if _p not in sys.path:
        sys.path.insert(0, _p)

import harness  # noqa: E402  (T0 is taken at this import)

PROGRAM = os.path.join(REPO, "fabric_tpu", "__init__.py")


def load_json(*parts) -> dict:
    with open(os.path.join(*parts)) as f:
        return json.load(f)


def load_module(kind: str, name: str):
    """`<kind>/<name>.py` under the benchmark's directory, by file."""
    path = os.path.join(BENCH, kind, name + ".py")
    spec = importlib.util.spec_from_file_location(
        f"{kind}.{name.replace('.', '_').replace('-', '_')}", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def metrics_of(manifest: dict, section: str, cell: str) -> list:
    """The metrics of one section that this cell reports: those that
    list it under `workloads`, or have no such key.  A per-layer metric
    without the key belongs to the cells that report what it moves."""
    e2e_here = {m["name"] for m in manifest["end_to_end"]
                if cell in m.get("workloads", [cell])}
    if section == "end_to_end":
        return [m for m in manifest[section] if m["name"] in e2e_here]
    return [m for m in manifest[section]
            if (cell in m["workloads"] if "workloads" in m
                else m["moves"] in e2e_here)]


def load_cell(manifest: dict, cell: str) -> tuple:
    """(workload, config) of a cell BENCHMARK.json names, from their files."""
    entry = next(w for w in manifest["workloads"] if w["name"] == cell)
    workload = load_json(BENCH, "workloads", cell + ".json")
    workload["chips"] = entry["chips"]
    return workload, load_json(BENCH, "configs", workload["config"] + ".json")


def run_cell(ctx: harness.Context, manifest: dict) -> dict:
    """Drive one cell and build the result line's object."""
    cell = ctx.workload["name"]
    driver = load_module("drivers", ctx.workload["driver"])
    out = driver.run(ctx)        # {"attempted", "failed", "end_to_end", "obs", "device"}
    if ctx.require_accelerator:
        harness.peaks_of(out["device"]["kind"])     # an unknown chip is an error
    correct = all(c["ok"] for c in ctx.checks)
    metrics = {}
    for m in metrics_of(manifest, "per_layer" if ctx.trace else "end_to_end",
                        cell):
        if ctx.trace:
            value = load_module("layer_metrics", m["name"]).read(out["obs"])
        else:
            value = out["end_to_end"].get(m["name"])
        if value is not None:        # nothing to read: left out of the line
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    line = {"correct": correct, "attempted": out["attempted"],
            "failed": out["failed"], "metrics": metrics,
            "device": out["device"]}
    if ctx.trace and out["obs"].get("trace"):
        line["breakdown"] = {
            "device_ops": out["obs"]["trace"]["device_ops"][:10],
            "idle_gaps": out["obs"]["trace"]["idle_gaps"][:10]}
    return line


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(prog="benchmark/run.py")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    if not os.path.exists(PROGRAM):
        # before anything is started: there is nothing to measure here
        sys.stderr.write(f"benchmark FAILED: the program is not in this "
                         f"checkout (no {PROGRAM})\n")
        return 2
    # a run that is told to end stops its nodes on the way out
    signal.signal(signal.SIGTERM, lambda *_: sys.exit(143))
    harness.adopt_orphans()
    try:
        return measure(args)
    finally:
        # every path out: nothing this run started outlives it
        left = harness.reap_descendants()
        if left:
            sys.stderr.write(f"benchmark: stopped on the way out: {left}\n")


def measure(args) -> int:
    manifest = load_json(REPO, "BENCHMARK.json")
    cells = [w["name"] for w in manifest["workloads"]]
    if args.workload not in cells:
        sys.stderr.write(f"unknown workload {args.workload!r}; "
                         f"BENCHMARK.json has {cells}\n")
        return 2
    workload, config = load_cell(manifest, args.workload)
    ctx = harness.Context(workload=workload, config=config, seed=args.seed,
                          seconds=args.seconds, trace=bool(args.trace))
    try:
        line = run_cell(ctx, manifest)
    except harness.BenchFailure as exc:
        sys.stderr.write(f"benchmark FAILED: {exc}\n")
        return 1
    if "jax" in sys.modules:
        sys.stderr.write("benchmark FAILED: the launcher imported jax\n")
        return 1
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
