"""`validate.array_share.catchup`, looked up by name: 100 in a catch-up
cell at a tiny size on the CPU whose blocks hold more unique items than
the validator's probe (a P-256 channel with no key-level validation
parameter: every block's signature table goes to the provider as
arrays), 0 on a chain whose assets carry a validation parameter (every
block on the classic tail, items), listed for the six catch-up cells,
and absent — not raised over — on an exposition without the counter."""

import json
import os

import harness
import run as launcher
import test_sbe_cell
from test_run_cells import tiny_context

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
NAME = "validate.array_share.catchup"
with open(os.path.join(os.path.dirname(BENCH), "BENCHMARK.json")) as f:
    MANIFEST = json.load(f)


def test_the_manifest_lists_it_for_the_catchup_cells():
    entry, = [m for m in MANIFEST["per_layer"] if m["name"] == NAME]
    catchup = [w["name"] for w in MANIFEST["workloads"]
               if w["name"].startswith("catchup.")]
    assert entry["workloads"] == catchup and len(catchup) == 6
    assert (entry["layer"], entry["moves"], entry["source"]) == (
        "validate", "catchup_tps", "program_counter")
    assert os.path.exists(os.path.join(BENCH, "layer_metrics", NAME + ".py"))


def test_a_traced_deep_tail_run_reads_100():
    ctx = tiny_context("catchup.cut10k", trace=True)
    ctx.workload.update(block_tx=100)       # 400 unique items > PROBE
    line = launcher.run_cell(ctx, MANIFEST)
    assert line["correct"], ctx.checks
    assert line["metrics"][NAME]["value"] == 100.0
    assert line["metrics"]["validate.deep_share.catchup"]["value"] == 100.0


def test_a_traced_run_under_validation_parameters_reads_0():
    ctx = test_sbe_cell.tiny_context(trace=True)
    line = launcher.run_cell(ctx, MANIFEST)
    assert line["correct"], [c for c in ctx.checks if not c["ok"]]
    assert line["metrics"][NAME]["value"] == 0.0
    assert line["metrics"]["validate.deep_share.catchup"]["value"] == 0.0


def test_the_reader_on_expositions_made_by_hand():
    read = launcher.load_module("layer_metrics", NAME).read
    prom = harness.parse_prom
    series = 'validator_handoff_sigs_total{channel="ch",form="%s",reason="%s"} %d\n'
    before = prom(series % ("arrays", "bypassed", 1000))
    after = prom(series % ("arrays", "bypassed", 27719)
                 + series % ("items", "scheme", 13281)
                 + series % ("items", "small_block", 0))
    assert round(read({"prom_before": before, "prom_after": after}), 2) == 66.8
    assert read({"prom_before": after, "prom_after": after}) is None
    # a program without the counter (the parent): nothing, and no raise
    old = prom('validator_tail_total{channel="ch",reason="no_sbe",tail="deep"} 5\n')
    assert read({"prom_before": old, "prom_after": old}) is None
    assert read({}) is None
