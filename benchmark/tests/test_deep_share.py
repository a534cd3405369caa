"""`validate.deep_share.catchup`, looked up by name: 100 in a catch-up
cell at a tiny size on the CPU (a channel with no key-level validation
parameter: every block takes the validator's deep C tail), listed for
the five catch-up cells and last in the manifest, and absent — not
raised over — on an exposition without the counter."""

import json
import os

import harness
import run as launcher
from test_run_cells import tiny_context

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
NAME = "validate.deep_share.catchup"
with open(os.path.join(os.path.dirname(BENCH), "BENCHMARK.json")) as f:
    MANIFEST = json.load(f)


def test_the_manifest_lists_it_for_the_catchup_cells():
    entry, = [m for m in MANIFEST["per_layer"] if m["name"] == NAME]
    catchup = [w["name"] for w in MANIFEST["workloads"]
               if w["name"].startswith("catchup.")]
    assert entry["workloads"] == catchup and len(catchup) == 5
    assert (entry["layer"], entry["moves"]) == ("validate", "catchup_tps")
    assert os.path.exists(os.path.join(BENCH, "layer_metrics", NAME + ".py"))


def test_a_traced_catchup_run_reads_100():
    ctx = tiny_context("catchup.cut10k", trace=True)
    line = launcher.run_cell(ctx, MANIFEST)
    assert line["correct"], ctx.checks
    assert line["metrics"][NAME]["value"] == 100.0
    # the lanes and the bypass read as they did: the tail moved neither
    assert line["metrics"]["commit.lanes_share.catchup"]["value"] == 100.0


def test_the_reader_on_expositions_made_by_hand():
    read = launcher.load_module("layer_metrics", NAME).read
    prom = harness.parse_prom
    before = prom('validator_tail_total{channel="ch",reason="no_sbe",tail="deep"} 1000\n')
    after = prom('validator_tail_total{channel="ch",reason="no_sbe",tail="deep"} 4000\n'
                 'validator_tail_total{channel="ch",reason="block_meta",tail="classic"} 600\n'
                 'validator_tail_total{channel="ch",reason="state_meta",tail="classic"} 400\n')
    assert read({"prom_before": before, "prom_after": after}) == 75.0
    assert read({"prom_before": after, "prom_after": after}) is None
    # a program without the counter (the parent): nothing, and no raise
    old = prom('verify_cache_bypassed_total{site="commit"} 5\n')
    assert read({"prom_before": old, "prom_after": old}) is None
    assert read({}) is None
