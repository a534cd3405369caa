"""`commit.lanes_prepared_share.catchup`, looked up by name: listed for
the cells that report `catchup_tps`, 100 in a traced catch-up run at a
tiny size on the CPU, read off expositions made by hand, and absent — not raised over — on a program without the counter (the
parent of the PR that brought it) or a window without a lane table."""

import json
import os

import pytest

import harness
import run as launcher
from test_run_cells import tiny_context

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SHARE = "commit.lanes_prepared_share.catchup"
with open(os.path.join(os.path.dirname(BENCH), "BENCHMARK.json")) as f:
    MANIFEST = json.load(f)


def test_the_manifest_lists_it_for_the_cells_that_report_catchup_tps():
    entry, = [m for m in MANIFEST["per_layer"] if m["name"] == SHARE]
    tps, = [m for m in MANIFEST["end_to_end"] if m["name"] == "catchup_tps"]
    assert entry["workloads"] == tps["workloads"]
    assert len(entry["workloads"]) == 8
    assert entry == {"name": SHARE, "unit": "%", "better": "higher",
                     "source": "program_counter", "layer": "commit",
                     "moves": "catchup_tps", "workloads": tps["workloads"]}
    assert MANIFEST["per_layer"][-1] == entry       # appended, nothing moved
    assert os.path.exists(os.path.join(BENCH, "layer_metrics", SHARE + ".py"))
    for cell in entry["workloads"]:
        assert SHARE in {m["name"] for m in launcher.metrics_of(
            MANIFEST, "per_layer", cell)}
    assert SHARE not in {m["name"] for m in launcher.metrics_of(
        MANIFEST, "per_layer", "served.steady")}


def test_a_traced_catchup_run_reads_100():
    ctx = tiny_context("catchup.cut10k", trace=True)
    line = launcher.run_cell(ctx, MANIFEST)
    assert line["correct"], ctx.checks
    assert line["metrics"][SHARE]["value"] == 100.0
    assert line["metrics"]["commit.lanes_share.catchup"]["value"] == 100.0


def _prom(**ats):
    return harness.parse_prom("".join(
        'ledger_lane_table_opened_total{channel="ch",at="%s"} %d\n' % kv
        for kv in ats.items()))


@pytest.mark.parametrize("before,after,want", [
    # every block of the window prepared in the validator's wait
    (dict(validator_wait=20000), dict(validator_wait=520000), 100.0),
    # none: a peer whose blocks reach the ledger unvalidated
    (dict(commit=500), dict(commit=1500), 0.0),
    # both inside the window: 30,000 tx ahead, 10,000 in the commit
    (dict(validator_wait=10000, commit=500),
     dict(validator_wait=40000, commit=10500), 75.0),
    # what came before the window does not count
    (dict(validator_wait=10000, commit=9000),
     dict(validator_wait=20000, commit=9000), 100.0),
    # no block of the window had a lane table
    (dict(validator_wait=10000), dict(validator_wait=10000), None)])
def test_the_share_on_expositions_made_by_hand(before, after, want):
    read = launcher.load_module("layer_metrics", SHARE).read
    assert read({"prom_before": _prom(**before),
                 "prom_after": _prom(**after)}) == want


def test_the_share_is_absent_on_a_program_without_the_counter():
    read = launcher.load_module("layer_metrics", SHARE).read
    parent = harness.parse_prom(
        'ledger_commit_source_total{channel="ch",source="lanes"} 250000\n')
    assert read({"prom_before": parent, "prom_after": parent}) is None
    assert read({"prom_before": parent}) is None
    assert read({}) is None
