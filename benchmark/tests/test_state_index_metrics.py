"""`commit.state_ms.catchup` and `commit.index_incremental_share.catchup`,
looked up by name: the span's median for every catch-up cell, the share
for the two cells whose blocks create and delete keys; both read off
spans and expositions made by hand, and absent — not raised over — on a
program without the counter, a window in which no key came or went, or a
run without the span."""

import json
import os

import pytest

import harness
import run as launcher

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
STATE_MS = "commit.state_ms.catchup"
SHARE = "commit.index_incremental_share.catchup"
with open(os.path.join(os.path.dirname(BENCH), "BENCHMARK.json")) as f:
    MANIFEST = json.load(f)


@pytest.mark.parametrize("name,unit,better,source,cells", [
    (STATE_MS, "ms", "lower", "program_span",
     [w["name"] for w in MANIFEST["workloads"]
      if w["name"].startswith("catchup.")]),
    (SHARE, "%", "higher", "program_counter",
     ["catchup.queries.bycolor", "catchup.sbe.owned"])])
def test_the_manifest_lists_it_for_its_cells(name, unit, better, source,
                                             cells):
    entry, = [m for m in MANIFEST["per_layer"] if m["name"] == name]
    assert entry["workloads"] == cells
    assert (entry["unit"], entry["better"], entry["source"], entry["layer"],
            entry["moves"]) == (unit, better, source, "commit", "catchup_tps")
    assert os.path.exists(os.path.join(BENCH, "layer_metrics", name + ".py"))
    for cell in cells:
        assert name in {m["name"] for m in launcher.metrics_of(
            MANIFEST, "per_layer", cell)}
    assert name not in {m["name"] for m in launcher.metrics_of(
        MANIFEST, "per_layer", "served.steady")}


def _prom(**modes):
    return harness.parse_prom("".join(
        'state_index_update_total{channel="ch",mode="%s"} %d\n' % kv
        for kv in modes.items()))


@pytest.mark.parametrize("before,after,want", [
    # both ways inside the window: 60 shard applies by bisects, 20 sorted
    (dict(none=100, incremental=40, merge=4),
     dict(none=900, incremental=100, merge=24), 75.0),
    (dict(none=5), dict(none=50, incremental=16), 100.0),
    (dict(incremental=7), dict(incremental=7, merge=3), 0.0),
    # no key came or went inside the window
    (dict(none=100, incremental=8), dict(none=900, incremental=8), None),
    (dict(none=1), dict(none=1), None)])
def test_the_share_on_expositions_made_by_hand(before, after, want):
    read = launcher.load_module("layer_metrics", SHARE).read
    assert read({"prom_before": _prom(**before),
                 "prom_after": _prom(**after)}) == want


def test_the_share_is_absent_on_a_program_without_the_counter():
    read = launcher.load_module("layer_metrics", SHARE).read
    old = harness.parse_prom(
        'state_shard_keys{channel="ch",shard="0"} 25000\n')
    assert read({"prom_before": old, "prom_after": old}) is None
    assert read({"prom_before": old}) is None
    assert read({}) is None


def test_the_median_on_spans_made_by_hand():
    read = launcher.load_module("layer_metrics", STATE_MS).read

    def span(trace, name, ms):
        return {"trace_id": trace, "name": name, "duration_s": ms / 1e3,
                "attributes": {}}
    spans = [span("a", "ledger.state_commit", 77.0),
             span("a", "ledger.mvcc", 38.0),
             span("b", "ledger.state_commit", 9.0),
             span("c", "ledger.state_commit", 12.0),
             span("c", "ledger.history_commit", 16.0)]
    assert round(read({"spans": spans}), 6) == 12.0
    # an untraced run, or a program that opens no such span
    assert read({"spans": [span("a", "ledger.mvcc", 38.0)]}) is None
    assert read({"spans": []}) is None
    assert read({}) is None
