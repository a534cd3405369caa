"""`commit.array_walk_share.catchup` and `commit.mvcc_ms.catchup`, looked
up by name: listed for the seven catch-up cells; 100 and a positive
median in a traced catch-up run at a tiny size on the CPU — on a chain
whose assets carry validation parameters and are deleted too (the array
pass drops a deleted key's parameter itself); read off expositions and
spans made by hand; and absent — not raised over — on a program without
the counter, or a run without the span."""

import json
import os

import harness
import run as launcher
import test_sbe_cell
from test_run_cells import tiny_context

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SHARE = "commit.array_walk_share.catchup"
MVCC_MS = "commit.mvcc_ms.catchup"
with open(os.path.join(os.path.dirname(BENCH), "BENCHMARK.json")) as f:
    MANIFEST = json.load(f)


def test_the_manifest_lists_both_for_the_catchup_cells():
    catchup = [w["name"] for w in MANIFEST["workloads"]
               if w["name"].startswith("catchup.")]
    assert len(catchup) == 7
    for name, unit, better, source in (
            (SHARE, "%", "higher", "program_counter"),
            (MVCC_MS, "ms", "lower", "program_span")):
        entry, = [m for m in MANIFEST["per_layer"] if m["name"] == name]
        assert entry["workloads"] == catchup
        assert (entry["unit"], entry["better"], entry["source"],
                entry["layer"], entry["moves"]) == (
                    unit, better, source, "commit", "catchup_tps")
        assert os.path.exists(os.path.join(BENCH, "layer_metrics",
                                           name + ".py"))
    assert [m["name"] for m in MANIFEST["per_layer"][-2:]] == [SHARE, MVCC_MS]


def test_a_traced_catchup_run_reads_100_and_a_median():
    ctx = tiny_context("catchup.cut10k", trace=True)
    line = launcher.run_cell(ctx, MANIFEST)
    assert line["correct"], ctx.checks
    assert line["metrics"][SHARE]["value"] == 100.0
    assert line["metrics"]["commit.lanes_share.catchup"]["value"] == 100.0
    assert 0.0 < line["metrics"][MVCC_MS]["value"] \
        < line["metrics"]["commit.block_ms"]["value"]


def test_a_traced_run_that_deletes_under_validation_parameters_reads_100():
    ctx = test_sbe_cell.tiny_context(trace=True)
    line = launcher.run_cell(ctx, MANIFEST)
    assert line["correct"], [c for c in ctx.checks if not c["ok"]]
    assert line["metrics"][SHARE]["value"] == 100.0
    assert line["metrics"]["validate.deep_share.catchup"]["value"] == 0.0


def test_the_share_on_expositions_made_by_hand():
    read = launcher.load_module("layer_metrics", SHARE).read
    prom = harness.parse_prom
    series = 'ledger_mvcc_walk_total{channel="ch",reason="%s",walk="%s"} %d\n'
    before = prom(series % ("none", "arrays", 20000))
    after = prom(series % ("none", "arrays", 320000)
                 + series % ("collision", "python", 10000)
                 + series % ("no_native", "python", 90000))
    assert read({"prom_before": before, "prom_after": after}) == 75.0
    assert read({"prom_before": after, "prom_after": after}) is None
    # a program without the counter (the parent): nothing, and no raise
    old = prom('ledger_commit_source_total{channel="ch",source="lanes"} 5\n')
    assert read({"prom_before": old, "prom_after": old}) is None
    assert read({}) is None


def test_the_median_on_spans_made_by_hand():
    read = launcher.load_module("layer_metrics", MVCC_MS).read

    def span(trace, name, ms):
        return {"trace_id": trace, "name": name, "duration_s": ms / 1e3,
                "attributes": {}}
    spans = [span("a", "ledger.mvcc", 40.0), span("a", "ledger.state_commit", 9.0),
             span("b", "ledger.mvcc", 60.0), span("c", "ledger.mvcc", 44.0),
             span("c", "validator.collect", 150.0)]
    assert round(read({"spans": spans}), 6) == 44.0
    # an untraced run, or a program that opens no such span
    assert read({"spans": [span("a", "ledger.state_commit", 9.0)]}) is None
    assert read({}) is None
