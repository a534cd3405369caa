"""`msp.device_share.distinct`, looked up by name: listed for the one
cell whose window validates certificate chains; 0 at a tiny size on the
CPU, where the software provider stands in the device peer's place and
every leaf link is checked on the host; read off expositions made by
hand; and absent — not raised over — on an exposition without the
counter."""

import json
import os

import harness
import run as launcher
import test_enrolled_cell

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
NAME = "msp.device_share.distinct"
CELL = "catchup.cut500.distinct"
with open(os.path.join(os.path.dirname(BENCH), "BENCHMARK.json")) as f:
    MANIFEST = json.load(f)


def test_the_manifest_lists_it_for_the_one_cell():
    entry, = [m for m in MANIFEST["per_layer"] if m["name"] == NAME]
    assert entry["workloads"] == [CELL]
    assert (entry["unit"], entry["better"], entry["layer"], entry["moves"],
            entry["source"]) == ("%", "higher", "identity", "catchup_tps",
                                 "program_counter")
    assert os.path.exists(os.path.join(BENCH, "layer_metrics", NAME + ".py"))


def test_a_traced_run_on_the_software_provider_reads_0():
    ctx = test_enrolled_cell.tiny_context(trace=True)
    line = launcher.run_cell(ctx, MANIFEST)
    assert line["correct"], [c for c in ctx.checks if not c["ok"]]
    assert line["metrics"][NAME]["value"] == 0.0
    assert line["metrics"]["msp.miss_share.cut500"]["value"] > 0.0


def test_the_reader_on_expositions_made_by_hand():
    read = launcher.load_module("layer_metrics", NAME).read
    prom = harness.parse_prom
    series = 'msp_chain_signatures_total{msp="%s",where="%s"} %d\n'
    before = prom(series % ("Org1", "host", 170) + series % ("Org2", "host", 166))
    after = prom(series % ("Org1", "host", 172) + series % ("Org2", "host", 166)
                 + series % ("Org1", "device", 16500)
                 + series % ("Org2", "device", 16498))
    assert round(read({"prom_before": before, "prom_after": after}), 3) \
        == 99.994
    assert read({"prom_before": before, "prom_after": before}) is None
    # a program without the counter (the parent): nothing, and no raise
    old = prom('msp_cache_total{msp="Org1",op="validate",result="miss"} 5\n')
    assert read({"prom_before": old, "prom_after": old}) is None
    assert read({}) is None
