"""`catchup.queries.bycolor` at a tiny size on the CPU, the software
provider in the device peer's place: found by name in the manifest,
`correct` on a sound path, not `correct` under the yes-verifier and on
a peer whose commit calls every range query stable (the control no
verifier's answer can cause or cover); the four metrics the ledger's
range counters feed are read in the traced run, and found absent — not
raised over — on a program without them."""

import json
import os

import pytest

import harness
import run as launcher

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
REPO = os.path.dirname(BENCH)
CELL, CONFIG = "catchup.queries.bycolor", "queries-and3-cut500"
NEW = {"commit.range_ms.queries", "commit.range_us_per_result.queries",
       "commit.range_held_share.queries", "commit.envelope_share.queries"}
with open(os.path.join(REPO, "BENCHMARK.json")) as f:
    MANIFEST = json.load(f)


def test_manifest_names_the_cell_its_configuration_and_its_metrics():
    entry = next(w for w in MANIFEST["workloads"] if w["name"] == CELL)
    assert (entry["config"], entry["chips"]) == (CONFIG, 1)
    assert len(entry["why"]) <= 200
    declared = next(c for c in MANIFEST["configs"] if c["name"] == CONFIG)
    workload, config = launcher.load_cell(MANIFEST, CELL)
    assert workload["driver"] == "queries_catchup" and workload["who"]
    assert workload["run_tx"] >= 400000
    assert declared["file"] == f"benchmark/configs/{CONFIG}.json"
    assert declared["source"] == config["source"]
    assert len(declared["source"]) <= 200 and len(declared["why"]) <= 200
    assert sorted(config["reduced"]) == sorted(declared["reduced"]) == [
        "blocks", "delivery", "peers_per_org"]
    assert (config["assets"], config["colors"], config["assets_per_color"],
            config["signatures_per_tx"], config["tamper_every"]) == (
        100000, 4000, 25, 4, 100)
    assert config["mix"] == {"TransferAsset": 0.75,
                             "TransferAssetByColor": 0.10,
                             "CreateAsset": 0.075, "DeleteAsset": 0.075}
    # and3-cut500 key for key, but for its contract and data
    control = launcher.load_json(BENCH, "configs", "and3-cut500.json")
    for key in ("channel", "orderers", "peer_orgs", "peers_per_org",
                "device_org", "batch", "client_identities",
                "signatures_per_tx", "tamper_every"):
        assert config[key] == control[key], key
    assert config["chaincode"]["policy"] == control["chaincode"]["policy"]
    assert {"assumed", "guarantees"} <= set(config)
    e2e = {m["name"] for m in launcher.metrics_of(MANIFEST, "end_to_end",
                                                  CELL)}
    assert e2e == {"catchup_tps", "setup_s"}
    layer = {m["name"]: m for m in launcher.metrics_of(MANIFEST, "per_layer",
                                                       CELL)}
    assert NEW <= set(layer)
    assert all(layer[n]["workloads"] == [CELL] and layer[n]["layer"] ==
               "commit" and layer[n]["moves"] == "catchup_tps" for n in NEW)
    # every per-layer metric of the control is read here too
    of_control = {m["name"] for m in launcher.metrics_of(
        MANIFEST, "per_layer", "catchup.cut500")}
    assert of_control <= set(layer)
    assert {"commit.lanes_share.catchup",
            "commit.array_walk_share.catchup"} <= of_control
    for name in layer:
        assert os.path.exists(os.path.join(BENCH, "layer_metrics",
                                           name + ".py")), name
    # the cell runs no kernel of its own: no roofline share is asked for
    assert not any("roofline" in n or "mfu" in n for n in layer)


def tiny_context(faults=(), trace=False) -> harness.Context:
    """400 assets in 16 colours of 25, blocks cut by count at 60 or by
    bytes before it, a backlog of 900 transactions of the mix.  The
    blocks the profiler would watch lie beyond the backlog: there is no
    chip to trace here."""
    workload, config = launcher.load_cell(MANIFEST, CELL)
    config.update(assets=400, colors=16, client_identities=12,
                  tamper_every=10,
                  batch=dict(config["batch"], max_message_count=60,
                             preferred_max_bytes=300_000),
                  device_peer=dict(config["device_peer"], bccsp="SW"))
    workload.update(run_tx=900, reference_blocks=3, generator_workers=2,
                    trace_blocks=[100, 101])
    return harness.Context(workload=workload, config=config, seed=2**31 + 45,
                           seconds=30.0, trace=trace,
                           require_accelerator=False,
                           faults=frozenset(faults))


def test_sound_path_is_correct():
    ctx = tiny_context()
    line = launcher.run_cell(ctx, MANIFEST)
    assert line["correct"], [c for c in ctx.checks if not c["ok"]]
    assert line["attempted"] == 900
    assert line["failed"] == 0
    assert set(line["metrics"]) == {"catchup_tps", "setup_s"}
    assert all(v["value"] > 0 for v in line["metrics"].values())
    compared = {c["name"]: c for c in ctx.checks}
    for what in ("phantoms_by_create", "bycolor_mvcc_by_transfer",
                 "bycolor_mvcc_by_delete", "ranges_held", "creates",
                 "deletes"):
        assert compared[f"{what} in the window's blocks, by the model"][
            "value"] >= 1
    for what in ("phantoms", "bycolor_mvcc", "bycolor_valid"):
        assert compared[f"{what} in the window's blocks, by the device "
                        "peer's flags against the model's"]["ok"]
    assert compared["transactions of the load phase not VALID (device peer)"][
        "value"] == 0
    assert compared["ids compared on the device peer"]["value"] > 400
    assert any(n.startswith("assets whose record or index entry")
               and "(device peer" in n for n in compared)
    assert any(n.startswith("assets whose record or index entry")
               and "(software peer" in n for n in compared)
    by_start = {n.split(" ")[0]: c for n, c in compared.items()
                if n.startswith("ledger_")}
    assert by_start["ledger_mvcc_range_queries_total{result=phantom}"][
        "value"] >= 1
    assert by_start["ledger_mvcc_range_reads_total"]["value"] > 100
    assert by_start["ledger_commit_source_total{source=envelopes}"][
        "value"] >= 800
    assert 20 <= compared["largest range any transaction of the chain "
                          "recorded, results (raw reads only: upstream "
                          "hashes above its degree of 50)"]["value"] <= 50


@pytest.mark.parametrize("fault", ["yes_verifier", "range_blind"])
def test_broken_path_is_not_correct(fault):
    ctx = tiny_context(faults=[fault])
    line = launcher.run_cell(ctx, MANIFEST)
    assert not line["correct"]
    failed = [c["name"] for c in ctx.checks if not c["ok"]]
    assert any("flags differ from the generator's" in n for n in failed)
    tampered = any("tampered" in n for n in failed)
    if fault == "yes_verifier":
        assert tampered
    else:
        # every signature is judged as it is: what goes wrong is a
        # phantom committed, and with it flags, counters and state
        assert not tampered
        assert any(n.startswith("phantoms in the window's blocks, by the "
                                "device peer's flags") for n in failed)
        assert any(n.startswith("assets whose record or index entry")
                   and "(device peer" in n for n in failed)
        assert not any("software peer)" in n and "differ from" in n
                       for n in failed)


def test_traced_run_reports_the_new_metrics():
    ctx = tiny_context(trace=True)
    line = launcher.run_cell(ctx, MANIFEST)
    assert line["correct"], [c for c in ctx.checks if not c["ok"]]
    listed = {m["name"] for m in launcher.metrics_of(MANIFEST, "per_layer",
                                                     CELL)}
    assert set(line["metrics"]) <= listed
    assert NEW <= set(line["metrics"]), sorted(line["metrics"])
    value = {n: line["metrics"][n]["value"] for n in line["metrics"]}
    assert value["commit.range_ms.queries"] > 0
    assert value["commit.range_us_per_result.queries"] > 0
    assert 50 < value["commit.range_held_share.queries"] < 100
    # every block of this backlog holds a by-colour transaction that
    # passed the gate, but for one or two
    assert value["commit.envelope_share.queries"] > 85
    assert value["commit.lanes_share.catchup"] == pytest.approx(
        100.0 - value["commit.envelope_share.queries"])
    assert value["commit.array_walk_share.catchup"] == pytest.approx(
        value["commit.lanes_share.catchup"])
    assert {"validate.block_ms", "commit.block_ms", "commit.mvcc_ms.catchup",
            "commit.fsync_ms.cut500"} <= set(value)


def read_all(obs) -> dict:
    return {name: launcher.load_module("layer_metrics", name).read(obs)
            for name in sorted(NEW)}


def test_readers_read_the_counters():
    def prom(text):
        return harness.parse_prom(text)
    before = prom('ledger_mvcc_range_queries_total{channel="ch",result="held"} 10\n'
                  'ledger_mvcc_range_queries_total{channel="ch",result="phantom"} 0\n'
                  'ledger_mvcc_range_reads_total{channel="ch"} 250\n'
                  'ledger_mvcc_range_seconds_sum{channel="ch"} 0.5\n'
                  'ledger_mvcc_range_seconds_count{channel="ch"} 5\n'
                  'ledger_commit_source_total{channel="ch",source="lanes"} 1000\n'
                  'validator_stage_seconds_count{stage="collect"} 2\n')
    after = prom('ledger_mvcc_range_queries_total{channel="ch",result="held"} 106\n'
                 'ledger_mvcc_range_queries_total{channel="ch",result="phantom"} 4\n'
                 'ledger_mvcc_range_reads_total{channel="ch"} 2750\n'
                 'ledger_mvcc_range_seconds_sum{channel="ch"} 0.55\n'
                 'ledger_mvcc_range_seconds_count{channel="ch"} 9\n'
                 'ledger_commit_source_total{channel="ch",source="lanes"} 1500\n'
                 'ledger_commit_source_total{channel="ch",source="envelopes"} 1500\n'
                 'validator_stage_seconds_count{stage="collect"} 7\n')
    got = read_all({"prom_before": before, "prom_after": after})
    assert got["commit.range_ms.queries"] == pytest.approx(10.0)
    assert got["commit.range_us_per_result.queries"] == pytest.approx(20.0)
    assert got["commit.range_held_share.queries"] == pytest.approx(96.0)
    assert got["commit.envelope_share.queries"] == pytest.approx(75.0)


def test_readers_find_nothing_on_a_program_without_the_counters():
    """As on the parent commit: no counter, no number, no error."""
    nothing = dict.fromkeys(sorted(NEW))
    parent = harness.parse_prom(
        'validator_stage_seconds_count{stage="collect"} 6\n'
        'process_uptime_seconds 50\n')
    assert read_all({"prom_before": {}, "prom_after": parent}) == nothing
    assert read_all({}) == nothing
    # the commit source is older than the range counters: where it alone
    # is there, it alone is read
    older = harness.parse_prom(
        'ledger_commit_source_total{channel="ch",source="lanes"} 500\n')
    got = read_all({"prom_before": {}, "prom_after": older})
    assert got.pop("commit.envelope_share.queries") == 0.0
    assert set(got.values()) == {None}
