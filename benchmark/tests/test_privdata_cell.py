"""`catchup.privdata.trade` at a tiny size on the CPU, the software
provider in the device peer's place: found by name in the manifest,
`correct` on a sound path, not `correct` under the yes-verifier and on a
ledger whose expiry step is skipped (the control no verifier's answer
can cause or cover); the four metrics the coordinator's and the ledger's
counters feed are read in the traced run, and found absent — not raised
over — on a program without them."""

import json
import os

import pytest

import harness
import run as launcher

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
REPO = os.path.dirname(BENCH)
CELL, CONFIG = "catchup.privdata.trade", "privdata-or2-cut500"
NEW = {"privdata.store_ms.trade": "privdata",
       "privdata.resolve_us.trade": "privdata",
       "privdata.decoded_share.trade": "privdata",
       "commit.expiry_ms.trade": "commit"}
with open(os.path.join(REPO, "BENCHMARK.json")) as f:
    MANIFEST = json.load(f)


def by_name(section: str) -> dict:
    """The manifest's entries by their name, never by their place."""
    return {e["name"]: e for e in MANIFEST[section]}


def test_manifest_names_the_cell_its_configuration_and_its_metrics():
    entry = by_name("workloads")[CELL]
    assert (entry["config"], entry["traffic"], entry["chips"]) == (
        CONFIG, "privdata.trade", 1)
    assert len(entry["why"]) <= 200
    declared = by_name("configs")[CONFIG]
    workload, config = launcher.load_cell(MANIFEST, CELL)
    assert workload["driver"] == "privdata_catchup" and workload["who"]
    assert workload["run_tx"] >= 500000
    assert workload["reference_orgs"] == ["Org2", "Org3"]
    assert declared["file"] == f"benchmark/configs/{CONFIG}.json"
    assert declared["source"] == config["source"]
    assert len(declared["source"]) <= 200 and len(declared["why"]) <= 200
    assert sorted(config["reduced"]) == sorted(declared["reduced"]) == [
        "blocks", "delivery", "dissemination", "peers_per_org"]
    assert config["architecture"] is None
    assert (config["assets"], config["signatures_per_tx"],
            config["tamper_every"]) == (100000, 2, 100)
    assert sum(config["mix"].values()) == pytest.approx(1.0)
    # and3-cut500 key for key, but for its contract, policy and data
    control = launcher.load_json(BENCH, "configs", "and3-cut500.json")
    for key in ("channel", "orderers", "peer_orgs", "peers_per_org",
                "device_org", "batch", "client_identities", "tamper_every"):
        assert config[key] == control[key], key
    assert config["chaincode"]["policy"] == "OR('Org1.peer','Org2.peer')"
    assert {"assumed", "guarantees"} <= set(config)
    # the sample's collections, as published and as the nodes are given
    # them
    published = {c["name"]: c for c in config["collections"]}
    assert published["assetCollection"]["blockToLive"] == 1000000
    for peer in ("device_peer", "reference_peer"):
        given = {c["name"]: c for c in config[peer]["collections"]}
        assert set(given) == set(published) == {
            "assetCollection", "Org1PrivateCollection",
            "Org2PrivateCollection"}
        for name, c in given.items():
            p = published[name]
            assert (c["btl"], c["required_peer_count"], c["max_peer_count"],
                    c["member_only_read"], c["member_only_write"]) == (
                p["blockToLive"], p["requiredPeerCount"], p["maxPeerCount"],
                p["memberOnlyRead"], p["memberOnlyWrite"]), name
            assert c.get("endorsement_policy", "") == p.get(
                "endorsementPolicy", {}).get("signaturePolicy", "")
        assert given["Org2PrivateCollection"]["members"] == ["Org2"]
        assert given["Org1PrivateCollection"]["btl"] == 3
        (cc,) = config[peer]["chaincodes"]
        assert cc["contract"] == "asset_private"
    from gen import privdata as gen
    model = gen.collections(tuple(config["trading_orgs"]))
    given = {c["name"]: c for c in config["device_peer"]["collections"]}
    for name, c in model.items():
        assert (list(c["members"]), c["btl"]) == (given[name]["members"],
                                                  given[name]["btl"])
    e2e = {m["name"] for m in launcher.metrics_of(MANIFEST, "end_to_end",
                                                  CELL)}
    assert e2e == {"catchup_tps", "setup_s"}
    layer = {m["name"]: m for m in launcher.metrics_of(MANIFEST, "per_layer",
                                                       CELL)}
    assert set(NEW) <= set(layer)
    for name, where in NEW.items():
        m = layer[name]
        assert (m["workloads"], m["layer"], m["moves"], m["better"]) == (
            [CELL], where, "catchup_tps", "lower"), name
    # every per-layer metric of the control is read here too, and the
    # share of the state's index kept up by the keys a block adds and
    # removes
    of_control = {m["name"] for m in launcher.metrics_of(
        MANIFEST, "per_layer", "catchup.cut500")}
    assert of_control <= set(layer)
    assert "commit.index_incremental_share.catchup" in layer
    for name in layer:
        assert os.path.exists(os.path.join(BENCH, "layer_metrics",
                                           name + ".py")), name
    # the cell runs no kernel of its own: no roofline share is asked for
    assert not any("roofline" in n or "mfu" in n for n in layer)
    # nothing the accepted benchmark had was taken away
    assert len(MANIFEST["workloads"]) == 10 and len(MANIFEST["configs"]) == 9
    assert by_name("end_to_end")["catchup_tps"]["bound"] == 0.08


def tiny_context(faults=(), trace=False) -> harness.Context:
    """600 assets, blocks cut by count at 100, a backlog of 2,400
    transactions of the mix.  The blocks the profiler would watch lie
    beyond the backlog: there is no chip to trace here."""
    workload, config = launcher.load_cell(MANIFEST, CELL)
    config.update(assets=600, client_identities=12, tamper_every=20,
                  batch=dict(config["batch"], max_message_count=100),
                  device_peer=dict(config["device_peer"], bccsp="SW"))
    workload.update(run_tx=2400, reference_blocks=8, generator_workers=2,
                    trace_blocks=[100, 101])
    return harness.Context(workload=workload, config=config, seed=2**31 + 48,
                           seconds=60.0, trace=trace,
                           require_accelerator=False,
                           faults=frozenset(faults))


def test_sound_path_is_correct():
    ctx = tiny_context()
    line = launcher.run_cell(ctx, MANIFEST)
    assert line["correct"], [c for c in ctx.checks if not c["ok"]]
    assert line["attempted"] == 2400
    assert line["failed"] == 0
    assert set(line["metrics"]) == {"catchup_tps", "setup_s"}
    assert all(v["value"] > 0 for v in line["metrics"].values())
    compared = {c["name"]: c for c in ctx.checks}
    for cause in ("tampered", "collection_policy", "conflict", "expired"):
        assert compared[f"transactions lost to `{cause}` in the window's "
                        "blocks, by the model"]["value"] >= 1
    assert compared["transactions of the load phase not VALID (device peer)"][
        "value"] == 0
    starts = ("collections whose hashed state differs from the model's",
              "the device peer's private store against the model's Org1 view",
              "Org2's software peer's private store against the model's "
              "Org2 view",
              "keys in Org3's software peer's private store (Org3 is a "
              "member of no collection)",
              "blocks whose flags or commit hash differ between the device "
              "peer and Org3's software peer (a member of no collection",
              "blocks whose flags or commit hash differ between the device "
              "peer and Org2's software peer (a member of "
              "Org2PrivateCollection, assetCollection)",
              "entries left in the device peer's transient store",
              "private write-sets the device peer recorded missing",
              "pulls of a private write-set the device peer made",
              "blocks of the chain whose bytes hold \"appraisedValue\"")
    for start in starts:
        assert any(n.startswith(start) and c["ok"]
                   for n, c in compared.items()), start
    by_start = {n.split(" ")[0]: c for n, c in compared.items()
                if n.startswith(("ledger_", "privdata_"))}
    assert by_start["ledger_pvt_expired_keys_total"]["value"] > 100
    assert by_start["privdata_txs_total{result=resolved}"]["value"] > 2000
    assert by_start["privdata_txs_total{result=not_member}"]["value"] > 500
    assert by_start["privdata_decoded_txs_total"]["value"] > 2000
    assert by_start["privdata_fetch_total"]["value"] == 0


@pytest.mark.parametrize("fault", ["yes_verifier", "expiry_blind"])
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
        # transfer committed after its appraisal's purge, and with it
        # flags, the expired-keys counter and the hashed state
        assert not tampered
        assert any(n.startswith("MVCC_READ_CONFLICT in the window's blocks")
                   for n in failed)
        assert any(n.startswith("ledger_pvt_expired_keys_total")
                   for n in failed)
        assert any(n.startswith("collections whose hashed state differs")
                   and "the device peer" in n for n in failed)
        assert not any("software peer)" in n and "differ from" in n
                       for n in failed)
        assert not any(n.startswith("collections whose hashed state")
                       and "software peer" in n for n in failed)


def test_traced_run_reports_the_new_metrics():
    ctx = tiny_context(trace=True)
    line = launcher.run_cell(ctx, MANIFEST)
    assert line["correct"], [c for c in ctx.checks if not c["ok"]]
    listed = {m["name"] for m in launcher.metrics_of(MANIFEST, "per_layer",
                                                     CELL)}
    assert set(line["metrics"]) <= listed
    assert set(NEW) <= set(line["metrics"]), sorted(line["metrics"])
    value = {n: line["metrics"][n]["value"] for n in line["metrics"]}
    assert value["privdata.store_ms.trade"] > 0
    assert value["privdata.resolve_us.trade"] > 0
    assert value["commit.expiry_ms.trade"] > 0
    # every VALID transaction of this chain writes under a collection
    assert value["privdata.decoded_share.trade"] == pytest.approx(100.0)
    # hashed rw-sets ride the lane table and the array walk like any
    # other point rw-set
    assert value["commit.lanes_share.catchup"] == pytest.approx(100.0)
    assert value["commit.array_walk_share.catchup"] == pytest.approx(100.0)
    assert value["validate.deep_share.catchup"] == pytest.approx(100.0)
    assert value["commit.index_incremental_share.catchup"] == \
        pytest.approx(100.0)
    assert {"validate.block_ms", "commit.block_ms", "commit.mvcc_ms.catchup",
            "commit.state_ms.catchup", "commit.fsync_ms.cut500"} <= set(value)


def read_all(obs) -> dict:
    return {name: launcher.load_module("layer_metrics", name).read(obs)
            for name in sorted(NEW)}


def test_readers_read_the_counters_and_the_span():
    def prom(text):
        return harness.parse_prom(text)
    before = prom('privdata_txs_total{channel="ch",result="resolved"} 100\n'
                  'privdata_txs_total{channel="ch",result="not_member"} 40\n'
                  'privdata_resolve_seconds_sum{channel="ch"} 0.5\n'
                  'privdata_resolve_seconds_count{channel="ch"} 100\n'
                  'privdata_decoded_txs_total{channel="ch"} 90\n'
                  'ledger_tx_total{channel="ch",code="VALID"} 90\n'
                  'ledger_tx_total{channel="ch",code="MVCC_READ_CONFLICT"} 9\n'
                  'ledger_pvt_expiry_seconds_sum{channel="ch"} 0.25\n'
                  'ledger_pvt_expiry_seconds_count{channel="ch"} 10\n')
    after = prom('privdata_txs_total{channel="ch",result="resolved"} 1100\n'
                 'privdata_txs_total{channel="ch",result="not_member"} 440\n'
                 'privdata_resolve_seconds_sum{channel="ch"} 0.53\n'
                 'privdata_resolve_seconds_count{channel="ch"} 1100\n'
                 'privdata_decoded_txs_total{channel="ch"} 840\n'
                 'ledger_tx_total{channel="ch",code="VALID"} 1090\n'
                 'ledger_tx_total{channel="ch",code="MVCC_READ_CONFLICT"} 50\n'
                 'ledger_pvt_expiry_seconds_sum{channel="ch"} 0.29\n'
                 'ledger_pvt_expiry_seconds_count{channel="ch"} 30\n')
    spans = [{"name": "privdata.store_block", "trace_id": t,
              "duration_s": d, "start": 0.0}
             for t, d in (("a", 0.020), ("b", 0.030), ("c", 0.025))]
    spans.append({"name": "ledger.mvcc", "trace_id": "a",
                  "duration_s": 9.0, "start": 0.0})
    got = read_all({"prom_before": before, "prom_after": after,
                    "spans": spans})
    assert got["privdata.store_ms.trade"] == pytest.approx(25.0)
    assert got["privdata.resolve_us.trade"] == pytest.approx(30.0)
    assert got["privdata.decoded_share.trade"] == pytest.approx(75.0)
    assert got["commit.expiry_ms.trade"] == pytest.approx(2.0)


def test_readers_find_nothing_on_a_program_without_the_counters():
    """As on the parent commit: no counter, no span, no number, no
    error."""
    nothing = dict.fromkeys(sorted(NEW))
    parent = harness.parse_prom(
        'ledger_tx_total{channel="ch",code="VALID"} 600\n'
        'validator_stage_seconds_count{stage="collect"} 6\n'
        'process_uptime_seconds 50\n')
    assert read_all({"prom_before": {}, "prom_after": parent,
                     "spans": [{"name": "ledger.mvcc", "trace_id": "a",
                                "duration_s": 1.0, "start": 0.0}]}) == nothing
    assert read_all({}) == nothing
    # a channel without collections moves none of them
    quiet = harness.parse_prom(
        'ledger_tx_total{channel="ch",code="VALID"} 600\n'
        'privdata_decoded_txs_total{channel="ch"} 0\n'
        'privdata_txs_total{channel="ch",result="resolved"} 0\n')
    got = read_all({"prom_before": {}, "prom_after": quiet})
    assert got.pop("privdata.decoded_share.trade") == 0.0
    assert set(got.values()) == {None}
