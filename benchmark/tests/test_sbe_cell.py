"""`catchup.sbe.owned` at a tiny size on the CPU, the software provider
in the device peer's place: found by name in the manifest, `correct` on
a sound path, not `correct` under the yes-verifier and on a peer blind
to key-level endorsement (the control the verifier's answers cannot
satisfy); the three metrics the validator's new counters and the classic
tail's spans feed are read in the traced run, and found absent — not
raised over — on a program without them."""

import json
import os

import pytest

import harness
import run as launcher

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
REPO = os.path.dirname(BENCH)
CELL, CONFIG = "catchup.sbe.owned", "sbe-and3-cut10k"
NEW = {"validate.collect_ms.sbe", "validate.gate_us_per_key.sbe",
       "validate.param_key_share.sbe"}
with open(os.path.join(REPO, "BENCHMARK.json")) as f:
    MANIFEST = json.load(f)


def test_manifest_names_the_cell_its_configuration_and_its_metrics():
    entry = next(w for w in MANIFEST["workloads"] if w["name"] == CELL)
    assert (entry["config"], entry["chips"]) == (CONFIG, 1)
    assert len(entry["why"]) <= 200
    declared = next(c for c in MANIFEST["configs"] if c["name"] == CONFIG)
    workload, config = launcher.load_cell(MANIFEST, CELL)
    assert workload["driver"] == "sbe_catchup" and workload["who"]
    assert declared["file"] == f"benchmark/configs/{CONFIG}.json"
    assert declared["source"] == config["source"]
    assert len(declared["source"]) <= 200
    assert sorted(config["reduced"]) == sorted(declared["reduced"]) == [
        "blocks", "delivery", "peers_per_org"]
    assert config["signatures_per_tx"] == 2 and config["assets"] == 100000
    assert abs(sum(config["mix"].values()) - 1.0) < 1e-9
    assert {"assumed", "guarantees"} <= set(config)
    e2e = {m["name"] for m in launcher.metrics_of(MANIFEST, "end_to_end",
                                                  CELL)}
    assert e2e == {"catchup_tps", "setup_s"}
    layer = {m["name"]: m for m in launcher.metrics_of(MANIFEST, "per_layer",
                                                       CELL)}
    assert NEW <= set(layer)
    assert all(layer[n]["workloads"] == [CELL]
               and layer[n]["moves"] == "catchup_tps" for n in NEW)
    assert "validate.deep_share.catchup" in layer
    for name in layer:
        assert os.path.exists(os.path.join(BENCH, "layer_metrics",
                                           name + ".py")), name
    # the cell runs no kernel of its own: no roofline share is asked for
    assert not any("roofline" in n or "mfu" in n for n in layer)


def tiny_context(faults=(), trace=False) -> harness.Context:
    """300 assets in three load blocks of 100, a backlog of 8 blocks of
    the mix.  The blocks the profiler would watch lie beyond the
    backlog: there is no chip to trace here."""
    workload, config = launcher.load_cell(MANIFEST, CELL)
    config.update(assets=300, client_identities=12, tamper_every=10,
                  device_peer=dict(config["device_peer"], bccsp="SW"))
    workload.update(block_tx=100, backlog_blocks=8, reference_blocks=2,
                    generator_workers=2, trace_blocks=[100, 101])
    return harness.Context(workload=workload, config=config, seed=2**31 + 39,
                           seconds=30.0, trace=trace,
                           require_accelerator=False,
                           faults=frozenset(faults))


def test_sound_path_is_correct():
    ctx = tiny_context()
    line = launcher.run_cell(ctx, MANIFEST)
    assert line["correct"], [c for c in ctx.checks if not c["ok"]]
    assert line["attempted"] == 800
    assert line["failed"] == 0
    assert set(line["metrics"]) == {"catchup_tps", "setup_s"}
    assert all(v["value"] > 0 for v in line["metrics"].values())
    compared = {c["name"]: c for c in ctx.checks}
    for what in ("wrong_org_failures", "overlay_failures", "mvcc_conflicts",
                 "deletes", "recreates"):
        assert compared[f"{what} in the window's blocks, by the model"][
            "value"] >= 1
        assert compared[f"{what} in the window's blocks, by the device "
                        "peer's flags"]["ok"]
    assert compared["transactions of the window validated on the classic "
                    "tail because the state holds validation parameters"][
        "value"] == 800
    assert compared["transactions of the window validated on the deep tail"][
        "value"] == 0
    assert compared["transactions of the load phase not VALID (device peer)"][
        "value"] == 0
    assert compared["ids compared on the device peer"]["value"] >= 300
    assert any(n.startswith("assets whose record or validation parameter")
               and "(device peer" in n for n in compared)
    assert any(n.startswith("assets whose record or validation parameter")
               and "(software peer" in n for n in compared)
    assert compared["validation parameters the device peer's state counts "
                    "(StateDB.meta_keys) against the model's"]["value"] > 250


@pytest.mark.parametrize("fault", ["yes_verifier", "sbe_blind"])
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
        # every signature is judged as it is: what fails is the policy
        # a blind peer holds the owner-endorsed updates to
        assert not tampered
        assert line["failed"] > 500


def test_traced_run_reports_the_new_metrics():
    ctx = tiny_context(trace=True)
    line = launcher.run_cell(ctx, MANIFEST)
    assert line["correct"], [c for c in ctx.checks if not c["ok"]]
    listed = {m["name"] for m in launcher.metrics_of(MANIFEST, "per_layer",
                                                     CELL)}
    assert set(line["metrics"]) <= listed
    assert NEW <= set(line["metrics"]), sorted(line["metrics"])
    assert all(line["metrics"][n]["value"] > 0 for n in NEW)
    assert 85 < line["metrics"]["validate.param_key_share.sbe"]["value"] < 100
    assert line["metrics"]["validate.deep_share.catchup"]["value"] == 0.0
    assert line["metrics"]["commit.lanes_share.catchup"]["value"] == 100.0
    assert {"validate.block_ms", "commit.block_ms"} <= set(line["metrics"])


def read_all(obs) -> dict:
    return {name: launcher.load_module("layer_metrics", name).read(obs)
            for name in sorted(NEW)}


def test_readers_read_the_counters_and_the_classic_tails_spans():
    before = harness.parse_prom(
        'validator_sbe_keys_total{channel="ch",judged="parameter"} 100\n'
        'validator_sbe_keys_total{channel="ch",judged="namespace"} 10\n'
        'validator_sbe_keys_total{channel="ch",judged="overlay"} 0\n')
    after = harness.parse_prom(
        'validator_sbe_keys_total{channel="ch",judged="parameter"} 1000\n'
        'validator_sbe_keys_total{channel="ch",judged="namespace"} 60\n'
        'validator_sbe_keys_total{channel="ch",judged="overlay"} 50\n')
    obs = {"prom_before": before, "prom_after": after,
           "spans": [{"name": "validator.gate", "start": 1.0,
                      "duration_s": 0.004, "trace_id": "a"},
                     {"name": "validator.collect", "start": 0.6,
                      "duration_s": 0.3, "trace_id": "a"},
                     {"name": "validator.gate", "start": 9.0,
                      "duration_s": 7.0, "trace_id": "b"}],
           "attributed_spans": [
               {"name": "validator.collect", "start": 0.6,
                "duration_s": 0.3, "attributes": {"tail": "classic"}},
               {"name": "validator.collect", "start": 2.6,
                "duration_s": 0.5, "attributes": {"tail": "classic"}},
               {"name": "validator.collect", "start": 3.6,
                "duration_s": 0.1, "attributes": {"tail": "deep"}},
               {"name": "validator.gate", "start": 1.0,
                "duration_s": 0.004, "attributes": {"sbe_keys": 500}}],
           "blocks": [{"start": 0.5, "end": 2.0,
                       "counts": {"sbe_keys": 500.0}},
                      {"start": 3.0, "end": 4.0,      # its spans are gone
                       "counts": {"sbe_keys": 500.0}}]}
    got = read_all(obs)
    assert got["validate.collect_ms.sbe"] == pytest.approx(400.0)
    assert got["validate.gate_us_per_key.sbe"] == pytest.approx(8.0)
    assert got["validate.param_key_share.sbe"] == pytest.approx(95.0)


def test_readers_find_nothing_on_a_program_without_the_counters():
    """As on the parent commit: no counter, no attributed span, no
    number, no error."""
    nothing = dict.fromkeys(sorted(NEW))
    parent = harness.parse_prom(
        'validator_tail_total{channel="ch",tail="deep",reason="no_sbe"} 9\n'
        'process_uptime_seconds 50\n')
    obs = {"prom_before": {}, "prom_after": parent,
           "spans": [{"name": "validator.gate", "start": 1.0,
                      "duration_s": 0.1, "trace_id": "t"}],
           "blocks": [{"start": 0.5, "end": 2.0,
                       "counts": {"reads": 5.0, "sbe_keys": 0.0}}]}
    assert read_all(obs) == nothing
    assert read_all({}) == nothing
    # a blind peer: the counters are there and no parameter answered
    blind = harness.parse_prom(
        'validator_sbe_keys_total{channel="ch",judged="namespace"} 40\n')
    share = launcher.load_module(
        "layer_metrics", "validate.param_key_share.sbe").read(
            {"prom_before": {}, "prom_after": blind})
    assert share == 0.0
