"""`catchup.cut500.distinct` at a tiny size on the CPU (a roll of 600, 6
blocks of 50, the MSP caches left at 100), the software provider in the
device peer's place: the generator a pure function of the seed, a
block's creators distinct, expected flags by class; `correct` on a sound
path, not `correct` under the yes-verifier and under MSPs that call
every chain valid; the four readers the cell brings, on a recorded
`obs`; a program without the roll fails before anything starts; the
manifest's appended entries, looked up by name."""

import json
import os

import pytest

import harness
import run as launcher
from gen import backlog, enrolled

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
REPO = os.path.dirname(BENCH)
CELL = "catchup.cut500.distinct"
CONTROL = "catchup.cut500"
NEW_METRICS = {"validate.identities_ms.cut500": [CELL, CONTROL],
               "msp.validate_us.distinct": [CELL],
               "msp.miss_share.cut500": [CELL, CONTROL],
               "validate.first_seen_share.distinct": [CELL]}
with open(os.path.join(REPO, "BENCHMARK.json")) as f:
    MANIFEST = json.load(f)
SEED = 2**31 + 41


def tiny_context(faults=(), trace=False) -> harness.Context:
    workload, config = launcher.load_cell(MANIFEST, CELL)
    config.update(keyspace=400, tamper_every=5, forge_every=20,
                  device_peer={"bccsp": "SW"})
    config["enrolment"] = dict(config["enrolment"], clients=600)
    workload.update(block_tx=50, backlog_blocks=5, reference_blocks=2,
                    generator_workers=2)
    return harness.Context(workload=workload, config=config, seed=SEED,
                           seconds=30.0, trace=trace,
                           require_accelerator=False,
                           faults=frozenset(faults))


def read(name: str, obs: dict):
    return launcher.load_module("layer_metrics", name).read(obs)


# -- the generator ----------------------------------------------------------------


def tiny_plan(seed=SEED):
    revoked = enrolled.draw_revoked(seed, 600, 3, 0.01)
    return revoked, enrolled.plan_backlog(seed, 6, 50, 400, 600, 5, 20,
                                          revoked)


def test_the_generator_is_a_pure_function_of_the_seed():
    assert tiny_plan() == tiny_plan()
    assert tiny_plan(SEED + 1) != tiny_plan()
    revoked, _ = tiny_plan()
    # 1% of 600, dealt to the orgs as the members are
    assert len(revoked) == 6
    assert sorted(i % 3 for i in revoked) == [0, 0, 1, 1, 2, 2]
    at_size = enrolled.draw_revoked(SEED, 100000, 3, 0.01)
    assert len(set(at_size)) == 1000
    assert [sum(1 for i in at_size if i % 3 == k) for k in range(3)] \
        == [334, 333, 333]
    # not every 100th member, which would fall on the tampered positions
    assert len({i % 100 for i in at_size}) > 50


def test_every_blocks_creators_are_distinct_and_return_late():
    _, plan = tiny_plan()
    for blk in plan:
        assert len({tx["creator"] for tx in blk["txs"]}) == 50
    first = {tx["creator"]: blk["number"] for blk in reversed(plan)
             for tx in blk["txs"]}
    # 600 clients over blocks of 50: an enrolment returns after 12 blocks
    assert all(n == first[tx["creator"]] for blk in plan[:12]
               for tx in blk["txs"] for n in [blk["number"]])
    with pytest.raises(ValueError):
        enrolled.plan_backlog(SEED, 1, 50, 400, 40, 5, 20, [])


def test_expected_flags_by_class():
    revoked, plan = tiny_plan()
    base = backlog.plan_backlog(SEED, 6, 50, 400, 600, 5)
    seen = {"revoked": 0, "forged": 0, "tampered": 0, "conflict": 0,
            "valid": 0}
    for blk, was in zip(plan, base):
        for t, (tx, old) in enumerate(zip(blk["txs"], was["txs"])):
            # backlog's draws, kept: key, nonce, creator rule
            assert (tx["key"], tx["nonce"], tx["creator"]) == (
                old["key"], old["nonce"], (blk["number"] * 50 + t) % 600)
            assert tx["forged"] == ((blk["number"] * 50 + t) % 20 == 10)
            assert tx["revoked"] == (tx["creator"] in revoked
                                     and not tx["forged"])
            if tx["forged"] or tx["revoked"]:
                assert tx["code"] == enrolled.BAD_CREATOR
                assert not tx["tampered"]
                seen["forged" if tx["forged"] else "revoked"] += 1
            elif tx["tampered"]:
                assert old["tampered"]
                assert tx["code"] == backlog.POLICY_FAILURE
                seen["tampered"] += 1
            else:
                seen["conflict" if tx["code"] == backlog.MVCC_CONFLICT
                     else "valid"] += tx["code"] in (backlog.MVCC_CONFLICT,
                                                     backlog.VALID)
    assert seen["forged"] == 15 and seen["revoked"] >= 1
    assert seen["tampered"] >= 50 and seen["conflict"] >= 1
    assert sum(seen.values()) == 300
    # a refused transaction writes nothing: what a later one reads is
    # the last VALID write, so no conflict is caused by a refused one
    version = {}
    for blk in plan:
        for t, tx in enumerate(blk["txs"]):
            assert tx["read"] == version.get(tx["key"])
        for t, tx in enumerate(blk["txs"]):
            if tx["code"] == backlog.VALID:
                version[tx["key"]] = [blk["number"], t]


# -- the cell ---------------------------------------------------------------------


def test_sound_path_is_correct():
    ctx = tiny_context()
    line = launcher.run_cell(ctx, MANIFEST)
    assert line["correct"], ctx.checks
    assert line["attempted"] == 5 * 50 and line["failed"] == 0
    assert set(line["metrics"]) == {"catchup_tps", "setup_s"}
    compared = {c["name"]: c for c in ctx.checks}
    made = {cls: next(c["value"] for n, c in compared.items()
                      if n.startswith(f"{cls}-creator transactions made"))
            for cls in ("revoked", "forged")}
    assert made["forged"] == 15 and made["revoked"] >= 1
    chains = next(c for n, c in compared.items()
                  if n.startswith("certificate chains validated"))
    assert chains["limit"] == 250 and chains["value"] >= 250
    share = next(c for n, c in compared.items()
                 if n.startswith("distinct creators per transaction"))
    assert share["value"] == 1.0


@pytest.mark.parametrize("fault", ["yes_verifier", "msp_blind"])
def test_broken_path_is_not_correct(fault):
    ctx = tiny_context(faults=[fault])
    line = launcher.run_cell(ctx, MANIFEST)
    assert not line["correct"]
    failed = {c["name"]: c for c in ctx.checks if not c["ok"]}
    assert any(n.startswith("blocks whose flags differ from the generator")
               for n in failed)
    refused = [c for n, c in failed.items()
               if "-creator transactions BAD_CREATOR_SIGNATURE (device peer"
               in n]
    if fault == "msp_blind":
        # every signature is judged as it is, and every one is valid:
        # the verifier's answers cannot cover for the MSP
        assert len(refused) == 2 and all(c["value"] == 0 for c in refused)
        assert not any(n.startswith("tampered envelopes not")
                       for n in failed)
    else:
        # the MSP still refuses them, whatever the verifier says
        assert not refused
        assert any(n.startswith("tampered envelopes not") for n in failed)


def test_traced_run_reports_per_layer_metrics():
    """No chip, no device provider: the readers of the trace and of the
    dispatch account find nothing and are left out; the identity layer's
    four and the spans' are there."""
    ctx = tiny_context(trace=True)
    line = launcher.run_cell(ctx, MANIFEST)
    assert line["correct"], ctx.checks
    listed = {m["name"] for m in launcher.metrics_of(MANIFEST, "per_layer",
                                                     CELL)}
    assert set(NEW_METRICS) <= listed and set(line["metrics"]) <= listed
    assert {"validate.block_ms", "commit.block_ms"} | set(NEW_METRICS) \
        <= set(line["metrics"])
    value = {n: m["value"] for n, m in line["metrics"].items()}
    assert value["msp.miss_share.cut500"] == 100.0
    assert value["validate.first_seen_share.distinct"] == 100.0
    assert value["validate.deep_share.catchup"] == 100.0
    assert 0 < value["validate.identities_ms.cut500"] \
        < value["validate.block_ms"]
    assert 20 < value["msp.validate_us.distinct"] < 5000


def test_a_program_without_the_roll_fails_before_anything_starts(monkeypatch):
    """The parent's shape: `provision_network` without `roll_size`."""
    from fabric_tpu.node import provision

    def provision_network(base_dir, n_orderers=3, peer_orgs=(),
                          clients_per_org=1, org_schemes=None):
        raise AssertionError("provisioning was started")

    monkeypatch.setattr(provision, "provision_network", provision_network)
    started = []
    monkeypatch.setattr(harness, "build_native",
                        lambda: started.append("native"))
    monkeypatch.setattr("subprocess.Popen",
                        lambda *a, **k: started.append("process"))
    ctx = tiny_context()
    with pytest.raises(harness.BenchFailure, match="roll_size"):
        launcher.run_cell(ctx, MANIFEST)
    assert started == [] and ctx.checks == []


# -- the manifest -----------------------------------------------------------------


def test_the_manifest_names_the_cell_its_config_and_its_metrics():
    by_name = {section: {e["name"]: e for e in MANIFEST[section]}
               for section in ("configs", "workloads", "per_layer",
                               "end_to_end")}
    cell = by_name["workloads"][CELL]
    assert cell["chips"] == 1 and cell["traffic"] == "cut500.distinct"
    assert cell["config"] == "enrolled100k-and3-cut500"
    config = by_name["configs"][cell["config"]]
    assert len(config["source"]) == 199
    assert config["reduced"] == ["blocks", "delivery", "peers_per_org"]
    with open(os.path.join(REPO, config["file"])) as f:
        deployment = json.load(f)
    assert deployment["source"] == config["source"]
    assert set(deployment["reduced"]) == set(config["reduced"])
    assert "client_identities" not in deployment
    assert deployment["enrolment"]["clients"] == 100000
    assert deployment["enrolment"]["revoked_share"] == 0.01
    # and3-cut500 key for key, but for its membership
    with open(os.path.join(BENCH, "configs", "and3-cut500.json")) as f:
        control = json.load(f)
    for key in ("channel", "orderers", "peer_orgs", "peers_per_org",
                "device_org", "chaincode", "batch",
                "keyspace", "key_distribution", "tamper_every",
                "device_peer", "reference_peer"):
        assert deployment[key] == control[key], key
    # but the floor of signatures a transaction: a refused creator's
    # brings none of its four to the device
    assert control["signatures_per_tx"] == 4
    assert deployment["signatures_per_tx"] == 3.9
    assert set(control["guarantees"]) - set(deployment["guarantees"]) == {
        "identical tx-filter flags, height and commit hash on the device "
        "peer and both SW peers"}
    # the cell is catchup.cut500's, line for line
    with open(os.path.join(BENCH, "workloads", CONTROL + ".json")) as f:
        pair = json.load(f)
    with open(os.path.join(BENCH, "workloads", CELL + ".json")) as f:
        mine = json.load(f)
    for key in ("block_tx", "backlog_blocks", "reference_org",
                "reference_blocks", "warm_rows", "generator_workers",
                "trace_blocks"):
        assert mine[key] == pair[key], key
    for name, cells in NEW_METRICS.items():
        metric = by_name["per_layer"][name]
        assert metric["workloads"] == cells
        assert metric["moves"] == "catchup_tps"
    assert CELL in by_name["end_to_end"]["catchup_tps"]["workloads"]
    # appended wherever its control is listed, and nowhere else
    for name, metric in by_name["per_layer"].items():
        if name not in NEW_METRICS:
            assert (CELL in metric["workloads"]) \
                == (CONTROL in metric["workloads"]), name
    assert all(w["chips"] == 1 for w in MANIFEST["workloads"])
    assert all("workloads" in m for m in MANIFEST["per_layer"])


# -- the readers, on a recorded obs -----------------------------------------------


def prom(text: str) -> dict:
    return harness.parse_prom(text)


BEFORE = prom("""
msp_cache_total{msp="Org1",op="validate",result="miss"} 168
msp_cache_total{msp="Org1",op="validate",result="hit"} 0
msp_cache_total{msp="Org2",op="validate",result="miss"} 167
msp_cache_total{msp="Org3",op="validate",result="miss"} 168
msp_cache_total{msp="Org1",op="deserialize",result="miss"} 169
msp_cache_total{msp="Org1",op="principal",result="hit"} 40
msp_validate_seconds_sum{msp="Org1",result="ok"} 0.04
msp_validate_seconds_count{msp="Org1",result="ok"} 166
msp_validate_seconds_sum{msp="Org1",result="revoked"} 0.0004
msp_validate_seconds_count{msp="Org1",result="revoked"} 2
validator_creators_total{channel="ch",seen="first"} 500
validator_creators_total{channel="ch",seen="again"} 0
""")
AFTER = prom("""
msp_cache_total{msp="Org1",op="validate",result="miss"} 1168
msp_cache_total{msp="Org1",op="validate",result="hit"} 6
msp_cache_total{msp="Org2",op="validate",result="miss"} 1167
msp_cache_total{msp="Org3",op="validate",result="miss"} 1162
msp_cache_total{msp="Org1",op="deserialize",result="miss"} 1169
msp_cache_total{msp="Org1",op="principal",result="hit"} 4000
msp_validate_seconds_sum{msp="Org1",result="ok"} 0.24
msp_validate_seconds_count{msp="Org1",result="ok"} 1156
msp_validate_seconds_sum{msp="Org1",result="revoked"} 0.0024
msp_validate_seconds_count{msp="Org1",result="revoked"} 12
validator_creators_total{channel="ch",seen="first"} 3485
validator_creators_total{channel="ch",seen="again"} 15
""")
SPANS = [{"name": "validator.identities", "trace_id": t, "start": 1.0,
          "duration_s": d}
         for t, d in (("a", 0.100), ("b", 0.120), ("c", 0.140))] + [
    {"name": "validator.collect", "trace_id": "a", "start": 1.0,
     "duration_s": 0.150}]


def test_the_new_readers_on_a_recorded_obs():
    obs = {"prom_before": BEFORE, "prom_after": AFTER, "spans": SPANS}
    assert read("validate.identities_ms.cut500", obs) == pytest.approx(120.0)
    # 1,000 validations in the window, 0.202 s
    assert read("msp.validate_us.distinct", obs) == pytest.approx(202.0)
    assert read("msp.miss_share.cut500", obs) == pytest.approx(
        100.0 * 2994 / 3000)
    assert read("validate.first_seen_share.distinct", obs) == pytest.approx(
        100.0 * 2985 / 3000)


def test_the_new_readers_find_nothing_on_a_program_without_the_series():
    """As on the parent: no span, no counter, no number and no error."""
    parent = prom('validator_tail_total{channel="ch",tail="deep"} 500\n')
    spans = [{"name": "validator.collect", "trace_id": "a", "start": 1.0,
              "duration_s": 0.02}]
    for obs in ({}, {"prom_before": {}, "prom_after": parent,
                     "spans": spans}):
        for name in NEW_METRICS:
            assert read(name, obs) is None
