"""`catchup.mixedcurve` at a tiny size on the CPU, the software provider
in the device peer's place: `correct` on a sound path, not `correct`
under the yes-verifier and under a verifier whose Ed25519 answers alone
are yes; the four readers the cell brings, on fixtures; the manifest's
appended entries, looked up by name."""

import json
import os

import pytest

import harness
import run as launcher

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
REPO = os.path.dirname(BENCH)
CELL = "catchup.mixedcurve"
NEW_METRICS = ("kernel.held_ms.ed25519_rows", "kernel.sig_us.ed25519_rows",
               "provider.pack_ms.ed25519.mixedcurve",
               "provider.ed25519_share.mixedcurve")
with open(os.path.join(REPO, "BENCHMARK.json")) as f:
    MANIFEST = json.load(f)


def tiny_context(faults=(), trace=False) -> harness.Context:
    workload, config = launcher.load_cell(MANIFEST, CELL)
    config.update(client_identities=6, keyspace=400, tamper_every=5,
                  device_peer={"bccsp": "SW"})
    workload.update(block_tx=60, backlog_blocks=5, reference_blocks=2,
                    generator_workers=2)
    return harness.Context(workload=workload, config=config, seed=2**31 + 17,
                           seconds=30.0, trace=trace,
                           require_accelerator=False,
                           faults=frozenset(faults))


def read(name: str, obs: dict):
    return launcher.load_module("layer_metrics", name).read(obs)


def test_sound_path_is_correct():
    ctx = tiny_context()
    line = launcher.run_cell(ctx, MANIFEST)
    assert line["correct"], ctx.checks
    assert line["attempted"] == 5 * 60 and line["failed"] == 0
    assert set(line["metrics"]) == {"catchup_tps", "setup_s"}
    compared = {c["name"]: c for c in ctx.checks}
    # 6 blocks x 12 tampered, Org2's and Org3's in turn
    assert compared["tampered Ed25519 endorsements made"]["value"] == 36
    assert any(n.startswith("tampered Ed25519 endorsements flagged")
               for n in compared)


@pytest.mark.parametrize("fault", ["yes_verifier", "yes_ed25519"])
def test_broken_path_is_not_correct(fault):
    ctx = tiny_context(faults=[fault])
    line = launcher.run_cell(ctx, MANIFEST)
    assert not line["correct"]
    failed = {c["name"]: c for c in ctx.checks if not c["ok"]}
    flagged = next(c for n, c in failed.items()
                   if n.startswith("tampered Ed25519 endorsements flagged"))
    assert flagged["value"] == 0 and flagged["limit"] == 36
    missed = next(c for n, c in failed.items()
                  if n.startswith("tampered envelopes not"))
    # the Ed25519-only yes-verifier lets Org3's through and no other
    assert missed["value"] == (36 if fault == "yes_ed25519" else 72)


def test_traced_run_reports_per_layer_metrics():
    """No chip, no device provider: the readers of the trace and of the
    dispatch account find nothing and are left out; the spans' are
    there."""
    ctx = tiny_context(trace=True)
    line = launcher.run_cell(ctx, MANIFEST)
    assert line["correct"], ctx.checks
    listed = {m["name"] for m in launcher.metrics_of(MANIFEST, "per_layer",
                                                     CELL)}
    assert set(NEW_METRICS) <= listed and set(line["metrics"]) <= listed
    assert {"validate.block_ms", "commit.block_ms"} <= set(line["metrics"])


def test_the_manifest_names_the_cell_its_config_and_its_metrics():
    by_name = {section: {e["name"]: e for e in MANIFEST[section]}
               for section in ("configs", "workloads", "per_layer",
                               "end_to_end")}
    cell = by_name["workloads"][CELL]
    assert cell["chips"] == 1 and cell["config"] == "mixedcurve-and3-cut10k"
    config = by_name["configs"][cell["config"]]
    assert len(config["source"]) <= 200
    assert config["reduced"] == ["blocks", "delivery", "peers_per_org"]
    with open(os.path.join(REPO, config["file"])) as f:
        deployment = json.load(f)
    assert deployment["org_schemes"] == {"Org3": "ed25519"}
    assert set(deployment["reduced"]) == set(config["reduced"])
    for name in NEW_METRICS:
        metric = by_name["per_layer"][name]
        assert metric["workloads"] == [CELL]
        assert metric["moves"] == "catchup_tps"
    assert CELL in by_name["end_to_end"]["catchup_tps"]["workloads"]
    for name in ("kernel.sig_us.rows", "provider.lane_fill.catchup",
                 "validate.block_ms", "provider.pack_ms.catchup"):
        assert CELL in by_name["per_layer"][name]["workloads"]
    # the P-256 program ends under the Ed25519 pack, before anybody
    # waits for it: the account has no held time to give (PERF.md §7)
    for name in ("kernel.held_ms.rows", "device.held_share.catchup"):
        assert CELL not in by_name["per_layer"][name]["workloads"]


def prom(text: str) -> dict:
    return harness.parse_prom(text)


BEFORE = prom("""
provider_dispatch_sigs_total{lane="rows",program="rows@256",site="validator"} 26719
provider_dispatch_sigs_total{lane="ed25519-rows",program="ed25519-rows@128",site="validator"} 13281
provider_dispatch_held_seconds_sum{lane="ed25519-rows",program="ed25519-rows@128"} 0.05
provider_dispatch_held_seconds_count{lane="ed25519-rows",program="ed25519-rows@128"} 1
provider_dispatch_pack_seconds_sum{lane="ed25519-rows",site="validator"} 0.08
provider_dispatch_pack_seconds_count{lane="ed25519-rows",site="validator"} 1
provider_dispatch_pack_seconds_sum{lane="ed25519-rows",site="warmup"} 9.0
validator_stage_seconds_count{stage="collect",channel="ch"} 1
provider_lane_fill_count{lane="ed25519-rows"} 2
provider_lane_fill_count{lane="rows"} 2
provider_lane_slots_total{lane="ed25519-rows",device="tpu:0"} 32768
provider_pad_slots_total{lane="ed25519-rows",device="tpu:0"} 6206
""")
AFTER = prom("""
provider_dispatch_sigs_total{lane="rows",program="rows@256",site="validator"} 133595
provider_dispatch_sigs_total{lane="ed25519-rows",program="ed25519-rows@128",site="validator"} 66405
provider_dispatch_held_seconds_sum{lane="ed25519-rows",program="ed25519-rows@128"} 0.25
provider_dispatch_held_seconds_count{lane="ed25519-rows",program="ed25519-rows@128"} 5
provider_dispatch_pack_seconds_sum{lane="ed25519-rows",site="validator"} 0.4
provider_dispatch_pack_seconds_count{lane="ed25519-rows",site="validator"} 5
provider_dispatch_pack_seconds_sum{lane="ed25519-rows",site="warmup"} 9.0
validator_stage_seconds_count{stage="collect",channel="ch"} 5
provider_lane_fill_count{lane="ed25519-rows"} 4
provider_lane_fill_count{lane="rows"} 4
provider_lane_slots_total{lane="ed25519-rows",device="tpu:0"} 65536
provider_pad_slots_total{lane="ed25519-rows",device="tpu:0"} 12412
""")


def test_the_new_readers_on_a_fixture():
    obs = {"prom_before": BEFORE, "prom_after": AFTER,
           "traced_prom_before": BEFORE, "traced_prom_after": AFTER,
           "trace": {"programs": {
               "jit_verify_words_rows": {"device_s": 0.1, "executions": 2},
               "jit__lambda": {"device_s": 0.11, "executions": 2}}}}
    assert read("kernel.held_ms.ed25519_rows", obs) == pytest.approx(50.0)
    assert read("provider.pack_ms.ed25519.mixedcurve", obs) == pytest.approx(80.0)
    assert read("provider.ed25519_share.mixedcurve", obs) == pytest.approx(
        100.0 * 53124 / 160000)
    # 2 executions, 2 dispatches, 2 x 13,281 real signatures
    assert read("kernel.sig_us.ed25519_rows", obs) == pytest.approx(
        1e6 * 0.1 / 26562)
    # executions that are not the lane's dispatches: an error, not a guess
    obs["trace"]["programs"]["jit_verify_words_rows"]["executions"] = 3
    with pytest.raises(harness.BenchFailure):
        read("kernel.sig_us.ed25519_rows", obs)


def test_the_new_readers_find_nothing_on_a_program_without_the_lanes():
    """As on a P-256-only window, or a program whose account books every
    row-grid dispatch under `rows`: no series, no number, no error."""
    p256 = prom('provider_dispatch_sigs_total{lane="rows",program="rows@384",'
                'site="validator"} 40000\n'
                'provider_lane_fill_count{lane="rows"} 1\n'
                'validator_stage_seconds_count{stage="collect"} 1\n')
    obs = {"prom_before": {}, "prom_after": p256,
           "traced_prom_before": {}, "traced_prom_after": p256,
           "trace": {"programs": {"jit__lambda": {"device_s": 0.085,
                                                  "executions": 1}}}}
    for name in NEW_METRICS:
        assert read(name, obs) is None
        assert read(name, {}) is None
