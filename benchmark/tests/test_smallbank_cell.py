"""`catchup.smallbank.hot` at a tiny size on the CPU, the software
provider in the device peer's place: `correct` on a sound path, not
`correct` under the yes-verifier and under one altered balance; the
per-layer metrics the ledger's counters feed are read."""

import json
import os

import pytest

import harness
import run as launcher

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
REPO = os.path.dirname(BENCH)
CELL = "catchup.smallbank.hot"
with open(os.path.join(REPO, "BENCHMARK.json")) as f:
    MANIFEST = json.load(f)


def tiny_context(faults=(), trace=False) -> harness.Context:
    workload, config = launcher.load_cell(MANIFEST, CELL)
    config.update(client_identities=6, accounts=150, tamper_every=5,
                  device_peer=dict(config["device_peer"], bccsp="SW"))
    workload.update(block_tx=60, backlog_blocks=5, reference_blocks=2,
                    generator_workers=2)
    return harness.Context(workload=workload, config=config, seed=2**31 + 13,
                           seconds=30.0, trace=trace,
                           require_accelerator=False,
                           faults=frozenset(faults))


def test_sound_path_is_correct():
    ctx = tiny_context()
    line = launcher.run_cell(ctx, MANIFEST)
    assert line["correct"], ctx.checks
    assert line["attempted"] == 5 * 60 and line["failed"] == 0
    assert set(line["metrics"]) == {"catchup_tps", "setup_s"}
    assert all(v["value"] > 0 for v in line["metrics"].values())
    compared = {c["name"]: c for c in ctx.checks}
    assert compared["accounts compared on the device peer"]["value"] == 150
    # 3 opening blocks (60 + 60 + 30 accounts), all VALID, then the window
    assert any(n.startswith("accounts whose balances differ from the "
                            "model's after block 7 (device peer)")
               for n in compared)
    assert any("(software peer)" in n and "block 4" in n for n in compared)


@pytest.mark.parametrize("fault", ["yes_verifier", "balance_flip"])
def test_broken_path_is_not_correct(fault):
    ctx = tiny_context(faults=[fault])
    line = launcher.run_cell(ctx, MANIFEST)
    assert not line["correct"]
    failed = [c["name"] for c in ctx.checks if not c["ok"]]
    if fault == "balance_flip":
        # only the state comparison sees it: flags and hashes are sound
        assert len(failed) == 2 and all("balances" in n for n in failed)
    else:
        assert any("tampered" in n for n in failed)


def test_traced_run_reports_per_layer_metrics():
    ctx = tiny_context(trace=True)
    line = launcher.run_cell(ctx, MANIFEST)
    assert line["correct"], ctx.checks
    listed = {m["name"] for m in launcher.metrics_of(MANIFEST, "per_layer",
                                                     CELL)}
    assert len(listed) == 11 and set(line["metrics"]) <= listed
    assert {"validate.block_ms", "commit.block_ms",
            "commit.valid_share.smallbank",
            "commit.mvcc_us_per_read.smallbank",
            "commit.apply_us_per_write.smallbank"} <= set(line["metrics"])
    share = line["metrics"]["commit.valid_share.smallbank"]["value"]
    assert 5.0 < share < 95.0
    assert all(v["value"] > 0 for v in line["metrics"].values())


def test_readers_find_nothing_on_a_program_without_the_counters():
    """As on the parent commit: no counter, no number, no error."""
    def read_all(obs):
        return {name: launcher.load_module("layer_metrics", name).read(obs)
                for name in ("commit.valid_share.smallbank",
                             "commit.mvcc_us_per_read.smallbank",
                             "commit.apply_us_per_write.smallbank")}
    obs = {"prom_before": {}, "prom_after": {},
           "spans": [{"name": "ledger.mvcc", "start": 1.0, "duration_s": 0.1,
                      "trace_id": "t"}],
           "blocks": [{"start": 0.5, "end": 2.0,
                       "counts": {"reads": 0.0, "writes": 0.0}}]}
    assert read_all(obs) == {"commit.valid_share.smallbank": None,
                             "commit.mvcc_us_per_read.smallbank": None,
                             "commit.apply_us_per_write.smallbank": None}
    assert read_all({}) == read_all(obs)
