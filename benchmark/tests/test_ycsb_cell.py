"""`catchup.ycsb.a` and `catchup.cut500` at a tiny size on the CPU, the
software provider in the device peer's place: `correct` on a sound
path, not `correct` under the yes-verifier and under one altered
record a block; the five metrics the ledger's new counters feed are
read in the traced run, and found absent — not raised over — on a
program without the counters."""

import json
import os

import pytest

import harness
import run as launcher

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
REPO = os.path.dirname(BENCH)
YCSB, CUT500 = "catchup.ycsb.a", "catchup.cut500"
NEW = {"commit.checkpoint_share.cut500", "commit.fsync_ms.cut500",
       "commit.apply_us_per_kb.ycsb", "provider.generic_share.cut500",
       "kernel.held_ms.generic.cut500"}
with open(os.path.join(REPO, "BENCHMARK.json")) as f:
    MANIFEST = json.load(f)


def tiny_context(cell, faults=(), trace=False) -> harness.Context:
    """150 records in blocks cut by bytes at ~10 transactions, a backlog
    of 300 updates; `bump` blocks of 60; both stores checkpoint every 4
    blocks, so a window holds several.  The blocks the profiler would
    watch lie beyond the backlog: there is no chip to trace here."""
    workload, config = launcher.load_cell(MANIFEST, cell)
    config.update(client_identities=6, tamper_every=5,
                  device_peer=dict(config["device_peer"], bccsp="SW",
                                   state={"checkpoint_every": 4}))
    if cell == YCSB:
        config.update(recordcount=150,
                      batch=dict(config["batch"], preferred_max_bytes=45000))
        workload.update(updates=300, reference_blocks=2, generator_workers=2,
                        chunk_tx=50)
    else:
        config.update(keyspace=400)
        workload.update(block_tx=60, backlog_blocks=9, reference_blocks=2,
                        generator_workers=2)
    return harness.Context(workload=workload, config=config, seed=2**31 + 35,
                           seconds=30.0, trace=trace,
                           require_accelerator=False,
                           faults=frozenset(faults))


def test_sound_path_is_correct():
    ctx = tiny_context(YCSB)
    line = launcher.run_cell(ctx, MANIFEST)
    assert line["correct"], ctx.checks
    assert line["attempted"] == 300 and line["failed"] == 0
    assert set(line["metrics"]) == {"catchup_tps", "setup_s"}
    assert all(v["value"] > 0 for v in line["metrics"].values())
    compared = {c["name"]: c for c in ctx.checks}
    assert compared["records compared on the device peer"]["value"] == 150
    assert compared["backlog blocks but the last not cut by bytes"]["ok"]
    assert any(n.startswith("records that differ from the model's")
               and "(device peer" in n for n in compared)
    assert any(n.startswith("records that differ from the model's")
               and "(software peer" in n for n in compared)
    assert any(n.startswith("MVCC_READ_CONFLICT flags") for n in compared)


@pytest.mark.parametrize("fault", ["yes_verifier", "record_flip"])
def test_broken_path_is_not_correct(fault):
    ctx = tiny_context(YCSB, faults=[fault])
    line = launcher.run_cell(ctx, MANIFEST)
    assert not line["correct"]
    failed = [c["name"] for c in ctx.checks if not c["ok"]]
    if fault == "record_flip":
        # only the record comparison sees it: flags and hashes are sound
        assert len(failed) == 1 and "(device peer" in failed[0], failed
        assert failed[0].startswith("records that differ from the model's")
    else:
        assert any("tampered" in n for n in failed)


@pytest.mark.parametrize("cell", [YCSB, CUT500])
def test_traced_run_reports_the_new_metrics(cell):
    ctx = tiny_context(cell, trace=True)
    line = launcher.run_cell(ctx, MANIFEST)
    assert line["correct"], ctx.checks
    listed = {m["name"] for m in launcher.metrics_of(MANIFEST, "per_layer",
                                                     cell)}
    assert set(line["metrics"]) <= listed
    # no chip here: what the dispatch account feeds has nothing to read
    want = (NEW & listed) - {"provider.generic_share.cut500",
                             "kernel.held_ms.generic.cut500"}
    assert want <= set(line["metrics"]), sorted(line["metrics"])
    assert (cell == YCSB) == ("commit.apply_us_per_kb.ycsb" in want)
    assert all(line["metrics"][n]["value"] > 0 for n in want)
    # listed for the cell whose traced window holds a checkpoint: on the
    # chip `catchup.cut500`'s ends before its block 256
    assert (cell == YCSB) == ("commit.checkpoint_share.cut500" in want)
    if cell == YCSB:
        share = line["metrics"]["commit.checkpoint_share.cut500"]["value"]
        assert 0 < share < 100
    assert line["metrics"]["commit.lanes_share.catchup"]["value"] == 100.0


def test_cut500_sound_path_is_correct():
    ctx = tiny_context(CUT500)
    line = launcher.run_cell(ctx, MANIFEST)
    assert line["correct"], ctx.checks
    assert line["attempted"] == 9 * 60 and line["failed"] == 0
    assert set(line["metrics"]) == {"catchup_tps", "setup_s"}


def read_all(obs) -> dict:
    return {name: launcher.load_module("layer_metrics", name).read(obs)
            for name in sorted(NEW)}


def test_readers_read_the_dispatch_account_and_the_counters():
    """The two readers of the dispatch account, which only a chip feeds,
    and the others, on expositions made by hand."""
    def prom(text):
        return harness.parse_prom(text)
    before = prom('provider_dispatch_sigs_total{lane="generic",program="generic@512",site="validator"} 100\n'
                  'provider_dispatch_sigs_total{lane="rows",program="rows@16",site="validator"} 300\n'
                  'process_uptime_seconds 10\n'
                  'validator_stage_seconds_count{stage="collect"} 2\n')
    after = prom('provider_dispatch_sigs_total{lane="generic",program="generic@512",site="validator"} 600\n'
                 'provider_dispatch_sigs_total{lane="rows",program="rows@16",site="validator"} 1800\n'
                 'provider_dispatch_held_seconds_sum{lane="generic",program="generic@512"} 0.088\n'
                 'provider_dispatch_held_seconds_count{lane="generic",program="generic@512"} 4\n'
                 'state_checkpoint_seconds_sum{channel="ch"} 1.5\n'
                 'history_checkpoint_seconds_sum{channel="ch"} 0.5\n'
                 'ledger_fsync_seconds_sum{store="blocks"} 0.010\n'
                 'ledger_fsync_seconds_sum{store="state"} 0.020\n'
                 'ledger_fsync_seconds_sum{store="history"} 0.030\n'
                 'ledger_fsync_seconds_count{store="blocks"} 6\n'
                 'ledger_fsync_seconds_count{store="state"} 6\n'
                 'ledger_fsync_seconds_count{store="history"} 6\n'
                 'process_uptime_seconds 50\n'
                 'validator_stage_seconds_count{stage="collect"} 6\n')
    obs = {"prom_before": before, "prom_after": after,
           "spans": [{"name": "ledger.state_commit", "start": 1.0,
                      "duration_s": 0.002, "trace_id": "a"},
                     {"name": "ledger.history_commit", "start": 1.1,
                      "duration_s": 0.001, "trace_id": "a"},
                     {"name": "ledger.mvcc", "start": 1.05,
                      "duration_s": 0.5, "trace_id": "a"},
                     {"name": "ledger.state_commit", "start": 9.0,
                      "duration_s": 7.0, "trace_id": "b"}],
           "blocks": [{"start": 0.5, "end": 2.0,
                       "counts": {"write_bytes": 3072.0}},
                      {"start": 3.0, "end": 4.0,      # its spans are gone
                       "counts": {"write_bytes": 1024.0}}]}
    got = read_all(obs)
    assert got["provider.generic_share.cut500"] == 25.0
    assert got["kernel.held_ms.generic.cut500"] == pytest.approx(22.0)
    assert got["commit.checkpoint_share.cut500"] == pytest.approx(5.0)
    assert got["commit.fsync_ms.cut500"] == pytest.approx(15.0)
    assert got["commit.apply_us_per_kb.ycsb"] == pytest.approx(1000.0)


def test_readers_find_nothing_on_a_program_without_the_counters():
    """As on the parent commit: no counter, no number, no error."""
    nothing = dict.fromkeys(sorted(NEW))
    parent = harness.parse_prom(
        'state_checkpoint_seconds_sum{channel="ch"} 1.5\n'
        'process_uptime_seconds 50\n'
        'validator_stage_seconds_count{stage="collect"} 6\n')
    obs = {"prom_before": {}, "prom_after": parent,
           "spans": [{"name": "ledger.state_commit", "start": 1.0,
                      "duration_s": 0.1, "trace_id": "t"}],
           "blocks": [{"start": 0.5, "end": 2.0,
                       "counts": {"writes": 5.0, "write_bytes": 0.0}}]}
    assert read_all(obs) == nothing
    assert read_all({}) == nothing
