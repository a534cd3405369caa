"""The harness drives a whole run, at a tiny size on the CPU with the
software provider in the device peer's place and the look for a chip
skipped: `correct` comes out true on a sound path and false where the
timed path is broken underneath."""

import json
import os

import pytest

import harness
import run as launcher

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
REPO = os.path.dirname(BENCH)


def load(*parts):
    with open(os.path.join(*parts)) as f:
        return json.load(f)


def tiny_context(cell: str, faults=(), trace=False) -> harness.Context:
    workload, config = launcher.load_cell(MANIFEST, cell)
    config.update(client_identities=6, keyspace=400, tamper_every=5,
                  device_peer={"bccsp": "SW"})
    if workload["driver"] == "catchup":
        workload.update(block_tx=60, backlog_blocks=5, reference_blocks=2,
                        generator_workers=2)
        seconds = 30.0
    else:
        workload.update(arrivals={"kind": "fixed_gaps", "rate": 8.0},
                        connections=6, pilot_tx=6, drain_s=30.0)
        seconds = 4.0
    return harness.Context(workload=workload, config=config, seed=2**31 + 11,
                           seconds=seconds, trace=trace,
                           require_accelerator=False,
                           faults=frozenset(faults))


MANIFEST = load(REPO, "BENCHMARK.json")


@pytest.mark.parametrize("cell", ["catchup.cut10k", "served.steady"])
def test_sound_path_is_correct(cell):
    ctx = tiny_context(cell)
    line = launcher.run_cell(ctx, MANIFEST)
    assert line["correct"], ctx.checks
    assert line["attempted"] > 0 and line["failed"] == 0
    names = {m["name"] for m in launcher.metrics_of(MANIFEST, "end_to_end",
                                                    cell)}
    assert set(line["metrics"]) | {"commit_p95_ms"} >= names
    assert all(v["value"] > 0 for v in line["metrics"].values())


@pytest.mark.parametrize("cell,fault", [
    ("catchup.cut10k", "yes_verifier"),     # the control: a guarantee broken
    ("served.steady", "yes_verifier"),
    ("served.steady", "ack_flip"),          # an answer altered where produced
])
def test_broken_path_is_not_correct(cell, fault):
    ctx = tiny_context(cell, faults=[fault])
    line = launcher.run_cell(ctx, MANIFEST)
    assert not line["correct"]
    assert any(not c["ok"] for c in ctx.checks)


def test_launcher_fails_without_an_accelerator(capfd):
    rc = launcher.main(["--workload", "catchup.cut10k", "--seed", "1",
                        "--seconds", "1", "--trace", "0"])
    out, _ = capfd.readouterr()
    assert rc != 0
    assert not out.strip().splitlines()[-1].startswith('{"correct"')


@pytest.mark.parametrize("cell", ["catchup.cut10k", "served.steady"])
def test_traced_run_reports_per_layer_metrics(cell):
    """No chip, so no profiler trace: the readers that need one return
    nothing and are left out; the counters' and spans' metrics are there."""
    ctx = tiny_context(cell, trace=True)
    line = launcher.run_cell(ctx, MANIFEST)
    assert line["correct"], ctx.checks
    listed = {m["name"] for m in launcher.metrics_of(MANIFEST, "per_layer",
                                                     cell)}
    assert set(line["metrics"]) <= listed
    want = ({"validate.block_ms", "commit.block_ms"} if cell.startswith("catchup")
            else {"gateway.endorse_ms.steady", "ordering.block_tx.steady"})
    assert want <= set(line["metrics"])
