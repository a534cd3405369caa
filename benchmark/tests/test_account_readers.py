"""The readers of the dispatch account and of the request path's spans:
each against two expositions the program's own account and tracer made
(so the series' names are the program's, letter for letter), and None
on an exposition that lacks its series — what the parent commit gives."""

import pytest

import harness
import run as launcher
from fabric_tpu.bccsp.dispatch_account import DispatchAccount
from fabric_tpu.ops_plane.metrics import MetricsRegistry
from fabric_tpu.ops_plane.tracing import Tracer

# a program without the account and the spans: the parent's exposition
BARE = """\
gateway_request_duration_seconds_sum{verb="endorse"} 8.0
gateway_request_duration_seconds_count{verb="endorse"} 20
validator_stage_seconds_count{channel="ch",stage="collect"} 2
span_duration_seconds_sum{span="committer.store_block"} 4.4
span_duration_seconds_count{span="committer.store_block"} 2
"""

NEW = ["device.held_share.steady", "provider.queue_wait_ms.steady",
       "kernel.held_ms.generic", "transport.handshake_ms.steady",
       "endorser.proposal_ms.steady", "gateway.fanout_ms.steady",
       "device.held_share.catchup", "kernel.held_ms.rows",
       "provider.pack_ms.catchup"]


def dispatch(account, lane, program, site, sigs, t_call, pack, enqueue,
             ready_at, observed=True):
    rec = account.enqueued(lane, program, site, sigs, t_call, t_call + pack,
                           t_call + pack + enqueue)
    account.ready(rec, ready_at, observed)


@pytest.fixture(scope="module")
def obs():
    """Before: one dispatch of each lane and one of each span.  After:
    a window in which the device peer served 4 endorsements and
    validated 2 blocks."""
    reg = MetricsRegistry()
    account = DispatchAccount(("tpu:0",), registry=reg)
    tracer = Tracer(registry=reg)
    tracer.enabled = True
    gateway = reg.histogram("gateway_request_duration_seconds", "")
    stage = reg.histogram("validator_stage_seconds", "")

    def spans(t0, n_endorse):
        with tracer.start_span("rpc.gateway.endorse"):
            for i in range(n_endorse):
                base = t0 + i
                tracer.record_span("endorser.validate", base, base + 0.030)
                tracer.record_span("endorser.simulate", base, base + 0.002)
                tracer.record_span("endorser.sign", base, base + 0.001)
                for _peer in range(2):
                    tracer.record_span("gateway.fanout", base, base + 0.100)
                    tracer.record_span("comm.handshake", base, base + 0.040)
                gateway.observe(0.4, verb="endorse")

    # set-up traffic, before the window
    dispatch(account, "generic", "generic@128", "warmup", 1, 0.0, 0.001,
             0.001, 0.5)
    dispatch(account, "rows", "rows@384", "warmup", 39000, 1.0, 0.3, 0.01,
             1.5)
    spans(0.0, 1)
    stage.observe(1.0, stage="collect", channel="ch")
    before = harness.parse_prom(reg.expose_text())

    # the window: four single-signature dispatches, back to back so the
    # second and fourth wait for the chip; two blocks on the rows lane
    dispatch(account, "generic", "generic@128", "endorser", 1, 10.000,
             0.002, 0.001, 10.027)              # held 24 ms, waited 0
    dispatch(account, "generic", "generic@128", "handshake", 1, 10.001,
             0.002, 0.001, 10.053)              # waited 23, held 26
    dispatch(account, "generic", "generic@128", "handshake", 1, 11.000,
             0.002, 0.001, 11.025)              # held 22, waited 0
    dispatch(account, "generic", "generic@128", "speculative", 1, 11.010,
             0.002, 0.001, 11.049, observed=False)   # nobody waited
    dispatch(account, "rows", "rows@384", "validator", 39000, 20.0, 0.400,
             0.010, 20.500)                     # held 90 ms
    dispatch(account, "rows", "rows@384", "validator", 39000, 23.0, 0.300,
             0.010, 23.420)                     # held 110 ms
    spans(10.0, 4)
    for _ in range(2):
        stage.observe(1.0, stage="collect", channel="ch")
    after = harness.parse_prom(reg.expose_text())
    return {"prom_before": before, "prom_after": after}


def read(name, obs):
    return launcher.load_module("layer_metrics", name).read(obs)


def test_the_readers_of_the_account(obs):
    seconds = harness.prom_delta(obs["prom_before"], obs["prom_after"],
                                 "process_uptime_seconds")
    assert seconds > 0
    held = 0.024 + 0.026 + 0.022 + 0.090 + 0.110
    for cell in ("steady", "catchup"):
        assert read(f"device.held_share.{cell}", obs) == pytest.approx(
            100.0 * held / seconds)
    # three observed generic dispatches; the unobserved one adds none
    assert read("kernel.held_ms.generic", obs) == pytest.approx(24.0)
    assert read("kernel.held_ms.rows", obs) == pytest.approx(100.0)
    # of six dispatches one waited 23 ms, and the unobserved one until
    # the third's end (11.025 - 11.013)
    assert read("provider.queue_wait_ms.steady", obs) == pytest.approx(
        (23.0 + 12.0) / 6)
    # the validator's packing, per block validated
    assert read("provider.pack_ms.catchup", obs) == pytest.approx(350.0)


def test_the_readers_of_the_spans(obs):
    assert read("transport.handshake_ms.steady", obs) == pytest.approx(40.0)
    assert read("endorser.proposal_ms.steady", obs) == pytest.approx(33.0)
    # two target peers at 100 ms each, per endorse verb
    assert read("gateway.fanout_ms.steady", obs) == pytest.approx(200.0)


@pytest.mark.parametrize("name", NEW)
def test_none_where_the_series_is_absent(name):
    bare = harness.parse_prom(BARE)
    assert read(name, {"prom_before": bare, "prom_after": bare}) is None
    assert read(name, {}) is None


def test_the_manifest_lists_the_new_metrics_last_and_as_issued():
    m = launcher.load_json(launcher.REPO, "BENCHMARK.json")
    assert [x["name"] for x in m["per_layer"]][-len(NEW):] == NEW
    for x in m["per_layer"][-len(NEW):]:
        cell = ("catchup.cut10k" if x["moves"] == "catchup_tps"
                else "served.steady")
        assert x["workloads"] == [cell]
        assert x["source"] == ("program_span" if x["layer"] in (
            "transport", "endorser", "gateway") else "program_counter")
