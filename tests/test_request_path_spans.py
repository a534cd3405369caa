"""The spans and stamps the block path and the ops surface gained with
the dispatch account: `committer.store_block`'s direct children cover
it, the ledger phases sit where they ran, checkpoints have a duration,
the tracer stays out of the collector, the profile route's reply puts a
trace beside the program's own record, and replay takes a hook."""

import gc
import json
import threading
import time
import urllib.request

import pytest

from fabric_tpu.ops_plane import registry, tracing
from fabric_tpu.ops_plane.metrics import MetricsRegistry
from fabric_tpu.ops_plane.tracing import Tracer


@pytest.fixture
def tracer_on():
    t = tracing.tracer
    was = t.enabled
    t.configure({"enabled": True})
    yield t
    t.enabled = was


def _committer(tmp_path, channel, n_tx):
    from fabric_tpu.bccsp.factory import FactoryOpts, init_factories
    from fabric_tpu.committer.committer import Committer
    from fabric_tpu.committer.txvalidator import PolicyRegistry, TxValidator
    from fabric_tpu.ledger import KVLedger
    from fabric_tpu.ledger.kvledger import LedgerConfig
    from fabric_tpu.msp import CachedMSP
    from fabric_tpu.msp.ca import DevOrg
    from fabric_tpu.policy import parse_policy
    from fabric_tpu.protocol import KVWrite, NsRwSet, TxRwSet, build

    provider = init_factories(FactoryOpts(default="SW"))
    org = DevOrg("SpanOrg")
    msps = {"SpanOrg": CachedMSP(org.msp())}
    validator = TxValidator(channel, msps, provider, PolicyRegistry(
        parse_policy("OR('SpanOrg.member')")))
    ledger = KVLedger(channel, LedgerConfig(root=str(tmp_path),
                                            snapshot_every=1))
    creator, endorser = org.new_identity("c"), org.new_identity("e")
    envs = [build.endorser_tx(
        channel, "cc", "1.0",
        TxRwSet((NsRwSet("cc", writes=(KVWrite("k%d" % i, b"v"),)),)),
        creator, [endorser]) for i in range(n_tx)]
    block = build.new_block(0, b"\x00" * 32, envs)
    return Committer(ledger, validator), block


def test_store_blocks_direct_children_leave_no_unnamed_stretch(
        tmp_path, tracer_on):
    from fabric_tpu.protocol import wire
    committer, block = _committer(tmp_path, "spans", 24)
    block = wire.parse_block(block.serialize())
    assert block.parsed[0] <= block.parsed[1]
    committer.store_block(block)
    rec = tracer_on.recorder.get(next(
        r["trace_id"] for r in tracer_on.recorder.list()["recent"]
        if r["root"] == "committer.store_block"))
    root = next(s for s in rec["spans"] if s["parent_id"] is None)
    kids = sorted((s for s in rec["spans"]
                   if s["parent_id"] == root["span_id"]),
                  key=lambda s: s["start"])
    names = [s["name"] for s in kids]
    for required in ("wire.parse_block", "committer.replay_check",
                     "validator.collect", "validator.dispatch_wait",
                     "validator.gate", "validator.finish",
                     "committer.config_check", "ledger.mvcc",
                     "ledger.block_commit", "ledger.state_commit",
                     "ledger.history_commit", "committer.observe",
                     "committer.notify", "committer.heap_boundary"):
        assert required in names, (required, names)
    # the parse ran before the hand-off; everything else inside the root
    assert kids[0]["name"] == "wire.parse_block"
    assert kids[0]["start"] + kids[0]["duration_s"] <= root["start"]
    # what no direct child covers, inside the root
    end = root["start"] + root["duration_s"]
    reach, uncovered = root["start"], 0.0
    for s in kids[1:]:
        assert root["start"] <= s["start"] <= end
        uncovered += max(0.0, s["start"] - reach)
        reach = max(reach, s["start"] + s["duration_s"])
    uncovered += max(0.0, end - reach)
    assert uncovered <= max(0.002, 0.10 * root["duration_s"]), (
        uncovered, root["duration_s"],
        [(s["name"], s["start"] - root["start"], s["duration_s"])
         for s in kids])
    # the ledger's phases where they ran: in order, not overlapping, and
    # the checkpoints (every block here) inside the phase that made them
    by = {s["name"]: s for s in kids}
    order = ["ledger.mvcc", "ledger.block_commit", "ledger.state_commit",
             "ledger.history_commit"]
    for a, b in zip(order, order[1:]):
        assert by[a]["start"] + by[a]["duration_s"] <= by[b]["start"]
    stats = committer.ledger.last_stats
    assert [n for n, _s, _e in stats.phase_spans] == order
    for name, start, stop in stats.phase_spans:
        assert by[name]["start"] == start
        assert by[name]["duration_s"] == pytest.approx(stop - start)
    assert stats.state_commit_s == pytest.approx(
        by["ledger.state_commit"]["duration_s"])
    for ckpt, phase in (("state.checkpoint", "ledger.state_commit"),
                        ("history.checkpoint", "ledger.history_commit")):
        c, ph = by[ckpt], by[phase]
        assert c["duration_s"] > 0.0
        assert ph["start"] <= c["start"]
        assert (c["start"] + c["duration_s"]
                <= ph["start"] + ph["duration_s"])


def test_a_collection_under_the_tracers_lock_returns(tracer_on):
    """The tracer hooks nothing into the collector: its locks are not
    re-entrant, and a collection runs on whichever thread allocates —
    one that holds them included."""
    before = list(gc.callbacks)
    Tracer().configure({"enabled": True})
    assert gc.callbacks == before
    done = threading.Event()

    def collect_under_the_lock():
        with tracer_on.start_span("test.gc"):
            with tracer_on._lock:
                gc.collect()
        done.set()

    threading.Thread(target=collect_under_the_lock, daemon=True).start()
    assert done.wait(10.0)


def test_each_exposition_stamps_the_uptime():
    reg = MetricsRegistry()
    t0 = time.perf_counter()
    a = reg.expose_text()
    time.sleep(0.02)
    b = reg.expose_text()
    elapsed = time.perf_counter() - t0

    def uptime(text):
        return float(next(line.split()[1] for line in text.splitlines()
                          if line.startswith("process_uptime_seconds ")))
    assert 0.02 <= uptime(b) - uptime(a) <= elapsed


def test_two_expositions_may_differ_in_the_uptime_alone(same_exposition):
    """The guard the zero-overhead tests hold /metrics to (conftest)."""
    reg = MetricsRegistry()
    reg.counter("committed_txs_total").add(5)
    a = reg.expose_text()
    time.sleep(0.01)
    b = reg.expose_text()
    assert a != b
    same_exposition(a, b)
    with pytest.raises(AssertionError):
        same_exposition(b, a)            # the clock ran backwards
    with pytest.raises(AssertionError):
        same_exposition(a, b.replace("committed_txs_total 5",
                                     "committed_txs_total 6"))
    with pytest.raises(AssertionError):
        same_exposition(a, b + "\nprofiler_samples_total 0")


def test_span_stats_and_metrics_are_one_store(tracer_on):
    with tracer_on.start_span("one.store"):
        pass
    hist = registry.get("span_duration_seconds")
    counts, total, n = hist.state_by("span")["one.store"]
    stats = tracer_on.span_stats()["one.store"]
    assert stats["count"] == n >= 1
    assert stats["total_s"] == pytest.approx(total, abs=1e-6)
    assert sum(stats["buckets"].values()) == n == sum(counts)
    assert "max_ms" not in stats
    assert not hasattr(tracer_on, "_stats")
    assert registry.get("provider_device_sync_seconds") is None


def test_profile_route_reply_puts_the_trace_beside_the_programs_record(
        tracer_on):
    from fabric_tpu.ops_plane import OperationsServer
    from fabric_tpu.ops_plane.profiling import register_routes
    import threading

    ops = OperationsServer("127.0.0.1", 0)
    register_routes(ops, enabled=True)
    ops.start()
    stop = threading.Event()
    # ending a trace takes seconds on a loaded host, and the worker
    # roots ~100 traces a second meanwhile: keep the window's
    held = tracer_on.recorder.max_traces
    tracer_on.recorder.max_traces = 16384

    def work():
        while not stop.is_set():
            with tracer_on.start_span("profiled.work"):
                time.sleep(0.01)

    worker = threading.Thread(target=work, daemon=True)
    worker.start()
    try:
        url = "http://%s:%d" % ops.addr
        req = urllib.request.Request(f"{url}/debug/profile?seconds=0.3",
                                     method="POST")
        body = json.loads(urllib.request.urlopen(req, timeout=180).read())
    finally:
        stop.set()
        worker.join()
        ops.stop()
        tracer_on.recorder.max_traces = held
    assert body["python_tracer"] is False        # off unless asked
    assert body["mark"] == "profile.mark"
    assert body["start_perf"] <= body["mark_perf"] <= body["end_perf"]
    assert body["end_perf"] - body["start_perf"] >= 0.3
    assert body["stop_s"] >= 0.0
    for edge in ("prom_before", "prom_after"):
        assert "process_uptime_seconds" in body[edge]
    inside = [s for s in body["spans"] if s["name"] == "profiled.work"]
    assert len(inside) >= 5
    assert all(body["start_perf"] <= s["start"] <= body["end_perf"]
               for s in body["spans"])


def test_replay_takes_a_per_block_hook(tmp_path, monkeypatch):
    """testing/replay.py: the hook stands around each block's parse and
    commit, sees the node, and can end the run."""
    from fabric_tpu.testing import replay as replay_mod

    class Node:
        mspid = "Org1"

        def __init__(self, cfg, data_dir=None):
            self.stored = []
            node = self

            class Coordinator:
                def store_block(self, block):
                    node.stored.append(int(block.header.number))

            class Blockstore:
                def get_by_number(self, number):
                    from fabric_tpu.protocol.types import META_TXFLAGS

                    class Stored:
                        class metadata:
                            items = {META_TXFLAGS: b"\x00"}
                    return Stored

            class Ledger:
                blockstore = Blockstore()
                height = 0
                commit_hash = b""

            self.coordinator, self.ledger = Coordinator(), Ledger()

        def _provider_status(self):
            return {}

        def stop(self):
            pass

    import fabric_tpu.node.peer as peer_mod
    from fabric_tpu.protocol import build
    monkeypatch.setattr(peer_mod, "PeerNode", Node)
    paths = []
    for n in range(3):
        path = tmp_path / f"b{n}"
        path.write_bytes(build.new_block(n, b"\x00" * 32, []).serialize())
        paths.append(str(path))
    seen = []

    def hook(node, i, store):
        if i == 2:
            raise StopIteration
        seen.append((i, list(node.stored)))
        rec = store()
        rec["hooked"] = True
        return rec

    out = replay_mod.replay({"data_dir": str(tmp_path)}, paths,
                            on_block=hook)
    assert seen == [(0, []), (1, [0])]
    assert [b["number"] for b in out["blocks"]] == [0, 1]
    assert all(b["hooked"] for b in out["blocks"])
    plain = replay_mod.replay({"data_dir": str(tmp_path)}, paths)
    assert [b["number"] for b in plain["blocks"]] == [0, 1, 2]


def test_the_banks_table_update_compiles_under_its_own_name():
    """A trace's `jit__lambda` is then the rows lane alone."""
    import jax
    import numpy as np
    from fabric_tpu.ops.device_bank import DeviceBank, bank_update
    assert bank_update.__name__ == "bank_update"
    bank = DeviceBank(2, (4, 3), lambda pk: np.full((4, 3), len(pk),
                                                    np.float32))
    assert bank.get_or_build(b"abc") is not None
    assert float(np.asarray(bank.array())[bank.lookup(b"abc")][0, 0]) == 3.0
    lowered = jax.jit(bank_update).lower(
        np.zeros((2, 4, 3), np.float32), np.zeros((4, 3), np.float32),
        np.int32(0))
    assert "jit_bank_update" in lowered.as_text()[:200]
