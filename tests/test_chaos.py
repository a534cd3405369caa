"""Robustness: the fault-injection plane + chaos drills.

Unit layer (fast, no topology):
  - no installed plan => hot path is a single attribute check, nothing fires
  - typed RpcTimeout / RpcClosed replace string-matched errors
  - same seed => byte-identical fault sequence (determinism)
  - sever cuts live channels + refuses dials; heal restores
  - the DegradingProvider trips to SW on a forced-fail JAXTPU-shaped
    primary with IDENTICAL validation flags, then probes back to healthy
  - committer acknowledges replayed blocks idempotently, rejects forks

Live layer (one in-process topology, module-scoped):
  - a seeded plan with drop+delay+dup active, plus one orderer
    kill/restart mid-traffic: every submitted tx commits exactly once
    (gateway dedup absorbs duplicated submit frames), all peers converge
    to the same height and commit hash, GET /faults shows the plan while
    installed and {"active": false} after, /healthz returns clean after
    heal.
"""

import hashlib
import json
import threading
import time
import urllib.error
import urllib.request

import numpy as np
import pytest

from fabric_tpu.comm import (FaultPlan, RpcClosed, RpcError, RpcServer,
                             RpcTimeout, connect, faults)
from fabric_tpu.msp import CachedMSP
from fabric_tpu.msp.ca import DevOrg


@pytest.fixture(scope="module", autouse=True)
def provider():
    from fabric_tpu.bccsp.factory import FactoryOpts, init_factories
    return init_factories(FactoryOpts(default="SW"))


@pytest.fixture(autouse=True)
def no_leaked_plan():
    """Every test starts and ends with NO plan installed."""
    faults.uninstall()
    yield
    faults.uninstall()


def _echo_server(org_name="ChaosOrg", delay_s=0.0):
    org = DevOrg(org_name)
    msps = {org_name: CachedMSP(org.msp())}

    def echo(body, peer):
        if delay_s:
            time.sleep(delay_s)
        return {"echo": body.get("x")}

    server = RpcServer("127.0.0.1", 0, org.new_identity("srv"), msps)
    server.serve("echo", echo)
    server.start()
    return org, msps, server


# ---------------------------------------------------------------------------
# unit: plane semantics
# ---------------------------------------------------------------------------

def test_no_plan_is_noop():
    """Production state: no plan installed, traffic untouched, and the
    injection gate is literally `_PLAN is None`."""
    assert faults.active() is None
    org, msps, server = _echo_server("NoPlanOrg")
    try:
        conn = connect(server.addr, org.new_identity("cli"), msps)
        for i in range(5):
            assert conn.call("echo", {"x": i})["echo"] == i
        conn.close()
    finally:
        server.stop()


def test_seeded_plan_is_deterministic():
    def run(seed):
        sent = []
        plan = FaultPlan(seed=seed).rule(
            method="m*", drop=0.3, dup=0.3, delay=0.1, delay_s=0.0)
        for i in range(300):
            plan.apply(1, "m1", "h:1", "req", lambda: sent.append(i))
        return plan.fired, len(sent)

    fired_a, n_a = run(1234)
    fired_b, n_b = run(1234)
    fired_c, _ = run(99)
    assert fired_a == fired_b and n_a == n_b
    assert fired_a != fired_c           # different seed, different history
    assert fired_a["drop"] > 0 and fired_a["dup"] > 0


def test_rule_scoping_and_max_fires():
    plan = FaultPlan(seed=0).rule(method="only.this", peer="h:1",
                                  drop=1.0, max_fires=2)
    sent = []
    for _ in range(5):
        plan.apply(1, "only.this", "h:1", "req", lambda: sent.append(1))
    plan.apply(1, "other", "h:1", "req", lambda: sent.append(1))
    plan.apply(1, "only.this", "h:2", "req", lambda: sent.append(1))
    # 2 dropped by max_fires, everything else delivered
    assert plan.fired["drop"] == 2 and len(sent) == 5


def test_typed_rpc_timeout():
    org, msps, server = _echo_server("TimeoutOrg", delay_s=5.0)
    try:
        conn = connect(server.addr, org.new_identity("cli"), msps)
        with pytest.raises(RpcTimeout):
            conn.call("echo", {"x": 1}, timeout=0.2)
        assert issubclass(RpcTimeout, RpcError)   # old handlers still work
        conn.close()
    finally:
        server.stop()


def test_typed_rpc_closed():
    org, msps, server = _echo_server("ClosedOrg", delay_s=1.0)
    try:
        conn = connect(server.addr, org.new_identity("cli"), msps)
        errs = []

        def call():
            try:
                conn.call("echo", {"x": 1}, timeout=10.0)
            except RpcError as exc:
                errs.append(exc)

        t = threading.Thread(target=call)
        t.start()
        time.sleep(0.2)
        conn.channel.close()          # the transport dies mid-call
        t.join(timeout=10)
        assert len(errs) == 1 and isinstance(errs[0], RpcClosed), errs
        # and starting a NEW call on the dead connection is RpcClosed too
        with pytest.raises(RpcClosed):
            conn.call("echo", {"x": 2}, timeout=1.0)
    finally:
        server.stop()


def test_sever_and_heal():
    org, msps, server = _echo_server("SeverOrg")
    try:
        conn = connect(server.addr, org.new_identity("cli"), msps)
        assert conn.call("echo", {"x": 1})["echo"] == 1

        plan = faults.install(FaultPlan(seed=3, name="sever-drill"))
        plan.sever(server.addr)
        # the live dialed channel was cut: next call sees RpcClosed
        with pytest.raises((RpcClosed, RpcTimeout)):
            conn.call("echo", {"x": 2}, timeout=2.0)
        # new dials are refused at the fault plane, not by the network
        with pytest.raises(ConnectionRefusedError):
            connect(server.addr, org.new_identity("cli2"), msps)
        assert plan.fired["sever_refused"] == 1
        assert plan.snapshot()["severed"], plan.snapshot()

        plan.heal()
        conn2 = connect(server.addr, org.new_identity("cli3"), msps)
        assert conn2.call("echo", {"x": 3})["echo"] == 3
        conn2.close()
    finally:
        faults.uninstall()
        server.stop()


def test_faulted_live_rpc_drop_then_delivery():
    """A drop rule makes the call time out; once the rule exhausts
    (max_fires) the retry succeeds on the same channel."""
    org, msps, server = _echo_server("DropOrg")
    try:
        faults.install(FaultPlan(seed=5).rule(
            method="echo", kind="req", drop=1.0, max_fires=1))
        conn = connect(server.addr, org.new_identity("cli"), msps)
        with pytest.raises(RpcTimeout):
            conn.call("echo", {"x": 1}, timeout=0.5)
        assert conn.call("echo", {"x": 2}, timeout=5.0)["echo"] == 2
        assert faults.active().fired["drop"] == 1
        conn.close()
    finally:
        faults.uninstall()
        server.stop()


def test_dup_req_frame_runs_handler_twice():
    """Duplicated request frames reach the handler twice — the raw
    material for the gateway-dedup live assertion below."""
    org = DevOrg("DupOrg")
    msps = {"DupOrg": CachedMSP(org.msp())}
    calls = []
    server = RpcServer("127.0.0.1", 0, org.new_identity("srv"), msps)
    server.serve("mark", lambda body, peer: calls.append(body["x"]) or {})
    server.start()
    try:
        faults.install(FaultPlan(seed=6).rule(
            method="mark", kind="req", dup=1.0, max_fires=1))
        conn = connect(server.addr, org.new_identity("cli"), msps)
        conn.call("mark", {"x": 1}, timeout=5.0)
        time.sleep(0.3)               # let the duplicate's handler finish
        assert calls.count(1) == 2, calls
        conn.close()
    finally:
        faults.uninstall()
        server.stop()


# ---------------------------------------------------------------------------
# unit: bccsp degradation
# ---------------------------------------------------------------------------

class _SickPrimary:
    """JAXTPU-shaped primary whose device dispatch fails N times, then
    recovers.  (A SoftwareProvider stands in for the device math so the
    flag-identity assertion costs no XLA compiles on CPU.)"""

    name = "jaxtpu"

    def __init__(self, fail_batches: int, inner):
        self.remaining = fail_batches
        self.inner = inner
        self.stats = {"fallbacks": 0}

    def batch_verify_async(self, items):
        items = list(items)
        if self.remaining > 0:
            self.remaining -= 1

            def boom():
                raise RuntimeError("device dispatch failed (forced)")
            return boom
        return self.inner.batch_verify_async(items)

    def batch_verify(self, items):
        return self.batch_verify_async(items)()

    def key_gen(self, scheme):
        return self.inner.key_gen(scheme)

    def sign(self, key, payload):
        return self.inner.sign(key, payload)

    def hash(self, data, algo="sha256"):
        return self.inner.hash(data, algo)


def _mixed_items(sw, n=6):
    from fabric_tpu.bccsp import SCHEME_P256, VerifyItem
    items = []
    for i in range(n):
        k = sw.key_gen(SCHEME_P256)
        digest = hashlib.sha256(b"payload%d" % i).digest()
        sig = sw.sign(k, digest)
        if i % 3 == 2:                # corrupt every third signature
            digest = hashlib.sha256(b"tampered%d" % i).digest()
        items.append(VerifyItem(SCHEME_P256, k.public_bytes(), sig, digest))
    return items


def test_degrading_provider_identical_flags_and_recovery():
    from fabric_tpu.bccsp.degrade import DegradingProvider
    from fabric_tpu.bccsp.sw import SoftwareProvider

    sw = SoftwareProvider()
    primary = _SickPrimary(fail_batches=3, inner=SoftwareProvider())
    deg = DegradingProvider(primary, sw, failure_threshold=2,
                            cooldown_base_s=0.05, cooldown_max_s=0.2)
    items = _mixed_items(sw)
    expected = sw.batch_verify(items)
    assert not expected.all() and expected.any()   # genuinely mixed

    # batches 1-2: primary resolve fails -> re-verified on SW, breaker
    # trips at the threshold; flags stay identical throughout
    for i in range(2):
        got = deg.batch_verify_async(items)()
        assert np.array_equal(got, expected), f"batch {i} diverged"
    assert deg.degraded is True
    assert deg.backend == "sw(degraded)"

    # degraded: routed straight to SW (the sick primary is not touched)
    before = primary.remaining
    got = deg.batch_verify(items)
    assert np.array_equal(got, expected)
    assert primary.remaining == before       # no device attempt while open

    # cooldown lapses; the probe hits the (one last failure) primary,
    # re-trips, then the next probe succeeds and restores HEALTHY
    deadline = time.time() + 10.0
    while deg.degraded and time.time() < deadline:
        time.sleep(0.06)
        got = deg.batch_verify(items)
        assert np.array_equal(got, expected)
    assert deg.degraded is False
    assert deg.backend == "jaxtpu"
    assert primary.remaining == 0

    # transition metrics made it to the registry
    from fabric_tpu.ops_plane import registry
    text = registry.expose_text()
    assert "bccsp_degraded" in text
    assert "bccsp_breaker_transitions_total" in text


def test_packed_verb_default_and_behind_the_breaker():
    """A signature table through the providers that do not pack from it:
    the software provider's default builds the items and answers with
    the item verb's verdicts; the degrading provider owns the verb — a
    sick primary's packed failures count against the same breaker, the
    fallback answers from the table, and while the breaker is open the
    primary is not touched."""
    from fabric_tpu.bccsp import SCHEME_P256, VerifyItem
    from fabric_tpu.bccsp.degrade import DegradingProvider
    from fabric_tpu.bccsp.sw import SoftwareProvider
    from fabric_tpu.native import load

    fc = load("_fastcollect")
    if fc is None:
        pytest.skip("no native extension")
    sw = SoftwareProvider()
    items = _mixed_items(sw)
    k = sw.key_gen("ed25519")
    items.append(VerifyItem("ed25519", k.public_bytes(), sw.sign(k, b"m"),
                            b"m"))
    table = fc.pack_items(items + items[:2], VerifyItem, SCHEME_P256)
    assert list(table) == items and table.n_rows == len(items) - 1
    expected = sw.batch_verify(items)
    assert not expected.all() and expected.any()
    assert np.array_equal(sw.batch_verify_packed_async(table)(), expected)

    class SickPacked(_SickPrimary):
        packed_calls = 0

        def batch_verify_packed_async(self, batch):
            self.packed_calls += 1
            return self.batch_verify_async(batch)

    primary = SickPacked(fail_batches=2, inner=SoftwareProvider())
    deg = DegradingProvider(primary, sw, failure_threshold=2,
                            cooldown_base_s=30.0, cooldown_max_s=60.0)
    for i in range(2):
        got = deg.batch_verify_packed_async(table)()
        assert np.array_equal(got, expected), f"batch {i} diverged"
    assert primary.packed_calls == 2 and primary.remaining == 0
    assert deg.degraded is True             # both failures were counted
    got = deg.batch_verify_packed_async(table)()
    assert np.array_equal(got, expected)
    assert primary.packed_calls == 2        # open: the primary is left alone

    # a primary that knows items only is handed the items, guarded alike
    plain = _SickPrimary(fail_batches=1, inner=SoftwareProvider())
    deg = DegradingProvider(plain, sw, failure_threshold=1,
                            cooldown_base_s=30.0, cooldown_max_s=60.0)
    assert np.array_equal(deg.batch_verify_packed_async(table)(), expected)
    assert plain.remaining == 0 and deg.degraded is True


# ---------------------------------------------------------------------------
# unit: committer idempotent replay
# ---------------------------------------------------------------------------

def _committer_world(provider):
    from fabric_tpu.committer import Committer, PolicyRegistry, TxValidator
    from fabric_tpu.ledger import KVLedger, LedgerConfig
    from fabric_tpu.policy import parse_policy

    org1, org2 = DevOrg("Org1"), DevOrg("Org2")
    msps = {o.mspid: CachedMSP(o.msp()) for o in (org1, org2)}
    policies = PolicyRegistry()
    policies.set_policy("cc", parse_policy(
        "AND('Org1.member', 'Org2.member')"))
    ledger = KVLedger("ch", LedgerConfig())
    validator = TxValidator("ch", msps, provider, policies)
    return org1, org2, Committer(ledger, validator)


def _one_block(org1, org2, committer, key):
    from fabric_tpu.protocol import KVWrite, NsRwSet, TxRwSet, build
    rwset = TxRwSet((NsRwSet("cc", writes=(KVWrite(key, b"v"),)),))
    env = build.endorser_tx("ch", "cc", "1.0", rwset,
                            org1.new_identity("client"),
                            [org1.new_identity("e1"),
                             org2.new_identity("e2")])
    lg = committer.ledger
    prev = (lg.blockstore.chain_info().current_hash
            if lg.height else b"\x00" * 32)
    return build.new_block(lg.height, prev, [env])


def test_committer_replay_is_idempotent(provider):
    from fabric_tpu.protocol import build
    org1, org2, committer = _committer_world(provider)
    notified = []
    committer.add_commit_listener(lambda b, f: notified.append(
        int(b.header.number)))

    b0 = _one_block(org1, org2, committer, "k0")
    first = committer.store_block(b0)
    b1 = _one_block(org1, org2, committer, "k1")
    committer.store_block(b1)
    assert committer.height == 2 and notified == [0, 1]

    # the same block delivered again (severed stream retry / duplicated
    # gossip push): acknowledged, nothing re-runs
    res = committer.store_block(b0)
    assert committer.height == 2
    assert notified == [0, 1]                  # listeners NOT re-fired
    assert res.final_flags.codes() == first.final_flags.codes()

    # but a DIFFERENT block at a committed height is a fork: hard error
    import dataclasses
    forged = _one_block(org1, org2, committer, "evil")
    forged.header = dataclasses.replace(forged.header, number=0)
    with pytest.raises(ValueError, match="divergent"):
        committer.store_block(forged)


# ---------------------------------------------------------------------------
# live topology under a seeded plan (+ orderer kill/restart)
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def chaos_net(tmp_path_factory, provider):
    from fabric_tpu.config import BatchConfig
    from fabric_tpu.testing import ChaosNet

    net = ChaosNet(
        str(tmp_path_factory.mktemp("chaosnet")), n_orderers=3,
        peer_orgs=["Org1", "Org2"], peers_per_org=1,
        batch=BatchConfig(max_message_count=4, timeout_s=0.1),
        gateway_cfg={"linger_s": 0.002, "max_batch": 8,
                     "broadcast_deadline_s": 30.0,
                     "rpc_timeout_s": 2.0,
                     "submit_timeout_s": 30.0},
        peer_overrides={"ops_port": 0,
                        # tight SLO windows so the blackout drill below
                        # flips an objective within seconds, not minutes
                        "slo": {"sample_interval_s": 0.2,
                                "short_window_s": 1.0,
                                "long_window_s": 3.0}})
    net.start()
    try:
        yield net
    finally:
        faults.uninstall()
        net.stop_all()


def _ops_get(peer, path):
    host, port = peer.ops.addr[:2]
    try:
        with urllib.request.urlopen(
                f"http://{host}:{port}{path}", timeout=5) as r:
            return r.status, json.loads(r.read().decode())
    except urllib.error.HTTPError as e:          # 503 still carries a body
        return e.code, json.loads(e.read().decode())


def test_chaos_convergence_exactly_once(chaos_net):
    """The acceptance drill: drop + delay + dup active under one seed,
    one orderer crash-stopped and restarted mid-traffic."""
    from fabric_tpu.protocol.txflags import TxFlags, ValidationCode
    from fabric_tpu.protocol.types import META_TXFLAGS

    net = chaos_net
    plan = faults.install(
        FaultPlan(seed=20260804, name="acceptance")
        # peer -> orderer broadcasts: lost and slowed frames
        .rule(method="broadcast_batch", kind="req", drop=0.25, max_fires=6)
        .rule(method="broadcast_batch", kind="*", delay=0.3, delay_s=0.02,
              max_fires=40)
        # client -> gateway submits: duplicated frames (handler runs
        # twice; the txid dedup window must absorb the second run)
        .rule(method="gateway.submit", kind="req", dup=0.5, max_fires=8)
        # raft heartbeat/append casts: adjacent frames swapped — raft's
        # term checks must tolerate out-of-order delivery.  The cast
        # stream is high-frequency, so the parked frame is always
        # released by the next heartbeat (no wedge).
        .rule(method="raft.step", kind="cast", reorder=0.25, max_fires=10))

    # while installed, the ops plane shows the plan on every node
    code, body = _ops_get(net.peers()[0], "/faults")
    assert code == 200 and body["active"] is True
    assert body["name"] == "acceptance" and body["seed"] == 20260804

    txids = {}
    errors = []

    def drive(org, tag, n):
        gw = net.client(org)
        try:
            for i in range(n):
                key = f"{tag}-{i}".encode()
                code, block = gw.submit_transaction(
                    "assets", "create", [key, b"owner"],
                    commit_timeout_s=60.0)
                txids[f"{tag}-{i}"] = (code, block)
        except Exception as exc:
            errors.append((tag, exc))
        finally:
            gw.close()

    threads = [threading.Thread(target=drive, args=("Org1", "a", 4)),
               threading.Thread(target=drive, args=("Org2", "b", 4))]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=120)

    # crash-stop one FOLLOWER orderer, drive more traffic, restart it
    follower = next(
        name for name, node in list(net.nodes.items())
        if net._specs[name][0] == "orderer"
        and node.support.chain.node.role != "leader")
    net.kill(follower)
    drive("Org1", "c", 4)
    net.restart(follower)

    faults.uninstall()
    assert not errors, errors
    assert len(txids) == 12
    assert all(code == int(ValidationCode.VALID)
               for code, _ in txids.values()), txids

    # all peers converge to one height + one commit hash
    assert net.wait_converged(timeout_s=60.0), (
        net.heights(), net.commit_hashes())

    # exactly-once at the ledger: every submitted key appears VALID in
    # exactly one committed tx across the whole chain — duplicated
    # submit frames never reached ordering twice
    from fabric_tpu.protocol import Envelope, Transaction
    ledger = net.peers()[0].channels["ch"].ledger
    valid_keys = []
    for num in range(ledger.height):
        blk = ledger.blockstore.get_by_number(num)
        flags = TxFlags.from_bytes(blk.metadata.items[META_TXFLAGS])
        for i, raw in enumerate(blk.data):
            if not flags.is_valid(i):
                continue
            payload = Envelope.deserialize(raw).payload_dict()
            if "actions" not in payload["data"]:
                continue                         # config/genesis envelope
            tx = Transaction.from_dict(payload["data"])
            for ta in tx.actions:
                for ns in ta.action.rwset.ns_rwsets:
                    for w in ns.writes:
                        valid_keys.append(w.key)
    for tag in txids:
        assert valid_keys.count(tag) == 1, (tag, valid_keys)

    # the plan actually fired all four fault kinds, and the fired
    # reorders are visible on the metrics surface
    assert plan.fired["drop"] > 0, plan.fired
    assert plan.fired["delay"] > 0, plan.fired
    assert plan.fired["dup"] > 0, plan.fired
    assert plan.fired["reorder"] > 0, plan.fired
    host, port = net.peers()[0].ops.addr[:2]
    with urllib.request.urlopen(
            f"http://{host}:{port}/metrics", timeout=5) as r:
        metrics_text = r.read().decode()
    assert 'fault_injected_total{action="reorder"' in metrics_text

    # after heal + uninstall: /faults is empty and /healthz is clean
    code, body = _ops_get(net.peers()[0], "/faults")
    assert code == 200 and body == {"active": False}
    deadline = time.time() + 30
    while time.time() < deadline:
        code, body = _ops_get(net.peers()[0], "/healthz")
        if code == 200:
            break
        time.sleep(0.5)
    assert code == 200, body
    assert body["status"] == "OK", body


def test_orderer_breaker_recovers_after_restart(chaos_net, caplog):
    """Severing every orderer trips all gateway breakers (healthz goes
    red) and flips the breaker_open_frac SLO to alerting — the alert
    lands on /slo, /slo/alerts, the jlog stream and the trace stream;
    healing lets the half-open probe close the breakers again."""
    import logging
    net = chaos_net
    gw_peer = net.peers()[0]
    bc = gw_peer.gateway.broadcaster

    with caplog.at_level(logging.WARNING,
                         logger="fabric_tpu.ops_plane.slo"):
        plan = faults.install(FaultPlan(seed=9, name="blackout"))
        plan.isolate([net.orderer_addr(n)
                      for n, (k, _) in net._specs.items()
                      if k == "orderer"])
        client = net.client("Org1")
        try:
            with pytest.raises(Exception):
                client.submit_transaction("assets", "create",
                                          [b"blackout", b"x"],
                                          commit_timeout_s=8.0)
        finally:
            client.close()
        assert bc.healthy() is False or bc._failures > 0

        # the sustained blackout burns through both SLO windows: the
        # peer's evaluator flips breaker_open_frac to alerting
        st = None
        deadline = time.time() + 30
        while time.time() < deadline:
            _, slo = _ops_get(gw_peer, "/slo")
            st = {o["name"]: o
                  for o in slo["objectives"]}["breaker_open_frac"]
            if st["state"] == "alerting":
                break
            time.sleep(0.3)
        assert st is not None and st["state"] == "alerting", st
        assert st["burn_short"] >= 1.0 and st["burn_long"] >= 1.0, st
        assert "breaker_open_frac" in slo["alerting"]
        _, alerts = _ops_get(gw_peer, "/slo/alerts")
        assert any(a["objective"] == "breaker_open_frac"
                   and a["state"] == "firing"
                   for a in alerts["active"]), alerts

    # the alert transition landed as a structured jlog record ...
    fired = [r for r in caplog.records if "slo.alert_fired" in r.message]
    assert any(json.loads(r.message)["objective"] == "breaker_open_frac"
               for r in fired), caplog.records
    # ... and as a root span in the trace stream
    _, doc = _ops_get(gw_peer, "/spans/stats")
    assert "slo.alert" in doc["spans"], sorted(doc["spans"])

    plan.heal()
    faults.uninstall()
    client = net.client("Org1")
    try:
        from fabric_tpu.protocol.txflags import ValidationCode
        code, _ = client.submit_transaction("assets", "create",
                                            [b"after-heal", b"x"],
                                            commit_timeout_s=60.0)
        assert code == int(ValidationCode.VALID)
    finally:
        client.close()
    assert bc.healthy() is True


def test_crash_stop_chaos_yields_zero_quarantines(chaos_net):
    """The no-false-positive gate: this module's drills threw every
    crash-stop fault at the topology — dropped/delayed/duplicated/
    reordered frames, an orderer kill/restart, an orderer blackout —
    and NONE of that can produce two validly-signed headers at one
    height, so the byzantine plane must have convicted nobody."""
    net = chaos_net
    for peer in net.peers():
        assert peer.byzantine is not None
        assert peer.byzantine.count() == 0, peer.byzantine.snapshot()
        mon = peer.channels[net.channel_id].byz_monitor
        assert mon is not None
        assert mon.proofs == []
        assert mon.witness.disputed_heights() == []
        # the ops route agrees with the in-process registries
        code, body = _ops_get(peer, "/byzantine")
        assert code == 200
        assert body["quarantined"] == 0
        assert body["reasons"] == {}
        assert body["channels"][net.channel_id]["fraud_proofs"] == 0
