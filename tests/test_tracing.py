"""Tx tracing + flight recorder (fabric_tpu/ops_plane/tracing).

Unit coverage: traceparent round-trip, recorder bounds/eviction with
slowest-retention, sampling-off propagation, Chrome trace-event JSON
shape.  Live coverage on the same in-process topology shape as
test_gateway (3 raft orderers, Org1/Org2 peers, SW provider): a traced
client tx yields ONE retrievable trace covering gateway admission,
endorsement, ordering, device batch-verify (with batch size), MVCC and
commit notification — over the recorder API and over the peer's ops
HTTP endpoint — and concurrent traced submits keep their traces
distinct (thread safety).
"""

import json
import threading
import time
import urllib.request

import pytest

from fabric_tpu.config import BatchConfig
from fabric_tpu.node.orderer import OrdererNode, load_signing_identity
from fabric_tpu.node.peer import PeerNode
from fabric_tpu.node.provision import provision_network
from fabric_tpu.ops_plane import tracing
from fabric_tpu.ops_plane.tracing import (
    FlightRecorder,
    Tracer,
    format_traceparent,
    parse_traceparent,
)
from fabric_tpu.protocol.txflags import ValidationCode


# ---------------------------------------------------------------------------
# unit: context propagation primitives
# ---------------------------------------------------------------------------

def test_traceparent_round_trip():
    t = Tracer(FlightRecorder())
    t.enabled = True
    span = t.start_span("root")
    tp = format_traceparent(span.context)
    assert tp.startswith("00-") and tp.endswith("-01")
    ctx = parse_traceparent(tp)
    assert ctx.trace_id == span.context.trace_id
    assert ctx.span_id == span.context.span_id
    assert ctx.sampled and ctx.remote
    span.end()
    # malformed inputs never raise, they just don't propagate
    for bad in (None, 7, "", "00-zz-xx-01", "00-abc-def-01",
                "00-" + "0" * 32, "no-dashes-at-all"):
        assert parse_traceparent(bad) is None


def test_recorder_bounds_eviction_and_slowest_retention():
    rec = FlightRecorder(max_traces=4, max_slow=2)
    durs = [0.01, 5.0, 0.02, 0.03, 3.0, 0.04, 0.05, 0.06, 0.07, 0.08]
    for i, d in enumerate(durs):
        rec.add({"trace_id": f"t{i}", "root_name": "r", "start_wall": 0.0,
                 "duration_s": d, "spans": [{"name": "r"}]})
    listing = rec.list()
    assert len(listing["recent"]) == 4          # ring bounded
    assert [r["trace_id"] for r in listing["recent"]] == \
        ["t9", "t8", "t7", "t6"]                # newest first
    # the two slowest survived eviction from the ring
    assert [r["trace_id"] for r in listing["slowest"]] == ["t1", "t4"]
    assert rec.get("t1") is not None            # reachable though evicted
    assert rec.get("t0") is None                # fast + evicted -> gone
    rec.clear()
    assert rec.list() == {"recent": [], "slowest": []}


def test_recorder_is_one_ring_and_configure():
    """One ring for every root: a frequent root rides it like the rest
    (the per-root `retention` cap went with its only user, the
    in-process window puller's trace), and Tracer.configure wires the
    ring's two bounds from the localconfig `tracing` sub-dict."""
    rec = FlightRecorder(max_traces=12, max_slow=0)
    for i in range(8):
        rec.add({"trace_id": f"n{i}", "root_name": "orderer.block",
                 "start_wall": 0.0, "duration_s": 0.001,
                 "spans": [{"name": "orderer.block"}]})
        rec.add({"trace_id": f"q{i}", "root_name": "quiet",
                 "start_wall": 0.0, "duration_s": 0.001,
                 "spans": [{"name": "quiet"}]})
    listing = rec.list()["recent"]
    assert [r["trace_id"] for r in listing] == [
        "q7", "n7", "q6", "n6", "q5", "n5", "q4", "n4", "q3", "n3",
        "q2", "n2"]                          # newest 12, whatever the root
    assert not hasattr(rec, "retention")
    t = Tracer(FlightRecorder())
    t.configure({"max_traces": 7, "max_slow": 3,
                 "retention": {"gossip.pull_window": 2}})   # ignored
    assert (t.recorder.max_traces, t.recorder.max_slow) == (7, 3)
    assert t.enabled and not hasattr(t.recorder, "retention")


def test_sampling_zero_records_nothing_but_propagates():
    t = Tracer(FlightRecorder())
    t.enabled = True
    t.sample_rate = 0.0
    with t.start_span("root") as root:
        assert root.recording and not root.context.sampled
        tp = format_traceparent(root.context)
        assert tp.endswith("-00")               # unsampled flag on the wire
        with t.start_span("child", require_parent=True) as child:
            assert not child.context.sampled    # decision rides the flags
    # server side of the unsampled context: span exists, records nothing
    ctx = t.context_from(tp)
    assert ctx is not None and not ctx.sampled
    t.start_span("rpc.x", parent=ctx, require_parent=True).end()
    assert t.recorder.list() == {"recent": [], "slowest": []}
    # but per-stage stats still observed (histograms are unsampled)
    assert t.span_stats()["root"]["count"] == 1


def test_disabled_tracer_is_noop_everywhere():
    t = Tracer(FlightRecorder())
    assert t.start_span("x") is tracing.NOOP_SPAN
    assert t.traceparent() is None
    assert t.context_from("00-" + "a" * 32 + "-" + "b" * 16 + "-01") is None
    t.record_span("y", 0.0, 1.0)
    assert t.recorder.list() == {"recent": [], "slowest": []}


def test_a_reserved_span_parents_what_is_recorded_before_it():
    """`reserve_span` mints the context of a span whose end is not known
    yet; spans recorded inside it name it as their parent, and it is
    recorded afterwards under the ambient one, with the reserved id."""
    t = Tracer(FlightRecorder())
    assert t.reserve_span() is None                 # off: nothing to reserve
    t.enabled = True
    assert t.reserve_span() is None                 # no ambient trace
    with t.start_span("root") as root:
        wait = t.reserve_span()
        t.record_span("inner", 1.0, 2.0, parent=wait)
        t.record_span("wait", 0.5, 3.0, attributes={"n": 1}, context=wait)
        t.record_span("plain", 3.0, 4.0, context=None)
    spans = {s["name"]: s for s in
             t.recorder.get(root.context.trace_id)["spans"]}
    assert spans["wait"]["span_id"] == wait.span_id
    assert spans["wait"]["parent_id"] == root.context.span_id
    assert spans["inner"]["parent_id"] == wait.span_id
    assert spans["plain"]["parent_id"] == root.context.span_id
    assert spans["wait"]["attributes"] == {"n": 1}
    t.sample_rate = 0.0
    with t.start_span("unsampled"):
        assert t.reserve_span() is None


def test_chrome_export_shape_and_late_span_merge():
    t = Tracer(FlightRecorder())
    t.enabled = True
    with t.start_span("root", attributes={"k": "v"}) as root:
        tid = root.context.trace_id
        t.start_span("child").end(end_time=root.start + 0.25)
    # a span ending AFTER its trace finalized still lands in the record
    late = t.start_span("late", parent=root.context)
    late.end()
    doc = t.export_chrome(tid)
    assert json.loads(json.dumps(doc))          # valid JSON end to end
    xs = [e for e in doc["traceEvents"] if e["ph"] == "X"]
    assert {e["name"] for e in xs} == {"root", "child", "late"}
    for e in xs:
        for key in ("name", "ph", "ts", "dur", "pid", "tid", "args"):
            assert key in e, f"{key} missing from {e['name']}"
        assert e["dur"] >= 0
    root_ev = next(e for e in xs if e["name"] == "root")
    assert root_ev["args"]["k"] == "v"
    assert root_ev["args"]["trace_id"] == tid
    # thread lanes carry metadata names
    assert any(e["ph"] == "M" and e["name"] == "thread_name"
               for e in doc["traceEvents"])
    assert t.export_chrome("f" * 32) is None


# ---------------------------------------------------------------------------
# live topology
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module", autouse=True)
def provider():
    from fabric_tpu.bccsp.factory import FactoryOpts, init_factories
    return init_factories(FactoryOpts(default="SW"))


@pytest.fixture(scope="module")
def net(tmp_path_factory):
    """Same shape as test_gateway's fixture; node constructors enable
    the process tracer via their localconfig `tracing` sub-dict."""
    base = str(tmp_path_factory.mktemp("trnet"))
    paths = provision_network(
        base, n_orderers=3, peer_orgs=["Org1", "Org2"], peers_per_org=1,
        batch=BatchConfig(max_message_count=8, timeout_s=0.1))
    orderers, peers = [], []
    try:
        for p in paths["orderers"]:
            with open(p) as f:
                cfg = json.load(f)
            orderers.append(OrdererNode(cfg, data_dir=cfg["data_dir"]).start())
        for i, p in enumerate(paths["peers"]):
            with open(p) as f:
                cfg = json.load(f)
            cfg["gateway"] = {"linger_s": 0.002, "max_batch": 8,
                              "broadcast_deadline_s": 20.0}
            if i == 0:
                cfg["ops_port"] = 0    # /traces + /spans/stats over HTTP
            peers.append(PeerNode(cfg, data_dir=cfg["data_dir"]).start())
        deadline = time.time() + 60
        while time.time() < deadline:
            if any(o.support.chain.node.role == "leader" for o in orderers):
                break
            time.sleep(0.2)
        else:
            raise AssertionError("no raft leader elected")
        yield {"paths": paths, "orderers": orderers, "peers": peers}
    finally:
        for n in peers + orderers:
            try:
                n.stop()
            except Exception:
                pass
        tracing.tracer.sample_rate = 1.0


def _client(net, org="Org1"):
    from fabric_tpu.gateway import GatewayClient
    with open(net["paths"]["clients"][org]) as f:
        cc = json.load(f)
    signer = load_signing_identity(cc["mspid"], cc["cert_pem"].encode(),
                                   cc["key_pem"].encode())
    peer = net["peers"][0]
    return GatewayClient(peer.rpc.addr, signer, peer.msps, channel_id="ch")


def _trace_names(trace_id, deadline_s=10.0):
    """Poll until the trace (plus linked block trace) holds a stable set
    of span names — late fragments (device resolve, server-side RPC
    ends) merge into the record shortly after the client returns."""
    names, doc = set(), None
    deadline = time.time() + deadline_s
    while time.time() < deadline:
        doc = tracing.tracer.export_chrome(trace_id)
        if doc is not None:
            names = {e["name"] for e in doc["traceEvents"]
                     if e["ph"] == "X"}
            if {"bccsp.batch_verify", "ledger.mvcc",
                    "gateway.commit_wait"} <= names:
                break
        time.sleep(0.1)
    return names, doc


def test_live_tx_trace_covers_pipeline(net):
    """One traced tx -> one retrievable trace spanning admission,
    endorsement, ordering, device batch-verify, MVCC and commit
    notification, with the block trace stitched in by link."""
    assert tracing.tracer.enabled     # node boot configured the tracer
    gw = _client(net)
    try:
        code, _ = gw.submit_transaction("assets", "create",
                                        [b"traced1", b"alice"],
                                        commit_timeout_s=60.0)
    finally:
        gw.close()
    assert code == int(ValidationCode.VALID)

    # the client.tx root is the newest request-family trace; it
    # finalizes only once the server-side RPC fragments end, which can
    # trail the client return by a beat — poll for it
    tid, deadline = None, time.time() + 10
    while tid is None and time.time() < deadline:
        recent = tracing.tracer.recorder.list()["recent"]
        tid = next((r["trace_id"] for r in recent
                    if r["root"] == "client.tx"), None)
        if tid is None:
            time.sleep(0.05)
    assert tid is not None, recent
    names, doc = _trace_names(tid)
    for required in ("client.tx", "gateway.queue_wait", "gateway.order",
                     "endorser.validate", "endorser.simulate",
                     "endorser.sign", "orderer.broadcast",
                     "committer.store_block", "bccsp.batch_verify",
                     "ledger.mvcc", "gateway.commit_wait"):
        assert required in names, f"{required} missing: {sorted(names)}"
    assert doc["otherData"]["n_traces_merged"] >= 2   # block trace linked
    # device verify span carries batch size + device wall time
    bv = next(e for e in doc["traceEvents"]
              if e.get("ph") == "X" and e["name"] == "bccsp.batch_verify")
    assert bv["args"]["batch_size"] >= 1
    assert bv["args"]["block_until_ready_s"] >= 0


def test_block_intake_trace_covers_deliver(net):
    """A delivered block roots `peer.block_intake` where its frame was
    received; the signature check, the admission, the gossip forward and
    the committer fall under it; and it links the orderer's trace of
    the same block, whose context rode beside the block."""
    gw = _client(net)
    try:
        code, number = gw.submit_transaction(
            "assets", "create", [b"intake1", b"alice"],
            commit_timeout_s=60.0)
    finally:
        gw.close()
    assert code == int(ValidationCode.VALID)
    rec, deadline = None, time.time() + 10
    while rec is None and time.time() < deadline:
        for r in tracing.tracer.recorder.list()["recent"]:
            if r["root"] != "peer.block_intake":
                continue
            cand = tracing.tracer.recorder.get(r["trace_id"])
            root = next(s for s in cand["spans"] if s["parent_id"] is None)
            if root["attributes"]["block"] == number:
                rec = cand
                break
        else:
            time.sleep(0.05)
    assert rec is not None
    by_name = {s["name"]: s for s in rec["spans"]}
    assert root["name"] == "peer.block_intake"
    assert root["attributes"]["txs"] >= 1 and root["attributes"]["bytes"] > 0
    for child in ("deliver.block_sig", "deliver.admit", "gossip.forward",
                  "committer.store_block"):
        assert by_name[child]["parent_id"] == root["span_id"], child
        assert by_name[child]["start"] >= root["start"]
    # (a device provider's `bccsp.batch_verify` falls under
    # `deliver.block_sig` as its ambient child; the software provider's
    # plain call opens no span)
    # the frame was received before it was parsed, and the parse is there
    parse = by_name["wire.parse_block"]
    assert root["start"] == parse["start"]
    # the orderer's trace of the block, linked; the followers' writes
    # join it a beat after the leader's
    (linked,) = root["attributes"]["links"]
    deadline = time.time() + 10
    while time.time() < deadline:
        orderer = tracing.tracer.recorder.get(linked)
        names = [s["name"] for s in orderer["spans"]] if orderer else []
        if names.count("orderer.write") == 3:     # leader + two followers
            break
        time.sleep(0.05)
    assert orderer["root_name"] == "orderer.block"
    for required in ("orderer.block", "orderer.batch_fill",
                     "orderer.cut_propose", "orderer.consensus"):
        assert required in names, (required, names)
    assert names.count("orderer.write") == 3
    # and back up: the orderer's block names the request it carried
    block_root = next(s for s in orderer["spans"]
                      if s["name"] == "orderer.block")
    assert len(block_root["attributes"]["back_links"]) >= 1


def test_live_trace_over_ops_http(net):
    ops = net["peers"][0].ops
    assert ops is not None
    host, port = ops._httpd.server_address[:2]

    def get(path):
        with urllib.request.urlopen(
                f"http://{host}:{port}{path}", timeout=5) as r:
            return json.loads(r.read())

    listing = get("/traces")
    assert listing["recent"], "flight recorder empty over HTTP"
    tid = listing["recent"][0]["trace_id"]
    doc = get(f"/traces/{tid}")
    assert doc["otherData"]["trace_id"] == tid
    assert any(e["ph"] == "X" for e in doc["traceEvents"])

    stats = get("/spans/stats")
    assert stats["enabled"] is True
    assert 0.0 <= stats["sample_rate"] <= 1.0
    for stage in ("gateway.queue_wait", "bccsp.batch_verify"):
        assert stage in stats["spans"], sorted(stats["spans"])
        assert stats["spans"][stage]["count"] >= 1


def test_live_concurrent_traces_stay_distinct(net):
    """Thread safety: parallel traced submits each finalize their own
    trace with their own txid — no span leaks across traces."""
    tids, errors, lock = {}, [], threading.Lock()

    def run(tag):
        gw = _client(net)
        try:
            with tracing.tracer.start_span("test.tx",
                                           attributes={"tag": tag}) as span:
                code, _ = gw.submit_transaction(
                    "assets", "create", [f"conc-{tag}".encode(), b"x"],
                    commit_timeout_s=60.0)
            with lock:
                tids[tag] = span.context.trace_id
            if code != int(ValidationCode.VALID):
                raise AssertionError(f"{tag}: code {code}")
        except Exception as exc:
            with lock:
                errors.append((tag, exc))
        finally:
            gw.close()

    threads = [threading.Thread(target=run, args=(f"w{i}",))
               for i in range(4)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=120)
    assert not errors, errors
    assert len(set(tids.values())) == 4
    for tag, tid in tids.items():
        names, doc = _trace_names(tid)
        assert "gateway.commit_wait" in names, (tag, sorted(names))
        tags = {e["args"]["tag"] for e in doc["traceEvents"]
                if e.get("ph") == "X" and "tag" in e.get("args", {})}
        assert tags == {tag}                   # nothing bled across


def test_live_sampling_zero_drops_new_traces(net):
    """With sample_rate 0 the pipeline still works but the recorder
    gains no new traces: the unsampled decision propagates end to end."""
    def recorded_ids():
        return {r["trace_id"]
                for r in tracing.tracer.recorder.list()["recent"]}

    time.sleep(0.5)           # let prior tests' fragments finalize
    before = recorded_ids()
    tracing.tracer.sample_rate = 0.0
    try:
        gw = _client(net)
        try:
            code, _ = gw.submit_transaction("assets", "create",
                                            [b"unsampled1", b"y"],
                                            commit_timeout_s=60.0)
        finally:
            gw.close()
        assert code == int(ValidationCode.VALID)
        time.sleep(0.5)       # let any stray fragments finalize
        assert recorded_ids() <= before, "unsampled tx left a trace"
    finally:
        tracing.tracer.sample_rate = 1.0


# -- spans recorded without the client's help --------------------------------

def _wait_trace(root_name, known, deadline_s=10.0):
    """The newest finished trace rooted at `root_name` not in `known`."""
    deadline = time.time() + deadline_s
    while time.time() < deadline:
        for r in tracing.tracer.recorder.list()["recent"]:
            if r["root"] == root_name and r["trace_id"] not in known:
                return tracing.tracer.recorder.get(r["trace_id"])
        time.sleep(0.05)
    return None


def _ids():
    return {r["trace_id"] for r in tracing.tracer.recorder.list()["recent"]}


def test_gateway_verb_without_traceparent_roots_its_trace(net):
    """A plain client (no span of its own, so no `tp` in its frames):
    the gateway roots the request's trace, and the layers under it
    record — the local endorser, one fan-out per target peer with its
    dial's handshake below it, the remote endorser through the
    fan-out's frame."""
    assert tracing.tracer.enabled
    time.sleep(0.3)
    known = _ids()
    gw = _client(net)
    try:
        assert tracing.tracer.current_context() is None
        gw.endorse("assets", "create", [b"rooted1", b"bob"])
    finally:
        gw.close()
    rec = _wait_trace("rpc.gateway.endorse", known)
    assert rec is not None, tracing.tracer.recorder.list()["recent"][:5]
    deadline = time.time() + 5
    while time.time() < deadline and not any(
            s["name"] == "rpc.endorse" for s in rec["spans"]):
        time.sleep(0.05)              # the remote fragment merges late
    spans = rec["spans"]
    by_id = {s["span_id"]: s for s in spans}
    names = [s["name"] for s in spans]
    root = next(s for s in spans if s["name"] == "rpc.gateway.endorse")
    assert root["parent_id"] is None
    for required in ("endorser.validate", "endorser.simulate",
                     "endorser.sign", "gateway.fanout", "comm.handshake",
                     "rpc.endorse"):
        assert required in names, (required, sorted(set(names)))
    fanout = next(s for s in spans if s["name"] == "gateway.fanout")
    assert fanout["parent_id"] == root["span_id"]
    shake = next(s for s in spans if s["name"] == "comm.handshake"
                 and s["parent_id"] == fanout["span_id"])
    assert shake["attributes"]["role"] == "initiator"
    # the remote peer's endorser, continued through the fan-out's frame
    remote = next(s for s in spans if s["name"] == "rpc.endorse")
    assert by_id[remote["parent_id"]]["name"] == "gateway.fanout"
    # the other end of that dial had no context: it roots no trace
    assert _wait_trace("comm.handshake", known, deadline_s=0.5) is None
    # and the layer's metric has something to read
    stats = tracing.tracer.span_stats()
    assert stats["comm.handshake"]["count"] >= 1
    assert stats["gateway.fanout"]["count"] >= 1


def test_tracer_off_records_nothing_and_stays_a_noop(net):
    time.sleep(0.5)
    before, stats = _ids(), tracing.tracer.span_stats()
    tracing.tracer.enabled = False
    try:
        # every site gets the shared no-op after one attribute load
        assert tracing.tracer.start_span("rpc.gateway.endorse") \
            is tracing.NOOP_SPAN
        assert tracing.tracer.context_from("00-" + "1" * 32 + "-"
                                           + "2" * 16 + "-01") is None
        gw = _client(net)
        try:
            gw.endorse("assets", "create", [b"dark1", b"eve"])
        finally:
            gw.close()
        time.sleep(0.5)
        assert _ids() == before
        assert tracing.tracer.span_stats() == stats
    finally:
        tracing.tracer.enabled = True


def test_root_trace_is_only_for_verbs_that_ask():
    """comm/rpc.py: a method served with root_trace roots a trace when
    its frame brought none; any other stays untraced."""
    from fabric_tpu.comm.rpc import RpcServer, connect
    from fabric_tpu.msp import CachedMSP
    from fabric_tpu.msp.ca import DevOrg
    org = DevOrg("RootOrg")
    msps = {"RootOrg": CachedMSP(org.msp())}
    server = RpcServer("127.0.0.1", 0, org.new_identity("srv"), msps)
    server.serve("front.door", lambda body, peer: {"ok": 1},
                 root_trace=True)
    server.serve("inner", lambda body, peer: {"ok": 1})
    server.start()
    was = tracing.tracer.enabled
    tracing.tracer.enabled = True
    try:
        known = _ids()
        conn = connect(server.addr, org.new_identity("cli"), msps)
        try:
            conn.call("inner", {})
            conn.call("front.door", {})
        finally:
            conn.close()
        rec = _wait_trace("rpc.front.door", known)
        assert rec is not None
        assert [s["name"] for s in rec["spans"]] == ["rpc.front.door"]
        time.sleep(0.2)
        roots = {r["root"] for r in tracing.tracer.recorder.list()["recent"]
                 if r["trace_id"] not in known}
        assert "rpc.inner" not in roots
    finally:
        tracing.tracer.enabled = was
        server.stop()
