"""The second after the orderer's 200: the instruments on the path of a
served block — the cutter's reasons and the orderer's counters, the
block traces rooted at the cut and at the peer's intake, the gateway's
account of a request's wait by stage, the notifier's walk — and their
absence with the tracer off.

Unit cases drive `RaftChain`s over test_raft's deterministic network;
live cases run the in-process topology of test_gateway (3 raft orderers,
Org1/Org2 peers, SW provider) with a batch the timer always cuts.
"""

import json
import threading
import time
import urllib.request

import pytest

from fabric_tpu.config import BatchConfig
from fabric_tpu.endorser.proposal import assemble_transaction
from fabric_tpu.gateway.notifier import CommitNotifier
from fabric_tpu.node.orderer import OrdererNode, load_signing_identity
from fabric_tpu.node.peer import PeerNode
from fabric_tpu.node.provision import provision_network
from fabric_tpu.ops_plane import registry, tracing
from fabric_tpu.orderer import blockcutter
from fabric_tpu.protocol.txflags import ValidationCode

from test_raft import chain_cluster, ord_env

V = int(ValidationCode.VALID)


@pytest.fixture(scope="module", autouse=True)
def provider():
    from fabric_tpu.bccsp.factory import FactoryOpts, init_factories
    return init_factories(FactoryOpts(default="SW"))


@pytest.fixture
def tracer_on():
    t = tracing.tracer
    was = t.enabled, t.sample_rate
    t.configure({"enabled": True, "sample_rate": 1.0})
    yield t
    t.enabled, t.sample_rate = was


@pytest.fixture
def tracer_off():
    t = tracing.tracer
    was = t.enabled
    t.enabled = False
    yield t
    t.enabled = was


def counter(name, **labels):
    m = registry.get(name)
    return m.value(**labels) if m is not None else 0.0


def observed(name, **labels):
    """(sum, count) of a histogram's series."""
    m = registry.get(name)
    key = tuple(sorted(labels.items()))
    with m._lock:
        return m._sum.get(key, 0.0), m._n.get(key, 0)


def traces_rooted(tracer, root_name, since=()):
    return [tracer.recorder.get(r["trace_id"])
            for r in tracer.recorder.list()["recent"]
            if r["root"] == root_name and r["trace_id"] not in since]


# ---------------------------------------------------------------------------
# (b) every cut roots a block trace and is counted under its reason
# ---------------------------------------------------------------------------

def _cut(chain, org, reason):
    """Make the leader's chain cut once, for `reason`."""
    cfg = chain.cutter.config
    if reason == "timer":
        chain.order(ord_env(org, 0))
        assert chain.tick_batch(time.monotonic() + cfg.batch_timeout_s + 1)
    elif reason == "count":
        for i in range(cfg.max_message_count):
            chain.order(ord_env(org, i))
    elif reason == "bytes":
        size = len(ord_env(org, 0).serialize())
        chain.cutter._static_config = BatchConfigOf(
            cfg, max_message_count=100, preferred_max_bytes=size + size // 2)
        chain.order(ord_env(org, 0))
        chain.order(ord_env(org, 1))         # would pass the preferred size
    elif reason == "oversize":
        chain.cutter._static_config = BatchConfigOf(
            cfg, preferred_max_bytes=16)
        chain.order(ord_env(org, 0))
    elif reason == "config":
        from fabric_tpu.protocol import build
        from fabric_tpu.protocol.types import TX_CONFIG
        chain.configure(build.signed_envelope(
            TX_CONFIG, "ch", {"config": {"x": b"y"}},
            org.new_identity("admin")))


def BatchConfigOf(cfg, **changes):
    from dataclasses import replace
    return replace(cfg, **changes)


@pytest.mark.parametrize("reason", ["timer", "count", "bytes", "oversize",
                                    "config"])
def test_a_cut_roots_a_block_trace_and_is_counted(tracer_on, reason):
    net, org = chain_cluster(3, max_message_count=3)
    chain = net.chains[net.elect().id]
    before = {r: counter("blockcutter_cut_total", channel="ch", reason=r)
              for r in ("timer", "count", "bytes", "oversize", "config")}
    fill_n = observed("blockcutter_block_fill_duration", channel="ch")[1]
    commit_n = observed("consensus_etcdraft_commit_duration",
                        channel="ch")[1]
    seen = {r["trace_id"] for r in tracer_on.recorder.list()["recent"]}
    _cut(chain, org, reason)
    net.pump()
    moved = {r: counter("blockcutter_cut_total", channel="ch", reason=r)
             - n for r, n in before.items()}
    assert moved == {**dict.fromkeys(before, 0.0), reason: 1.0}
    assert observed("blockcutter_block_fill_duration",
                    channel="ch")[1] == fill_n + 1
    assert observed("consensus_etcdraft_commit_duration",
                    channel="ch")[1] == commit_n + 1
    (rec,) = traces_rooted(tracer_on, "orderer.block", seen)
    spans = {}
    for s in rec["spans"]:
        spans.setdefault(s["name"], []).append(s)
    root = spans["orderer.block"][0]
    assert root["parent_id"] is None and root["status"] == "OK"
    assert root["attributes"]["reason"] == reason
    (fill,) = spans["orderer.batch_fill"]
    assert fill["attributes"]["reason"] == reason
    assert fill["attributes"]["txs"] == root["attributes"]["txs"] >= 1
    assert fill["attributes"]["bytes"] > 0
    assert fill["start"] == root["start"]
    for name in ("orderer.batch_fill", "orderer.cut_propose",
                 "orderer.consensus"):
        assert spans[name][0]["parent_id"] == root["span_id"], name
    # the leader's write is the root's child; each follower's is its
    # fragment of the same trace, under the context the entry carried
    writes = spans["orderer.write"]
    assert len(writes) == 3
    assert all(w["parent_id"] == root["span_id"] for w in writes)
    assert len({w["attributes"]["block"] for w in writes}) == 1
    # fill, propose, consensus, the leader's write: in that order
    order = [fill, spans["orderer.cut_propose"][0],
             spans["orderer.consensus"][0], min(writes,
                                                key=lambda w: w["start"])]
    for a, b in zip(order, order[1:]):
        assert a["start"] + a["duration_s"] <= b["start"] + 1e-6
    # what a deliver stream would send beside the block: this trace
    number = writes[0]["attributes"]["block"]
    for c in net.chains.values():
        ctx = tracing.parse_traceparent(c.block_traceparent(number))
        assert ctx.trace_id == rec["trace_id"]


def test_an_envelope_that_came_traced_is_back_linked(tracer_on):
    net, org = chain_cluster(1, max_message_count=2)
    chain = net.chains[net.elect().id]
    seen = {r["trace_id"] for r in tracer_on.recorder.list()["recent"]}
    requests = []
    for i in range(2):
        with tracer_on.start_span("test.request", parent=None) as req:
            requests.append(req.context.trace_id)
            chain.order(ord_env(org, i))
    net.pump()
    (rec,) = traces_rooted(tracer_on, "orderer.block", seen)
    root = next(s for s in rec["spans"] if s["name"] == "orderer.block")
    assert root["attributes"]["back_links"] == requests
    assert "links" not in root["attributes"]
    # a request's export does not follow the block up to its siblings;
    # the block's own export shows its requests
    doc = tracer_on.export_chrome(rec["trace_id"])
    assert doc["otherData"]["n_traces_merged"] == 3
    # bounded: the cutter keeps the first MAX_LINKS
    cutter = blockcutter.BlockCutter(blockcutter.BatchConfig(
        max_message_count=blockcutter.MAX_LINKS + 8))
    for i in range(blockcutter.MAX_LINKS + 8):
        with tracer_on.start_span("test.request", parent=None):
            (batches, _) = cutter.ordered(ord_env(org, i))
    assert len(batches[0].links) == blockcutter.MAX_LINKS


# ---------------------------------------------------------------------------
# (d) a leader change, and a proposal lost to it
# ---------------------------------------------------------------------------

def test_a_leader_change_and_a_proposal_lost_to_it_are_counted(tracer_on):
    net, org = chain_cluster(3, max_message_count=1)
    changes = counter("consensus_etcdraft_leader_changes", channel="ch")
    failures = counter("consensus_etcdraft_proposal_failures", channel="ch")
    old = net.elect()
    net.pump()
    # every node learnt of the first leader
    assert counter("consensus_etcdraft_leader_changes",
                   channel="ch") == changes + 3
    assert counter("consensus_etcdraft_is_leader", channel="ch") == 1.0
    old_chain = net.chains[old.id]
    old_chain.order(ord_env(org, 0))
    net.pump()
    assert old_chain.writer.ledger.height == 1
    # cut off, the old leader still cuts and proposes: nobody hears it
    net.dropped.add(old.id)
    seen = {r["trace_id"] for r in tracer_on.recorder.list()["recent"]}
    old_chain.order(ord_env(org, 1))
    lost_index = old.last_index()
    assert lost_index in old_chain._open
    new = net.elect()
    new_chain = net.chains[new.id]
    new_chain.order(ord_env(org, 2))
    net.pump()
    # the two that elected it learnt of the new leader
    assert counter("consensus_etcdraft_leader_changes",
                   channel="ch") == changes + 5
    assert counter("consensus_etcdraft_proposal_failures",
                   channel="ch") == failures
    # back in touch, the old leader's entry is overwritten by the new
    # term's: its proposal is lost, counted once, its block trace ended
    # in error — and it holds the block the others hold
    net.dropped.discard(old.id)
    net.tick_all(3)
    assert old.role == "follower" and not old_chain._open
    assert counter("consensus_etcdraft_leader_changes",
                   channel="ch") == changes + 6
    assert counter("consensus_etcdraft_proposal_failures",
                   channel="ch") == failures + 1
    heights = {c.writer.ledger.height for c in net.chains.values()}
    assert heights == {2}
    lost = [rec for rec in traces_rooted(tracer_on, "orderer.block", seen)
            if any(s["name"] == "orderer.block" and s["status"] == "ERROR"
                   for s in rec["spans"])]
    assert len(lost) == 1
    root = next(s for s in lost[0]["spans"] if s["name"] == "orderer.block")
    assert root["attributes"]["lost_to_term"] == new.term
    # a deposed leader whose batch timer fires discards the batch: the
    # same counter, where there used to be a silent `return False`
    follower_chain = old_chain
    follower_chain.cutter._static_config = BatchConfigOf(
        follower_chain.cutter.config, max_message_count=5)
    follower_chain.cutter.ordered(ord_env(org, 3))
    follower_chain._batch_deadline = 0.0
    assert follower_chain.tick_batch(time.monotonic()) is False
    assert counter("consensus_etcdraft_proposal_failures",
                   channel="ch") == failures + 2


def test_the_wal_and_the_appends_are_measured(tmp_path):
    net, org = chain_cluster(3, tmp=str(tmp_path), max_message_count=1)
    leader = net.elect()
    net.pump()
    persist_n = observed("consensus_etcdraft_data_persist_duration",
                         channel="ch")[1]
    write_n = observed("orderer_block_write_seconds", channel="ch")[1]
    sent = {to: counter("consensus_etcdraft_append_bytes_total",
                        channel="ch", to=str(to))
            for to in net.chains if to != leader.id}
    env = ord_env(org, 0)
    net.chains[leader.id].order(env)
    net.pump()
    # the leader's append and its commit mark, each follower's append
    # and commit mark: at least one drain each that wrote something
    assert observed("consensus_etcdraft_data_persist_duration",
                    channel="ch")[1] >= persist_n + 3
    assert observed("orderer_block_write_seconds",
                    channel="ch")[1] == write_n + 3
    for to, was in sent.items():
        moved = counter("consensus_etcdraft_append_bytes_total",
                        channel="ch", to=str(to)) - was
        assert moved >= len(env.serialize())
    assert counter("consensus_etcdraft_committed_block_number",
                   channel="ch") == 0
    # a drain that wrote nothing observes nothing
    quiet = observed("consensus_etcdraft_data_persist_duration",
                     channel="ch")[1]
    for chain in net.chains.values():
        assert chain.process_ready().persist_s is None
    assert observed("consensus_etcdraft_data_persist_duration",
                    channel="ch")[1] == quiet


# ---------------------------------------------------------------------------
# (e) the notifier's walk
# ---------------------------------------------------------------------------

class CountingBlock:
    """A block whose every read is counted."""

    def __init__(self, n):
        self.n = n
        self.reads = 0
        self.intake = (0.0, 0.0)

    def __getattr__(self, name):
        self.reads += 1
        raise AssertionError(f"the block's {name} was read")


class CountingFlags:
    def __init__(self):
        self.reads = 0

    def codes(self):
        self.reads += 1
        raise AssertionError("the flags were read")


def test_a_block_nobody_waits_for_is_not_walked(monkeypatch):
    from fabric_tpu.protocol import wire
    walked = []
    monkeypatch.setattr(wire, "lane_txids",
                        lambda block: walked.append(block) or [])
    notifier = CommitNotifier("ch")
    block, flags = CountingBlock(10_000), CountingFlags()
    notifier.on_block(block, flags)
    assert (walked, block.reads, flags.reads) == ([], 0, 0)
    assert not notifier._history
    # one transaction in flight: now the block is read
    notifier.watch("tx-in-flight")
    with pytest.raises(AssertionError):
        notifier.on_block(block, flags)
    assert walked == [block]


# ---------------------------------------------------------------------------
# (f) tracer off: the shared no-op, nowhere a span object
# ---------------------------------------------------------------------------

@pytest.fixture
def no_span_made(monkeypatch):
    made = []
    real = tracing.Span.__init__

    def counting(self, tracer, name, *a, **kw):
        made.append(name)
        real(self, tracer, name, *a, **kw)
    monkeypatch.setattr(tracing.Span, "__init__", counting)
    return made


def test_tracer_off_the_cut_and_the_apply_make_no_span(tracer_off,
                                                        no_span_made):
    net, org = chain_cluster(3, max_message_count=2)
    chain = net.chains[net.elect().id]
    for reason in ("timer", "count", "config"):
        _cut(chain, org, reason)
        net.pump()
    assert chain.writer.ledger.height >= 3
    assert no_span_made == []
    # nothing for a deliver frame to carry, nothing in the entries
    assert all(c.block_traceparent(0) is None for c in net.chains.values())
    serde = chain._serde
    assert all("tp" not in serde.decode(e.data)
               for e in chain.node.log if e.data)


# ---------------------------------------------------------------------------
# live: the stage account, the cluster picture, tracer off end to end
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def net(tmp_path_factory):
    """test_gateway's topology with a batch only the timer cuts."""
    base = str(tmp_path_factory.mktemp("stagenet"))
    paths = provision_network(
        base, n_orderers=3, peer_orgs=["Org1", "Org2"], peers_per_org=1,
        batch=BatchConfig(max_message_count=500, timeout_s=0.25))
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
                cfg["ops_port"] = 0
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


def _client(net, org="Org1"):
    from fabric_tpu.gateway import GatewayClient
    with open(net["paths"]["clients"][org]) as f:
        cc = json.load(f)
    signer = load_signing_identity(cc["mspid"], cc["cert_pem"].encode(),
                                   cc["key_pem"].encode())
    peer = net["peers"][0]
    return GatewayClient(peer.rpc.addr, signer, peer.msps, channel_id="ch")


def _wave(net, tag, n):
    """`n` transactions at once through the one gateway: -> for each,
    (txid, code, block, the client's clock when the answer came)."""
    out, errors, lock = [], [], threading.Lock()

    def run(i):
        gw = _client(net)
        try:
            sp, responses = gw.endorse(
                "assets", "create", [f"{tag}-{i}".encode(), b"alice"])
            env = assemble_transaction(sp, responses, gw.signer)
            txid = env.header().channel_header.txid
            gw.submit_envelope(env, timeout_s=30.0)
            code, block = gw.commit_status(txid, timeout_s=60.0)
            with lock:
                out.append((txid, code, block, time.perf_counter()))
        except Exception as exc:
            with lock:
                errors.append(exc)
        finally:
            gw.close()
    threads = [threading.Thread(target=run, args=(i,)) for i in range(n)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=120)
    assert not errors, errors
    return out


class StageBook:
    """Every `_account_wait` of a gateway: for which txid, what it
    booked, the stage spans it recorded, and the clock on both sides."""

    def __init__(self, svc, monkeypatch):
        self.calls = {}   # txid -> [(stages, spans, t_before, t_after)]
        real, book, lock = svc._account_wait, self, threading.Lock()
        real_record = tracing.tracer.record_span
        now = {}

        class Recording:
            def __init__(self, inner):
                self.inner = inner

            def observe(self, value, **labels):
                now.setdefault("stages", {})[labels["stage"]] = value
                self.inner.observe(value, **labels)

        def record_span(name, start, end, *a, **kw):
            if name.startswith("gateway."):
                now.setdefault("spans", {})[name] = (start, end)
            return real_record(name, start, end, *a, **kw)

        def account(channel_id, txid, got, t_arrival, span):
            with lock:           # one booking at a time: `now` is shared
                now.clear()
                t_before = time.perf_counter()
                real(channel_id, txid, got, t_arrival, span)
                book.calls.setdefault(txid, []).append(
                    (now.get("stages", {}), now.get("spans", {}),
                     t_before, time.perf_counter()))
        monkeypatch.setattr(svc, "_m_stage", Recording(svc._m_stage))
        monkeypatch.setattr(tracing.tracer, "record_span", record_span)
        monkeypatch.setattr(svc, "_account_wait", account)


def test_the_four_stages_sum_to_reply_minus_200(net, monkeypatch):
    svc = net["peers"][0].gateway
    book = StageBook(svc, monkeypatch)
    timer_cuts = counter("blockcutter_cut_total", channel="ch",
                         reason="timer")
    count_cuts = counter("blockcutter_cut_total", channel="ch",
                         reason="count")
    counts = {stage: observed("gateway_commit_stage_seconds", channel="ch",
                              stage=stage)[1]
              for stage in ("ordered", "intake", "commit", "answer")}
    answered = []
    for wave in range(3):
        answered += _wave(net, f"w{wave}", 8)
    assert len(answered) == 24 and all(a[1] == V for a in answered)
    assert len({a[2] for a in answered}) >= 3         # >= 3 blocks,
    assert counter("blockcutter_cut_total", channel="ch",
                   reason="timer") >= timer_cuts + 3   # the timer's
    assert counter("blockcutter_cut_total", channel="ch",
                   reason="count") == count_cuts
    for txid, _code, _block, t_client in answered:
        ((stages, spans, t_before, t_after),) = book.calls[txid]
        assert set(stages) == {"ordered", "intake", "commit", "answer"}
        assert all(v >= 0.0 for v in stages.values())
        t_200 = svc._recent[txid][2]
        # the reply is stamped inside the booking, between the two
        # reads of the clock around it ...
        total = sum(stages.values())
        assert t_before - t_200 - 1e-3 <= total <= t_after - t_200 + 1e-3
        assert t_after <= t_client
        # ... and is where the last of the four stage spans ends, which
        # begin at the 200 and leave no gap: the identity, to the
        # millisecond (to the float, in fact)
        chain = [spans[name] for name in (
            "gateway.ordered_wait", "gateway.block_intake",
            "gateway.block_commit", "gateway.answer")]
        assert chain[0][0] == t_200
        assert all(a[1] == b[0] for a, b in zip(chain, chain[1:]))
        reply = chain[-1][1]
        assert t_before <= reply <= t_after
        assert total == pytest.approx(reply - t_200, abs=1e-6)
        assert [e - s for s, e in chain] == pytest.approx(
            [stages[k] for k in ("ordered", "intake", "commit", "answer")])
        # most of the wait is the batch timer's
        assert stages["ordered"] > stages["answer"]
    for stage, n in counts.items():
        assert observed("gateway_commit_stage_seconds", channel="ch",
                        stage=stage)[1] == n + 24
    # a commit_status that arrives after the commit waited for none of
    # the block's life: it books its own wait to `answer`, and no more
    txid = answered[0][0]
    gw = _client(net)
    try:
        t0 = time.perf_counter()
        assert gw.commit_status(txid, timeout_s=10.0)[0] == V
        elapsed = time.perf_counter() - t0
    finally:
        gw.close()
    (_first, (late, late_spans, _t0, _t1)) = book.calls[txid]
    assert set(late) == {"answer"} and 0.0 <= late["answer"] <= elapsed
    assert set(late_spans) == {"gateway.answer"}
    for stage, n in counts.items():
        assert observed("gateway_commit_stage_seconds", channel="ch",
                        stage=stage)[1] == n + 24 + (stage == "answer")


def test_cluster_export_holds_request_orderer_block_and_peer_block(net):
    """`GET /traces/<request>?cluster=1`: the request with its four
    stage spans, the peer's block trace and the orderer's, in one."""
    assert tracing.tracer.enabled
    ((txid, code, _block, _t),) = _wave(net, "pic", 1)
    assert code == V
    host, port = net["peers"][0].ops._httpd.server_address[:2]
    want = {"gateway.commit_wait", "gateway.ordered_wait",
            "gateway.block_intake", "gateway.block_commit",
            "gateway.answer", "orderer.block", "orderer.batch_fill",
            "orderer.consensus", "orderer.write", "peer.block_intake",
            "deliver.block_sig", "gossip.forward", "committer.store_block"}
    names, doc, deadline = set(), None, time.time() + 10
    while not want <= names and time.time() < deadline:
        tid = next(
            (r["trace_id"] for r in tracing.tracer.recorder.list()["recent"]
             if any(s["name"] == "gateway.commit_wait"
                    and s["attributes"]["txid"] == txid
                    for s in tracing.tracer.recorder.get(
                        r["trace_id"])["spans"])), None)
        if tid is not None:
            with urllib.request.urlopen(
                    f"http://{host}:{port}/traces/{tid}?cluster=1",
                    timeout=5) as r:
                doc = json.loads(r.read())
            names = {e["name"] for e in doc["traceEvents"]
                     if e.get("ph") == "X"}
        time.sleep(0.1)
    assert want <= names, sorted(want - names)
    assert doc["otherData"]["cluster"] is True
    assert doc["otherData"]["truncated"] is False
    spans = [e for e in doc["traceEvents"] if e.get("ph") == "X"]
    wait = next(e for e in spans if e["name"] == "gateway.commit_wait")
    stages = [e for e in spans if e["name"] in (
        "gateway.ordered_wait", "gateway.block_intake",
        "gateway.block_commit", "gateway.answer")]
    assert len(stages) == 4
    assert all(e["args"]["parent_id"] == wait["args"]["span_id"]
               for e in stages)
    fill = next(e for e in spans if e["name"] == "orderer.batch_fill")
    assert fill["args"]["reason"] == "timer"
    # three trace families in the one picture
    assert doc["otherData"]["n_traces_merged"] >= 3


def test_tracer_off_a_served_block_makes_no_span(net, tracer_off,
                                                 no_span_made):
    """The deliver loop, the gossip forward, the committer, the notifier
    and the stage account, end to end with the tracer off: the counters
    move, no span object is made, no context rides the wire."""
    orderers = net["orderers"]
    n = observed("gateway_commit_stage_seconds", channel="ch",
                 stage="ordered")[1]
    heights = [o.support.ledger.height for o in orderers]
    answered = _wave(net, "off", 4)
    assert all(a[1] == V for a in answered)
    assert no_span_made == []
    assert observed("gateway_commit_stage_seconds", channel="ch",
                    stage="ordered")[1] == n + 4
    for o, h in zip(orderers, heights):
        chain = o.support.chain
        for number in range(h, o.support.ledger.height):
            assert chain.block_traceparent(number) is None
        written = o.support.ledger.height - h
        assert written >= 1
        entries = [e for e in chain.node.log if e.data][-written:]
        assert all("tp" not in chain._serde.decode(e.data) for e in entries)
