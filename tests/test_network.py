"""Network plane: authenticated channels, RPC, multi-process raft cluster.

Reference behaviors covered (VERDICT.md missing #3, weak #4/#6):
  - mutually authenticated transport bound to MSP identities; peers
    outside the channel MSPs are rejected at handshake
    (internal/pkg/comm mTLS + gossip signed handshake),
  - Broadcast/Deliver as network services over that transport,
  - an nwo-style multi-PROCESS integration test: 3 orderer OS processes
    over sockets, e2e ordering, leader kill + continued service
    (integration/nwo/network.go:173, integration/raft/cft_test.go).
"""
import json
import os
import time

import pytest

from fabric_tpu.bccsp.factory import FactoryOpts, init_factories
from fabric_tpu.comm import HandshakeError, RpcError, RpcServer, connect
from fabric_tpu.msp import CachedMSP
from fabric_tpu.msp.ca import DevOrg
from fabric_tpu.node.provision import provision_orderers
from fabric_tpu.protocol import Envelope, KVWrite, NsRwSet, TxRwSet, build
from fabric_tpu.testing.procnet import (
    load_client,
    node_status,
    spawn_node,
    stop_nodes,
    wait_orderer_leader,
    wait_peer_heights,
)


@pytest.fixture(scope="module", autouse=True)
def provider():
    return init_factories(FactoryOpts(default="SW"))


# ---------------------------------------------------------------------------
# secure channel / rpc unit tests (in-process)
# ---------------------------------------------------------------------------

def test_secure_channel_auth_and_roundtrip():
    org = DevOrg("NetOrg")
    rogue = DevOrg("RogueOrg")
    msps = {"NetOrg": CachedMSP(org.msp())}

    got = []
    server = RpcServer("127.0.0.1", 0, org.new_identity("srv"), msps)
    server.serve("echo", lambda body, peer: {
        "echo": body["x"], "peer_msp": peer.mspid})
    server.start()
    try:
        conn = connect(server.addr, org.new_identity("cli"), msps)
        out = conn.call("echo", {"x": b"hello"})
        assert out["echo"] == b"hello" and out["peer_msp"] == "NetOrg"
        conn.close()

        # a peer from an org outside the channel MSPs is rejected
        with pytest.raises((HandshakeError, ConnectionError, OSError, RpcError)):
            c = connect(server.addr, rogue.new_identity("evil"),
                        {"RogueOrg": CachedMSP(rogue.msp())})
            c.call("echo", {"x": b"sneak"}, timeout=3.0)
    finally:
        server.stop()


def test_rpc_stream():
    org = DevOrg("NetOrg2")
    msps = {"NetOrg2": CachedMSP(org.msp())}
    server = RpcServer("127.0.0.1", 0, org.new_identity("srv"), msps)

    def counter(body, peer):
        for i in range(body["n"]):
            yield {"i": i}
    server.serve_stream("count", counter)
    server.start()
    try:
        conn = connect(server.addr, org.new_identity("cli"), msps)
        got = [b["i"] for b in conn.call_stream("count", {"n": 4})]
        assert got == [0, 1, 2, 3]
        conn.close()
    finally:
        server.stop()


# ---------------------------------------------------------------------------
# multi-process cluster (nwo-style)
# ---------------------------------------------------------------------------

def _client_bits(base):
    return load_client(os.path.join(base, "client.json"))


def _env(i, signer, channel="ch"):
    rw = TxRwSet((NsRwSet("cc", writes=(KVWrite(f"k{i}", b"v"),)),))
    return build.endorser_tx(channel, "cc", "1.0", rw, signer, [signer])


def _wait_leader(cc, signer, msps, deadline=30.0):
    """(cluster entry, status) of the raft leader."""
    addr = wait_orderer_leader(
        [("127.0.0.1", n["port"]) for n in cc["cluster"]], signer, msps,
        deadline_s=deadline)
    node = next(n for n in cc["cluster"] if n["port"] == addr[1])
    return node, node_status(addr, signer, msps)


@pytest.mark.slow
def test_three_process_cluster_survives_leader_kill(tmp_path):
    base = str(tmp_path)
    paths = provision_orderers(base, 3)
    procs = {}
    try:
        for p in paths:
            with open(p) as f:
                rid = json.load(f)["raft_id"]
            procs[rid] = spawn_node("fabric_tpu.node.orderer", p)

        cc, signer, msps = _client_bits(base)
        leader_node, st = _wait_leader(cc, signer, msps)
        leader_conn = connect(("127.0.0.1", leader_node["port"]), signer, msps)

        # order 4 envelopes -> 2 blocks (max_message_count=2)
        for i in range(4):
            out = leader_conn.call(
                "broadcast", {"envelope": _env(i, signer).serialize()},
                timeout=10.0)
            assert out["status"] == 200, out

        # deliver from a FOLLOWER: replication happened over sockets
        followers = [n for n in cc["cluster"]
                     if n["port"] != leader_node["port"]]
        fconn = connect(("127.0.0.1", followers[0]["port"]), signer, msps)
        blocks = []
        seek_payload = b"seek:ch:0:1"
        sd = {"data": seek_payload, "identity": signer.serialize(),
              "signature": signer.sign(seek_payload)}
        for item in fconn.call_stream("deliver", {
                "channel": "ch", "start": 0, "stop": 1, "timeout_s": 20,
                "signed_data": sd}):
            blocks.append(Envelope.deserialize(
                __import__("fabric_tpu.protocol.types",
                           fromlist=["Block"]).Block.deserialize(
                    item["block"]).data[0]))
        assert len(blocks) == 2
        fconn.close()

        # kill the leader; the remaining two must elect and keep ordering
        victim = None
        for rid, proc in procs.items():
            if cc["cluster"][rid - 1]["port"] == leader_node["port"]:
                victim = rid
        procs[victim].kill()
        procs[victim].wait(timeout=10)
        leader_conn.close()

        new_leader, st = _wait_leader(
            cc_without(cc, victim), signer, msps, deadline=45.0)
        conn2 = connect(("127.0.0.1", new_leader["port"]), signer, msps)
        for i in range(4, 8):
            out = conn2.call(
                "broadcast", {"envelope": _env(i, signer).serialize()},
                timeout=10.0)
            assert out["status"] == 200, out
        # ordering is async past broadcast: poll until the new blocks land
        deadline = time.time() + 20
        while time.time() < deadline:
            st = conn2.call("status", {}, timeout=5.0)
            if st["height"] >= 4:
                break
            time.sleep(0.3)
        assert st["height"] >= 4, st   # 4 blocks total across the kill
        conn2.close()
    finally:
        stop_nodes(procs.values())


def cc_without(cc, victim_rid):
    out = dict(cc)
    out["cluster"] = [n for n in cc["cluster"]
                      if n["raft_id"] != victim_rid]
    return out


# ---------------------------------------------------------------------------
# Full topology: client -> endorse (2 orgs) -> broadcast -> raft (3 orderers)
# -> deliver -> validate -> commit, surviving an orderer leader kill, with
# private data distributed only to collection members.
# (reference: cmd/peer/main.go, internal/peer/node/start.go,
#  integration/nwo full-network tests)
# ---------------------------------------------------------------------------

def _remote_endorse(addr, signer, msps, sp):
    from fabric_tpu.endorser.proposal import ProposalResponse
    from fabric_tpu.protocol.types import Endorsement
    conn = connect(tuple(addr), signer, msps, timeout=5.0)
    try:
        out = conn.call("endorse", {"proposal": sp.proposal_bytes,
                                    "signature": sp.signature}, timeout=20.0)
    finally:
        conn.close()
    e = (Endorsement(out["endorser"], out["endorsement_sig"])
         if out.get("endorser") else None)
    return ProposalResponse(out["status"], out["message"], out["payload"], e)


@pytest.mark.slow
def test_full_topology_endorse_order_commit_privdata(tmp_path):
    from fabric_tpu.endorser import assemble_transaction
    from fabric_tpu.endorser.proposal import signed_proposal
    from fabric_tpu.node.provision import provision_network

    net = provision_network(
        str(tmp_path), n_orderers=3, peer_orgs=["Org1", "Org2"],
        peers_per_org=2,
        chaincodes=[
            {"name": "assets", "version": "1.0", "contract": "asset_demo",
             "policy": "AND('Org1.member', 'Org2.member')"},
            {"name": "pvtcc", "version": "1.0", "contract": "asset_demo",
             "policy": "OR('Org1.member')"},
        ],
        collections=[{"ns": "pvtcc", "name": "secrets",
                      "members": ["Org1"], "btl": 0}])
    procs = []
    try:
        for p in net["orderers"]:
            procs.append(spawn_node("fabric_tpu.node.orderer", p))
        peer_addrs = {}
        for p in net["peers"]:
            with open(p) as f:
                pc = json.load(f)
            peer_addrs[f"{pc['mspid']}_{pc['port']}"] = (
                pc["host"], pc["port"])
            procs.append(spawn_node("fabric_tpu.node.peer", p))
        org1_peers = sorted(k for k in peer_addrs if k.startswith("Org1"))
        org2_peers = sorted(k for k in peer_addrs if k.startswith("Org2"))

        cc, signer, msps = load_client(net["clients"]["Org1"])
        orderers = [tuple(o) for o in cc["orderers"]]
        leader = wait_orderer_leader(orderers, signer, msps,
                                     deadline_s=90.0)

        def submit(sp, endorse_on):
            responses = [_remote_endorse(peer_addrs[k], signer, msps, sp)
                         for k in endorse_on]
            assert all(r.status == 200 for r in responses), responses
            envlp = assemble_transaction(sp, responses, signer)
            conn = connect(tuple(leader), signer, msps, timeout=5.0)
            try:
                out = conn.call("broadcast",
                                {"envelope": envlp.serialize()}, timeout=20.0)
            finally:
                conn.close()
            assert out["status"] == 200, out
            return envlp.header().channel_header.txid

        # wait for peers to come up (first endorse retries inside
        # _remote_endorse via the leader wait above; just poll status)
        wait_peer_heights(peer_addrs, signer, msps, 0, deadline_s=60.0)

        # -- public txs through the full pipeline --------------------------
        for i in range(4):
            sp = signed_proposal("ch", "assets", "create",
                                 [b"asset%d" % i, b"alice"], signer)
            submit(sp, endorse_on=[org1_peers[0], org2_peers[0]])

        # -- a private-data tx (collection members: Org1 only) -------------
        sp = signed_proposal("ch", "pvtcc", "put_private",
                             [b"secrets", b"sec1", b"classified"], signer)
        pvt_txid = submit(sp, endorse_on=[org1_peers[0]])

        sts = wait_peer_heights(peer_addrs, signer, msps, 1,
                                deadline_s=150.0)
        # every peer at the same height must hold identical commit hashes
        by_height = {}
        for name, st in sts.items():
            by_height.setdefault(st["height"], set()).add(st["commit_hash"])
        for h, hashes in by_height.items():
            assert len(hashes) == 1, f"divergent commit hash at {h}: {sts}"

        # -- kill the orderer leader; ordering must continue ---------------
        victim_idx = orderers.index(tuple(leader))
        procs[victim_idx].kill()
        procs[victim_idx].wait(timeout=10)
        remaining = [o for o in orderers if o != tuple(leader)]
        leader = wait_orderer_leader(remaining, signer, msps,
                                     deadline_s=60.0)
        pre = max(s["height"] for s in sts.values() if s)
        for i in range(4, 6):
            sp = signed_proposal("ch", "assets", "create",
                                 [b"asset%d" % i, b"alice"], signer)
            submit(sp, endorse_on=[org1_peers[0], org2_peers[0]])
        sts = wait_peer_heights(peer_addrs, signer, msps, pre + 1,
                                deadline_s=150.0)
        final_heights = {s["height"] for s in sts.values()}
        assert len(final_heights) >= 1
        hashes = {s["commit_hash"] for s in sts.values()
                  if s["height"] == max(final_heights)}
        assert len(hashes) == 1, f"post-failover divergence: {sts}"

        # -- privdata: members hold cleartext, non-members never do --------
        def fetch_pvt(from_peer, as_signer, as_msps):
            conn = connect(peer_addrs[from_peer], as_signer, as_msps,
                           timeout=5.0)
            try:
                return conn.call("privdata.fetch", {
                    "txid": pvt_txid, "namespace": "pvtcc",
                    "collection": "secrets"}, timeout=10.0)
            finally:
                conn.close()

        # Org1 client asking an Org1 peer: cleartext present (directly or
        # via the peer's reconcile loop) on BOTH org1 peers eventually
        deadline = time.time() + 120
        got = {}
        while time.time() < deadline:
            got = {k: fetch_pvt(k, signer, msps) for k in org1_peers}
            if all(g.get("found") for g in got.values()):
                break
            time.sleep(1.0)
        assert all(g.get("found") for g in got.values()), got
        assert all(b"classified" in g["values"] for g in got.values())

        # Org2 (non-member) asking an Org1 peer: DENIED
        cc2, signer2, msps2 = load_client(net["clients"]["Org2"])
        out = fetch_pvt(org1_peers[0], signer2, msps2)
        assert not out.get("found") and out.get("denied"), out
        # and the Org2 peers themselves never hold the cleartext
        for k in org2_peers:
            out = fetch_pvt(k, signer, msps)
            assert not out.get("found"), out
    finally:
        stop_nodes(procs)


class SickDevice:
    """A provider whose device fails every verify, as JaxTpuProvider
    reports it under `bccsp_degrade: false`."""

    def __init__(self, inner):
        self._inner = inner

    def batch_verify(self, items):
        from fabric_tpu.bccsp.provider import DeviceError
        raise DeviceError("injected: device resolve failed")

    def __getattr__(self, name):
        return getattr(self._inner, name)


def test_peer_fail_stops_on_device_error(tmp_path):
    """`bccsp_degrade: false`: a DeviceError on the commit path stops the
    peer (main exits non-zero) instead of being retried forever or
    recomputed on another provider."""
    from fabric_tpu.bccsp.provider import DeviceError
    from fabric_tpu.config import BatchConfig
    from fabric_tpu.node.orderer import OrdererNode
    from fabric_tpu.node.peer import PeerNode
    from fabric_tpu.node.provision import provision_network

    net = provision_network(
        str(tmp_path), n_orderers=1, peer_orgs=["Org1"],
        batch=BatchConfig(max_message_count=1, timeout_s=0.1))
    with open(net["orderers"][0]) as f:
        ocfg = json.load(f)
    with open(net["peers"][0]) as f:
        pcfg = json.load(f)
    orderer = OrdererNode(ocfg, data_dir=ocfg["data_dir"]).start()
    peer = PeerNode(pcfg, data_dir=pcfg["data_dir"])
    peer.provider = SickDevice(peer.provider)    # what the deliver loop asks
    peer.start()
    try:
        cc, signer, msps = load_client(net["clients"]["Org1"])
        addr = wait_orderer_leader([tuple(o) for o in cc["orderers"]],
                                   signer, msps, deadline_s=30.0)
        conn = connect(addr, signer, msps)
        try:
            out = conn.call("broadcast",
                            {"envelope": _env(0, signer).serialize()},
                            timeout=10.0)
        finally:
            conn.close()
        assert out["status"] == 200, out
        assert peer._stop.wait(30.0), "peer kept running on a sick device"
        assert isinstance(peer.fatal, DeviceError)
        assert peer.ledger.height == 0       # nothing committed in its place
    finally:
        peer.stop()
        orderer.stop()


def test_peer_fail_stops_on_device_error_from_gossip(tmp_path):
    """The same from the gossip intake thread: a block another peer
    sent reaches the signature check and the committer through
    `GossipState.handle`, whose transport would log and drop the error."""
    from fabric_tpu.bccsp.provider import DeviceError
    from fabric_tpu.config import BatchConfig
    from fabric_tpu.gossip.state import MSG_BLOCK
    from fabric_tpu.node.orderer import OrdererNode
    from fabric_tpu.node.peer import PeerNode
    from fabric_tpu.node.provision import provision_network
    from fabric_tpu.orderer.deliver import SeekInfo

    net = provision_network(
        str(tmp_path), n_orderers=1, peer_orgs=["Org1"],
        batch=BatchConfig(max_message_count=1, timeout_s=0.1))
    with open(net["orderers"][0]) as f:
        ocfg = json.load(f)
    with open(net["peers"][0]) as f:
        pcfg = json.load(f)
    orderer = OrdererNode(ocfg, data_dir=ocfg["data_dir"]).start()
    # never started: its deliver loop must not find the block first
    peer = PeerNode(pcfg, data_dir=pcfg["data_dir"])
    ch = peer.channels["ch"]
    try:
        cc, signer, msps = load_client(net["clients"]["Org1"])
        addr = wait_orderer_leader([tuple(o) for o in cc["orderers"]],
                                   signer, msps, deadline_s=30.0)
        conn = connect(addr, signer, msps)
        try:
            out = conn.call("broadcast",
                            {"envelope": _env(0, signer).serialize()},
                            timeout=10.0)
        finally:
            conn.close()
        assert out["status"] == 200, out
        height = peer.ledger.height
        block, _, _, _ = next(iter(ch.deliver_client.deliver(
            "ch", SeekInfo(start=height, stop=height,
                           behavior="block_until_ready"), timeout_s=10)))
        ch.mcs.provider = SickDevice(ch.mcs.provider)
        ch.gossip.state.handle(MSG_BLOCK, "127.0.0.1:1",
                               {"block": block.serialize()})
        assert peer._stop.is_set(), "peer kept running on a sick device"
        assert isinstance(peer.fatal, DeviceError)
        assert peer.ledger.height == height  # nothing committed in its place
    finally:
        peer.stop()
        orderer.stop()
