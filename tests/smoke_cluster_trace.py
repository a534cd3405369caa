"""Live cross-node trace assembly smoke (called by smoke.sh).

Boots a REAL multi-process topology — one raft orderer plus an Org1 and
an Org2 peer, each its own OS process with its own flight recorder —
submits one transaction through the gateway, then asserts that
`GET /traces/<id>?cluster=1` on the gateway peer's ops endpoint returns
ONE merged Chrome trace containing spans from >= 3 distinct nodes
(gateway peer, endorsing peer, orderer), with the commit_wait link
pulling the committer's block trace into the same export.

In-process topologies share the process-global tracer, so every ops
endpoint would serve the same recorder and a "cluster" merge would be
vacuously complete.  Only separate processes prove the fan-out, the
traceparent propagation on endorse/broadcast RPCs, and the transitive
link-following actually cross node boundaries — which is why this is a
subprocess drill and not a pytest fixture.
"""

import json
import sys
import tempfile
import time
import urllib.error
import urllib.request

from fabric_tpu.bccsp.factory import FactoryOpts, init_factories
from fabric_tpu.config import BatchConfig
from fabric_tpu.gateway import GatewayClient
from fabric_tpu.node.provision import free_ports, provision_network
from fabric_tpu.ops_plane import tracing
from fabric_tpu.protocol.txflags import ValidationCode
from fabric_tpu.testing.procnet import (
    load_client,
    spawn_node,
    stop_nodes,
    wait_status,
)


# the three trace families one request's picture holds (PR 37): its own
# wait by stage, the peer's block trace from the frame's receipt, the
# orderer's from the cut
PICTURE = {"gateway.commit_wait", "gateway.ordered_wait",
           "gateway.block_intake", "gateway.block_commit", "gateway.answer",
           "orderer.broadcast", "orderer.block", "orderer.batch_fill",
           "orderer.consensus", "orderer.write", "peer.block_intake",
           "deliver.block_sig", "gossip.forward", "committer.store_block"}


def main() -> int:
    init_factories(FactoryOpts(default="SW"))
    with tempfile.TemporaryDirectory() as base:
        net = provision_network(
            base, n_orderers=1, peer_orgs=["Org1", "Org2"], peers_per_org=1,
            batch=BatchConfig(max_message_count=8, timeout_s=0.05))

        # pin ops ports up front: every node gets the SAME cluster_trace
        # peer list (own endpoint included — nodes serve self in-process)
        node_paths = net["orderers"] + net["peers"]
        ops_ports = free_ports(len(node_paths))
        ops_eps = [f"127.0.0.1:{p}" for p in ops_ports]
        rpc_addrs = []
        for path, port in zip(node_paths, ops_ports):
            with open(path) as f:
                cfg = json.load(f)
            cfg["ops_port"] = port
            cfg["cluster_trace"] = {"peers": ops_eps, "timeout_s": 3.0}
            cfg["tracing"] = {"enabled": True, "sample_rate": 1.0}
            rpc_addrs.append((cfg["host"], cfg["port"]))
            with open(path, "w") as f:
                json.dump(cfg, f)

        procs = []
        try:
            for path, module in zip(
                    node_paths,
                    ["fabric_tpu.node.orderer"] * len(net["orderers"])
                    + ["fabric_tpu.node.peer"] * len(net["peers"])):
                procs.append(spawn_node(module, path))

            cc, signer, msps = load_client(net["clients"]["Org1"])
            wait_status(rpc_addrs[0], signer, msps,
                        lambda st: st.get("role") == "leader",
                        "raft leader", 60.0)
            for addr in rpc_addrs[1:]:
                wait_status(addr, signer, msps, lambda st: True,
                            "peer serving", 60.0)

            # the client roots `client.tx` in THIS process; the
            # traceparent rides the gateway submit so every node-side
            # span lands in the same trace id
            tracing.configure({"enabled": True, "sample_rate": 1.0})
            gw = GatewayClient(rpc_addrs[1], signer, msps, channel_id="ch")
            try:
                code, _ = gw.submit_transaction(
                    "assets", "create", [b"cluster1", b"alice"],
                    commit_timeout_s=90.0)
            finally:
                gw.close()
            if code != int(ValidationCode.VALID):
                print(f"FAIL: tx code {code}", file=sys.stderr)
                return 1
            tid = next((r["trace_id"]
                        for r in tracing.tracer.recorder.list()["recent"]
                        if r["root"] == "client.tx"), None)
            if tid is None:
                print("FAIL: no client.tx root in the local recorder",
                      file=sys.stderr)
                return 1

            # query the GATEWAY peer's ops endpoint; server-side
            # fragments finalize asynchronously, so poll briefly
            gw_ops = ops_eps[1]
            url = f"http://{gw_ops}/traces/{tid}?cluster=1"
            doc, deadline = None, time.time() + 20
            while time.time() < deadline:
                try:
                    with urllib.request.urlopen(url, timeout=10) as r:
                        doc = json.loads(r.read())
                except (urllib.error.URLError, OSError):
                    doc = None
                if doc and doc["otherData"]["n_nodes"] >= 3 and PICTURE <= {
                        e["name"] for e in doc["traceEvents"]}:
                    break
                time.sleep(0.3)
            if not doc:
                print("FAIL: cluster trace never became available",
                      file=sys.stderr)
                return 1

            other = doc["otherData"]
            nodes = other["nodes"]
            spans = [e for e in doc["traceEvents"] if e.get("ph") == "X"]
            names = {e["name"] for e in spans}
            pids = {e["pid"] for e in spans}
            ok = (other.get("cluster") is True
                  and other["n_nodes"] >= 3
                  and len(pids) >= 3
                  and not other["truncated"]
                  and other["n_traces_merged"] >= 3
                  and PICTURE <= names)
            if not ok:
                print(f"FAIL: merged trace malformed: nodes={nodes} "
                      f"names={sorted(names)} other={other}",
                      file=sys.stderr)
                return 1
            print(f"OK: cluster trace {tid} merged {len(spans)} spans "
                  f"from {other['n_nodes']} nodes "
                  f"({other['n_traces_merged']} traces linked): "
                  f"{dict(sorted(nodes.items()))}")
            return 0
        finally:
            stop_nodes(procs)


if __name__ == "__main__":
    sys.exit(main())
