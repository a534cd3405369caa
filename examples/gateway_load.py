"""Closed-loop gateway load driver: N client workers, one front door.

Boots a full in-process network (raft orderer cluster + one peer per
org), then runs a closed loop: each worker keeps exactly one
transaction in flight — endorse -> submit -> commit_status through the
peer's gateway — and issues the next the moment the previous commits.
Closed-loop load is the honest way to exercise the admission queue:
offered load adapts to what the pipeline sustains, so the batcher's
coalescing (not a generator's pacing) sets the broadcast batch size.

Prints per-verb latency percentiles, end-to-end commit latency, and
the gateway's own metrics (queue depth, batch-size histogram, retry
counters) at the end.

Run CPU-only:
  JAX_PLATFORMS=cpu \
      python examples/gateway_load.py [--workers 8] [--txs 25] \
      [--orderers 3] [--kill-orderer]

--kill-orderer stops one orderer mid-run to demonstrate the
broadcaster's failover: the run must still complete with every tx
VALID.
"""

import argparse
import json
import statistics
import tempfile
import threading
import time

from fabric_tpu.bccsp.factory import FactoryOpts, init_factories
from fabric_tpu.config import BatchConfig
from fabric_tpu.gateway import GatewayClient
from fabric_tpu.node.orderer import OrdererNode, load_signing_identity
from fabric_tpu.node.peer import PeerNode
from fabric_tpu.node.provision import provision_network
from fabric_tpu.protocol.txflags import ValidationCode


def _pct(xs, q):
    if not xs:
        return float("nan")
    xs = sorted(xs)
    return xs[min(len(xs) - 1, int(q * len(xs)))]


def boot(base, n_orderers):
    paths = provision_network(
        base, n_orderers=n_orderers, peer_orgs=["Org1", "Org2"],
        peers_per_org=1,
        batch=BatchConfig(max_message_count=32, timeout_s=0.05))
    orderers, peers = [], []
    for p in paths["orderers"]:
        with open(p) as f:
            cfg = json.load(f)
        cfg["ops_port"] = 0         # scrapeable end-to-end: every node
        orderers.append(OrdererNode(cfg, data_dir=cfg["data_dir"]).start())
    for p in paths["peers"]:
        with open(p) as f:
            cfg = json.load(f)
        cfg["gateway"] = {"linger_s": 0.005, "max_batch": 64}
        cfg["ops_port"] = 0         # /metrics, /slo, /traces, /gateway
        peers.append(PeerNode(cfg, data_dir=cfg["data_dir"]).start())
    deadline = time.time() + 60
    while time.time() < deadline:
        if any(o.support.chain.node.role == "leader" for o in orderers):
            return paths, orderers, peers
        time.sleep(0.2)
    raise SystemExit("no raft leader elected")


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--workers", type=int, default=8)
    ap.add_argument("--txs", type=int, default=25,
                    help="transactions per worker")
    ap.add_argument("--orderers", type=int, default=3)
    ap.add_argument("--kill-orderer", action="store_true",
                    help="stop one orderer mid-run (failover demo)")
    args = ap.parse_args()

    init_factories(FactoryOpts(default="SW"))
    with tempfile.TemporaryDirectory() as base:
        print(f"booting {args.orderers} orderers + 2 peers ...")
        paths, orderers, peers = boot(base, args.orderers)
        gw_peer = peers[0]
        with open(paths["clients"]["Org1"]) as f:
            cc = json.load(f)
        signer = load_signing_identity(
            cc["mspid"], cc["cert_pem"].encode(), cc["key_pem"].encode())

        lat_endorse, lat_commit, lat_e2e = [], [], []
        bad, trace_ids, lock = [], [], threading.Lock()
        from fabric_tpu.ops_plane import tracing

        def worker(wid):
            gw = GatewayClient(gw_peer.rpc.addr, signer, gw_peer.msps,
                               channel_id="ch")
            try:
                for i in range(args.txs):
                    key = f"w{wid}-tx{i}".encode()
                    t0 = time.monotonic()
                    # one root span per tx: all three gateway verbs ride
                    # this context, so the whole lifecycle is ONE trace
                    with tracing.tracer.start_span(
                            "client.tx",
                            attributes={"worker": wid, "i": i}) as span:
                        sp, responses = gw.endorse(
                            "assets", "create", [key, b"load"])
                        t1 = time.monotonic()
                        from fabric_tpu.endorser.proposal import (
                            assemble_transaction)
                        env = assemble_transaction(sp, responses, signer)
                        txid = env.header().channel_header.txid
                        gw.submit_envelope(env, timeout_s=60.0)
                        code, _ = gw.commit_status(txid, timeout_s=60.0)
                    t2 = time.monotonic()
                    with lock:
                        if span.recording and not trace_ids:
                            trace_ids.append(span.context.trace_id)
                        lat_endorse.append(t1 - t0)
                        lat_commit.append(t2 - t1)
                        lat_e2e.append(t2 - t0)
                        if code != int(ValidationCode.VALID):
                            bad.append((txid, code))
            except Exception as exc:
                with lock:
                    bad.append((f"w{wid}", repr(exc)))
            finally:
                gw.close()

        start = time.monotonic()
        threads = [threading.Thread(target=worker, args=(w,))
                   for w in range(args.workers)]
        for t in threads:
            t.start()
        if args.kill_orderer and len(orderers) > 1:
            time.sleep(1.0)
            victim = orderers.pop()
            print(f"killing orderer {victim.rpc.addr} mid-run ...")
            victim.stop()
        for t in threads:
            t.join()
        wall = time.monotonic() - start

        total = args.workers * args.txs
        print(f"\n{total} txs, {args.workers} closed-loop workers, "
              f"{wall:.2f}s wall -> {total / wall:.1f} tx/s")
        for name, xs in (("endorse", lat_endorse),
                         ("submit+commit", lat_commit),
                         ("end-to-end", lat_e2e)):
            if xs:
                print(f"  {name:14s} p50 {_pct(xs, .5) * 1e3:7.1f} ms   "
                      f"p95 {_pct(xs, .95) * 1e3:7.1f} ms   "
                      f"mean {statistics.mean(xs) * 1e3:7.1f} ms")
        if bad:
            print(f"  FAILURES: {bad[:5]}{' ...' if len(bad) > 5 else ''}")

        from fabric_tpu.ops_plane import registry
        print("\ngateway metrics:")
        for line in registry.expose_text().splitlines():
            if line.startswith("gateway_") and not line.startswith("#"):
                print(" ", line)

        # every node is scrapeable: render one cluster-top frame over
        # the live ops surfaces (the watch form of this is
        # `python -m fabric_tpu.node.top --targets ...`)
        from fabric_tpu.node import top as cluster_top
        targets = ",".join(f"{n.ops.addr[0]}:{n.ops.addr[1]}"
                           for n in peers + orderers if n.ops is not None)
        print(f"\ncluster top (--targets {targets}):")
        rows = [cluster_top.collect_node(t) for t in targets.split(",")]
        print(cluster_top.render(rows))

        # fetch one tx's trace over the peer's ops server: the flight
        # recorder stitches the request trace to its block trace, so the
        # Chrome JSON covers admission -> endorse -> order -> device
        # verify -> MVCC -> commit notification in one Perfetto load
        if trace_ids and gw_peer.ops is not None:
            import urllib.request
            host, port = gw_peer.ops.addr
            url = f"http://{host}:{port}/traces/{trace_ids[0]}"
            with urllib.request.urlopen(url, timeout=5) as r:
                doc = json.loads(r.read())
            names = {e["name"] for e in doc["traceEvents"]
                     if e.get("ph") == "X"}
            print(f"\ntrace {trace_ids[0]} "
                  f"({len(doc['traceEvents'])} events) via {url}")
            stages = {"admission": "gateway.queue_wait",
                      "endorsement": "endorser.simulate",
                      "ordering": "orderer.broadcast",
                      "device batch-verify": "bccsp.batch_verify",
                      "MVCC": "ledger.mvcc",
                      "commit notification": "gateway.commit_wait"}
            for stage, span_name in stages.items():
                mark = "ok" if span_name in names else "MISSING"
                print(f"  {stage:22s} {span_name:22s} {mark}")
                if span_name not in names:
                    bad.append(("trace", f"missing span {span_name}"))

        for n in peers + orderers:
            try:
                n.stop()
            except Exception:
                pass
        raise SystemExit(1 if bad else 0)


if __name__ == "__main__":
    main()
