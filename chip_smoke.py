#!/usr/bin/env python3
"""chip_smoke.py — the served path, once, on the chip.

    gateway -> endorse (3 orgs) -> Raft (3 orderers) -> deliver
            -> validate (device) -> commit

A launcher that never imports jax.  It starts the system the way the
README's "Running" section says users do — OS processes from configs
written by `provision_network` — drives traffic from the client's side
of the gateway, and checks what came out against two peers that verify
with OpenSSL and share no code with the device path.

Deployment: 3 Raft orderers; Org1/Org2/Org3 with one peer each;
chaincode `assets` under AND('Org1.member','Org2.member','Org3.member')
(3 endorsements + 1 creator signature per tx); block cutting at
`BatchConfig()`'s defaults; 64 enrolled client identities.  Org1's peer
runs `"bccsp": "JAXTPU", "bccsp_degrade": false` and hosts the gateway
the clients use — the one process that touches the chip.  Org2's and
Org3's peers run `"bccsp": "SW"`: the plain reference.  Every peer
verifies for itself (the provisioner's attestation-trust opt-in is
taken out), or the reference peers would take the gateway's word for
creator signatures.

Phases: preflight (no accelerator -> exit 1 before any work) · build
the three native extensions from source · provision and start · warm,
in the device peer's process, exactly the program shapes traffic will
use · a pilot batch · the serving window (endorse, then submit, then
commit_status; no compilation allowed inside) · cross-check of every
block on all three peers · the device peer's own account of what it
did · stop the network · one BASELINE-size block (10,000 tx, 40,000
signatures, none seen before) on top of the served chain, handed to
each peer's own committer by `fabric_tpu.testing.replay` — Org1's in a
process that takes the chip, Org2's and Org3's host-only.

The endorse phase is bounded by the clock, not only by TARGET_TX: the
contract gives 1200 s with compilation included, and on this path every
endorsement costs the device three single-signature dispatches (the
proposal check and two fan-out handshakes).  Where the clock cuts the
count the cut is printed; the shapes are never cut.

On a host with more than one chip the device peer runs `"bccsp_mesh":
true` over all of them, and the smoke also checks that every chip took
dispatches and holds memory.  Nothing else differs, and nothing is
passed in: `python chip_smoke.py` is the whole interface.

The last line of stdout is one JSON object naming the device as JAX
reported it to the device peer.  Any failed check exits non-zero
without it.
"""

from __future__ import annotations

import concurrent.futures
import glob
import itertools
import json
import os
import random
import shutil
import subprocess
import sys
import tempfile
import threading
import time
import urllib.request

REPO = os.path.dirname(os.path.abspath(__file__))

SEED = 2026
CHANNEL = "ch"
CHAINCODE = "assets"
PEER_ORGS = ("Org1", "Org2", "Org3")
N_ORDERERS = 3
N_CLIENTS = 64               # BASELINE's creator population
KEYSPACE = 100_000           # uniform
TARGET_TX = 10_000
TAMPER_EVERY = 100           # ~1%: one endorsement-signature byte flipped
CONFLICT_PAIRS = 8           # deliberate same-key pairs
PILOT_TX = 128
BIG_BLOCK_TX = 10_000        # x (3 endorsements + 1 creator) = 40,000 sigs
WORKERS = 32

LIMIT_S = 1200.0             # the contract's limit, compilation included
ENDORSE_UNTIL_S = 640.0      # no new endorsement after this much of it

VALID, POLICY_FAILURE, MVCC_CONFLICT = 0, 10, 11


class SmokeFailure(Exception):
    pass


def say(msg: str) -> None:
    print(f"[smoke {time.monotonic() - _T0:7.1f}s] {msg}", flush=True)


def check(cond: bool, what: str) -> None:
    if not cond:
        raise SmokeFailure(what)
    say(f"ok: {what}")


_T0 = time.monotonic()


# -- preflight ---------------------------------------------------------------

def probe_accelerator() -> dict:
    """What JAX finds, asked in a child that exits (and so releases the
    chip) before any node starts.  The launcher itself stays off jax."""
    code = ("import json, jax; d = jax.devices(); "
            "print(json.dumps({'platform': d[0].platform, "
            "'kind': d[0].device_kind, 'count': len(d)}))")
    proc = subprocess.run([sys.executable, "-c", code], capture_output=True,
                          text=True, timeout=180)
    if proc.returncode != 0:
        raise SmokeFailure("jax failed to start:\n" + proc.stderr[-2000:])
    info = json.loads(proc.stdout.strip().splitlines()[-1])
    if info["platform"] == "cpu":
        raise SmokeFailure("JAX finds no accelerator (platform cpu)")
    return info


def build_native() -> None:
    """All four extensions from the committed .c sources.  A copied
    tree's .so says nothing by its mtime, so stale ones go first."""
    from fabric_tpu import native
    ndir = os.path.dirname(native.__file__)
    for so in glob.glob(os.path.join(ndir, "*.so")):
        os.remove(so)
    for name in ("_ftlv", "_fastcollect", "_fastparse", "_fastmvcc"):
        check(native.load(name) is not None, f"native extension {name} "
              "built from source and loaded")


# -- small helpers -----------------------------------------------------------

def http_json(method: str, url: str, body=None, timeout: float = 30.0):
    data = None if body is None else json.dumps(body).encode()
    req = urllib.request.Request(url, data=data, method=method)
    with urllib.request.urlopen(req, timeout=timeout) as resp:
        return json.loads(resp.read())


def http_text(url: str) -> str:
    with urllib.request.urlopen(url, timeout=30.0) as resp:
        return resp.read().decode()


def read_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def write_json(path: str, doc: dict) -> None:
    with open(path, "w") as f:
        json.dump(doc, f)


def maps_hold_jax(pid: int) -> list:
    """Shared objects of jax / libtpu mapped into a process."""
    hits = set()
    with open(f"/proc/{pid}/maps") as f:
        for line in f:
            path = line.split()[-1]
            base = os.path.basename(path)
            if "libtpu" in base or "/jaxlib/" in path or "/jax/" in path:
                hits.add(base)
    return sorted(hits)


def run_pool(fn, items, workers: int = WORKERS) -> list:
    """fn over items on a thread pool; every result is read, so a
    worker's exception surfaces here."""
    with concurrent.futures.ThreadPoolExecutor(workers) as pool:
        return [f.result() for f in [pool.submit(fn, it) for it in items]]


# -- the network -------------------------------------------------------------

class Network:
    """The provisioned deployment as OS processes, plus what a client
    needs to talk to it."""

    def __init__(self, base: str, mesh: bool):
        from fabric_tpu.config import BatchConfig
        from fabric_tpu.node.provision import free_ports, provision_network
        from fabric_tpu.testing.procnet import load_client

        self.base = base
        self.procs = {}          # name -> Popen
        self.net = provision_network(
            base, n_orderers=N_ORDERERS, peer_orgs=list(PEER_ORGS),
            peers_per_org=1, channel_id=CHANNEL, batch=BatchConfig(),
            clients_per_org=-(-N_CLIENTS // len(PEER_ORGS)))
        # one environment for every node: what tells the device peer
        # apart is its config, and JAX there takes the accelerator by
        # default.  The package is run from the checkout, not installed.
        self.env = dict(os.environ)
        self.env["PYTHONPATH"] = os.pathsep.join(
            [REPO] + self.env.get("PYTHONPATH", "").split(os.pathsep))
        self.peer_cfg = {}       # org -> node cfg dict
        self.ops = {}            # org -> "http://host:port"
        ops_ports = free_ports(len(PEER_ORGS))
        for path, port in zip(self.net["peers"], ops_ports):
            cfg = read_json(path)
            cfg["ops_port"] = port
            # node defaults: every peer verifies every signature itself
            cfg.pop("verify_once", None)
            if cfg["mspid"] == PEER_ORGS[0]:
                cfg["bccsp"] = "JAXTPU"
                cfg["bccsp_degrade"] = False
                if mesh:
                    cfg["bccsp_mesh"] = True
            else:
                cfg["bccsp"] = "SW"
            write_json(path, cfg)
            self.peer_cfg[cfg["mspid"]] = cfg
            self.ops[cfg["mspid"]] = f"http://127.0.0.1:{port}"
        self.peer_addr = {org: (cfg["host"], cfg["port"])
                          for org, cfg in self.peer_cfg.items()}
        # the 64 identities, org by org in turn
        pool = [p for turn in itertools.zip_longest(
            *(self.net["client_pool"][org] for org in PEER_ORGS))
            for p in turn if p is not None]
        self.clients = [load_client(p)[1] for p in pool[:N_CLIENTS]]
        cc, self.signer, self.msps = load_client(
            self.net["clients"][PEER_ORGS[0]])
        self.orderers = [tuple(o) for o in cc["orderers"]]

    def start(self) -> None:
        from fabric_tpu.testing.procnet import spawn_node
        for path in self.net["orderers"]:
            name = os.path.basename(path)[:-5]
            self.procs[name] = spawn_node(
                "fabric_tpu.node.orderer", path, env=self.env,
                log_path=os.path.join(self.base, name + ".log"))
        for path in self.net["peers"]:
            org = read_json(path)["mspid"]
            self.procs["peer" + org] = spawn_node(
                "fabric_tpu.node.peer", path, env=self.env,
                log_path=os.path.join(self.base, f"peer{org}.log"))

    def stop(self) -> None:
        from fabric_tpu.testing.procnet import stop_nodes
        stop_nodes(self.procs.values())

    def assert_alive(self) -> None:
        for name, proc in self.procs.items():
            if proc.poll() is not None:
                raise SmokeFailure(f"{name} exited with {proc.returncode}:\n"
                                   + self.log_tail(name))

    def log_tail(self, name: str, n: int = 3000) -> str:
        try:
            with open(os.path.join(self.base, name + ".log"), "rb") as f:
                return f.read()[-n:].decode("utf-8", "replace")
        except OSError:
            return "(no log)"

    def provider_status(self, org: str) -> dict:
        return http_json("GET", self.ops[org] + "/state")["provider"]

    def wait_ops(self, org: str, deadline_s: float) -> dict:
        """provider_status once the peer's ops server answers."""
        deadline = time.monotonic() + deadline_s
        while True:
            self.assert_alive()
            try:
                return self.provider_status(org)
            except OSError:
                if time.monotonic() > deadline:
                    raise SmokeFailure(f"{org}'s ops endpoint never came "
                                       "up:\n" + self.log_tail("peer" + org))
                time.sleep(0.5)

    def statuses(self) -> dict:
        from fabric_tpu.testing.procnet import node_status
        return {org: node_status(addr, self.signer, self.msps)
                for org, addr in self.peer_addr.items()}

    def wait_heights(self, want: int, deadline_s: float) -> dict:
        from fabric_tpu.testing.procnet import wait_peer_heights
        return wait_peer_heights(self.peer_addr, self.signer, self.msps,
                                 want, deadline_s=deadline_s)

    def chain_tip(self, org: str) -> dict:
        """{"height", "current_hash", ...} from the peer's qscc."""
        from fabric_tpu.comm import connect
        conn = connect(self.peer_addr[org], self.signer, self.msps,
                       timeout=10.0)
        try:
            return conn.call("qscc.chain_info", {"channel": CHANNEL},
                             timeout=30.0)
        finally:
            conn.close()

    def fetch_blocks(self, org: str, lo: int, hi: int) -> list:
        """Blocks [lo, hi) as the peer's qscc serves them."""
        from fabric_tpu.comm import connect
        from fabric_tpu.protocol.types import Block
        conn = connect(self.peer_addr[org], self.signer, self.msps,
                       timeout=10.0)
        try:
            return [Block.deserialize(conn.call(
                "qscc.block_by_number",
                {"channel": CHANNEL, "number": n}, timeout=60.0)["block"])
                for n in range(lo, hi)]
        finally:
            conn.close()


def block_flags(block) -> list:
    """[(txid, validation code)] of one committed block."""
    from fabric_tpu.protocol import wire
    from fabric_tpu.protocol.txflags import TxFlags
    from fabric_tpu.protocol.types import META_TXFLAGS
    codes = TxFlags.from_bytes(block.metadata.items[META_TXFLAGS]).codes()
    # (type, channel, txid) by the native parser build_native checked in
    return [(wire.envelope_summary(raw)[2], int(code))
            for raw, code in zip(block.data, codes)]


# -- traffic -----------------------------------------------------------------

def flip_last_byte(sig: bytes) -> bytes:
    """Still DER, no longer a signature of anything."""
    return sig[:-1] + bytes([sig[-1] ^ 0x01])


class Traffic:
    """Transactions through GatewayClient, one connection per enrolled
    client identity."""

    def __init__(self, nw: Network, seed: int):
        from fabric_tpu.gateway import GatewayClient
        self.nw = nw
        self.rng = random.Random(seed)
        self.gws = [GatewayClient(nw.peer_addr[PEER_ORGS[0]], signer,
                                  nw.msps, channel_id=CHANNEL, seed=i)
                    for i, signer in enumerate(nw.clients)]
        self._next = 0
        self._lock = threading.Lock()

    def connect_all(self) -> None:
        run_pool(lambda gw: gw.warm(), self.gws)

    def close(self) -> None:
        for gw in self.gws:
            gw.close()

    def plan(self, n: int) -> list:
        """n transactions: client, key, and which are tampered or share
        a key on purpose.  Made from the seed alone."""
        txs = []
        for _ in range(n):
            i = self._next
            self._next += 1
            txs.append({"i": i, "client": i % len(self.gws),
                        "key": "k%06d" % self.rng.randrange(KEYSPACE),
                        "tampered": i % TAMPER_EVERY == TAMPER_EVERY - 1,
                        "pair": None})
        clean = [t for t in txs if not t["tampered"]]
        for p in range(min(CONFLICT_PAIRS, len(clean) // 4)):
            a, b = clean[4 * p], clean[4 * p + 1]
            b["key"] = a["key"] = "pair%04d_%d" % (p, a["i"])
            a["pair"] = b["pair"] = p
        return txs

    def endorse(self, tx: dict) -> dict:
        from fabric_tpu.endorser.proposal import (ProposalResponse,
                                                  assemble_transaction)
        from fabric_tpu.protocol import Endorsement
        gw = self.gws[tx["client"]]
        sp, responses = gw.endorse(CHAINCODE, "bump", [tx["key"].encode()])
        if len(responses) != len(PEER_ORGS):
            raise SmokeFailure(f"tx {tx['i']}: {len(responses)} "
                               "endorsements, want one per org")
        if tx["tampered"]:
            r = responses[1]
            responses[1] = ProposalResponse(
                r.status, r.message, r.payload,
                Endorsement(r.endorsement.endorser,
                            flip_last_byte(r.endorsement.signature)))
        env = assemble_transaction(sp, responses, gw.signer)
        tx["txid"] = env.header().channel_header.txid
        tx["env"] = env
        return tx

    def endorse_until(self, txs: list, deadline: float) -> list:
        """Endorse in order until done or the clock says stop; returns
        the endorsed prefix."""
        it = iter(txs)
        done = []
        lock = threading.Lock()

        def worker(_):
            while time.monotonic() < deadline:
                with lock:
                    tx = next(it, None)
                if tx is None:
                    return
                self.endorse(tx)
                with lock:
                    done.append(tx)

        run_pool(worker, range(WORKERS))
        return sorted(done, key=lambda t: t["i"])

    def submit(self, tx: dict) -> dict:
        from fabric_tpu.comm import RpcError
        from fabric_tpu.gateway.client import GatewayShedError
        gw = self.gws[tx["client"]]
        for attempt in range(20):
            try:
                gw.submit_envelope(tx["env"], timeout_s=30.0)
                return tx
            except (GatewayShedError, RpcError) as exc:
                # shed or queue-full: the node asked us to come back
                last = exc
                time.sleep(0.1 * (attempt + 1))
        raise SmokeFailure(f"tx {tx['i']} never admitted: {last}")

    def commit_status(self, tx: dict) -> dict:
        gw = self.gws[tx["client"]]
        tx["code"], tx["block"] = gw.commit_status(tx["txid"],
                                                   timeout_s=90.0)
        return tx

    def run(self, txs: list) -> list:
        """submit -> commit_status for already endorsed transactions."""
        run_pool(self.submit, txs, workers=2 * WORKERS)
        return run_pool(self.commit_status, txs, workers=2 * WORKERS)


# -- checks over committed blocks -------------------------------------------

def cross_check(nw: Network, lo: int, hi: int, acked: list) -> dict:
    """Blocks [lo, hi) from all three peers: identical flags everywhere,
    and every acknowledged commit read back with the gateway's code."""
    per_org = {}
    for org in PEER_ORGS:
        per_org[org] = [block_flags(b) for b in nw.fetch_blocks(org, lo, hi)]
    dev = per_org[PEER_ORGS[0]]
    for org in PEER_ORGS[1:]:
        check(per_org[org] == dev,
              f"blocks {lo}..{hi - 1}: tx-filter flags on {org} (SW) equal "
              f"those on {PEER_ORGS[0]} (device)")
    where = {}
    for n, flags in enumerate(dev, start=lo):
        for txid, code in flags:
            where[txid] = (code, n)
    # block -1: the gateway answered from its block store (the commit
    # beat the notifier), which names no block
    bad = [t for t in acked
           if where.get(t["txid"], (None, None))[0] != t["code"]
           or t["block"] not in (-1, where[t["txid"]][1])]
    check(not bad, f"{len(acked)} acknowledged commits read back from all "
          "three peers with the gateway's code and block"
          + (f" (first mismatch: tx {bad[0]['i']} acked "
             f"{(bad[0]['code'], bad[0]['block'])} ledger "
             f"{where.get(bad[0]['txid'])})" if bad else ""))
    tampered = [t for t in acked if t["tampered"]]
    check(all(t["code"] == POLICY_FAILURE for t in tampered),
          f"{len(tampered)} tampered envelopes are "
          "ENDORSEMENT_POLICY_FAILURE everywhere")
    pairs = {}
    for t in acked:
        if t["pair"] is not None:
            pairs.setdefault(t["pair"], []).append(t["code"])
    check(all(sorted(c) == [VALID, MVCC_CONFLICT] for c in pairs.values()),
          f"{len(pairs)} same-key pairs: one VALID, one MVCC_READ_CONFLICT")
    sizes = [len(flags) for flags in dev]
    codes = [c for flags in dev for _, c in flags]
    return {"blocks": len(dev), "block_sizes": sizes, "txs": len(codes),
            "valid": codes.count(VALID),
            "policy_failure": codes.count(POLICY_FAILURE),
            "mvcc_conflict": codes.count(MVCC_CONFLICT)}


def check_same_ledger(nw: Network) -> dict:
    sts = nw.statuses()
    check(len({s["height"] for s in sts.values()}) == 1
          and len({s["commit_hash"] for s in sts.values()}) == 1,
          "height and commit_hash equal on the device peer and both SW "
          f"peers (height {sts[PEER_ORGS[0]]['height']}, "
          f"{sts[PEER_ORGS[0]]['commit_hash'][:16]}…)")
    return sts[PEER_ORGS[0]]


# -- the 10,000-tx block -----------------------------------------------------

def build_big_block_envelopes(nw: Network, n: int, seed: int) -> list:
    """n endorser transactions signed by the network's own identities
    (the three peers endorse, the 64 clients create), ~1% with one
    endorsement-signature byte flipped.  -> [(raw, txid, tampered)]"""
    from fabric_tpu.node.orderer import load_signing_identity
    from fabric_tpu.protocol import (ChaincodeAction, Endorsement, KVWrite,
                                     NsRwSet, Transaction,
                                     TransactionAction, TxRwSet, build)
    from fabric_tpu.protocol.types import TX_ENDORSER

    rng = random.Random(seed)
    endorsers = [load_signing_identity(
        cfg["mspid"], cfg["cert_pem"].encode(), cfg["key_pem"].encode())
        for cfg in (nw.peer_cfg[org] for org in PEER_ORGS)]
    out = []
    for i in range(n):
        creator = nw.clients[i % len(nw.clients)]
        nonce = rng.randbytes(24)
        txid = build.compute_txid(nonce, creator.serialize())
        rwset = TxRwSet((NsRwSet(CHAINCODE, writes=(
            KVWrite("big%06d" % i, b"1"),)),))
        ta = TransactionAction(
            build.proposal_hash(CHANNEL, txid, CHAINCODE, ()),
            ChaincodeAction(CHAINCODE, "1.0", rwset))
        ends = [build.endorse(ta, e) for e in endorsers]
        tampered = i % TAMPER_EVERY == TAMPER_EVERY - 1
        if tampered:
            ends[1] = Endorsement(ends[1].endorser,
                                  flip_last_byte(ends[1].signature))
        ta = TransactionAction(ta.proposal_hash, ta.action, tuple(ends))
        env = build.signed_envelope(TX_ENDORSER, CHANNEL,
                                    Transaction((ta,)).to_dict(), creator,
                                    nonce=nonce)
        out.append((env.serialize(), txid, tampered))
    return out


def write_big_block(nw: Network, tip: dict, envs: list) -> str:
    """The envelopes as block number `height` on top of the served
    chain, one serialized Block in a file."""
    from fabric_tpu.protocol.types import (Block, BlockHeader,
                                           BlockMetadata, block_data_hash)
    data = [raw for raw, _, _ in envs]
    block = Block(BlockHeader(tip["height"], tip["current_hash"],
                              block_data_hash(data)), data, BlockMetadata())
    path = os.path.join(nw.base, "bigblock.bin")
    with open(path, "wb") as f:
        f.write(block.serialize())
    return path


def replay_big_block(nw: Network, path: str) -> dict:
    """The block through every (stopped) peer's own committer, each in
    a process of its own: Org1's with the device provider, Org2's and
    Org3's host-only.  -> {org: report}"""
    from fabric_tpu.node.warmup import BLOCK_10K_ROWS
    procs = {}
    for cfg_path in nw.net["peers"]:
        org = read_json(cfg_path)["mspid"]
        argv = [sys.executable, "-m", "fabric_tpu.testing.replay",
                cfg_path, path]
        if org == PEER_ORGS[0]:
            argv += ["--warm-rows", ",".join(map(str, BLOCK_10K_ROWS))]
        with open(os.path.join(nw.base, f"replay{org}.log"), "wb") as log:
            procs[org] = subprocess.Popen(argv, env=nw.env, stderr=log,
                                          stdout=subprocess.PIPE)
    reports = {}
    for org, proc in procs.items():
        out, _ = proc.communicate(timeout=LIMIT_S)
        if proc.returncode != 0:
            raise SmokeFailure(f"replay on {org}'s peer exited "
                               f"{proc.returncode}:\n"
                               + nw.log_tail("replay" + org))
        reports[org] = json.loads(out.decode().strip().splitlines()[-1])
    return reports


# -- main --------------------------------------------------------------------

def run() -> dict:
    """The whole smoke; returns the device dict for the last line."""
    found = probe_accelerator()
    mesh = found["count"] > 1
    say(f"preflight: jax finds {found}"
        + ("; the device peer shards over all of them" if mesh else ""))
    sys.path.insert(0, REPO)
    build_native()

    from fabric_tpu.bccsp.factory import FactoryOpts, init_factories
    from fabric_tpu.node.warmup import SERVED_GENERIC, SERVED_ROWS
    from fabric_tpu.testing.procnet import wait_orderer_leader, wait_status
    init_factories(FactoryOpts(default="SW"))     # the launcher's own

    base = tempfile.mkdtemp(prefix="chip_smoke_")
    nw = Network(base, mesh=mesh)
    traffic = None
    try:
        t_setup = time.monotonic()
        nw.start()
        say(f"started {len(nw.procs)} processes: {sorted(nw.procs)}; "
            f"block cutting at BatchConfig() defaults (500 msgs / 10 MB / "
            f"2 MB / 2 s — upstream sample configtx.yaml, assumed)")
        wait_orderer_leader(nw.orderers, nw.signer, nw.msps,
                            deadline_s=90.0)
        dev_org = PEER_ORGS[0]
        for org in PEER_ORGS[1:]:
            wait_status(nw.peer_addr[org], nw.signer, nw.msps,
                        lambda st: True, f"peer {org} serving", 180.0)
        # the device peer is awaited on its ops port: an RPC needs a
        # handshake, whose signature check would compile inside the
        # dial's time-out before anything is warm
        st0 = nw.wait_ops(dev_org, 240.0)
        nw.assert_alive()
        device = st0["device"]
        check(device is not None and st0["name"] == "jaxtpu",
              f"{dev_org}'s peer runs the jaxtpu provider")
        say(f"device peer: jax {device['jax']} / jaxlib {device['jaxlib']} "
            f"/ libtpu {device['libtpu']}; devices {device['devices']}; "
            f"compile cache at {device['compile_cache_dir']}")
        check(device["platform"] == "tpu",
              f"device peer's platform is tpu ({device['device_kind']} "
              f"x {device['device_count']})")

        # the big block's envelopes are built while the peer compiles
        big_box = {}
        big_thread = threading.Thread(
            target=lambda: big_box.update(envs=build_big_block_envelopes(
                nw, BIG_BLOCK_TX, SEED + 1)), daemon=True)
        big_thread.start()

        warm = http_json("POST", nw.ops[dev_org] + "/bccsp/warmup",
                         {"generic": list(SERVED_GENERIC),
                          "rows": list(SERVED_ROWS)}, timeout=LIMIT_S)
        say(f"warm-up in the device peer: {warm['timings']} "
            f"({warm['seconds']} s)")
        wait_status(nw.peer_addr[dev_org], nw.signer, nw.msps,
                    lambda st: True, f"peer {dev_org} serving", 60.0)
        traffic = Traffic(nw, SEED)
        traffic.connect_all()
        say(f"{len(traffic.gws)} client identities connected to the gateway")

        pilot = traffic.endorse_until(traffic.plan(PILOT_TX),
                                      time.monotonic() + 300.0)
        traffic.run(pilot)
        h_pilot = nw.wait_heights(max(t["block"] for t in pilot) + 1,
                                  60.0)[dev_org]["height"]
        setup_s = time.monotonic() - t_setup
        st1 = nw.provider_status(dev_org)
        c1 = st1["device"]["compile"]
        say(f"pilot: {len(pilot)} tx committed; set-up (start + compile + "
            f"warm-up + pilot) {setup_s:.1f} s; compiled so far: "
            f"{c1['compiles']} programs in {c1['compile_s']} s, "
            f"persistent cache hits {c1['cache_hits']}, "
            f"writes {c1['cache_writes']}")

        # ---- the serving window ------------------------------------------
        t_serve = time.monotonic()
        endorsed = traffic.endorse_until(traffic.plan(TARGET_TX),
                                         _T0 + ENDORSE_UNTIL_S)
        t_endorsed = time.monotonic()
        if len(endorsed) < TARGET_TX:
            say(f"CUT: endorsed {len(endorsed)} of {TARGET_TX} planned tx "
                f"before the {ENDORSE_UNTIL_S:.0f} s mark of the "
                f"{LIMIT_S:.0f} s limit (count cut, shapes kept)")
        check(len(endorsed) >= 500, "at least one full block's worth of "
              f"transactions endorsed ({len(endorsed)})")
        acked = traffic.run(endorsed)
        serve_s = time.monotonic() - t_serve
        h_serve = nw.wait_heights(max(t["block"] for t in acked) + 1,
                                  120.0)[dev_org]["height"]
        st2 = nw.provider_status(dev_org)
        c2 = st2["device"]["compile"]
        say(f"serving window: {len(acked)} tx acknowledged in "
            f"{serve_s:.1f} s (endorse {t_endorsed - t_serve:.1f} s, "
            f"submit+commit {serve_s - (t_endorsed - t_serve):.1f} s)")
        check(c2["compiles"] == c1["compiles"],
              "0 compilations inside the serving window "
              f"({c2['compiles'] - c1['compiles']})")
        summary = cross_check(nw, h_pilot, h_serve, acked)
        full = sum(1 for n in summary["block_sizes"] if n >= 500)
        say(f"serving window on the ledger: {summary['txs']} tx in "
            f"{summary['blocks']} blocks ({full} of 500 tx; sizes "
            f"{summary['block_sizes']}); VALID {summary['valid']}, "
            f"ENDORSEMENT_POLICY_FAILURE {summary['policy_failure']}, "
            f"MVCC_READ_CONFLICT {summary['mvcc_conflict']}")
        check(summary["txs"] == len(acked), "every acknowledged tx is in "
              "the window's blocks and nothing else is")
        check_same_ledger(nw)
        served_sigs = st2["stats"]["device_sigs"] - st1["stats"]["device_sigs"]
        check(served_sigs >= 4 * len(acked),
              f"device verified >= 4 signatures per committed tx in the "
              f"window ({served_sigs} for {len(acked)} tx)")

        # ---- the device peer's own account of what it served ------------
        nw.assert_alive()
        stats = st2["stats"]
        n_tampered = sum(t["tampered"] for t in pilot + acked)
        check(stats["fallbacks"] == 0, "provider fallbacks == 0")
        check(stats["host_rejects"] <= n_tampered,
              f"host_rejects {stats['host_rejects']} <= tampered "
              f"signatures {n_tampered}")
        check(st2["backend"] == "jaxtpu" and not st2["degraded"],
              "backend jaxtpu, bccsp_degraded 0")
        health = http_json("GET", nw.ops[dev_org] + "/healthz")
        check(health["status"] == "OK", f"/healthz OK ({health})")
        check(all(st2["native"].values()),
              f"three native extensions loaded in the device peer "
              f"({st2['native']})")
        for org in PEER_ORGS[1:]:
            check(nw.provider_status(org)["device"] is None,
                  f"{org}'s peer (SW) reports no device")
        for name, proc in nw.procs.items():
            if name != "peer" + dev_org:
                check(not maps_hold_jax(proc.pid),
                      f"{name} has neither jax nor libtpu mapped")
        mem = st2["device"]["memory"]
        if mesh:
            slots = {}
            for line in http_text(nw.ops[dev_org] + "/metrics").splitlines():
                if line.startswith("provider_lane_slots_total{"):
                    lab = line.split('device="')[1].split('"')[0]
                    slots[lab] = slots.get(lab, 0.0) + float(line.split()[-1])
            labels = {f"{device['platform']}:{m['id']}" for m in mem}
            check(set(slots) == labels and all(slots.values()),
                  f"provider_lane_slots_total non-zero for every device "
                  f"label ({slots})")
            check(all(m["bytes_in_use"] for m in mem),
                  "bytes_in_use non-zero on every device "
                  f"({[m['bytes_in_use'] for m in mem]})")
        tip = nw.chain_tip(dev_org)
        traffic.close()
        nw.stop()                    # the chip and the ledgers are free

        # ---- the 10,000-tx block -----------------------------------------
        big_thread.join()
        envs = big_box["envs"]
        t_big = time.monotonic()
        reports = replay_big_block(nw, write_big_block(nw, tip, envs))
        big_s = time.monotonic() - t_big
        dev = reports[dev_org]
        big_tampered = sum(tampered for _, _, tampered in envs)
        want = bytes(POLICY_FAILURE if tampered else VALID
                     for _, _, tampered in envs).hex()
        check(dev["blocks"][0]["flags"] == want,
              f"big block on the device peer: {len(envs)} tx, "
              f"{big_tampered} tampered are ENDORSEMENT_POLICY_FAILURE, "
              "the rest VALID")
        for org in PEER_ORGS[1:]:
            rep = reports[org]
            check(rep["blocks"] == [dict(dev["blocks"][0],
                                         seconds=rep["blocks"][0]["seconds"])]
                  and (rep["height"], rep["commit_hash"])
                  == (dev["height"], dev["commit_hash"]),
                  f"big block: flags, height and commit_hash on {org} (SW, "
                  "host-only process) equal the device peer's "
                  f"(height {dev['height']}, {dev['commit_hash'][:16]}…)")
            check(not rep["jax_imported"] and rep["provider"]["device"]
                  is None, f"{org}'s replay process never imported jax")
        bstats, bdev = dev["provider"]["stats"], dev["provider"]["device"]
        check(bdev["platform"] == device["platform"]
              and bstats["device_sigs"] >= 4 * len(envs)
              and bstats["fallbacks"] == 0
              and bstats["host_rejects"] <= big_tampered,
              f"big block: {bstats['device_sigs']} signatures verified on "
              f"{bdev['platform']} in {bstats['dispatches']} dispatches, "
              f"fallbacks 0, host_rejects {bstats['host_rejects']}")
        say(f"big block: three replay processes {big_s:.1f} s; device "
            f"process: init {dev['init_s']} s, warm-up {dev['warm']} "
            f"({dev['warm_s']} s), store_block "
            f"{dev['blocks'][0]['seconds']} s "
            f"(SW peers {[reports[o]['blocks'][0]['seconds'] for o in PEER_ORGS[1:]]} s); "
            f"compiled {bdev['compile']}")
        check("jax" not in sys.modules and "jaxlib" not in sys.modules,
              "the launcher never imported jax")

        say("smoke readings (not metrics): "
            + json.dumps({
                "jax": device["jax"], "jaxlib": device["jaxlib"],
                "libtpu": device["libtpu"], "devices": device["devices"],
                "setup_s": round(setup_s, 1),
                "warmup_s": warm["seconds"],
                "serving_s": round(serve_s, 1),
                "serving_tx": len(acked),
                "serving_compilations": c2["compiles"] - c1["compiles"],
                "committed_tx": len(pilot) + len(acked) + len(envs),
                "device_sigs": stats["device_sigs"] + bstats["device_sigs"],
                "dispatches": stats["dispatches"] + bstats["dispatches"],
                "programs_compiled": [c2["compiles"],
                                      bdev["compile"]["compiles"]],
                "compile_s": [c2["compile_s"], bdev["compile"]["compile_s"]],
                "persistent_cache_hits": [c2["cache_hits"],
                                          bdev["compile"]["cache_hits"]],
                "persistent_cache_writes": [c2["cache_writes"],
                                            bdev["compile"]["cache_writes"]],
                "peak_bytes_in_use": [
                    [m["peak_bytes_in_use"] for m in mem],
                    [m["peak_bytes_in_use"] for m in bdev["memory"]]],
                "height": dev["height"],
                "wall_s": round(time.monotonic() - _T0, 1)}))
        return {"platform": device["platform"],
                "kind": device["device_kind"],
                "count": device["device_count"]}
    except BaseException:
        for name in ["peer" + PEER_ORGS[0], "replay" + PEER_ORGS[0]]:
            sys.stderr.write(f"---- tail of {name}.log ----\n"
                             + nw.log_tail(name) + "\n")
        raise
    finally:
        if traffic is not None:
            traffic.close()
        nw.stop()
        shutil.rmtree(base, ignore_errors=True)


def main() -> int:
    try:
        device = run()
    except SmokeFailure as exc:
        sys.stderr.write(f"chip_smoke FAILED: {exc}\n")
        return 1
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
