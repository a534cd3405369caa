#!/usr/bin/env python3
"""Drill: one 10,000-tx block (about 34 MB in one Raft entry) through
the ordering service.  The reproducer for ROADMAP's open item D9.

    PYTHONPATH=$PWD python tests/drill_big_block_ordered.py

`chip_smoke.py`'s deployment (3 Raft orderers, Org1/Org2/Org3 x 1 peer,
as OS processes) with every peer on `"bccsp": "SW"`, so it runs anywhere
and takes no chip.  A config update signed by the org admins lifts the
block-cutting limits to 10,000 msgs / 64 MB / 30 s; then 10,000 endorser
transactions built from the network's own identities (3 endorsements +
1 creator signature each, ~1% tampered) go to the Raft leader through
`broadcast_batch`, and the drill waits for every peer to commit the one
block they make.

Known so far (PERF.md, PR 21): the outcome varies from run to run, in
the build sandbox too.  Where the block commits it does so 10-20 s after
the broadcast.  Where it does not, the orderers accepted all 10,000
envelopes with status 200, the Raft term rose while the leader was busy
with them, another orderer leads afterwards with a log that never held
the entry, and no height moves again.  (A term change does not always
lose it: one run went from term 1 to 3 and committed.)  The drill prints
the term before and after, and, whatever the outcome, what each
orderer's Raft counters read after it (`/metrics`, PR 37): leader
changes, lost proposals, the bytes appended to each follower against the
entry's own, what persisting and committing took.  Exit 0: the block
committed everywhere with the expected flags.  Exit 1: it did not; the
status of every node and the tail of each orderer's log go to stderr.
"""

from __future__ import annotations

import dataclasses
import os
import shutil
import sys
import tempfile
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

import chip_smoke as cs  # noqa: E402  (the deployment and the envelopes)

N_TX = 10_000
BROADCAST_CHUNK = 250        # envelopes per broadcast_batch call (~1 MB)
COMMIT_DEADLINE_S = 300.0


def lift_batch_limits(nw: cs.Network, leader) -> None:
    """Config update: one block may hold N_TX messages."""
    from fabric_tpu.comm import connect
    from fabric_tpu.config import BatchConfig, ChannelConfig
    from fabric_tpu.config.configtx import build_config_envelope
    from fabric_tpu.testing.procnet import load_client

    admins = [load_client(path) for path in nw.net["admins"].values()]
    current = ChannelConfig.deserialize(
        bytes.fromhex(admins[0][0]["channel_config_hex"]))
    lifted = dataclasses.replace(
        current, sequence=current.sequence + 1,
        batch=BatchConfig(N_TX, 64 << 20, 64 << 20, 30.0))
    env = build_config_envelope(lifted, [signer for _, signer, _ in admins])
    conn = connect(leader, nw.signer, nw.msps)
    try:
        out = conn.call("broadcast", {"envelope": env.serialize()},
                        timeout=30.0)
    finally:
        conn.close()
    cs.check(out["status"] == 200, f"config update admitted ({out})")


def broadcast_all(nw: cs.Network, leader, envs: list) -> None:
    from fabric_tpu.comm import connect
    conn = connect(leader, nw.signer, nw.msps)
    try:
        for lo in range(0, len(envs), BROADCAST_CHUNK):
            out = conn.call("broadcast_batch", {"envelopes": [
                raw for raw, _, _ in envs[lo:lo + BROADCAST_CHUNK]]},
                timeout=120.0)
            bad = [s for s in out["statuses"] if s != 200]
            if bad:
                raise cs.SmokeFailure(f"broadcast refused at {lo}: {out}")
    finally:
        conn.close()


D9_SERIES = ("consensus_etcdraft_is_leader",
             "consensus_etcdraft_leader_changes",
             "consensus_etcdraft_proposal_failures",
             "consensus_etcdraft_append_bytes_total",
             "consensus_etcdraft_data_persist_duration",
             "consensus_etcdraft_commit_duration",
             "blockcutter_cut_total", "deliver_blocks_sent")


def say_raft_counters(orderer_ops: dict) -> None:
    """Each orderer's D9 series, as its `/metrics` has them now."""
    import urllib.request
    for name, url in sorted(orderer_ops.items()):
        try:
            with urllib.request.urlopen(url + "/metrics", timeout=10) as r:
                text = r.read().decode()
        except OSError as exc:
            cs.say(f"{name}: no exposition ({exc!r})")
            continue
        for line in text.splitlines():
            if line.startswith(D9_SERIES) and "_bucket" not in line:
                cs.say(f"{name}: {line}")


def run() -> None:
    from fabric_tpu.bccsp.factory import FactoryOpts, init_factories
    from fabric_tpu.testing.procnet import (node_status, wait_orderer_leader,
                                            wait_status)
    init_factories(FactoryOpts(default="SW"))
    base = tempfile.mkdtemp(prefix="drill_big_block_")
    nw = cs.Network(base, mesh=False)
    for path in nw.net["peers"]:
        cfg = cs.read_json(path)
        cfg["bccsp"] = "SW"
        cfg.pop("bccsp_degrade", None)
        cs.write_json(path, cfg)
    from fabric_tpu.node.provision import free_ports
    orderer_ops = {}
    for path, port in zip(nw.net["orderers"],
                          free_ports(len(nw.net["orderers"]))):
        cfg = cs.read_json(path)
        cfg["ops_port"] = port
        cs.write_json(path, cfg)
        orderer_ops[os.path.basename(path)[:-5]] = f"http://127.0.0.1:{port}"
    try:
        nw.start()
        leader = wait_orderer_leader(nw.orderers, nw.signer, nw.msps,
                                     deadline_s=90.0)
        for org in cs.PEER_ORGS:
            wait_status(nw.peer_addr[org], nw.signer, nw.msps,
                        lambda st: True, f"peer {org} serving", 180.0)
        h0 = nw.statuses()[cs.PEER_ORGS[0]]["height"]
        lift_batch_limits(nw, leader)
        nw.wait_heights(h0 + 1, 60.0)
        cs.say(f"config block committed on every peer (height {h0 + 1})")

        t0 = time.monotonic()
        envs = cs.build_big_block_envelopes(nw, N_TX, cs.SEED + 1)
        size = sum(len(raw) for raw, _, _ in envs)
        cs.say(f"{len(envs)} envelopes, {size / 1e6:.1f} MB, built in "
               f"{time.monotonic() - t0:.1f} s")
        term0 = node_status(leader, nw.signer, nw.msps)["term"]
        t1 = time.monotonic()
        broadcast_all(nw, leader, envs)
        cs.say(f"orderer accepted all {len(envs)} envelopes in "
               f"{time.monotonic() - t1:.1f} s (raft term {term0})")
        try:
            nw.wait_heights(h0 + 2, COMMIT_DEADLINE_S)
        except Exception as exc:
            say_raft_counters(orderer_ops)
            sys.stderr.write(f"peers: {nw.statuses()}\n")
            for addr in nw.orderers:
                sys.stderr.write(f"orderer {addr}: "
                                 f"{node_status(addr, nw.signer, nw.msps)}\n")
            sys.stderr.write(f"raft term at the broadcast: {term0}\n")
            for name in sorted(nw.procs):
                if name.startswith("orderer"):
                    sys.stderr.write(f"---- tail of {name}.log ----\n"
                                     + nw.log_tail(name) + "\n")
            raise cs.SmokeFailure(
                f"the {len(envs)}-tx block reached no peer in "
                f"{COMMIT_DEADLINE_S:.0f} s: {exc}")
        cs.say(f"every peer committed it {time.monotonic() - t1:.1f} s after "
               "the first broadcast (raft term now "
               f"{node_status(leader, nw.signer, nw.msps)['term']})")
        say_raft_counters(orderer_ops)
        want = [(txid, cs.POLICY_FAILURE if tampered else cs.VALID)
                for _, txid, tampered in envs]
        for org in cs.PEER_ORGS:
            (block,) = nw.fetch_blocks(org, h0 + 1, h0 + 2)
            cs.check(cs.block_flags(block) == want,
                     f"{org}: one block of {len(want)} tx with the "
                     "expected flags")
        cs.check_same_ledger(nw)
    finally:
        nw.stop()
        shutil.rmtree(base, ignore_errors=True)


if __name__ == "__main__":
    try:
        run()
    except cs.SmokeFailure as exc:
        sys.stderr.write(f"drill FAILED: {exc}\n")
        sys.exit(1)
    print("drill ok", flush=True)
