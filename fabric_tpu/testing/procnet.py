"""nwo-style process harness: a provisioned network as OS processes.

The reference's integration/nwo (network.go:173) starts every orderer
and peer as its own process and drives them from the client's side.
This is that harness for `provision_network` output: nodes start the
way the README's "Running" section says users start them
(`python -m fabric_tpu.node.orderer|peer <cfg>`), and the helpers wait
on what a client can observe (status RPCs), never on process internals.

Shared by tests/test_network.py, tests/smoke_cluster_trace.py and
chip_smoke.py.  Never imports jax: the process that drives a network
must not take the chip from the node that owns it.
"""

from __future__ import annotations

import json
import subprocess
import sys
import time
from typing import Callable, Dict, Iterable, Optional

from fabric_tpu.comm import connect
from fabric_tpu.config import Bundle, ChannelConfig
from fabric_tpu.node.orderer import load_signing_identity


def spawn_node(module: str, cfg_path: str, env: Optional[dict] = None,
               log_path: Optional[str] = None) -> subprocess.Popen:
    """Start `python -m <module> <cfg_path>`; output goes to `log_path`
    (appended) or is dropped."""
    if log_path is None:
        out = subprocess.DEVNULL
    else:
        out = open(log_path, "ab")
    try:
        return subprocess.Popen([sys.executable, "-m", module, cfg_path],
                                env=env, stdout=out,
                                stderr=subprocess.STDOUT)
    finally:
        if log_path is not None:
            out.close()     # the child holds its own descriptor


def stop_nodes(procs: Iterable[subprocess.Popen],
               timeout_s: float = 10.0) -> None:
    procs = list(procs)
    for proc in procs:
        if proc.poll() is None:
            proc.kill()
    for proc in procs:
        try:
            proc.wait(timeout=timeout_s)
        except subprocess.TimeoutExpired:
            pass


def load_client(path: str):
    """(client cfg dict, signing identity, channel MSPs) from one of the
    provisioner's client/admin JSON files."""
    with open(path) as f:
        cc = json.load(f)
    signer = load_signing_identity(cc["mspid"], cc["cert_pem"].encode(),
                                   cc["key_pem"].encode())
    bundle = Bundle(ChannelConfig.deserialize(
        bytes.fromhex(cc["channel_config_hex"])))
    return cc, signer, bundle.msps


def node_status(addr, signer, msps, timeout: float = 5.0) -> dict:
    """One `status` RPC against an orderer or a peer."""
    conn = connect(tuple(addr), signer, msps, timeout=timeout)
    try:
        return conn.call("status", {}, timeout=timeout + 5.0)
    finally:
        conn.close()


def wait_status(addr, signer, msps, pred: Callable[[dict], bool],
                what: str, deadline_s: float) -> dict:
    """Poll `status` at `addr` until pred(status) holds."""
    t0, last = time.time(), None
    while time.time() - t0 < deadline_s:
        try:
            st = node_status(addr, signer, msps, timeout=2.0)
            if pred(st):
                return st
            last = st
        except Exception as exc:     # node not up yet: keep polling
            last = exc
        time.sleep(0.3)
    raise AssertionError(f"timeout waiting for {what}: {last}")


def wait_orderer_leader(orderers, signer, msps, deadline_s: float = 45.0):
    """The address of whichever orderer reports role == leader."""
    t0, last = time.time(), None
    while time.time() - t0 < deadline_s:
        for addr in orderers:
            try:
                st = node_status(addr, signer, msps, timeout=2.0)
                if st["role"] == "leader":
                    return tuple(addr)
                last = st
            except Exception as exc:
                last = exc
        time.sleep(0.3)
    raise AssertionError(f"no orderer leader: {last}")


def wait_peer_heights(peers: Dict[str, tuple], signer, msps, want: int,
                      deadline_s: float = 120.0) -> Dict[str, dict]:
    """Poll every peer's status until all report height >= want;
    returns {name: status}."""
    t0 = time.time()
    sts: Dict[str, Optional[dict]] = {}
    while time.time() - t0 < deadline_s:
        sts = {}
        for name, addr in peers.items():
            try:
                sts[name] = node_status(addr, signer, msps)
            except Exception:
                sts[name] = None
        if all(s is not None and s["height"] >= want for s in sts.values()):
            return sts
        time.sleep(0.4)
    raise AssertionError(f"peers never reached height {want}: {sts}")
