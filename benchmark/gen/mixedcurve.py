"""A deployment whose orgs sign on different curves, and its backlog.

`gen/deployment.py` provisions every org on P-256 with a fixed argument
list; a configuration with `org_schemes` ({org: scheme}) needs the
program's per-org provisioning, so this file brings the deployment step
of its own — only what a catch-up driver uses of one: identities, node
configs, the environment, the file a generator's worker reads.  No
process is started here.

The backlog is `gen/backlog.py`'s (same plan, same rw-sets, same
expected flags); what differs is who signs — the deployment's own
identities, so the re-enrolled org's endorsements and creator
signatures are Ed25519 — and which endorsement a tampered envelope has
broken: `tamper_orgs` in turn, so that each kernel has to say no.
Never imports jax.
"""

from __future__ import annotations

import inspect
import itertools
import os

from gen import backlog
from gen.deployment import read_json, write_json
from harness import BenchFailure


def require_program_support() -> None:
    """A program from before per-org schemes cannot provision this
    deployment: said before anything is started."""
    from fabric_tpu.node.provision import provision_network
    if "org_schemes" not in inspect.signature(provision_network).parameters:
        raise BenchFailure("the program cannot provision a per-org "
                           "signature scheme (provision_network has no "
                           "org_schemes)")


class Deployment:
    """Provisioned from a configuration dict with `org_schemes`."""

    def __init__(self, base: str, config: dict, repo: str,
                 device_peer_extra: dict):
        from fabric_tpu.config import BatchConfig
        from fabric_tpu.node.provision import provision_network

        self.base = base
        self.config = config
        self.channel = config["channel"]
        self.chaincode = config["chaincode"]["name"]
        self.orgs = list(config["peer_orgs"])
        self.device_org = config["device_org"]
        b = config["batch"]
        n_clients = int(config["client_identities"])
        self.net = provision_network(
            base, n_orderers=int(config["orderers"]), peer_orgs=self.orgs,
            peers_per_org=int(config["peers_per_org"]),
            channel_id=self.channel,
            batch=BatchConfig(int(b["max_message_count"]),
                              int(b["absolute_max_bytes"]),
                              int(b["preferred_max_bytes"]),
                              float(b["timeout_s"])),
            clients_per_org=-(-n_clients // len(self.orgs)),
            org_schemes=dict(config["org_schemes"]))
        self.env = dict(os.environ)
        self.env["PYTHONPATH"] = os.pathsep.join(
            [repo] + self.env.get("PYTHONPATH", "").split(os.pathsep))
        self.peer_cfg_path = {}  # org -> path
        for path in self.net["peers"]:
            cfg = read_json(path)
            # node defaults: every peer verifies every signature itself
            cfg.pop("verify_once", None)
            if cfg["mspid"] == self.device_org:
                cfg.update(config["device_peer"])
                cfg.update(device_peer_extra)
            else:
                cfg.update(config["reference_peer"])
            write_json(path, cfg)
            self.peer_cfg_path[cfg["mspid"]] = path
        # the enrolled identities, org by org in turn
        pool = [p for turn in itertools.zip_longest(
            *(self.net["client_pool"][org] for org in self.orgs))
            for p in turn if p is not None][:n_clients]
        self.client_cfgs = pool
        self.file = os.path.join(base, "deployment.json")
        write_json(self.file, {
            "peer_cfgs": [self.peer_cfg_path[o] for o in self.orgs],
            "client_cfgs": pool})


def tampered_endorser(t: int, tamper_every: int, n_choices: int) -> int:
    """Which of `tamper_orgs` has its endorsement broken in the
    tampered transaction at position `t` of its block: in turn."""
    return (t // tamper_every) % n_choices


def build_block_data(block_plan: dict, channel: str, chaincode: str,
                     endorsers: list, creators: list, tamper_every: int,
                     tamper_at: list) -> list:
    """One block's serialized envelopes, in order: `gen/backlog.py`'s
    `build_block_data`, with the tampered endorsement taken in turn
    from the endorsers at positions `tamper_at`."""
    from fabric_tpu.protocol import (ChaincodeAction, Endorsement, KVRead,
                                     KVWrite, NsRwSet, Transaction,
                                     TransactionAction, TxRwSet, Version,
                                     build)
    from fabric_tpu.protocol.types import TX_ENDORSER

    data = []
    for t, tx in enumerate(block_plan["txs"]):
        creator = creators[tx["creator"]]
        nonce = bytes.fromhex(tx["nonce"])
        txid = build.compute_txid(nonce, creator.serialize())
        key = backlog.key_name(tx["key"])
        read = None if tx["read"] is None else Version(*tx["read"])
        rwset = TxRwSet((NsRwSet(
            chaincode, reads=(KVRead(key, read),),
            writes=(KVWrite(key, str(tx["value"]).encode()),)),))
        args = [b"bump", key.encode()]
        ta = TransactionAction(
            build.proposal_hash(channel, txid, chaincode, args),
            ChaincodeAction(chaincode, "1.0", rwset,
                            response_payload=str(tx["value"]).encode()))
        ends = [build.endorse(ta, e) for e in endorsers]
        if tx["tampered"]:
            at = tamper_at[tampered_endorser(t, tamper_every,
                                             len(tamper_at))]
            ends[at] = Endorsement(ends[at].endorser,
                                   backlog.flip_last_byte(ends[at].signature))
        ta = TransactionAction(ta.proposal_hash, ta.action, tuple(ends))
        env = build.signed_envelope(TX_ENDORSER, channel,
                                    Transaction((ta,)).to_dict(), creator,
                                    nonce=nonce)
        data.append(env.serialize())
    return data


_IDENTITIES = {}                 # per worker process: loaded once


def worker_build(deployment_file: str, channel: str, chaincode: str,
                 block_plan: dict, tamper_every: int,
                 tamper_at: list) -> list:
    """`build_block_data` as a pool's task."""
    if deployment_file not in _IDENTITIES:
        _IDENTITIES[deployment_file] = backlog.load_identities(
            deployment_file)
    endorsers, creators = _IDENTITIES[deployment_file]
    return build_block_data(block_plan, channel, chaincode, endorsers,
                            creators, tamper_every, tamper_at)
