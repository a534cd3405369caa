"""A deployment whose clients are an application's users, and its backlog.

`gen/deployment.py` enrols `client_identities` clients as one config
file each and loads every one; a channel whose account holders are
themselves Fabric identities has a hundred thousand.  A configuration
with an `enrolment` key

    "enrolment": {"clients": 100000, "scheme": "p256",
                  "revoked_share": 0.01}

(and no `client_identities`) needs the program's roll — one artefact an
org, `provision_network(roll_size=, roll_revoked=)` — so this file
brings the deployment step of its own, only what a catch-up driver uses
of one: node configs, the environment, the file a generator's worker
reads.  `clients` are dealt to the peer orgs org by org in turn;
`revoked_share` of them, drawn from the seed org by org, are revoked:
each org's CRL, signed by its CA, is in that org's MSP in the channel
config from the genesis block.  The configuration's `forge_every` is
traffic: one envelope in so many comes from a forger.

The backlog is `gen/backlog.py`'s plan (same keys, nonces, tampered
positions and creator rule: transaction t of block b is signed by
enrolment (block_tx * b + t) mod clients), simulated again with the
roll in hand: a transaction whose creator is revoked or forged is
BAD_CREATOR_SIGNATURE and writes nothing, then `backlog`'s rules.  The
simulation shares no code with the program's `msp/`: who is revoked and
which envelopes are forged are decided here, from the seed.  Never
imports jax.
"""

from __future__ import annotations

import datetime
import inspect
import os
import random
import time

from gen import backlog
from gen.deployment import read_json, write_json
from harness import BenchFailure, say

BAD_CREATOR = 4                  # ValidationCode.BAD_CREATOR_SIGNATURE


def require_program_support() -> None:
    """A program from before the roll cannot enrol this deployment (it
    would write a client config a member, each carrying the channel
    config): said before anything is started."""
    from fabric_tpu.node.provision import provision_network
    if "roll_size" not in inspect.signature(provision_network).parameters:
        raise BenchFailure("the program cannot enrol a roll of clients "
                           "(provision_network has no roll_size): this "
                           "deployment's 100,000 enrolments need one "
                           "roll an org and the CRLs in the channel config")


def draw_revoked(seed: int, clients: int, n_orgs: int, share: float) -> list:
    """The revoked roll members (indices, ascending): round(share x
    clients) of them, dealt to the orgs as the members are and drawn
    within each org from the seed."""
    total = round(share * clients)
    revoked = []
    for k in range(n_orgs):
        members = range(k, clients, n_orgs)        # org k's, by index
        n = len(range(k, total, n_orgs))
        rng = random.Random(f"enrolled/{seed}/revoked/{k}")
        revoked.extend(rng.sample(members, n))
    return sorted(revoked)


class Deployment:
    """Provisioned from a configuration dict with `enrolment`."""

    def __init__(self, base: str, config: dict, repo: str,
                 device_peer_extra: dict, seed: int):
        from fabric_tpu.config import BatchConfig
        from fabric_tpu.node.provision import provision_network

        self.base = base
        self.config = config
        self.channel = config["channel"]
        self.chaincode = config["chaincode"]["name"]
        self.orgs = list(config["peer_orgs"])
        self.device_org = config["device_org"]
        enrolment = config["enrolment"]
        self.clients = int(enrolment["clients"])
        self.revoked = draw_revoked(seed, self.clients, len(self.orgs),
                                    float(enrolment["revoked_share"]))
        b = config["batch"]
        t0 = time.monotonic()
        self.net = provision_network(
            base, n_orderers=int(config["orderers"]), peer_orgs=self.orgs,
            peers_per_org=int(config["peers_per_org"]),
            channel_id=self.channel,
            batch=BatchConfig(int(b["max_message_count"]),
                              int(b["absolute_max_bytes"]),
                              int(b["preferred_max_bytes"]),
                              float(b["timeout_s"])),
            roll_size=self.clients, roll_revoked=self.revoked)
        say(f"enrolment: {self.clients} clients over {len(self.orgs)} orgs, "
            f"{len(self.revoked)} revoked, with the rest of the "
            f"provisioning {time.monotonic() - t0:.1f} s")
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
        self.file = os.path.join(base, "deployment.json")
        write_json(self.file, {
            "peer_cfgs": [self.peer_cfg_path[o] for o in self.orgs],
            "rolls": [self.net["rolls"][o] for o in self.orgs]})


# -- the plan -------------------------------------------------------------------


def plan_backlog(seed: int, n_blocks: int, block_tx: int, keyspace: int,
                 clients: int, tamper_every: int, forge_every: int,
                 revoked) -> list:
    """`backlog.plan_backlog`'s plan, simulated again with the roll in
    hand.  A tx gains "revoked" (its creator's enrolment is) and
    "forged" (the envelope comes from a forger under that member's
    name: one in `forge_every` over the chain); "tampered" (`backlog`'s
    rule: one endorsement byte flipped) stays true only where the flip
    is what the committer meets — a transaction of a revoked or forged
    creator is refused before its endorsements are looked at, and is
    not flipped as well.  "read", "value" and "code" are simulated
    anew: a refused transaction writes nothing."""
    if block_tx > clients:
        raise ValueError("a block's creators cannot be distinct: "
                         f"{block_tx} tx over {clients} clients")
    blocks = backlog.plan_backlog(seed, n_blocks, block_tx, keyspace,
                                  clients, tamper_every)
    gone = frozenset(revoked)
    version, count = {}, {}
    for blk in blocks:
        written, commits = set(), []
        for t, tx in enumerate(blk["txs"]):
            nth = blk["number"] * block_tx + t
            tx["forged"] = nth % forge_every == forge_every // 2
            tx["revoked"] = tx["creator"] in gone and not tx["forged"]
            bad_creator = tx["forged"] or tx["revoked"]
            tx["tampered"] = tx["tampered"] and not bad_creator
            key = tx["key"]
            tx["read"] = version.get(key)
            tx["value"] = count.get(key, 0) + 1
            if bad_creator:
                tx["code"] = BAD_CREATOR
            elif tx["tampered"]:
                tx["code"] = backlog.POLICY_FAILURE
            elif key in written:
                tx["code"] = backlog.MVCC_CONFLICT
            else:
                tx["code"] = backlog.VALID
                written.add(key)
                commits.append((key, [blk["number"], t], tx["value"]))
        for key, ver, value in commits:
            version[key] = ver
            count[key] = value
    return blocks


# -- the roll, as a generator's worker reads it -----------------------------------


class Roll:
    """The enrolled clients, from the rolls the deployment wrote: a
    member is loaded when a block needs it (each signs once in 200
    blocks of 500), and a forger is made under a member's name."""

    def __init__(self, roll_paths: list):
        self.orgs = [read_json(p) for p in roll_paths]
        self._rogue = None       # the forger's "CA" key, one a process

    def member(self, index: int):
        from fabric_tpu.node.orderer import load_signing_identity
        org = self.orgs[index % len(self.orgs)]
        j = index // len(self.orgs)
        return load_signing_identity(org["mspid"], org["cert_pem"][j].encode(),
                                     org["key_pem"][j].encode())

    def forger(self, index: int):
        """A signing identity whose certificate carries member
        `index`'s subject and its CA's issuer name, with a key of the
        forger's own, signed by a key that is not the CA's."""
        from fabric_tpu.bccsp import SCHEME_P256
        from fabric_tpu.bccsp.sw import SigningKey
        from fabric_tpu.crypto import ec, hashes, x509
        from fabric_tpu.msp.identity import SigningIdentity
        if self._rogue is None:
            self._rogue = ec.generate_private_key(ec.SECP256R1())
        victim = self.member(index)
        key = ec.generate_private_key(ec.SECP256R1())
        now = datetime.datetime.now(datetime.timezone.utc)
        cert = (x509.CertificateBuilder()
                .subject_name(victim.cert.subject)
                .issuer_name(victim.cert.issuer)
                .public_key(key.public_key())
                .serial_number(x509.random_serial_number())
                .not_valid_before(now - datetime.timedelta(minutes=5))
                .not_valid_after(now + datetime.timedelta(days=3650))
                .add_extension(x509.BasicConstraints(ca=False,
                                                     path_length=None),
                               critical=True)
                .sign(self._rogue, hashes.SHA256()))
        return SigningIdentity(victim.mspid, cert,
                               SigningKey(SCHEME_P256, key))


_LOADED = {}                     # per worker process: loaded once


def worker_build(deployment_file: str, channel: str, chaincode: str,
                 block_plan: dict) -> list:
    """One block's serialized envelopes, as a pool's task:
    `backlog.build_block_data` with the block's creators taken off the
    roll (a forger where the plan says so)."""
    if deployment_file not in _LOADED:
        dep = read_json(deployment_file)
        _LOADED[deployment_file] = (load_endorsers(dep["peer_cfgs"]),
                                    Roll(dep["rolls"]))
    endorsers, roll = _LOADED[deployment_file]
    creators = {tx["creator"]: (roll.forger if tx["forged"]
                                else roll.member)(tx["creator"])
                for tx in block_plan["txs"]}
    return backlog.build_block_data(block_plan, channel, chaincode,
                                    endorsers, creators)


def load_endorsers(peer_cfgs: list) -> list:
    """The peers' own signing identities: they endorse."""
    from fabric_tpu.node.orderer import load_signing_identity
    out = []
    for path in peer_cfgs:
        cfg = read_json(path)
        out.append(load_signing_identity(cfg["mspid"],
                                         cfg["cert_pem"].encode(),
                                         cfg["key_pem"].encode()))
    return out
