"""A backlog of endorsed blocks, made from the seed.

`plan_backlog` is a pure function of its arguments: which key each
transaction bumps, who creates it, whether one endorsement signature is
tampered, the version it read, and — by a serial simulation of the
ledger's rules, sharing no code with the program — the validation code
every committer must give it.  `build_block_data` turns one block's plan
into signed envelopes (the slow part: four OpenSSL signatures per
transaction), and is what the parallel workers run.

Adapted from `chip_smoke.py`'s `build_big_block_envelopes`: that one
writes blind, this one records `bump`'s read-modify-write rw-set (one
read with its version, one write) so MVCC has work to do and
intra-block repeats conflict.
"""

from __future__ import annotations

import json
import random

VALID, POLICY_FAILURE, MVCC_CONFLICT = 0, 10, 11


def key_name(k: int) -> str:
    return "k%06d" % k


def plan_backlog(seed: int, n_blocks: int, block_tx: int, keyspace: int,
                 n_creators: int, tamper_every: int,
                 first_block: int = 0) -> list:
    """-> [block plan], a block plan being {"number", "txs": [tx]} with
    tx = {"key", "creator", "tampered", "read": None | [block, txnum],
    "value", "nonce": hex, "code"}."""
    rng = random.Random(seed)
    version = {}                 # key -> [block, txnum] of its last write
    count = {}                   # key -> value bump would read
    blocks = []
    for b in range(n_blocks):
        number = first_block + b
        # every tx of a block was simulated against the state committed
        # before the block: that is what a backlog of blocks cut under
        # load holds, and what makes an intra-block repeat a conflict
        written = set()
        txs = []
        commits = []
        for t in range(block_tx):
            key = rng.randrange(keyspace)
            tampered = t % tamper_every == tamper_every - 1
            tx = {"key": key, "creator": (b * block_tx + t) % n_creators,
                  "tampered": tampered, "read": version.get(key),
                  "value": count.get(key, 0) + 1,
                  "nonce": rng.randbytes(24).hex()}
            if tampered:
                tx["code"] = POLICY_FAILURE
            elif key in written:
                tx["code"] = MVCC_CONFLICT
            else:
                tx["code"] = VALID
                written.add(key)
                commits.append((key, [number, t], tx["value"]))
            txs.append(tx)
        for key, ver, value in commits:
            version[key] = ver
            count[key] = value
        blocks.append({"number": number, "txs": txs})
    return blocks


def expected_flags_hex(block_plan: dict) -> str:
    return bytes(tx["code"] for tx in block_plan["txs"]).hex()


def flip_last_byte(sig: bytes) -> bytes:
    """Still DER, no longer a signature of anything."""
    return sig[:-1] + bytes([sig[-1] ^ 0x01])


def load_identities(deployment_file: str):
    """(endorsers, creators) as signing identities, from the file the
    deployment wrote: the peers' own keys endorse, the enrolled clients
    create."""
    from fabric_tpu.node.orderer import load_signing_identity
    with open(deployment_file) as f:
        dep = json.load(f)

    def load(path):
        with open(path) as f:
            cfg = json.load(f)
        return load_signing_identity(cfg["mspid"], cfg["cert_pem"].encode(),
                                     cfg["key_pem"].encode())
    return ([load(p) for p in dep["peer_cfgs"]],
            [load(p) for p in dep["client_cfgs"]])


def build_block_data(block_plan: dict, channel: str, chaincode: str,
                     endorsers: list, creators: list) -> list:
    """One block's serialized envelopes, in order."""
    from fabric_tpu.protocol import (ChaincodeAction, Endorsement, KVRead,
                                     KVWrite, NsRwSet, Transaction,
                                     TransactionAction, TxRwSet, Version,
                                     build)
    from fabric_tpu.protocol.types import TX_ENDORSER

    data = []
    for tx in block_plan["txs"]:
        creator = creators[tx["creator"]]
        nonce = bytes.fromhex(tx["nonce"])
        txid = build.compute_txid(nonce, creator.serialize())
        key = key_name(tx["key"])
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
            ends[1] = Endorsement(ends[1].endorser,
                                  flip_last_byte(ends[1].signature))
        ta = TransactionAction(ta.proposal_hash, ta.action, tuple(ends))
        env = build.signed_envelope(TX_ENDORSER, channel,
                                    Transaction((ta,)).to_dict(), creator,
                                    nonce=nonce)
        data.append(env.serialize())
    return data


def chain_block(data: list, number: int, previous_hash: bytes):
    """(serialized Block, its header hash): the block as the deliver
    service would hand it to a peer, and the link the next one needs."""
    from fabric_tpu.protocol import block_header_hash
    from fabric_tpu.protocol.types import (Block, BlockHeader, BlockMetadata,
                                           block_data_hash)
    header = BlockHeader(number, previous_hash, block_data_hash(data))
    return (Block(header, data, BlockMetadata()).serialize(),
            block_header_hash(header))


GENESIS_PREVIOUS_HASH = b"\x00" * 32


# -- parallel workers ---------------------------------------------------------

_IDENTITIES = {}                 # per worker process: loaded once


def worker_build(deployment_file: str, channel: str, chaincode: str,
                 block_plan: dict) -> list:
    """`build_block_data` as a pool's task: a spawned worker loads the
    deployment's identities on its first block."""
    if deployment_file not in _IDENTITIES:
        _IDENTITIES[deployment_file] = load_identities(deployment_file)
    endorsers, creators = _IDENTITIES[deployment_file]
    return build_block_data(block_plan, channel, chaincode, endorsers,
                            creators)
