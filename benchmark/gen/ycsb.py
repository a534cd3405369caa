"""YCSB over a key-value contract for the yardstick: generator and plain
model, from the seed.

The benchmark's own copy of `fabric_tpu/testing/ycsb_model.py`, as
`smallbank.py` is of the SmallBank model: the yardstick must not move
when the program does (`tests/test_ycsb.py` holds the two copies to the
same chain for the same seed).  Shares no code with the program's
`ledger/`, `committer/` or contract.

YCSB's core workload (Cooper et al., SoCC 2010) as Blockbench's `kvstore`
macro benchmark drives it (Dinh et al., SIGMOD 2017):

  key naming   `user` + FNV-64 of the record number (`insertorder=hashed`,
               `Utils.fnvhash64`);
  key choice   `ScrambledZipfianGenerator`: Gray's zipfian over
               10,000,000,000 items with constant 0.99 and the
               precomputed zeta, its draw hashed onto the records;
  record       ten fields `field0`..`field9` of 100 printable bytes, one
               state value `field0=<bytes> field1=<bytes> ... `, drawn
               from the seed and the transaction's place in the chain.

`iter_txs` is a pure function of its arguments: a load phase that inserts
every record in record order, none tampered, then workload A's updates —
`write(key, record)`, a blind write (0 reads, 1 write) — over keys the
zipfian draws, one in `tamper_every` tampered.  The serial block rule
has two outcomes on such a chain: ENDORSEMENT_POLICY_FAILURE for a
tampered transaction, VALID otherwise; MVCC_READ_CONFLICT never.

Which transactions share a block is the orderer's business: `Cutter`
hands the built envelopes to the program's own `BlockCutter` under the
deployment's `batch` (envelope sizes depend on the signatures'
encodings, so where a block ends is known only once they are built).
"""

from __future__ import annotations

import hashlib
import random

VALID, POLICY_FAILURE, MVCC_CONFLICT = 0, 10, 11

FIELDS, FIELD_LENGTH = 10, 100
ZIPFIAN_CONSTANT = 0.99
ZETAN = 26.46902820178302        # zeta(ITEM_COUNT, 0.99), as YCSB carries it
ITEM_COUNT = 10_000_000_000
_MASK = (1 << 64) - 1
# 94 printable bytes, none a space: the record's separator
_PRINTABLE = bytes(33 + b % 94 for b in range(256))


def fnvhash64(val: int) -> int:
    """YCSB's `Utils.fnvhash64` of a non-negative number."""
    h = 0xCBF29CE484222325
    for _ in range(8):
        h = ((h ^ (val & 0xFF)) * 1099511628211) & _MASK
        val >>= 8
    return abs(h - (1 << 64) if h >> 63 else h)      # Math.abs of a long


def key_name(record: int) -> str:
    return "user%d" % fnvhash64(record)


class ScrambledZipfian:
    """Records 0..items-1, a few of them hot and the hot ones scattered."""

    def __init__(self, items: int):
        self.items = items
        theta = ZIPFIAN_CONSTANT
        self.alpha = 1.0 / (1.0 - theta)
        self.zeta2 = 1.0 + 0.5 ** theta
        self.eta = ((1.0 - (2.0 / ITEM_COUNT) ** (1.0 - theta))
                    / (1.0 - self.zeta2 / ZETAN))

    def rank(self, u: float) -> int:
        """Gray's zipfian over ITEM_COUNT items for a uniform u."""
        uz = u * ZETAN
        if uz < 1.0:
            return 0
        if uz < self.zeta2:
            return 1
        return int(ITEM_COUNT * (self.eta * u - self.eta + 1.0) ** self.alpha)

    def record_of(self, u: float) -> int:
        return fnvhash64(self.rank(u)) % self.items

    def draw(self, rng) -> int:
        return self.record_of(rng.random())


def record(seed: int, serial: int) -> bytes:
    """The record that transaction `serial` of the chain from `seed`
    writes: `field0=<100 bytes> ... field9=<100 bytes> `."""
    raw = random.Random((seed << 32) ^ serial).randbytes(
        FIELDS * FIELD_LENGTH).translate(_PRINTABLE)
    return b"".join(b"field%d=%s " % (i, raw[i * FIELD_LENGTH:
                                             (i + 1) * FIELD_LENGTH])
                    for i in range(FIELDS))


def iter_txs(seed: int, recordcount: int, updates: int, n_creators: int,
             tamper_every: int):
    """Yields the chain's transactions in order: `recordcount` inserts
    (phase "load", record order, none tampered), then `updates` updates
    (phase "run", keys from the scrambled zipfian, one in `tamper_every`
    tampered).  A tx is {"serial", "phase", "record", "key", "creator",
    "tampered", "nonce" (hex)}; what it writes is `record(seed, serial)`."""
    rng = random.Random(seed)
    draw = ScrambledZipfian(recordcount).draw
    for serial in range(recordcount + updates):
        load = serial < recordcount
        n = serial if load else draw(rng)
        run_index = serial - recordcount
        yield {"serial": serial, "phase": "load" if load else "run",
               "record": n, "key": key_name(n),
               "creator": serial % n_creators,
               "tampered": (not load and run_index % tamper_every
                            == tamper_every - 1),
               "nonce": rng.randbytes(24).hex()}


def plan_txs(*args) -> list:
    return list(iter_txs(*args))


def code_of(tx: dict) -> int:
    """The serial block rule on a chain of blind writes."""
    return POLICY_FAILURE if tx["tampered"] else VALID


# -- envelopes ---------------------------------------------------------------------

def build_envelopes(txs: list, seed: int, channel: str, chaincode: str,
                    endorsers: list, creators: list) -> list:
    """The transactions as serialized, endorsed, signed envelopes, in
    order.  Every endorser signs; a tampered one has a byte of its
    second endorsement's signature flipped."""
    from fabric_tpu.protocol import (ChaincodeAction, Endorsement, KVWrite,
                                     NsRwSet, Transaction, TransactionAction,
                                     TxRwSet, build)
    from fabric_tpu.protocol.types import TX_ENDORSER
    from gen.backlog import flip_last_byte

    raws = []
    for tx in txs:
        creator = creators[tx["creator"]]
        nonce = bytes.fromhex(tx["nonce"])
        txid = build.compute_txid(nonce, creator.serialize())
        value = record(seed, tx["serial"])
        rwset = TxRwSet((NsRwSet(chaincode, reads=(),
                                 writes=(KVWrite(tx["key"], value),)),))
        ta = TransactionAction(
            build.proposal_hash(channel, txid, chaincode,
                                [b"write", tx["key"].encode(), value]),
            ChaincodeAction(chaincode, "1.0", rwset, response_payload=b"ok"))
        ends = [build.endorse(ta, e) for e in endorsers]
        if tx["tampered"]:
            ends[1] = Endorsement(ends[1].endorser,
                                  flip_last_byte(ends[1].signature))
        ta = TransactionAction(ta.proposal_hash, ta.action, tuple(ends))
        raws.append(build.signed_envelope(
            TX_ENDORSER, channel, Transaction((ta,)).to_dict(), creator,
            nonce=nonce).serialize())
    return raws


_IDENTITIES = {}                 # per worker process: loaded once


def worker_build(deployment_file: str, channel: str, chaincode: str,
                 seed: int, txs: list) -> list:
    """`build_envelopes` as a pool's task: a spawned worker loads the
    deployment's identities on its first chunk."""
    from gen.backlog import load_identities
    if deployment_file not in _IDENTITIES:
        _IDENTITIES[deployment_file] = load_identities(deployment_file)
    endorsers, creators = _IDENTITIES[deployment_file]
    return build_envelopes(txs, seed, channel, chaincode, endorsers, creators)


# -- the cut -----------------------------------------------------------------------

class Cutter:
    """The program's own `BlockCutter` under the deployment's `batch`,
    fed serialized envelopes; says why each batch was cut: "count" (it
    holds `max_message_count`), "oversize" (one message over the
    preferred size, alone), "bytes" (the next message would have passed
    the preferred size) or "end" (the batch timer's cut: `flush`)."""

    def __init__(self, batch: dict):
        from fabric_tpu.config import BatchConfig
        from fabric_tpu.orderer.blockcutter import BlockCutter
        self.config = BatchConfig(int(batch["max_message_count"]),
                                  int(batch["absolute_max_bytes"]),
                                  int(batch["preferred_max_bytes"]),
                                  float(batch["timeout_s"]))
        self._cutter = BlockCutter(self.config)

    def _why(self, data: list) -> str:
        if len(data) >= self.config.max_message_count:
            return "count"
        if len(data) == 1 and len(data[0]) > self.config.preferred_max_bytes:
            return "oversize"
        return "bytes"

    def ordered(self, raw: bytes) -> list:
        """-> [(envelopes, reason)] cut by this envelope's arrival."""
        from fabric_tpu.protocol import Envelope
        batches, _ = self._cutter.ordered(Envelope.deserialize(raw))
        return [(data, self._why(data)) for data in batches]

    def flush(self) -> list:
        data = self._cutter.cut()
        return [(data, "end")] if data else []


def cut_chain(envelopes, batch: dict):
    """Yields the chain's blocks as its envelopes arrive — `envelopes`
    an iterable of (phase, serialized envelope) in chain order:
    {"number", "phase", "reason", "first" (serial of its first tx), "txs"
    (how many), "data"}.  Each phase is cut by a cutter of its own: the
    batch timer ends it."""
    number = first = 0
    cutter = phase = None

    def blocks(cuts):
        nonlocal number, first
        for data, reason in cuts:
            yield {"number": number, "phase": phase, "reason": reason,
                   "first": first, "txs": len(data), "data": data}
            number, first = number + 1, first + len(data)

    for tx_phase, raw in envelopes:
        if tx_phase != phase:
            if cutter is not None:
                yield from blocks(cutter.flush())
            cutter, phase = Cutter(batch), tx_phase
        yield from blocks(cutter.ordered(raw))
    if cutter is not None:
        yield from blocks(cutter.flush())


# -- what the judge keeps ------------------------------------------------------------

def digest(value) -> str:
    return None if value is None else hashlib.sha256(value).hexdigest()


def records_after(written: list, blocks: list, upto: int, seed: int,
                  recordcount: int) -> list:
    """SHA-256 (hex) of every record 0..recordcount-1 after the blocks
    numbered <= `upto`, None for one never written: the last VALID write
    of each, from the plan alone.  `written[serial]` is the record that
    transaction wrote, -1 where it was tampered with; `blocks` are
    `cut_chain`'s, without their data."""
    last = [None] * recordcount
    for block in blocks:
        if block["number"] > upto:
            break
        for serial in range(block["first"], block["first"] + block["txs"]):
            if written[serial] >= 0:
                last[written[serial]] = serial
    return [None if s is None else digest(record(seed, s)) for s in last]
