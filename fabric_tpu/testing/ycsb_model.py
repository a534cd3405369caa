"""YCSB over a key-value contract in plain Python: the reference the
system is compared with.

YCSB's core workload (Cooper et al., SoCC 2010) as Blockbench's `kvstore`
macro benchmark drives it (Dinh et al., SIGMOD 2017), written again from
their definitions and sharing no code with `ledger/`, `committer/` or
`chaincode/kvstore.py`:

  key naming       `user` + FNV-64 of the record number
                   (`insertorder=hashed`, `Utils.fnvhash64`: xor then
                   multiply over the number's eight octets, low first,
                   in signed 64-bit arithmetic, absolute value);
  key choice       `ScrambledZipfianGenerator`: Gray's zipfian over
                   10,000,000,000 items with constant 0.99 and the
                   precomputed zeta, its draw hashed onto the records;
  record           ten fields `field0`..`field9` of 100 printable bytes,
                   one state value `field0=<bytes> field1=<bytes> ... `
                   as Blockbench's driver joins them, drawn from the seed
                   and the transaction's place in the chain;
  store            a dict.

A chain is a load phase that inserts every record in record order and a
run phase of workload A's updates (`write` of a whole record to a key
the zipfian draws; A's reads are evaluated at a peer and never ordered).
Every transaction is a blind write, so the serial block rule has two
outcomes here, and keeps the third for a transaction that read:

  ENDORSEMENT_POLICY_FAILURE  if an endorsement of it was tampered with,
  MVCC_READ_CONFLICT          else if a key it read is no longer at the
                              version it read,
  VALID                       otherwise, and only then its writes count.

`plan_txs` is a pure function of its arguments.  Which transactions
share a block is the orderer's business: `cut` hands the built envelopes
to the program's own `BlockCutter` under the deployment's batch sizes
(envelope sizes depend on the signatures' encodings, so a cut may move
by a transaction between two builds of one plan; the plan does not).
"""

from __future__ import annotations

import random

VALID, POLICY_FAILURE, MVCC_CONFLICT = 0, 10, 11

FIELDS, FIELD_LENGTH = 10, 100
ZIPFIAN_CONSTANT = 0.99
ZETAN = 26.46902820178302        # zeta(ITEM_COUNT, 0.99), as YCSB carries it
ITEM_COUNT = 10_000_000_000
_MASK = (1 << 64) - 1
# 94 printable bytes, none a space: the record's separator
_PRINTABLE = bytes(33 + b % 94 for b in range(256))


class Rejected(Exception):
    """The contract refuses: it raises SimulationError."""


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


class Store:
    """Every value, and the version of its last write."""

    def __init__(self):
        self.data = {}           # key -> bytes
        self.version = {}        # key -> [block, tx number]

    def simulate(self, fn: str, args) -> dict:
        """What an endorser's simulation of `fn(*args)` against this
        store records: reads with the versions read, writes (a value of
        None deletes), and the response payload.  Raises Rejected."""
        if fn == "write":
            key, value = args
            return {"fn": fn, "reads": [], "writes": [[key, bytes(value)]],
                    "payload": b"ok"}
        if fn == "read":
            (key,) = args
            if key not in self.data:
                raise Rejected(f"no such key: {key}")
            return {"fn": fn, "reads": [[key, self.version.get(key)]],
                    "writes": [], "payload": self.data[key]}
        if fn == "del":
            (key,) = args
            return {"fn": fn, "reads": [], "writes": [[key, None]],
                    "payload": b"ok"}
        raise Rejected(f"unknown function {fn!r}")

    def commit_block(self, number: int, txs: list) -> list:
        """The serial block rule over `txs` (each with `reads`, `writes`
        and `tampered`).  -> the validation codes; the VALID
        transactions' writes are applied."""
        codes = []
        for n, tx in enumerate(txs):
            if tx.get("tampered"):
                codes.append(POLICY_FAILURE)
            elif any(self.version.get(k) != v for k, v in tx["reads"]):
                codes.append(MVCC_CONFLICT)
            else:
                codes.append(VALID)
                for key, value in tx["writes"]:
                    if value is None:
                        self.data.pop(key, None)
                        self.version.pop(key, None)
                    else:
                        self.data[key] = value
                        self.version[key] = [number, n]
        return codes


# -- the chain ---------------------------------------------------------------

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


def as_simulated(tx: dict, seed: int) -> dict:
    """The tx with the read-write set its `write` leaves."""
    return dict(tx, reads=[], writes=[[tx["key"], record(seed, tx["serial"])]])


# -- envelopes, cuts, blocks -------------------------------------------------

def flip_last_byte(sig: bytes) -> bytes:
    """Still DER, no longer a signature of anything."""
    return sig[:-1] + bytes([sig[-1] ^ 0x01])


def rwset_of(sim: dict, chaincode: str):
    """A `Store.simulate` result's read-write set as the protocol's."""
    from fabric_tpu.protocol import KVRead, KVWrite, NsRwSet, TxRwSet, Version
    return TxRwSet((NsRwSet(
        chaincode,
        reads=tuple(KVRead(k, None if v is None else Version(*v))
                    for k, v in sim["reads"]),
        writes=tuple(KVWrite(k, is_delete=True) if v is None
                     else KVWrite(k, v) for k, v in sim["writes"])),))


def build_envelope(tx: dict, seed: int, channel: str, chaincode: str,
                   endorsers: list, creators: list) -> bytes:
    """One transaction as a serialized, endorsed, signed envelope.
    Every endorser signs; a tampered one has a byte of its second
    endorsement's signature flipped."""
    from fabric_tpu.protocol import (ChaincodeAction, Endorsement,
                                     Transaction, TransactionAction, build)
    from fabric_tpu.protocol.types import TX_ENDORSER
    creator = creators[tx["creator"]]
    nonce = bytes.fromhex(tx["nonce"])
    txid = build.compute_txid(nonce, creator.serialize())
    value = record(seed, tx["serial"])
    sim = {"reads": [], "writes": [[tx["key"], value]]}
    ta = TransactionAction(
        build.proposal_hash(channel, txid, chaincode,
                            [b"write", tx["key"].encode(), value]),
        ChaincodeAction(chaincode, "1.0", rwset_of(sim, chaincode),
                        response_payload=b"ok"))
    ends = [build.endorse(ta, e) for e in endorsers]
    if tx["tampered"]:
        ends[1] = Endorsement(ends[1].endorser,
                              flip_last_byte(ends[1].signature))
    ta = TransactionAction(ta.proposal_hash, ta.action, tuple(ends))
    return build.signed_envelope(
        TX_ENDORSER, channel, Transaction((ta,)).to_dict(), creator,
        nonce=nonce).serialize()


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


def cut(raws, batch: dict) -> list:
    """[(envelopes, reason)] of one phase's envelopes, in order."""
    cutter = Cutter(batch)
    out = []
    for raw in raws:
        out.extend(cutter.ordered(raw))
    return out + cutter.flush()


def chain_block(data: list, number: int, previous_hash: bytes):
    """-> (serialized Block, its header hash)."""
    from fabric_tpu.protocol import block_header_hash
    from fabric_tpu.protocol.types import (Block, BlockHeader, BlockMetadata,
                                           block_data_hash)
    header = BlockHeader(number, previous_hash, block_data_hash(data))
    return (Block(header, data, BlockMetadata()).serialize(),
            block_header_hash(header))


def build_chain(txs: list, seed: int, batch: dict, channel: str,
                chaincode: str, endorsers: list, creators: list) -> list:
    """The whole chain: each phase's envelopes cut under `batch`.
    -> [{"number", "txs", "reason", "raw"}], txs as `as_simulated`."""
    blocks, prev = [], b"\x00" * 32
    for phase in ("load", "run"):
        mine = [tx for tx in txs if tx["phase"] == phase]
        raws = [build_envelope(tx, seed, channel, chaincode, endorsers,
                               creators) for tx in mine]
        at = 0
        for data, reason in cut(raws, batch):
            raw, prev = chain_block(data, len(blocks), prev)
            blocks.append({"number": len(blocks), "reason": reason,
                           "raw": raw,
                           "txs": [as_simulated(tx, seed)
                                   for tx in mine[at:at + len(data)]]})
            at += len(data)
    return blocks
