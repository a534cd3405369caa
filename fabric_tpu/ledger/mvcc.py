"""MVCC state validation — must stay bit-identical to the reference's.

Reference parity: core/ledger/kvledger/txmgmt/validation/validator.go —
validateAndPrepareBatch (:83), validateKVRead (:175), and
rangequery_validator.go.  Semantics preserved exactly:

- txs are considered in block order; only txs whose flag is still VALID
  after the signature/policy gate are state-validated;
- a read is valid iff its recorded version equals the key's current
  committed version, where "current" includes writes of *preceding valid
  txs in this same block* (the in-flight update batch);
- range queries are re-executed against committed-state-merged-with-batch
  and compared read-for-read; a mismatch (changed value version, added or
  removed key) is a PHANTOM_READ_CONFLICT;
- a valid tx's writes join the batch at Version(block_num, tx_num).

The verify-then-gate restructure (SURVEY.md §7) does not touch this pass:
it runs after the TPU verdict bitmap has been folded into the flags.
"""

from __future__ import annotations

from typing import List, Optional, Tuple

from fabric_tpu.protocol import (
    Envelope,
    KVRead,
    NsRwSet,
    Transaction,
    TxRwSet,
    Version,
)
from fabric_tpu.protocol.txflags import TxFlags, ValidationCode
from fabric_tpu.protocol.types import RangeQueryInfo, TX_ENDORSER

from .statedb import StateDB, UpdateBatch


def _read_conflict(db: StateDB, batch: UpdateBatch, ns: str,
                   read: KVRead) -> Optional[str]:
    """validateKVRead (validator.go:175): version equality, nil-safe.
    -> None when the read still holds, else who answered otherwise:
    "block" (a write an earlier valid tx of this block staged) or
    "state" (the committed state)."""
    found, vv = batch.get(ns, read.key)
    if not found:
        vv = db.get(ns, read.key)
    committed = None if vv is None else vv.version  # None: absent or deleted
    if committed is None and read.version is None:
        return None
    if (committed is None or read.version is None
            or committed.block_num != read.version.block_num
            or committed.tx_num != read.version.tx_num):
        return "block" if found else "state"
    return None


def _validate_read(db: StateDB, batch: UpdateBatch, ns: str,
                   read: KVRead) -> bool:
    return _read_conflict(db, batch, ns, read) is None


class MvccTally:
    """What one block's serial walk did, in plain ints: the ledger adds
    them to its counters once per block."""
    __slots__ = ("reads", "conflicts_block", "conflicts_state")

    def __init__(self):
        self.reads = self.conflicts_block = self.conflicts_state = 0


def _merged_range(db: StateDB, batch: UpdateBatch, ns: str,
                  start_key: str, end_key: str):
    """Committed range merged with the in-flight batch, key-ordered
    (the combined iterator in rangequery_validator.go)."""
    committed = {k: vv for k, vv in db.range_scan(ns, start_key, end_key)}
    for (bns, key), vv in batch.items():
        if bns != ns:
            continue
        if key < start_key or (end_key and key >= end_key):
            continue
        if vv is None:
            committed.pop(key, None)
        else:
            committed[key] = vv
    return sorted(committed.items())


def _validate_range_query(db: StateDB, batch: UpdateBatch, ns: str,
                          rq: RangeQueryInfo) -> bool:
    """Raw-reads replay: result set must match read-for-read.  If the
    recorded iterator was NOT exhausted, the replay may see extra trailing
    keys; any difference within the consumed prefix is a phantom."""
    actual = _merged_range(db, batch, ns, rq.start_key, rq.end_key)
    recorded = rq.reads
    if rq.itr_exhausted and len(actual) != len(recorded):
        return False
    if len(actual) < len(recorded):
        return False
    for rec, (key, vv) in zip(recorded, actual):
        if rec.key != key:
            return False
        if rec.version is None:
            return False  # recorded a missing key that now exists
        if (vv.version.block_num != rec.version.block_num
                or vv.version.tx_num != rec.version.tx_num):
            return False
    return True


def parse_endorser_tx(env: Envelope) -> Optional[Tuple[str, TxRwSet]]:
    """(txid, rwset) of an endorser tx envelope; None for other tx types.
    Decodes the payload exactly once — this runs per tx in the commit hot
    path, so no repeated FTLV decoding."""
    payload = env.payload_dict()
    ch = payload["header"]["channel_header"]
    if ch["type"] != TX_ENDORSER:
        return None
    tx = Transaction.from_dict(payload["data"])
    if not tx.actions:
        return None
    return ch["txid"], tx.actions[0].action.rwset


def extract_rwset(env: Envelope) -> Optional[TxRwSet]:
    """Compatibility wrapper over parse_endorser_tx."""
    parsed = parse_endorser_tx(env)
    return None if parsed is None else parsed[1]


def validate_and_prepare_batch(
        db: StateDB, block_num: int,
        envelopes: List[Envelope], flags: TxFlags,
        tally: Optional[MvccTally] = None,
) -> Tuple[UpdateBatch, List[Tuple[int, str, str, str, bytes, bool]]]:
    """validateAndPrepareBatch (validator.go:83).

    Mutates `flags` (MVCC_READ_CONFLICT / PHANTOM_READ_CONFLICT /
    BAD_RWSET) and returns (update_batch, history_writes) where
    history_writes = (tx_num, txid, ns, key, value, is_delete) of VALID txs.
    `tally`, when given, takes the reads validated and the conflicts.
    """
    batch = UpdateBatch()
    reads = against_block = against_state = 0
    history: List[Tuple[int, str, str, str, bytes, bool]] = []
    for tx_num, env in enumerate(envelopes):
        if not flags.is_valid(tx_num):
            continue
        try:
            parsed = parse_endorser_tx(env)
        except Exception:
            flags.set(tx_num, ValidationCode.BAD_RWSET)
            continue
        if parsed is None:
            continue  # config txs etc. don't carry kv rwsets
        txid, rwset = parsed
        ok = True
        for ns_rw in rwset.ns_rwsets:
            for read in ns_rw.reads:
                reads += 1
                against = _read_conflict(db, batch, ns_rw.namespace, read)
                if against is not None:
                    flags.set(tx_num, ValidationCode.MVCC_READ_CONFLICT)
                    if against == "block":
                        against_block += 1
                    else:
                        against_state += 1
                    ok = False
                    break
            if not ok:
                break
            for rq in ns_rw.range_queries:
                if not _validate_range_query(db, batch, ns_rw.namespace, rq):
                    flags.set(tx_num, ValidationCode.PHANTOM_READ_CONFLICT)
                    ok = False
                    break
            if not ok:
                break
        if not ok:
            continue
        version = Version(block_num, tx_num)
        for ns_rw in rwset.ns_rwsets:
            for w in ns_rw.writes:
                if w.is_delete:
                    batch.delete(ns_rw.namespace, w.key, version)
                else:
                    batch.put(ns_rw.namespace, w.key, w.value, version)
                history.append((tx_num, txid, ns_rw.namespace, w.key,
                                w.value, w.is_delete))
    if tally is not None:
        tally.reads += reads
        tally.conflicts_block += against_block
        tally.conflicts_state += against_state
    return batch, history
