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
- a valid tx's writes join the batch at Version(block_num, tx_num);
- a valid tx's delete of (ns, key) takes the key's validation parameter
  (`<ns>#meta`, key) with it, at the same version and whatever the rw-set
  said of `#meta` (committer/sbe.py): upstream keeps a key's metadata in
  its versioned value, so there the parameter cannot outlive the key.

The verify-then-gate restructure (SURVEY.md §7) does not touch this pass:
it runs after the TPU verdict bitmap has been folded into the flags.

One walk, two sources.  `validate_and_prepare_batch` is the one serial
walk and the oracle; its per-tx records come from one of two suppliers:

- the *envelope source* decodes each still-VALID envelope
  (`parse_endorser_tx`: the whole payload, endorsements and all) and
  names a key by its (namespace, key) pair;
- the *lane source* reads the block's `wire.LaneTable` — the C walker's
  one pass over the block (`BlockView.rwset_lanes`) — and names a key by
  its interned slot: keys are decoded and their committed versions
  fetched once a slot (`committed_versions`), no Envelope, Transaction
  or TxRwSet is built and `block.data` is not materialised.  The table
  is a pure function of the block's bytes, so whoever asks first opens
  it: the validator, while it waits for the device
  (`wire.prepare_lanes`), and the `ledger.mvcc` span then holds the walk
  alone; the commit (`lane_source_of`, inside the span) for a block
  nobody validated first.  `ledger_lane_table_opened_total{at}` says
  which.

Which one a block takes is read off the block (`lane_source_of`), set by
nobody: the lane source when the block is a `BlockView`, the extractor
is native, no two keys of the block collide in the hash, the table's tx
count is the flags', and no tx whose flag is still VALID has status
RANGE or UNKNOWN (range replay stays host work over decoded reads);
otherwise the whole block goes through the envelope source.

One walk, two forms.  Over a lane table the walk is one pass over the
table's arrays (`_array_walk`, native/fastmvcc.c): a slot's shard hashed
once, over the key's bytes in the block; the committed versions fetched
a shard at a time into three arrays; the decision in C, in lane order;
the batch and the history rows built from the surviving write lanes.
The per-transaction Python walk below stays as the envelope source's
walk, as the walk of a process whose extension did not build (`walk_of`
says which, and why) and as the oracle the array pass is held to
(tests/test_commit_lanes.py: three ways on the same bytes).
"""

from __future__ import annotations

import time
from typing import List, Optional, Tuple

import numpy as np

from fabric_tpu.protocol import wire
from fabric_tpu.protocol import (
    Envelope,
    Transaction,
    TxRwSet,
    Version,
)
from fabric_tpu.protocol.txflags import TxFlags, ValidationCode
from fabric_tpu.protocol.types import RangeQueryInfo, TX_ENDORSER

from .statedb import (META_SUFFIX, StateDB, UpdateBatch, VersionedValue,
                      _fastmvcc, shard_of)


class MvccTally:
    """What one block's serial walk did, in plain ints: the ledger adds
    them to its counters once per block.  `walk` is the form the walk
    took — "arrays" | "python" — and `reason` why not arrays (`walk_of`;
    None where the source was the envelopes: the source's reason says)."""
    __slots__ = ("reads", "conflicts_block", "conflicts_state", "walk",
                 "reason", "ranges_held", "ranges_phantom", "range_reads",
                 "range_s")

    def __init__(self):
        self.reads = self.conflicts_block = self.conflicts_state = 0
        self.walk, self.reason = "python", None
        # range queries replayed (only the envelope source's walk meets
        # one): how each came out, the results the replays re-read, and
        # the seconds spent in them
        self.ranges_held = self.ranges_phantom = self.range_reads = 0
        self.range_s = 0.0


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
                          rq: RangeQueryInfo,
                          tally: Optional[MvccTally] = None) -> bool:
    """Raw-reads replay: result set must match read-for-read.  If the
    recorded iterator was NOT exhausted, the replay may see extra trailing
    keys; any difference within the consumed prefix is a phantom."""
    t0 = time.perf_counter()
    actual = _merged_range(db, batch, ns, rq.start_key, rq.end_key)
    held = _same_results(rq, actual)
    if tally is not None:
        tally.range_reads += len(actual)
        tally.range_s += time.perf_counter() - t0
        if held:
            tally.ranges_held += 1
        else:
            tally.ranges_phantom += 1
    return held


def _same_results(rq: RangeQueryInfo, actual: list) -> bool:
    """The recorded raw reads against the replay's, key and version."""
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


# -- the two sources ----------------------------------------------------------
#
# A source gives (records, committed, ident_of).  `records` yields, for each tx whose
# flag is VALID and that carries a kv rw-set, in block order,
#   (tx_num, txid, groups, writes)   or   (tx_num, None, None, None)
# the second where the rw-set does not decode (BAD_RWSET).  `groups` is one
# (reads, range_queries) pair a namespace, reads being (ident, version) with
# version None | (block_num, tx_num); `writes` yields (ident, ns, key, value,
# is_delete).  `ident` names a key within the block, `committed(ident)`
# is the version the state holds for it, None when absent, and
# `ident_of(ns, key)` is the ident of a key the block may not name at all
# (None then: nothing in the block can ask for it).


def _version_pair(version: Optional[Version]):
    return None if version is None else (version.block_num, version.tx_num)


def _envelope_source(db: StateDB, envelopes: List[Envelope], flags: TxFlags):
    def records():
        for tx_num, env in enumerate(envelopes):
            if not flags.is_valid(tx_num):
                continue
            try:
                parsed = parse_endorser_tx(env)
            except Exception:
                yield tx_num, None, None, None
                continue
            if parsed is None:
                continue  # config txs etc. don't carry kv rwsets
            txid, rwset = parsed
            yield (tx_num, txid,
                   [([((n.namespace, r.key), _version_pair(r.version))
                      for r in n.reads],
                     [(n.namespace, rq) for rq in n.range_queries])
                    for n in rwset.ns_rwsets],
                   [((n.namespace, w.key), n.namespace, w.key, w.value,
                     w.is_delete)
                    for n in rwset.ns_rwsets for w in n.writes])

    def committed(ident):
        vv = db.get(*ident)
        return None if vv is None else _version_pair(vv.version)

    return records(), committed, lambda ns, key: (ns, key)


def committed_versions(db: StateDB, key_strs) -> list:
    """The version the state holds for each (namespace, key), None when
    absent: one look-up a slot of a lane table."""
    return [None if vv is None else _version_pair(vv.version)
            for vv in (db.get(ns, key) for ns, key in key_strs)]


def _lane_records(table: "wire.LaneTable", flags: TxFlags):
    """The lane source's records.  Lanes ascend by tx and keep the
    oracle's order within one (namespace by namespace, reads then
    writes), and no tx that gets here has a range query: one group."""
    txs = np.arange(table.n_tx)
    rd, wr = table.reads, table.writes
    r_end = np.searchsorted(rd[:, 0], txs, side="right").tolist()
    w_end = np.searchsorted(wr[:, 0], txs, side="right").tolist()
    reads = [(slot, (blk, txn) if has else None)
             for slot, has, blk, txn in rd[:, 1:].tolist()]
    w_rows = wr[:, 1:].tolist()
    key_strs, txids, base = table.key_strs, table.txids, table.base
    r0 = w0 = 0
    for tx_num, status in enumerate(table.status.tolist()):
        r1, w1 = r_end[tx_num], w_end[tx_num]
        if status != wire.LANE_SKIP and flags.is_valid(tx_num):
            if status == wire.LANE_BAD:
                yield tx_num, None, None, None
            else:
                yield (tx_num, txids[tx_num], ((reads[r0:r1], ()),),
                       ((slot, *key_strs[slot], bytes(base[off:off + n]),
                         bool(is_delete))
                        for slot, is_delete, off, n in w_rows[w0:w1]))
        r0, w0 = r1, w1


def _lane_idents(table: "wire.LaneTable"):
    """ident_of for a lane table: the slot of (ns, key), None where the
    block names no such key.  The map is built at the first call: only a
    block that deletes a key pays for it."""
    slots: dict = {}

    def ident_of(ns: str, key: str):
        if not slots:
            slots.update((k, i) for i, k in enumerate(table.key_strs))
        return slots.get((ns, key))
    return ident_of


def _lane_source(db: StateDB, table: "wire.LaneTable", flags: TxFlags):
    return (_lane_records(table, flags),
            committed_versions(db, table.key_strs).__getitem__,
            _lane_idents(table))


def lane_source_of(block, flags: TxFlags):
    """The rule, read off the block: (its LaneTable, None) when the lane
    source may supply the walk, else (None, reason) — wire.lane_table's,
    or "count" / "range" / "unknown" (see the head of this module)."""
    table, reason = wire.lane_table(block)
    if table is None:
        return None, reason
    if table.n_tx != len(flags):
        return None, "count"
    valid = np.frombuffer(flags.to_bytes(), dtype=np.uint8) == int(
        ValidationCode.VALID)
    status = table.status[valid]
    if (status == wire.LANE_RANGE).any():
        return None, "range"
    if (status == wire.LANE_UNKNOWN).any():
        return None, "unknown"
    return table, None


def walk_of():
    """The second rule, read off what this process built: (the form the
    walk over a lane table takes, why not arrays).  The array pass
    (`_array_walk`) gives every block the Python walk's answers — the
    parameter a delete takes along included, folded into its decision —
    so the one reason left is "no_native": `native/fastmvcc.c` did not
    build.  FABRIC_TPU_NO_NATIVE=1 never gets here: without the native
    extractor there is no lane table (`lane_source_of`: "no_native")."""
    if _fastmvcc is None:
        return "python", "no_native"
    return "arrays", None


_WALK_CODES = (int(ValidationCode.VALID),
               int(ValidationCode.MVCC_READ_CONFLICT),
               int(ValidationCode.BAD_RWSET))


def _parameter_idents(db: StateDB, table: "wire.LaneTable", n_shards: int):
    """What the array walk needs to drop a deleted key's validation
    parameter as `_stage_writes` does: (companion, keys, shards), or None
    where no delete of the block can meet one — the state holds no
    parameter and the block names no `#meta` namespace.  `companion` has,
    for each write lane that deletes (ns, key), the ident of (`ns#meta`,
    key): its slot where the block names it; else, where the state holds
    it, one past the slots (`keys`, `shards`: those idents' keys and
    their shards); else -1: neither named nor held, so nothing in this
    block can give it a parameter."""
    key_strs = table.key_strs
    if not db.meta_keys()[1] and not any(
            ns.endswith(META_SUFFIX) for ns, _key in key_strs):
        return None
    wr = table.writes
    slot_of = {k: i for i, k in enumerate(key_strs)}
    companion = np.full(len(wr), -1, dtype=np.int64)
    past: dict = {}
    keys, shards = [], []
    rows = np.flatnonzero(wr[:, 2])
    for row, slot in zip(rows.tolist(), wr[rows, 1].tolist()):
        ns, key = key_strs[slot]
        if ns.endswith(META_SUFFIX):
            continue
        meta = (ns + META_SUFFIX, key)
        ident = slot_of.get(meta)
        if ident is None:
            ident = past.get(meta)
        if ident is None and db.get(*meta) is not None:
            ident = past[meta] = len(key_strs) + len(keys)
            keys.append(meta)
            shards.append(shard_of(*meta, n_shards))
        if ident is not None:
            companion[row] = ident
    return companion, keys, np.asarray(shards, dtype=np.int32)


def _array_walk(db: StateDB, block_num: int, table: "wire.LaneTable",
                flags: TxFlags, tally: Optional[MvccTally]):
    """`validate_and_prepare_batch` over a lane table as passes over its
    arrays (native/fastmvcc.c): each slot hashed to its shard once, over
    the key's bytes in the block; the committed versions fetched a shard
    at a time into three arrays; the read-by-read decision, and which
    write lanes survive it, in C, in lane order; the batch and the
    history rows built from the surviving rows in one loop, the batch's
    per-shard split filled from the shard each slot already carries."""
    n_shards = db.n_shards
    key_strs, wr = table.key_strs, table.writes
    shards = np.frombuffer(
        _fastmvcc.slot_shards(table.base, table.keys, n_shards),
        dtype=np.int32)
    has, blk, txn = db.versions_of(key_strs, shards)
    companion = None
    if wr[:, 2].any():
        found = _parameter_idents(db, table, n_shards)
        if found is not None:
            # idents past the slots: parameters the state holds (at a
            # version no lane can ask for) and the block does not name
            companion, past_keys, past_shards = found
            key_strs = key_strs + past_keys
            has = np.pad(has, (0, len(past_keys)), constant_values=1)
            blk, txn = (np.pad(a, (0, len(past_keys))) for a in (blk, txn))
            shards = np.concatenate((shards, past_shards))
    codes = bytearray(flags.to_bytes())
    reads, against_block, against_state, staged = _fastmvcc.walk(
        table.tx, table.reads, wr, codes, has, blk, txn, block_num,
        companion, _WALK_CODES)
    flags.load(codes)
    if tally is not None:
        tally.reads += reads
        tally.conflicts_block += against_block
        tally.conflicts_state += against_state
    staged = np.frombuffer(staged, dtype=np.int64).reshape(-1, 2)
    rows = wr[staged[:, 0]]
    base, txids = table.base, table.txids
    updates: dict = {}
    history: List[Tuple[int, str, str, str, bytes, bool]] = []
    at = -1
    for (tx_num, slot, is_delete, off, n), drop in zip(
            rows.tolist(), staged[:, 1].tolist()):
        if drop >= 0:                 # the parameter goes with its key:
            updates[key_strs[drop]] = None      # no history row of its own
            continue
        if tx_num != at:
            at, txid = tx_num, txids[tx_num]
            version = Version(block_num, tx_num)
        k = key_strs[slot]
        value = bytes(base[off:off + n])
        updates[k] = None if is_delete else VersionedValue(value, version)
        history.append((tx_num, txid, k[0], k[1], value, bool(is_delete)))
    by_shard = None
    if n_shards > 1:
        # the dict keeps a key's first position: idents in that order
        idents = np.where(staged[:, 1] >= 0, staged[:, 1], rows[:, 1])
        if len(updates) != len(idents):
            _, first = np.unique(idents, return_index=True)
            idents = idents[np.sort(first)]
        lists: List[list] = [[] for _ in range(n_shards)]
        for item, shard in zip(updates.items(), shards[idents].tolist()):
            lists[shard].append(item)
        by_shard = (n_shards, lists)
    return UpdateBatch.from_staged(updates, by_shard), history


def _stage_writes(db: StateDB, batch: UpdateBatch, history: list,
                  staged: dict, ident_of, block_num: int, tx_num: int,
                  txid: str, writes) -> None:
    """A valid tx's writes join the batch at Version(block_num, tx_num),
    and each key it deletes loses its validation parameter there too."""
    version = Version(block_num, tx_num)
    pair = (block_num, tx_num)
    deleted = None
    for ident, ns, key, value, is_delete in writes:
        if is_delete:
            batch.delete(ns, key, version)
            staged[ident] = None
            if not ns.endswith(META_SUFFIX):
                deleted = deleted or []
                deleted.append((ns, key))
        else:
            batch.put(ns, key, value, version)
            staged[ident] = pair
        history.append((tx_num, txid, ns, key, value, is_delete))
    # after all of the tx's own writes: the delete wins over a parameter
    # the same rw-set sets.  One look-up a delete; the history index keeps
    # the writes the rw-sets hold, so the drop is no row of its own (a
    # replay from stored flags, kvledger._apply_derived, reads the same)
    for ns, key in deleted or ():
        meta_ns = ns + META_SUFFIX
        found, vv = batch.get(meta_ns, key)
        if (vv if found else db.get(meta_ns, key)) is None:
            continue                 # no parameter, or dropped already
        batch.delete(meta_ns, key, version)
        ident = ident_of(meta_ns, key)
        if ident is not None:
            staged[ident] = None


def validate_and_prepare_batch(
        db: StateDB, block_num: int,
        source, flags: TxFlags,
        tally: Optional[MvccTally] = None,
) -> Tuple[UpdateBatch, List[Tuple[int, str, str, str, bytes, bool]]]:
    """validateAndPrepareBatch (validator.go:83).

    `source` is the block's envelopes, in order (None for one that does
    not decode), or its `wire.LaneTable` where `lane_source_of` gave one.
    Mutates `flags` (MVCC_READ_CONFLICT / PHANTOM_READ_CONFLICT /
    BAD_RWSET) and returns (update_batch, history_writes) where
    history_writes = (tx_num, txid, ns, key, value, is_delete) of VALID txs.
    `tally`, when given, takes the reads validated and the conflicts.
    """
    if isinstance(source, wire.LaneTable):
        walk, reason = walk_of()
        if tally is not None:
            tally.walk, tally.reason = walk, reason
        if walk == "arrays":
            return _array_walk(db, block_num, source, flags, tally)
        records, committed, ident_of = _lane_source(db, source, flags)
    else:
        records, committed, ident_of = _envelope_source(db, source, flags)
    batch = UpdateBatch()
    # ident -> version of the writes that valid txs of this block staged
    # so far (None: a staged delete); what `batch` holds, by ident
    staged: dict = {}
    reads = against_block = against_state = 0
    history: List[Tuple[int, str, str, str, bytes, bool]] = []
    for tx_num, txid, groups, writes in records:
        if groups is None:
            flags.set(tx_num, ValidationCode.BAD_RWSET)
            continue
        ok = True
        for ns_reads, range_queries in groups:
            for ident, version in ns_reads:
                reads += 1
                # validateKVRead (validator.go:175): version equality,
                # nil-safe, against the block's own writes first
                if ident in staged:
                    if staged[ident] != version:
                        against_block += 1
                        ok = False
                elif committed(ident) != version:
                    against_state += 1
                    ok = False
                if not ok:
                    flags.set(tx_num, ValidationCode.MVCC_READ_CONFLICT)
                    break
            if not ok:
                break
            for ns, rq in range_queries:
                if not _validate_range_query(db, batch, ns, rq, tally):
                    flags.set(tx_num, ValidationCode.PHANTOM_READ_CONFLICT)
                    ok = False
                    break
            if not ok:
                break
        if ok:
            _stage_writes(db, batch, history, staged, ident_of, block_num,
                          tx_num, txid, writes)
    if tally is not None:
        tally.reads += reads
        tally.conflicts_block += against_block
        tally.conflicts_state += against_state
    return batch, history
