"""The channel ledger: block store + state DB + history DB, committed in
lock-step with crash recovery.

Reference parity: core/ledger/kvledger/kv_ledger.go —
  CommitLegacy (:425-508): MVCC validate-and-prepare (:452), commit-hash
  chaining (:459-465), block+pvtdata store (:470), state DB (:477),
  history DB (:487), with per-phase timing metrics (:491-499);
  recovery.go: replay blocks above each DB's savepoint on open;
  rebuild_dbs.go / reset.go / rollback.go admin operations.

The block store is the source of truth; state/history are derived and
self-heal on open (recoverDBs).
"""

from __future__ import annotations

import collections
import hashlib
import logging
import os
import shutil
import time
from dataclasses import dataclass, field
from typing import Dict, List, Optional

from fabric_tpu.protocol import Block
from fabric_tpu.protocol.wire import lane_table, n_txs
from fabric_tpu.protocol.txflags import TxFlags, ValidationCode
from fabric_tpu.protocol.types import META_COMMIT_HASH, META_TXFLAGS

from .blkstorage import BlockStore
from .historydb import HistoryDB
from .mvcc import MvccTally, lane_source_of, validate_and_prepare_batch
from .pvtexpiry import expire_and_schedule
from .statedb import StateDB

logger = logging.getLogger("fabric_tpu.ledger")


def _safe_envelopes(block: Block):
    """Deserialize leniently: undecodable entries become None — they carry
    a non-VALID flag already, so MVCC never touches them."""
    from fabric_tpu.protocol import Envelope
    out = []
    for raw in block.data:
        try:
            out.append(Envelope.deserialize(raw))
        except Exception:
            out.append(None)
    return out


def _history_writes_from_flags(envelopes, flags: TxFlags):
    """History records of a block's VALID txs, trusting the stored flags
    (used on replay when MVCC must not re-run)."""
    from fabric_tpu.ledger.mvcc import parse_endorser_tx
    history = []
    for tx_num, env in enumerate(envelopes):
        if env is None or not flags.is_valid(tx_num):
            continue
        try:
            parsed = parse_endorser_tx(env)
        except Exception:
            continue
        if parsed is None:
            continue
        txid, rwset = parsed
        for ns_rw in rwset.ns_rwsets:
            for w in ns_rw.writes:
                history.append((tx_num, txid, ns_rw.namespace, w.key,
                                w.value, w.is_delete))
    return history


@dataclass
class LedgerConfig:
    root: Optional[str] = None          # None = fully in-memory
    enable_history: bool = True
    snapshot_every: int = 256
    # key-hash stripe width for the state plane (statedb + historydb):
    # independently locked + independently flushable shards; 1 = the
    # flat store (differential oracle)
    state_shards: int = 8
    # {hashed namespace `ns$collection`: block-to-live} of the channel's
    # collections whose keys expire (`CollectionRegistry.block_to_live`);
    # empty: the commit has no expiry step
    pvt_btl: Dict[str, int] = field(default_factory=dict)


@dataclass
class CommitStats:
    """Per-phase timings (kv_ledger.go:491-499 metric parity)."""
    block_num: int = 0
    state_validation_s: float = 0.0
    block_commit_s: float = 0.0
    state_commit_s: float = 0.0
    history_commit_s: float = 0.0
    pvt_expiry_s: float = 0.0
    valid_txs: int = 0
    total_txs: int = 0
    # (span name, start, end) of each phase as it really ran, on
    # perf_counter: what the committer records as the ledger.* spans,
    # with the attributes `span_attrs` holds under the span's name
    phase_spans: list = field(default_factory=list)
    span_attrs: dict = field(default_factory=dict)

    def phase(self, name: str, attr: str, t0: float,
              attributes: Optional[dict] = None) -> None:
        """Close a phase that began at `t0`: its seconds into `attr`,
        its real interval into `phase_spans`."""
        t1 = time.perf_counter()
        setattr(self, attr, getattr(self, attr) + t1 - t0)
        self.phase_spans.append((name, t0, t1))
        if attributes:
            self.span_attrs[name] = attributes


class KVLedger:
    def __init__(self, channel_id: str, config: Optional[LedgerConfig] = None):
        self.channel_id = channel_id
        self.config = config or LedgerConfig()
        root = self.config.root
        bdir = sdir = hdir = None
        if root is not None:
            base = os.path.join(root, channel_id)
            bdir = os.path.join(base, "blocks")
            sdir = os.path.join(base, "state")
            hdir = os.path.join(base, "history")
        self.blockstore = BlockStore(bdir)
        self.statedb = self._new_statedb(sdir)
        self.historydb = (self._new_historydb(hdir)
                          if self.config.enable_history else None)
        self._commit_hash = b"\x00" * 32
        self.last_stats = CommitStats()
        # set by _recover: how much work reopening this ledger cost
        self.last_recovery: Dict[str, int] = {
            "replayed_blocks": 0, "start": 0, "height": 0}
        self._recover()

    # -- recovery (recovery.go) --------------------------------------------

    def _new_statedb(self, sdir: Optional[str]) -> StateDB:
        return StateDB(sdir, snapshot_every=self.config.snapshot_every,
                       n_shards=self.config.state_shards,
                       channel=self.channel_id)

    def _new_historydb(self, hdir: Optional[str]) -> HistoryDB:
        return HistoryDB(hdir, n_shards=self.config.state_shards,
                         checkpoint_every=self.config.snapshot_every,
                         channel=self.channel_id)

    def _recover(self) -> None:
        """Replay blocks above each derived DB's savepoint (bounded to
        the post-checkpoint tail now that the derived DBs checkpoint)."""
        height = self.blockstore.height
        base = self.blockstore.base
        self.last_recovery = {"replayed_blocks": 0, "start": height,
                              "height": height}
        if height == 0:
            return
        # restore the commit-hash chain: from the last block's metadata
        # when stored, else from the snapshot-bootstrap marker (a freshly
        # installed snapshot has base == height, no blocks yet)
        if height - 1 >= base:
            last = self.blockstore.get_by_number(height - 1)
            self._commit_hash = last.metadata.items.get(
                META_COMMIT_HASH, b"\x00" * 32)
        elif self.blockstore.bootstrap_commit_hash is not None:
            self._commit_hash = self.blockstore.bootstrap_commit_hash
        # replay from the LOWEST derived-DB savepoint: a crash between the
        # state commit and the history commit leaves history one block
        # behind, and both commits are idempotent via their savepoint guards
        savepoints = [self.statedb.savepoint]
        if self.historydb is not None:
            savepoints.append(self.historydb.savepoint)
        lowest = min((-1 if sp is None else sp) for sp in savepoints)
        start = lowest + 1
        if start < base:
            # blocks below the snapshot base are pruned; the installed
            # state checkpoint is the only source for them.  If a derived
            # DB lost its checkpoint this replay CANNOT reconstruct the
            # pre-snapshot writes — re-bootstrap from a serving peer.
            logger.warning(
                "%s: derived-DB savepoint %d below snapshot base %d — "
                "pre-snapshot history is pruned; replaying from base",
                self.channel_id, lowest, base)
            start = base
        replayed = 0
        for num in range(start, height):
            block = self.blockstore.get_by_number(num)
            self._apply_derived(block)
            replayed += 1
            logger.info("%s: recovered block %d into state/history",
                        self.channel_id, num)
        self.last_recovery = {"replayed_blocks": replayed, "start": start,
                              "height": height}

    def _apply_derived(self, block: Block) -> None:
        """Recovery replay of one stored block (final txflags in metadata)
        into the derived DBs.  If the block is already in the state DB
        (<= its savepoint), MVCC must NOT re-run — the state already
        contains this block's writes and every read would falsely
        conflict; the stored flags are authoritative, so history writes
        are extracted directly from the VALID txs."""
        num = block.header.number
        flags = TxFlags.from_bytes(block.metadata.items[META_TXFLAGS])
        envelopes = _safe_envelopes(block)
        state_has_it = (self.statedb.savepoint is not None
                        and num <= self.statedb.savepoint)
        if state_has_it:
            history = _history_writes_from_flags(envelopes, flags)
        else:
            batch, history = validate_and_prepare_batch(
                self.statedb, num, envelopes, flags)
            self._expire_private(batch, num)
            self.statedb.apply_updates(batch, num)
        if self.historydb is not None:
            self.historydb.commit(num, history)  # savepoint-guarded, idempotent

    def _count_block(self, flags: TxFlags, tally: MvccTally,
                     history: list, mvcc_attrs: dict,
                     opened_at: Optional[str]) -> None:
        """One committed block into the always-on counters: its
        transactions by final code, the writes of its valid txs
        (`history`: how many, and their key + value bytes), the reads
        the walk checked and the conflicts it found, the range queries
        it replayed (`tally`), which source supplied its rw-sets and
        which form the walk took (`mvcc_attrs`, the `ledger.mvcc`
        span's), and where its lane table was first opened
        (`opened_at`; None: it has none)."""
        from fabric_tpu.ops_plane import registry
        ch = self.channel_id
        txs = registry.counter(
            "ledger_tx_total", "transactions committed, by final "
            "validation code")
        for code, n in collections.Counter(flags.codes()).items():
            txs.add(n, channel=ch, code=ValidationCode(code).name)
        registry.counter(
            "ledger_state_writes_total", "writes of valid transactions "
            "applied to state and history").add(len(history), channel=ch)
        registry.counter(
            "ledger_state_write_bytes_total", "key + value bytes of those "
            "writes").add(sum(len(w[3].encode()) + len(w[4])
                              for w in history), channel=ch)
        registry.counter(
            "ledger_commit_source_total", "transactions of the blocks the "
            "serial MVCC walk validated, by what supplied their rw-sets: "
            "the block's lane table, or its envelopes decoded again").add(
                len(flags), channel=ch, source=mvcc_attrs["source"])
        if opened_at is not None:
            registry.counter(
                "ledger_lane_table_opened_total", "transactions of the "
                "blocks that have a lane table, by where it was first "
                "opened: ahead of the commit, while the validator waited "
                "for the device, or inside the commit").add(
                    len(flags), channel=ch, at=opened_at)
        registry.counter(
            "ledger_mvcc_walk_total", "transactions of those blocks, by the "
            "form the walk took: one pass over the lane table's arrays, or "
            "one Python iteration a transaction, and why").add(
                len(flags), channel=ch, walk=mvcc_attrs["walk"],
                reason=mvcc_attrs.get("reason", "none"))
        registry.counter(
            "ledger_mvcc_reads_total", "reads validated, by the commit "
            "path that counts them (the serial MVCC walk)").add(
                tally.reads, channel=ch, path="serial")
        conflicts = registry.counter(
            "ledger_mvcc_conflicts_total", "reads that no longer held, by "
            "who answered: an earlier valid tx of the block, or the state")
        conflicts.add(tally.conflicts_block, channel=ch, path="serial",
                      against="block")
        conflicts.add(tally.conflicts_state, channel=ch, path="serial",
                      against="state")
        replayed = tally.ranges_held + tally.ranges_phantom
        if replayed:
            ranges = registry.counter(
                "ledger_mvcc_range_queries_total", "range queries replayed "
                "at commit, by outcome: the result set held, or a phantom")
            ranges.add(tally.ranges_held, channel=ch, result="held")
            ranges.add(tally.ranges_phantom, channel=ch, result="phantom")
            registry.counter(
                "ledger_mvcc_range_reads_total", "results the replayed "
                "range queries re-read (state merged with the block's "
                "batch)").add(tally.range_reads, channel=ch)
            registry.histogram(
                "ledger_mvcc_range_seconds", "seconds a block's range "
                "replays took: one observation a block that replayed at "
                "least one").observe(tally.range_s, channel=ch)

    def _expire_private(self, batch, block_num: int) -> int:
        """The block's expiry step (ledger/pvtexpiry.py); 0 on a channel
        whose collections never expire."""
        if not self.config.pvt_btl:
            return 0
        return expire_and_schedule(self.statedb, batch, block_num,
                                   self.config.pvt_btl)

    def _count_expiry(self, expired: int, seconds: float) -> None:
        from fabric_tpu.ops_plane import registry
        registry.counter(
            "ledger_pvt_expired_keys_total", "hashed keys of private data "
            "deleted because their collection's block-to-live ended").add(
                expired, channel=self.channel_id)
        registry.histogram(
            "ledger_pvt_expiry_seconds", "seconds a block's expiry step "
            "took: one observation a block of a channel whose collections "
            "expire").observe(seconds, channel=self.channel_id)

    _APPLY_BUCKETS = (1.0, 4.0, 16.0, 64.0, 256.0, 1024.0, 4096.0,
                      16384.0, float("inf"))

    def _observe_apply(self, n_state: int, n_history: int) -> None:
        try:
            from fabric_tpu.ops_plane import registry
            h = registry.histogram(
                "commit_graph_apply_batch_size",
                "coalesced per-block apply sizes (keys / history rows)",
                buckets=self._APPLY_BUCKETS)
            h.observe(float(n_state), db="state", channel=self.channel_id)
            h.observe(float(n_history), db="history",
                      channel=self.channel_id)
        except Exception:
            pass

    # -- commit (kv_ledger.go:425-508) -------------------------------------

    def commit(self, block: Block) -> CommitStats:
        """Commit a block whose metadata txflags were finalized by the
        txvalidator.  MVCC runs here (ValidateAndPrepare), then the
        commit-hash chains, then block store, state, history."""
        if self.paused:
            raise RuntimeError(
                f"channel {self.channel_id!r} is paused (resume() first)")
        if META_TXFLAGS not in block.metadata.items:
            raise ValueError("block metadata missing txflags "
                             "(txvalidator must run first)")
        # reject wrong-numbered / wrong-parent blocks BEFORE any state
        # (incl. the commit-hash chain) advances — duplicate or out-of-order
        # delivery is normal under gossip and must leave the ledger untouched
        info = self.blockstore.chain_info()
        if block.header.number != info.height:
            raise ValueError(
                f"out-of-order commit: got block {block.header.number}, "
                f"expected {info.height}")
        expected_prev = info.current_hash if info.height else b"\x00" * 32
        if block.header.previous_hash != expected_prev:
            raise ValueError(
                f"block {block.header.number} previous_hash mismatch")
        stats = CommitStats(block_num=block.header.number,
                            total_txs=n_txs(block))

        t0 = time.perf_counter()
        flags = TxFlags.from_bytes(block.metadata.items[META_TXFLAGS])
        tally = MvccTally()
        # the walk reads the block's lane table where the block allows
        # it (open already where the validator prepared it in its wait;
        # else extracted here, inside the span), else its envelopes,
        # decoded again
        source, reason = lane_source_of(block, flags)
        if source is not None:
            mvcc_attrs = {"source": "lanes"}
        else:
            source = _safe_envelopes(block)
            mvcc_attrs = {"source": "envelopes", "reason": reason}
        batch, history = validate_and_prepare_batch(
            self.statedb, block.header.number, source, flags, tally)
        # the form the walk took; "python" comes with why: the source's
        # reason above, or the walk's own (mvcc.walk_of)
        mvcc_attrs["walk"] = tally.walk
        if tally.reason is not None:
            mvcc_attrs["reason"] = tally.reason
        if tally.ranges_held or tally.ranges_phantom:
            mvcc_attrs.update(
                range_queries=tally.ranges_held + tally.ranges_phantom,
                range_reads=tally.range_reads,
                range_ms=round(1e3 * tally.range_s, 3))
        # split the batch by shard before the apply takes shard locks
        batch.preshard(getattr(self.statedb, "n_shards", 1))
        stats.phase("ledger.mvcc", "state_validation_s", t0, mvcc_attrs)
        if self.config.pvt_btl:
            # after MVCC, outside the commit hash: the hashed keys whose
            # block-to-live ends with this block leave with its batch
            t0 = time.perf_counter()
            expired = self._expire_private(batch, block.header.number)
            batch.preshard(getattr(self.statedb, "n_shards", 1))
            stats.phase("ledger.pvt_expiry", "pvt_expiry_s", t0,
                        {"expired": expired})
            self._count_expiry(expired, stats.pvt_expiry_s)
        stats.valid_txs = flags.valid_count()
        # MVCC may have flipped more flags — write the final bitmap back
        block.metadata.items[META_TXFLAGS] = flags.to_bytes()

        # commit-hash chaining (kv_ledger.go:459-465): binds flags+data to
        # the previous commit hash so divergent peers are detectable
        self._commit_hash = hashlib.sha256(
            self._commit_hash + block.header.data_hash + flags.to_bytes()
        ).digest()
        block.metadata.items[META_COMMIT_HASH] = self._commit_hash

        t0 = time.perf_counter()
        self.blockstore.add_block(block)
        stats.phase("ledger.block_commit", "block_commit_s", t0)

        t0 = time.perf_counter()
        self.statedb.apply_updates(batch, block.header.number)
        stats.phase("ledger.state_commit", "state_commit_s", t0)

        if self.historydb is not None:
            t0 = time.perf_counter()
            self.historydb.commit(block.header.number, history)
            stats.phase("ledger.history_commit", "history_commit_s", t0)

        self._observe_apply(len(batch), len(history))
        # open by now, where the block has one: the walk asked first
        table, _ = lane_table(block)
        self._count_block(flags, tally, history, mvcc_attrs,
                          table.opened_at if table is not None else None)
        if batch.touches_meta:
            # only such a batch moves the count: a channel without
            # key-level endorsement never shows the series
            from fabric_tpu.ops_plane import registry
            registry.gauge(
                "ledger_state_meta_keys", "key-level validation parameters "
                "the state holds after the block's apply").set(
                    self.statedb.meta_keys()[1], channel=self.channel_id)
        self.last_stats = stats
        logger.info(
            "[%s] committed block %d: %d/%d valid | validation=%.1fms "
            "block=%.1fms state=%.1fms history=%.1fms",
            self.channel_id, stats.block_num, stats.valid_txs,
            stats.total_txs, stats.state_validation_s * 1e3,
            stats.block_commit_s * 1e3, stats.state_commit_s * 1e3,
            stats.history_commit_s * 1e3)
        return stats

    # -- queries ------------------------------------------------------------

    @property
    def height(self) -> int:
        return self.blockstore.height

    @property
    def commit_hash(self) -> bytes:
        return self._commit_hash

    def get_state(self, ns: str, key: str) -> Optional[bytes]:
        vv = self.statedb.get(ns, key)
        return None if vv is None else vv.value

    def range_query(self, ns: str, start_key: str, end_key: str, limit: int = 0):
        return self.statedb.range_scan(ns, start_key, end_key, limit)

    def get_history(self, ns: str, key: str):
        if self.historydb is None:
            raise RuntimeError("history DB disabled")
        return self.historydb.get_history(ns, key)

    def state_status(self) -> dict:
        """Shard/checkpoint/recovery introspection (the /state ops route)."""
        out = {
            "channel": self.channel_id,
            "height": self.height,
            "commit_hash": self._commit_hash.hex(),
            "block_base": self.blockstore.base,
            "last_recovery": dict(self.last_recovery),
            "state": self.statedb.status(),
        }
        if self.historydb is not None:
            out["history"] = self.historydb.status()
        return out

    def snapshot_export(self):
        """Force a checkpoint of both derived DBs so a consistent
        (manifest + shard files) set exists on disk for state transfer.
        -> (state_manifest, history_manifest|None); None when in-memory
        or before the first block."""
        sm = self.statedb.checkpoint()
        hm = self.historydb.checkpoint() if self.historydb is not None else None
        return sm, hm

    # -- admin (reset.go / rollback.go / pause_resume.go / rebuild_dbs.go) --

    @property
    def paused(self) -> bool:
        """pause_resume.go: a paused channel refuses commits until
        resumed; the flag survives restarts via a marker file."""
        if self.config.root is None:
            return getattr(self, "_paused_mem", False)
        return os.path.exists(os.path.join(self.config.root, "PAUSED"))

    def pause(self) -> None:
        if self.config.root is None:
            self._paused_mem = True
            return
        with open(os.path.join(self.config.root, "PAUSED"), "w") as f:
            f.write("paused")

    def resume(self) -> None:
        if self.config.root is None:
            self._paused_mem = False
            return
        try:
            os.unlink(os.path.join(self.config.root, "PAUSED"))
        except FileNotFoundError:
            pass

    def rollback(self, target_height: int) -> None:
        """Roll the channel back to `target_height` blocks and rebuild
        the derived DBs from the retained chain (kvledger/rollback.go —
        there the peer re-fetches dropped blocks from ordering; here the
        deliver client does the same on restart)."""
        if target_height >= self.height:
            return
        self.blockstore.truncate(target_height)
        self.rebuild_dbs()

    def reset(self) -> None:
        """Reset to the genesis block only (kvledger/reset.go): all state
        re-derivable, blocks re-fetched from ordering by the deliver
        client."""
        self.rollback(1 if self.height else 0)

    def rebuild_dbs(self) -> None:
        """Drop state+history and rebuild from the block store."""
        sdir, hdir = self.statedb.root, None
        if self.historydb is not None:
            hdir = self.historydb.root
        for d in (sdir, hdir):
            if d and os.path.isdir(d):
                shutil.rmtree(d)
        self.statedb = self._new_statedb(sdir)
        if self.config.enable_history:
            self.historydb = self._new_historydb(hdir)
        self._commit_hash = b"\x00" * 32
        self._recover()
