"""Commit-time coordinator: match, fetch, commit, purge private data.

Reference parity: /root/reference/gossip/privdata/coordinator.go
StoreBlock — before/with the block commit, assemble each valid tx's
private write-sets: transient store first, then pull from collection
member peers (pvtdataprovider.go / fetcher), verify cleartext against
the on-chain hashes, commit to the pvt store, process BTL purges, and
purge the transient store.  Missing collections are recorded for
reconciliation (reconcile.go), which retries the pull later.
"""

from __future__ import annotations

import logging
import time
from dataclasses import dataclass, field
from typing import Callable, Dict, List, Optional, Tuple

from fabric_tpu.ops_plane import registry as metrics, tracing
from fabric_tpu.protocol import Envelope, wire
from fabric_tpu.protocol.txflags import TxFlags, ValidationCode
from fabric_tpu.protocol.types import META_TXFLAGS, TxRwSet

from .collection import PVT_SEP, CollectionRegistry, hash_key, hash_value
from .pvtdatastore import PvtDataStore
from .transientstore import TransientStore

logger = logging.getLogger("fabric_tpu.privdata")


@dataclass
class MissingPvtData:
    block_num: int
    txid: str
    namespace: str
    collection: str
    # on-chain hashed writes (hashed key -> hashed value, None = delete):
    # reconciliation MUST re-verify fetched cleartext against these — a
    # malicious peer answering the pull must not be able to poison state
    # (reference: gossip/privdata/reconcile.go verifies vs the block).
    expected: Dict[str, object] = field(default_factory=dict)


class Coordinator:
    """Wraps a Committer with private-data assembly.

    fetch: optional callable (txid, namespace, collection) -> dict|None —
    the network pull from member peers (reconciliation transport).
    mspid: this peer's org (collection membership decisions).
    """

    def __init__(self, committer, registry: CollectionRegistry,
                 transient: TransientStore, pvt_store: PvtDataStore,
                 mspid: str, fetch: Optional[Callable] = None):
        self.committer = committer
        self.registry = registry
        self.transient = transient
        self.pvt_store = pvt_store
        self.mspid = mspid
        self.fetch = fetch
        self.missing: List[MissingPvtData] = []

    @property
    def height(self) -> int:
        return self.committer.height

    @property
    def validator(self):
        return self.committer.validator

    @property
    def ledger(self):
        return self.committer.ledger

    # -- the StoreBlock composition -----------------------------------------

    def store_block(self, block):
        result = self.committer.store_block(block)
        with tracing.tracer.start_span(
                "privdata.store_block", require_parent=True,
                parent=getattr(result, "trace", None)):
            self._store_private(block)
        return result

    def _store_private(self, block) -> None:
        """The private half of StoreBlock, after the block's commit.
        Which VALID txs write to a collection is read off the block's
        lane table (`wire.lane_table`) and only those are decoded; a tx
        the table does not speak for, and a block without a table, is
        decoded as before."""
        flags = TxFlags.from_bytes(block.metadata.items[META_TXFLAGS])
        valid = [t for t, code in enumerate(flags.codes())
                 if code == ValidationCode.VALID]
        table, _ = wire.lane_table(block)
        txids = wire.lane_txids(block)
        known = {t: txids[t] for t in valid if txids[t] is not None}
        private = (set(table.txs_writing_under(PVT_SEP))
                   if table is not None else ())
        decode = [t for t in valid if t not in known or t in private]
        writes: Dict[Tuple[str, str], Dict[str, object]] = {}
        btl: Dict[Tuple[str, str], int] = {}
        sets = dict.fromkeys(("resolved", "missing", "not_member"), 0)
        for tx_num in decode:
            try:
                env = Envelope.deserialize(block.data[tx_num])
                txid = env.header().channel_header.txid
                rwset = _tx_rwset(env)
            except Exception:
                continue
            known[tx_num] = txid
            if rwset is None:
                continue
            for ns_set in rwset.ns_rwsets:
                if PVT_SEP not in ns_set.namespace or not ns_set.writes:
                    continue
                ns, coll = ns_set.namespace.split(PVT_SEP, 1)
                cfg = self.registry.get(ns, coll)
                if cfg is None or not cfg.is_member(self.mspid):
                    sets["not_member"] += 1
                    continue   # not our collection: hashes only
                expected = {w.key: (None if w.is_delete else w.value)
                            for w in ns_set.writes}
                clear = self._resolve(txid, ns, coll, expected)
                if clear is None:
                    sets["missing"] += 1
                    self.missing.append(MissingPvtData(
                        block.header.number, txid, ns, coll, dict(expected)))
                    continue
                sets["resolved"] += 1
                writes.setdefault((ns, coll), {}).update(clear)
                self.pvt_store.record_tx(txid, ns, coll, clear,
                                         block_num=block.header.number,
                                         btl=cfg.block_to_live)
                btl[(ns, coll)] = cfg.block_to_live
        if writes:
            self.pvt_store.commit(block.header.number, writes, btl)
        purged = self.pvt_store.process_purges(block.header.number)
        # the block's VALID txs that decode: nothing to purge them from
        # while the transient store holds nothing
        if len(self.transient):
            self.transient.purge_by_txids(known.values())
        self._count_block(len(decode), sets, purged)

    def _count_block(self, decoded: int, sets: dict, purged: int) -> None:
        """One block's private half into the always-on counters.  A
        channel that never saw a private write-set shows none of them."""
        if not (decoded or purged or any(sets.values())):
            return
        ch = self.ledger.channel_id
        metrics.counter(
            "privdata_decoded_txs_total", "envelopes the private half of "
            "a block's commit decoded: the VALID transactions that write "
            "under a collection, or that the lane table does not speak "
            "for").add(decoded, channel=ch)
        by_result = metrics.counter(
            "privdata_txs_total", "private write-sets of VALID "
            "transactions, one a transaction and collection: cleartext "
            "matched to the on-chain hashes, missing (recorded for "
            "reconciliation), or of a collection this peer is no member of")
        for result, n in sets.items():
            by_result.add(n, channel=ch, result=result)
        metrics.counter(
            "privdata_purged_keys_total", "private keys the pvt store "
            "dropped because their block-to-live ended").add(
                purged, channel=ch)
        metrics.gauge(
            "privdata_transient_entries", "transactions whose private "
            "write-sets the transient store holds").set(
                len(self.transient), channel=ch)

    def _resolve(self, txid: str, ns: str, coll: str,
                 expected: Dict[str, object]) -> Optional[dict]:
        """Find cleartext matching the on-chain hashes: the transient
        store's candidates first, the network fetcher only where none of
        them explains every hashed write."""
        t0 = time.perf_counter()
        try:
            for sets in self.transient.get(txid):
                if (ns, coll) in sets:
                    out = _match_hashes(sets[(ns, coll)], expected)
                    if out is not None:
                        return out
            if self.fetch is not None:
                metrics.counter(
                    "privdata_fetch_total", "pulls of a private write-set "
                    "from the member peers at commit").add(
                        1, channel=self.ledger.channel_id)
                fetched = self.fetch(txid, ns, coll)
                if fetched:
                    return _match_hashes(fetched, expected)
            return None
        finally:
            metrics.histogram(
                "privdata_resolve_seconds", "seconds matching one private "
                "write-set's cleartext to its on-chain hashes took, fetch "
                "included").observe(time.perf_counter() - t0,
                                    channel=self.ledger.channel_id)

    # -- reconciliation ------------------------------------------------------

    def reconcile(self) -> int:
        """Retry missing collections via the fetcher (reconcile.go).
        Returns how many were recovered."""
        if self.fetch is None:
            return 0
        recovered = 0
        still = []
        for m in self.missing:
            fetched = self.fetch(m.txid, m.namespace, m.collection)
            verified = _match_hashes(fetched, m.expected) if fetched else None
            if verified is not None:
                cfg = self.registry.get(m.namespace, m.collection)
                self.pvt_store.commit(
                    m.block_num, {(m.namespace, m.collection): verified},
                    {(m.namespace, m.collection):
                     cfg.block_to_live if cfg else 0})
                self.pvt_store.record_tx(
                    m.txid, m.namespace, m.collection, verified,
                    block_num=m.block_num,
                    btl=cfg.block_to_live if cfg else 0)
                recovered += 1
            else:
                if fetched:
                    logger.warning(
                        "reconcile: fetched pvtdata for %s %s/%s failed "
                        "hash verification; discarding", m.txid,
                        m.namespace, m.collection)
                still.append(m)
        self.missing = still
        return recovered


def _tx_rwset(env: Envelope) -> Optional[TxRwSet]:
    try:
        from fabric_tpu.protocol.types import Transaction
        tx = Transaction.from_dict(env.payload_dict()["data"])
        return tx.actions[0].action.rwset if tx.actions else None
    except Exception:
        return None


def _match_hashes(cleartext: dict, expected: Dict[str, object]) -> Optional[dict]:
    """Check a candidate cleartext set against the on-chain hashed writes.
    Accepts the candidate only if EVERY hashed write is explained."""
    out = {}
    for hk, hv in expected.items():
        found = None
        for key, value in cleartext.items():
            if hash_key(key) == hk:
                found = (key, value)
                break
        if found is None:
            return None
        key, value = found
        if hv is None:           # delete
            if value is not None:
                return None
        else:
            if value is None or hash_value(value) != hv:
                return None
        out[key] = value
    return out
