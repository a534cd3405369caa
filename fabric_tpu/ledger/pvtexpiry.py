"""Expiry of private data's hashed keys (a collection's block-to-live).

Reference parity: core/ledger/kvledger/txmgmt/pvtstatepurgemgmt (the
purge manager and its expiry keeper) as lockbased_txmgr.Commit drives it:
a key of a collection with `blockToLive` = BTL, written by block N and
not written again since, is deleted — the private key and its hashed key —
with the commit of block N + BTL + 1, on every peer, member of the
collection or not.  The deletes join the block's update batch after MVCC
validated the block against the state as it was, so a transaction of the
expiring block still reads the key; one ordered later that read it is a
MVCC_READ_CONFLICT.  A key the expiring block itself writes or deletes is
left to that write.  The deletes are no transaction's: they enter neither
the history nor the commit hash (which binds the block's data and flags).

The expiry index — which block expires which hashed keys — lives in the
state database itself, under the reserved namespace `EXPIRY_NS`: one
entry a block that wrote under a namespace with a BTL, keyed by (expiry
block, writing block) in fixed-width hex so that one range scan returns
what is due, its value the written hashed keys by namespace.  It is
written and consumed through the same update batch as the keys it speaks
for, so the WAL, the checkpoints, recovery replay and a state snapshot
carry it with no code of their own, and a torn commit cannot part the two.
The cleartext's own purge is `PvtDataStore.process_purges`, by the same
rule; the coordinator runs it after the block's commit.
"""

from __future__ import annotations

from typing import Dict

from fabric_tpu.protocol import Version
from fabric_tpu.utils import serde

from .statedb import StateDB, UpdateBatch

EXPIRY_NS = "_pvt_expiry"


def entry_key(expiry_block: int, written_block: int) -> str:
    return "%016x%016x" % (expiry_block, written_block)


def expire_and_schedule(db: StateDB, batch: UpdateBatch, block_num: int,
                        btl: Dict[str, int]) -> int:
    """The expiry step of block `block_num`'s commit, over its update
    batch as MVCC left it: add the deletes of the hashed keys that fall
    due, drop their index entries, and enter the block's own writes under
    the namespaces `btl` names ({`ns$collection`: BTL}) for their expiry.
    -> how many hashed keys expire with this block."""
    version = Version(block_num, 0)      # a tombstone's, never read back
    expired = 0
    for key, vv in db.range_scan(EXPIRY_NS, "", "%016x" % (block_num + 1)):
        written = int(key[16:], 16)
        for ns, hashed_keys in serde.decode(vv.value):
            for hk in hashed_keys:
                if batch.get(ns, hk)[0]:
                    continue             # this block's own write stands
                held = db.get_version(ns, hk)
                if held is not None and held.block_num == written:
                    batch.delete(ns, hk, version)
                    expired += 1
        batch.delete(EXPIRY_NS, key, version)
    due = {}                             # expiry block -> {ns: [hashed keys]}
    for (ns, hk), vv in batch.items():
        life = btl.get(ns)
        if life and vv is not None:
            due.setdefault(block_num + life + 1, {}).setdefault(
                ns, []).append(hk)
    for expiry_block, by_ns in due.items():
        batch.put(EXPIRY_NS, entry_key(expiry_block, block_num),
                  serde.encode([[ns, keys] for ns, keys in by_ns.items()]),
                  version)
    return expired
