"""Private data pillar: collections, transient + pvtdata stores, coordinator.

The reference's private-data capability (core/transientstore/store.go,
core/ledger/pvtdatastorage/store.go, gossip/privdata/coordinator.go,
pvtdataprovider.go, reconcile.go), with the same on-chain/off-chain
split:
  - a chaincode writes to a named COLLECTION: the public rwset carries
    only hash(key) -> hash(value) writes under namespace "ns$collection";
    the cleartext keys/values travel off-chain.  The shim
    (chaincode/stub.py) takes private inputs from the proposal's
    transient map, checks a collection's member-only flags against the
    creator's org, and gives a non-member the value's hash
    (`get_private_data_hash`),
  - a collection may carry its own endorsement policy: the validator
    judges the writes under its hashed namespace by it, and by the
    chaincode's where it has none (`PolicyRegistry.policy_for`),
  - at endorsement the cleartext is staged in the endorser's
    TransientStore and distributed to collection member peers over the
    authenticated comm plane,
  - at commit the Coordinator matches each valid tx's private write-set
    hashes against transient/received data (pulling from peers only
    when that fails), commits cleartext to the PvtDataStore, and purges
    its keys whose block-to-live (BTL) ended,
  - the ledger expires the HASHED keys by the same rule on every peer
    (ledger/pvtexpiry.py), so a read of an expired hash fails MVCC
    everywhere alike,
  - non-member peers commit the block with hashes only; a later
    reconciliation pull can backfill if the peer joins the collection.
Always-on account: `privdata_txs_total{result}`,
`privdata_decoded_txs_total`, `privdata_resolve_seconds`,
`privdata_purged_keys_total`, `privdata_fetch_total`,
`privdata_transient_entries`; span `privdata.store_block`.
"""

from .collection import (CollectionConfig, CollectionRegistry,
                         chaincode_of, pvt_namespace)
from .transientstore import TransientStore
from .pvtdatastore import PvtDataStore
from .coordinator import Coordinator, MissingPvtData

__all__ = [
    "CollectionConfig", "CollectionRegistry", "chaincode_of",
    "pvt_namespace",
    "TransientStore", "PvtDataStore", "Coordinator", "MissingPvtData",
]
