"""Median per block of `privdata.store_block`: the private half of a
block's commit, after the ledger's — the VALID transactions that write
under a collection decoded from their envelopes, their cleartext matched
to the on-chain hashes out of the transient store, the private store's
commit and purge, the transient store's purge.  None where the run kept
no such span (untraced, or a program without it)."""
from readers import block_ms


def read(obs):
    return block_ms(obs, ("privdata.store_block",))
