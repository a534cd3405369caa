"""Median per block of `ledger.mvcc`: the version fetch, the read-by-read
decision, the staging of the block's writes and the batch's split by
shard.  None where the run kept no such span (untraced)."""
from readers import block_ms


def read(obs):
    return block_ms(obs, ("ledger.mvcc",))
