"""Median per block of `ledger.state_commit`: the state store's write-ahead
record and its fsync, the apply of the block's batch to the shards'
key maps and ordered key lists, and the shard fan-out's hand-off.  None
where the run kept no such span (untraced)."""
from readers import block_ms


def read(obs):
    return block_ms(obs, ("ledger.state_commit",))
