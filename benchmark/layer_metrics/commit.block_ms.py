"""Median per block of ledger.mvcc + block_commit + state_commit +
history_commit."""
from readers import block_ms


def read(obs):
    return block_ms(obs, ("ledger.mvcc", "ledger.block_commit",
                          "ledger.state_commit", "ledger.history_commit"))
