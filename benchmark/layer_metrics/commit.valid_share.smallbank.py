"""Share of the window's committed transactions that were VALID: the
useful work of a block over hot accounts."""
from ledger_readers import valid_share_pct


def read(obs):
    return valid_share_pct(obs)
