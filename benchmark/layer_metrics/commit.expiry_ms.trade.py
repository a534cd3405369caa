"""Time the ledger's expiry step took per block of the window
(`ledger_pvt_expiry_seconds`, one observation a block of a channel whose
collections expire): the scan of the expiry index for what falls due,
the version check and delete of each hashed key, the entry of the
block's own writes.  None on a program that has no such histogram, or on
a channel without a block-to-live."""
from account_readers import mean_ms


def read(obs):
    return mean_ms(obs, "ledger_pvt_expiry_seconds")
