"""Share of the transactions the serial MVCC walk validated in the
window whose block was walked as one pass over its lane table's arrays
(the rest took one Python iteration a transaction: the block's rw-sets
came from its envelopes, or the native pass did not build).  None on a
program that has no such counter."""
from harness import prom_delta


def read(obs):
    before, after = obs.get("prom_before"), obs.get("prom_after")
    if before is None or after is None:
        return None
    if "ledger_mvcc_walk_total" not in after:
        return None
    txs = prom_delta(before, after, "ledger_mvcc_walk_total")
    if txs <= 0:
        return None
    return 100.0 * prom_delta(before, after, "ledger_mvcc_walk_total",
                              walk="arrays") / txs
