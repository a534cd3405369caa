"""Share of the transactions the serial MVCC walk validated in the
window whose rw-sets came from the block's envelopes, decoded again
(`ledger_commit_source_total{source="envelopes"}` over both sources):
the complement of `commit.lanes_share.catchup`, under a name a later
change to what a block with range queries costs can be read against.
None on a program that has no such counter."""
from harness import prom_delta


def read(obs):
    before, after = obs.get("prom_before"), obs.get("prom_after")
    if before is None or after is None:
        return None
    if "ledger_commit_source_total" not in after:
        return None
    txs = prom_delta(before, after, "ledger_commit_source_total")
    if txs <= 0:
        return None
    return 100.0 * prom_delta(before, after, "ledger_commit_source_total",
                              source="envelopes") / txs
