"""Share of the range queries replayed in the window whose result set
held (`ledger_mvcc_range_queries_total{result="held"}` over both
results; the rest were phantoms).  None on a program that has no such
counter, or where the window replayed none."""
from harness import prom_delta


def read(obs):
    before, after = obs.get("prom_before"), obs.get("prom_after")
    if before is None or after is None:
        return None
    replayed = prom_delta(before, after, "ledger_mvcc_range_queries_total")
    if replayed <= 0:
        return None
    return 100.0 * prom_delta(before, after,
                              "ledger_mvcc_range_queries_total",
                              result="held") / replayed
