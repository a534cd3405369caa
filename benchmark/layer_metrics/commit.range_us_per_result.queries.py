"""Microseconds of range replay (`ledger_mvcc_range_seconds`) per result
the replays re-read (`ledger_mvcc_range_reads_total`), over the window:
what one key of a recorded range costs the commit.  None on a program
without the two series, or where the window re-read nothing."""
from harness import prom_delta


def read(obs):
    before, after = obs.get("prom_before"), obs.get("prom_after")
    if before is None or after is None:
        return None
    results = prom_delta(before, after, "ledger_mvcc_range_reads_total")
    if results <= 0 or "ledger_mvcc_range_seconds_sum" not in after:
        return None
    return 1e6 * prom_delta(before, after,
                            "ledger_mvcc_range_seconds_sum") / results
