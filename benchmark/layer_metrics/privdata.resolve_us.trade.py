"""Microseconds of hash matching (`privdata_resolve_seconds`: one
observation a private write-set, fetch included) per write-set resolved
(`privdata_txs_total{result="resolved"}`), over the window: what finding
one collection's cleartext for one transaction costs the commit.  None
on a program without the two series, or where the window resolved none."""
from harness import prom_delta


def read(obs):
    before, after = obs.get("prom_before"), obs.get("prom_after")
    if before is None or after is None:
        return None
    resolved = prom_delta(before, after, "privdata_txs_total",
                          result="resolved")
    if resolved <= 0 or "privdata_resolve_seconds_sum" not in after:
        return None
    return 1e6 * prom_delta(before, after,
                            "privdata_resolve_seconds_sum") / resolved
