"""What the readers of the ledger's counters share.  The counters
(`ledger_tx_total`, `ledger_mvcc_reads_total`, `ledger_state_writes_total`)
are read as the window's difference of the two expositions, or per block
from what a driver left beside each block (`obs["blocks"]`: start, end and
`counts`, on the spans' clock).  Each returns None where the program has
no such counter or the run kept no spans."""
from harness import prom_delta


def valid_share_pct(obs):
    """Transactions committed VALID over all committed in the window."""
    before, after = obs.get("prom_before"), obs.get("prom_after")
    if before is None or after is None:
        return None
    total = prom_delta(before, after, "ledger_tx_total")
    if total <= 0:
        return None
    return 100.0 * prom_delta(before, after, "ledger_tx_total",
                              code="VALID") / total


def span_us_per(obs, names: tuple, count: str):
    """Microseconds of the named spans per unit of `count`, over the
    blocks that have both: a span belongs to the block it started in."""
    seconds = units = 0.0
    for block in obs.get("blocks", ()):
        inside = [s["duration_s"] for s in obs.get("spans", ())
                  if s["name"] in names
                  and block["start"] <= s["start"] <= block["end"]]
        n = block.get("counts", {}).get(count, 0)
        if inside and n > 0:
            seconds += sum(inside)
            units += n
    return 1e6 * seconds / units if units else None
