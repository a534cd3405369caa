"""Share of the window's committed transactions, in blocks that have a
lane table, whose table was opened ahead of the commit — while the
validator waited for the device — and not inside it
(`ledger_lane_table_opened_total{at="validator_wait"}` over all its
`at`).  None on a program that has no such counter, or where no block of
the window had a lane table."""
from harness import prom_delta


def read(obs):
    before, after = obs.get("prom_before"), obs.get("prom_after")
    if before is None or after is None:
        return None
    if "ledger_lane_table_opened_total" not in after:
        return None
    txs = prom_delta(before, after, "ledger_lane_table_opened_total")
    if txs <= 0:
        return None
    return 100.0 * prom_delta(before, after, "ledger_lane_table_opened_total",
                              at="validator_wait") / txs
