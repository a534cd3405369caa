"""Share of the window's VALID transactions whose envelope the private
half of the commit decoded again in Python
(`privdata_decoded_txs_total` over `ledger_tx_total{code="VALID"}`): 100
where every valid transaction writes under a collection and the hashed
writes are read off the envelope; a commit that reads them off the
block's lane table lowers it.  None on a program without the counter, or
where the window committed nothing valid."""
from harness import prom_delta


def read(obs):
    before, after = obs.get("prom_before"), obs.get("prom_after")
    if before is None or after is None:
        return None
    valid = prom_delta(before, after, "ledger_tx_total", code="VALID")
    if valid <= 0 or "privdata_decoded_txs_total" not in after:
        return None
    return 100.0 * prom_delta(before, after,
                              "privdata_decoded_txs_total") / valid
