"""Share of the transactions validated in the window whose block took
the validator's deep C tail (digest, assemble, gate with no per-tx
Python) and not the classic one: the rule reads the state and the block,
and a channel with no key-level validation parameter reads 100.  None on
a program that has no such counter."""
from harness import prom_delta


def read(obs):
    before, after = obs.get("prom_before"), obs.get("prom_after")
    if before is None or after is None:
        return None
    if "validator_tail_total" not in after:
        return None
    txs = prom_delta(before, after, "validator_tail_total")
    if txs <= 0:
        return None
    return 100.0 * prom_delta(before, after, "validator_tail_total",
                              tail="deep") / txs
