"""Share of the window's transactions whose creator the block's memo
did not know, so that the validator resolved it through the MSP
(`validator_creators_total`: first ÷ first + again): 100 where every
creator of a block is distinct, 12.8 where 64 clients share a block of
500.  None on a program without the counter."""
from harness import prom_delta


def read(obs):
    before, after = obs.get("prom_before"), obs.get("prom_after")
    if before is None or after is None:
        return None
    txs = prom_delta(before, after, "validator_creators_total")
    if txs <= 0:
        return None
    return 100.0 * prom_delta(before, after, "validator_creators_total",
                              seen="first") / txs
