"""Mean time an observed generic-lane dispatch kept the chip from the
next (program + transfers), in a catch-up cell cut at ~500 transactions:
`kernel.held_ms.generic`'s reading under a name that moves
`catchup_tps`."""
from account_readers import mean_ms


def read(obs):
    return mean_ms(obs, "provider_dispatch_held_seconds", lane="generic")
