"""Mean time an observed generic-lane dispatch kept the chip from the
next (program + transfers)."""
from account_readers import mean_ms


def read(obs):
    return mean_ms(obs, "provider_dispatch_held_seconds", lane="generic")
