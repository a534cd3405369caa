"""Median per block of validator.collect + dispatch_wait + gate."""
from readers import block_ms


def read(obs):
    return block_ms(obs, ("validator.collect", "validator.dispatch_wait",
                          "validator.gate"))
