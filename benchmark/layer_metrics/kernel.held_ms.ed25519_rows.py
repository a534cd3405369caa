"""Mean time an observed dispatch of the Ed25519 fixed-comb rows lane
kept the chip from the next (program + transfers).  None on a program
whose account does not name the lane."""
from account_readers import mean_ms


def read(obs):
    return mean_ms(obs, "provider_dispatch_held_seconds", lane="ed25519-rows")
