"""`validator.gate` per key the classic gate looked a policy up for
(`validator_sbe_keys_total`, every `judged`: written keys and `#meta`
keys), over the blocks that have both the span and the count.  None on
a program that has no such counter."""
from ledger_readers import span_us_per


def read(obs):
    return span_us_per(obs, ("validator.gate",), "sbe_keys")
