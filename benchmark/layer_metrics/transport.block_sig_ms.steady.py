"""Mean orderer-signature check of a delivered block on the device peer
(one single-signature dispatch, its queue included): span
deliver.block_sig."""
from account_readers import mean_ms


def read(obs):
    return mean_ms(obs, "span_duration_seconds", span="deliver.block_sig")
