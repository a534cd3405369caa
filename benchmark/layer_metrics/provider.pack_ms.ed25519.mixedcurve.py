"""The provider's host packing of the Ed25519 rows dispatch for the
validator — SHA-512 over each whole message, the reduction mod L, the
word gathers — per block validated (the count of
validator_stage_seconds{stage="collect"}).  None on a program whose
account does not name the lane."""
from account_readers import mean_ms


def read(obs):
    return mean_ms(obs, "provider_dispatch_pack_seconds",
                   lane="ed25519-rows", site="validator",
                   per=("validator_stage_seconds_count",
                        {"stage": "collect"}))
