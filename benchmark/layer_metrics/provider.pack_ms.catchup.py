"""The provider's host packing for the validator, per block validated
(the count of validator_stage_seconds{stage="collect"})."""
from account_readers import mean_ms


def read(obs):
    return mean_ms(obs, "provider_dispatch_pack_seconds", site="validator",
                   per=("validator_stage_seconds_count",
                        {"stage": "collect"}))
