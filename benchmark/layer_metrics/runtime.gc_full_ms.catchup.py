"""Time inside the collector's full (generation-2) passes, thaws
included, per block validated (the count of
validator_stage_seconds{stage="collect"}).  None on a program that
keeps no such account."""
from account_readers import mean_ms


def read(obs):
    return mean_ms(obs, "runtime_gc_full_seconds",
                   per=("validator_stage_seconds_count",
                        {"stage": "collect"}))
