"""Time the commit's serial walk spent replaying recorded range queries
against state merged with the block's batch
(`ledger_mvcc_range_seconds`, one observation a block that replayed at
least one) per block validated in the window (the count of
validator_stage_seconds{stage="collect"}).  None on a program that has
no such histogram, or where no block of the window replayed a range."""
from account_readers import mean_ms


def read(obs):
    return mean_ms(obs, "ledger_mvcc_range_seconds",
                   per=("validator_stage_seconds_count",
                        {"stage": "collect"}))
