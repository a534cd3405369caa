"""Time inside the ledger's per-block fsyncs (block file, state WAL,
history WAL: `ledger_fsync_seconds`, every store) per block validated
(the count of validator_stage_seconds{stage="collect"}).  None on a
program that does not time them."""
from account_readers import mean_ms


def read(obs):
    return mean_ms(obs, "ledger_fsync_seconds",
                   per=("validator_stage_seconds_count",
                        {"stage": "collect"}))
