"""`ledger.state_commit` + `ledger.history_commit` per write of a valid
transaction applied."""
from ledger_readers import span_us_per


def read(obs):
    return span_us_per(obs, ("ledger.state_commit", "ledger.history_commit"),
                       "writes")
