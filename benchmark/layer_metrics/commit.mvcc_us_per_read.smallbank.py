"""`ledger.mvcc` per read the serial walk validated (parse of the
still-valid envelopes included, as the span has it)."""
from ledger_readers import span_us_per


def read(obs):
    return span_us_per(obs, ("ledger.mvcc",), "reads")
