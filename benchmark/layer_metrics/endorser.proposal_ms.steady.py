"""Mean time of one proposal in the device peer's own endorser: the
three endorser.* spans' summed seconds over the count of
endorser.validate (every proposal begins there)."""
from account_readers import mean_ms

SPANS = ("endorser.validate", "endorser.simulate", "endorser.sign")


def read(obs):
    proposals = ("span_duration_seconds_count", {"span": SPANS[0]})
    parts = [mean_ms(obs, "span_duration_seconds", span=s, per=proposals)
             for s in SPANS]
    return None if parts[0] is None else sum(p or 0.0 for p in parts)
