"""Mean gateway time per endorse verb in the window:
gateway_request_duration_seconds{verb="endorse"} sum / count deltas."""
from harness import prom_delta


def read(obs):
    before, after = obs.get("prom_before"), obs.get("prom_after")
    if before is None or after is None:
        return None
    name = "gateway_request_duration_seconds"
    n = prom_delta(before, after, name + "_count", verb="endorse")
    if n <= 0:
        return None
    return 1e3 * prom_delta(before, after, name + "_sum", verb="endorse") / n
