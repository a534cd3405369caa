"""What the readers of the dispatch account and of the request path's
spans share: both read the window as the difference of the two
expositions the drivers leave (`obs["prom_before"]`, `obs["prom_after"]`)
and return None where their series is absent, as on a program that has
no account."""
from harness import prom_delta


def mean_ms(obs, series, per=None, **labels):
    """1e3 x the window's sum of histogram `series{labels}` over the
    window's count of `per` — a (count series, labels) pair, the
    series' own `_count{labels}` when not given.  None when an
    exposition is missing, the series observed nothing in the window,
    or `per` counted nothing."""
    before, after = obs.get("prom_before"), obs.get("prom_after")
    if before is None or after is None:
        return None
    if prom_delta(before, after, series + "_count", **labels) <= 0:
        return None
    per_name, per_labels = per or (series + "_count", labels)
    n = prom_delta(before, after, per_name, **per_labels)
    if n <= 0:
        return None
    return 1e3 * prom_delta(before, after, series + "_sum", **labels) / n


def held_share(obs):
    """Share (%) of the window the device was held by observed
    dispatches: the window's provider_device_held_seconds_total over
    its process_uptime_seconds (both on the device peer's
    perf_counter), averaged over the peer's devices."""
    before, after = obs.get("prom_before"), obs.get("prom_after")
    if before is None or after is None:
        return None
    held = after.get("provider_device_held_seconds_total")
    seconds = prom_delta(before, after, "process_uptime_seconds")
    if not held or seconds <= 0:
        return None
    return (100.0 * prom_delta(before, after,
                               "provider_device_held_seconds_total")
            / len(held) / seconds)
