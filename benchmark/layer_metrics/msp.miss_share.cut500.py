"""Share of the window's look-ups of the MSPs' validation caches that
the cache could not answer (`msp_cache_total{op="validate"}`: miss ÷
hit + miss, every MSP): ~0 where a channel's identities fit the caches'
100 entries, ~100 where a block brings more distinct creators than
that.  None on a program without the counter."""
from harness import prom_delta


def read(obs):
    before, after = obs.get("prom_before"), obs.get("prom_after")
    if before is None or after is None:
        return None
    asked = prom_delta(before, after, "msp_cache_total", op="validate")
    if asked <= 0:
        return None
    return 100.0 * prom_delta(before, after, "msp_cache_total",
                              op="validate", result="miss") / asked
