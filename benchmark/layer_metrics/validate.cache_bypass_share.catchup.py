"""Share of the signatures the validator sent to the device in the
window that went there with no look-up in the verdict cache and were
stored nowhere (the rest of a block whose probe found the cache
silent).  None on a program that has no such counter."""
from harness import prom_delta


def read(obs):
    before, after = obs.get("prom_before"), obs.get("prom_after")
    if before is None or after is None:
        return None
    if "verify_cache_bypassed_total" not in after:
        return None
    sigs = prom_delta(before, after, "provider_dispatch_sigs_total",
                      site="validator")
    if sigs <= 0:
        return None
    return 100.0 * prom_delta(before, after, "verify_cache_bypassed_total",
                              site="commit") / sigs
