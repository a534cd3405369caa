"""Share (%) of the signatures the device verified in the window that
rode the P-256 ladder lane (`generic`): 25.0 while a block's creators
earn no comb table (one creator signature beside three endorsements).
None where nothing was dispatched."""
from harness import prom_delta


def read(obs):
    before, after = obs.get("prom_before"), obs.get("prom_after")
    if before is None or after is None:
        return None
    total = prom_delta(before, after, "provider_dispatch_sigs_total")
    if total <= 0:
        return None
    return 100.0 * prom_delta(before, after, "provider_dispatch_sigs_total",
                              lane="generic") / total
