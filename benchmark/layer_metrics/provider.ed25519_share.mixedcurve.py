"""Share of the signatures the device verified in the window that went
to the Ed25519 lanes (`ed25519-rows`, `ed25519`): 33.2% when the mix is
the configuration's.  None on a program whose account names no such
lane, or where nothing was dispatched."""
from harness import prom_delta

LANES = ("ed25519-rows", "ed25519")


def read(obs):
    before, after = obs.get("prom_before"), obs.get("prom_after")
    if before is None or after is None:
        return None
    series = after.get("provider_dispatch_sigs_total", ())
    if not any(labels.get("lane") in LANES for labels, _ in series):
        return None
    total = prom_delta(before, after, "provider_dispatch_sigs_total")
    if total <= 0:
        return None
    return 100.0 * sum(prom_delta(before, after,
                                  "provider_dispatch_sigs_total", lane=lane)
                       for lane in LANES) / total
