"""Share of the keys the classic gate looked a policy up for in the
window that a validation parameter governed — a committed one
(`judged="parameter"`) or one the block's own earlier transaction set
(`"overlay"`) — and not the namespace's policy.  0 on a peer blind to
key-level endorsement; None on a program that has no such counter."""
from harness import prom_delta


def read(obs):
    before, after = obs.get("prom_before"), obs.get("prom_after")
    if before is None or after is None:
        return None
    if "validator_sbe_keys_total" not in after:
        return None
    keys = prom_delta(before, after, "validator_sbe_keys_total")
    if keys <= 0:
        return None
    return 100.0 * (
        prom_delta(before, after, "validator_sbe_keys_total",
                   judged="parameter")
        + prom_delta(before, after, "validator_sbe_keys_total",
                     judged="overlay")) / keys
