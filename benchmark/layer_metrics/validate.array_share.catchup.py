"""Share of the unique verify items of the blocks validated in the
window that the validator handed the provider as arrays — rows of the
deep tail's signature table, no VerifyItem built for one — and not as
items: 100 where every block is P-256 on the deep tail, above the
probe's size and bypassed by the verdict cache; the P-256 share of a
block that mixes curves; 0 on the classic tail.  None on a program that
has no such counter."""
from harness import prom_delta


def read(obs):
    before, after = obs.get("prom_before"), obs.get("prom_after")
    if before is None or after is None:
        return None
    if "validator_handoff_sigs_total" not in after:
        return None
    sigs = prom_delta(before, after, "validator_handoff_sigs_total")
    if sigs <= 0:
        return None
    return 100.0 * prom_delta(before, after, "validator_handoff_sigs_total",
                              form="arrays") / sigs
