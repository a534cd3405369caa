"""Share (%) of the window the two whole-store checkpoints took: the
window's `state_checkpoint_seconds` + `history_checkpoint_seconds` over
its `process_uptime_seconds` (all on the device peer's perf_counter;
both fire inside the commit, every 256 blocks).  None on a program that
does not time the history store's, or where neither store wrote one."""
from harness import prom_delta

STORES = ("state_checkpoint_seconds", "history_checkpoint_seconds")


def read(obs):
    before, after = obs.get("prom_before"), obs.get("prom_after")
    if before is None or after is None:
        return None
    if any(name + "_sum" not in after for name in STORES):
        return None
    seconds = prom_delta(before, after, "process_uptime_seconds")
    if seconds <= 0:
        return None
    return 100.0 * sum(prom_delta(before, after, name + "_sum")
                       for name in STORES) / seconds
