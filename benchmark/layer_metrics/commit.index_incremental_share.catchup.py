"""Share of the window's shard applies that changed some key's existence
whose ordered key list followed by one bisect a key, in place
(`state_index_update_total{mode="incremental"}` over `incremental` +
`merge`; the rest filtered and sorted the shard's whole list).  None on
a program that has no such counter, or where no shard apply of the
window added or removed a key (`mode="none"` alone)."""
from harness import prom_delta


def read(obs):
    before, after = obs.get("prom_before"), obs.get("prom_after")
    if before is None or after is None:
        return None
    if "state_index_update_total" not in after:
        return None
    incremental = prom_delta(before, after, "state_index_update_total",
                             mode="incremental")
    structural = incremental + prom_delta(
        before, after, "state_index_update_total", mode="merge")
    if structural <= 0:
        return None
    return 100.0 * incremental / structural
