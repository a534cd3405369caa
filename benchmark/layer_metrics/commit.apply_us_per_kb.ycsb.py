"""`ledger.state_commit` + `ledger.history_commit` per KiB of key +
value bytes applied (`ledger_state_write_bytes_total`, read by the child
beside each block), over the blocks that have both: a span belongs to
the block it started in.  None on a program without the counter, or in
a run that kept no spans."""
import bisect

NAMES = ("ledger.state_commit", "ledger.history_commit")


def read(obs):
    spans = sorted((s["start"], s["duration_s"]) for s in obs.get("spans", ())
                   if s["name"] in NAMES)
    starts = [s for s, _ in spans]
    seconds = units = 0.0
    for block in obs.get("blocks", ()):
        n = block.get("counts", {}).get("write_bytes", 0)
        lo = bisect.bisect_left(starts, block["start"])
        hi = bisect.bisect_right(starts, block["end"])
        if hi > lo and n > 0:
            seconds += sum(d for _, d in spans[lo:hi])
            units += n
    return 1e6 * seconds / (units / 1024.0) if units else None
