"""Median per block of the `validator.collect` spans that ran on the
validator's classic tail (`tail="classic"`: the per-transaction collect
a channel with key-level validation parameters takes), over the
window's blocks the profiler did not watch.  None where the run kept no
such span: untraced, or a program whose collect spans carry no tail."""
import statistics


def read(obs):
    took = [s["duration_s"] for s in obs.get("attributed_spans", ())
            if s["name"] == "validator.collect"
            and s["attributes"].get("tail") == "classic"]
    return 1e3 * statistics.median(took) if took else None
