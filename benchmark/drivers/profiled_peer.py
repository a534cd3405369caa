"""The device peer of a traced served run: `fabric_tpu.node.peer`,
unchanged, plus one ops route that captures the profiler's trace with
options the program's own `POST /debug/profile` does not take.

    POST /bench/profile?seconds=N  ->  {"trace_dir", "mark_perf", "stop_s",
                                        "attempts", "prom_before",
                                        "prom_after"}

The two expositions of the peer's metrics are taken at the capture's own
edges, in this process: ending a trace takes many seconds, and counters
read from outside would cover those too.

Why not the program's route: it starts `jax.profiler` with the
interpreter's tracer on; in a peer with dozens of busy threads a
one-second capture then takes minutes to end (PERF.md §6, PR 23).  Only
the process that holds the chip can trace it, so the route has to live
there; nothing else of the peer is touched, and only `--trace 1` runs
start the peer this way.
"""

import glob
import os
import shutil
import sys
import tempfile
import time

from fabric_tpu.node import peer

# a trace that holds device op events is tens of megabytes; one without
# (no whole execution fell inside the slice) is far under this
MIN_DEVICE_TRACE_BYTES = 5_000_000
TRIES = 40


def capture(path: str, body: bytes):
    """Slices of `seconds`, one after another, until one holds device
    work (or TRIES are spent: then `trace_dir` is null).  A slice is kept
    short because ending a capture costs about a minute for every
    generic-lane execution in it; an empty one costs nothing."""
    import jax
    from fabric_tpu.ops_plane import registry
    seconds = float(path.split("seconds=", 1)[1].split("&", 1)[0])
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    for attempt in range(1, TRIES + 1):
        out_dir = tempfile.mkdtemp(prefix="bench_trace_")
        prom_before = registry.expose_text()
        jax.profiler.start_trace(out_dir, profiler_options=options)
        try:
            with jax.profiler.TraceAnnotation("bench.mark"):
                mark = time.perf_counter()
            time.sleep(seconds)
            prom_after = registry.expose_text()
        finally:
            t_stop = time.perf_counter()
            jax.profiler.stop_trace()
        stop_s = time.perf_counter() - t_stop
        size = sum(os.path.getsize(f) for f in glob.glob(
            os.path.join(out_dir, "**", "*.xplane.pb"), recursive=True))
        if size >= MIN_DEVICE_TRACE_BYTES:
            return 200, {"trace_dir": out_dir, "mark_perf": mark,
                         "stop_s": stop_s, "attempts": attempt,
                         "prom_before": prom_before,
                         "prom_after": prom_after}
        shutil.rmtree(out_dir, ignore_errors=True)
        time.sleep(0.2)
    return 200, {"trace_dir": None, "attempts": TRIES}


class ProfiledPeer(peer.PeerNode):
    def __init__(self, *args, **kwargs):
        super().__init__(*args, **kwargs)
        self.ops.register_route("POST", "/bench/profile", capture)


if __name__ == "__main__":
    peer.PeerNode = ProfiledPeer
    sys.exit(peer.main())
