"""Profiling surface: host+device trace capture behind the ops plane.

Reference parity: the peer serves Go pprof when peer.profile.enabled
(/root/reference/internal/peer/node/start.go:813-825); the orderer
likewise (orderer/common/server/main.go:408).  The TPU-native analogue
captures BOTH planes:

  * device: jax.profiler traces (XLA/TPU timeline, one .trace per
    capture) — POST /debug/profile?seconds=N[&python=1] writes a trace
    directory and returns its path with what puts the trace beside the
    program's own record: a `profile.mark` annotation and its
    `perf_counter` reading (the offset between the two clocks), the
    registry's exposition at both edges of the capture, and the
    recorder's spans that began inside it.  The interpreter's tracer
    stays off unless `python=1` asks: in a node with dozens of busy
    threads it makes a one-second capture take minutes to end;
  * host: cProfile over the same window — POST /debug/pprof?seconds=N
    returns pstats text for the capture window;
  * per-phase device timings: the provider's dispatch/resolve spans are
    recorded as histogram metrics (fabric_tpu/ops_plane/metrics.py) and
    appear on /metrics alongside the commit-phase timings.

Wire-up: node/peer.py and node/orderer.py register these routes on
their OperationsServer when `profiling: true` is configured.
"""

from __future__ import annotations

import cProfile
import io
import os
import pstats
import tempfile
import threading
import time

_lock = threading.Lock()


MARK = "profile.mark"


def capture_device_trace(seconds: float, out_dir: str = None,
                         python_tracer: bool = False) -> dict:
    """Capture a jax.profiler trace for `seconds`; returns metadata.

    The trace is written under out_dir (default: a fresh directory in
    the system tmpdir) in TensorBoard/xplane format — load with
    `tensorboard --logdir` or xprof.  Device work happening in other
    threads during the window is captured too (the point: profile a
    serving node under live block traffic).

    Beside the files: `mark_perf`, the `perf_counter` reading taken
    inside the trace's `profile.mark` annotation (trace time - mark_perf
    puts the program's spans on the trace's clock); `start_perf` /
    `end_perf`, the capture's edges; `prom_before` / `prom_after`, the
    registry's exposition at those edges (taken here, because ending a
    trace can take long and counters read from outside would cover that
    too); `spans`, the flight recorder's spans that began between them;
    `stop_s`, what ending the trace cost."""
    import jax

    from . import registry, tracing

    out_dir = out_dir or tempfile.mkdtemp(prefix="fabric_tpu_trace_")
    if not _lock.acquire(blocking=False):
        return {"error": "a capture is already in progress"}
    try:
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 1 if python_tracer else 0
        prom_before = registry.expose_text()
        jax.profiler.start_trace(out_dir, profiler_options=options)
        try:
            start = time.perf_counter()
            with jax.profiler.TraceAnnotation(MARK):
                mark = time.perf_counter()
            time.sleep(seconds)
            prom_after = registry.expose_text()
        finally:
            end = time.perf_counter()
            jax.profiler.stop_trace()
        stop_s = time.perf_counter() - end
    finally:
        _lock.release()
    files = []
    for root, _dirs, names in os.walk(out_dir):
        files.extend(os.path.join(root, n) for n in names)
    recorder = tracing.tracer.recorder
    spans = [{"name": s["name"], "start": s["start"],
              "duration_s": s["duration_s"], "trace_id": s["trace_id"],
              "thread": s["thread"]}
             for rec in recorder.list()["recent"]
             for s in (recorder.get(rec["trace_id"]) or {"spans": ()})["spans"]
             if start <= s["start"] <= end]
    return {"trace_dir": out_dir, "seconds": seconds,
            "python_tracer": bool(python_tracer),
            "files": sorted(files)[:50],
            "mark": MARK, "mark_perf": mark,
            "start_perf": start, "end_perf": end, "stop_s": stop_s,
            "prom_before": prom_before, "prom_after": prom_after,
            "spans": spans}


def capture_host_profile(seconds: float, top: int = 40) -> dict:
    """cProfile the whole process for `seconds`; returns pstats text.

    Captures all Python work in the window (the Go pprof CPU-profile
    shape).  Note: profiles only Python frames — device time shows as
    blocking calls into jax."""
    if not _lock.acquire(blocking=False):
        return {"error": "a capture is already in progress"}
    prof = cProfile.Profile()
    try:
        prof.enable()
        time.sleep(seconds)
    finally:
        prof.disable()
        _lock.release()
    buf = io.StringIO()
    stats = pstats.Stats(prof, stream=buf)
    stats.sort_stats("cumulative").print_stats(top)
    return {"seconds": seconds, "pstats": buf.getvalue()}


def register_routes(ops, enabled: bool = True) -> None:
    """Install /debug/profile (device) and /debug/pprof (host) on an
    OperationsServer.  Gated by config like the reference's
    peer.profile.enabled — profiling endpoints stall the serving
    process and must be opt-in."""
    if not enabled:
        return

    def _seconds(path: str, default: float = 3.0) -> float:
        if "?" in path:
            for kv in path.split("?", 1)[1].split("&"):
                if kv.startswith("seconds="):
                    try:
                        return min(60.0, max(0.1, float(kv[8:])))
                    except ValueError:
                        pass
        return default

    def device(path: str, body: bytes):
        query = path.split("?", 1)[1].split("&") if "?" in path else ()
        return 200, capture_device_trace(
            _seconds(path), python_tracer="python=1" in query)

    def host(path: str, body: bytes):
        return 200, capture_host_profile(_seconds(path))

    ops.register_route("POST", "/debug/profile", device)
    ops.register_route("POST", "/debug/pprof", host)
