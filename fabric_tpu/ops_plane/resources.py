"""Process resource telemetry: stdlib /proc collector feeding /metrics.

The leak gate (ROADMAP direction #4) needs RSS, fd count, thread
count, GC pressure, native arena-pool occupancy and verdict-cache
occupancy AS TIME SERIES — none of which the registry records today.
This collector samples them on an interval into plain gauges, so the
existing exposition (`/metrics`), the SLO evaluator and the timeseries
ring store all pick them up with zero extra wiring:

  process_resident_memory_bytes   /proc/self/status VmRSS
  process_open_fds                len(/proc/self/fd)
  process_threads                 threading.active_count()
  process_allocated_blocks        sys.getallocatedblocks() — the
                                  crispest pure-Python ref-leak proxy
  process_gc_collections_total    gc.get_stats(), {generation=} label
  process_gc_uncollectable_total  gc.get_stats(), {generation=} label
  native_arena_pool_free          _fastparse.stats() pool gauges
  native_arena_pool_hit_total     (arena reuse economics; absent when
  native_arena_pool_miss_total     the native parser isn't built)
  native_arena_pool_drop_total
  jax_live_buffer_bytes           sum of live jax array nbytes — only
                                  when jax is ALREADY imported (the
                                  collector never initializes a device)

Extra per-node series (verdict-cache occupancy, queue depths...) ride
`add_source(name, fn)`: fn() -> float, sampled with the same cadence
and surfaced as a gauge of the same name.

Zero-overhead guarantee: gauges register at construction time, so a
node that leaves the `resources` config sub-dict disabled constructs
nothing and its /metrics output is byte-identical to before this
module existed.  All reads are stdlib (/proc, gc, sys, threading) and
every probe degrades to "metric absent" off-Linux or when a source is
missing, never to an exception on the sampling thread.

`provenance()` also lives here: the {platform, device_kind, n_devices,
hostname} stamp `__graft_entry__.dryrun_multichip` puts in its record,
so that a virtual CPU mesh is never read as a chip.
"""

from __future__ import annotations

import gc
import logging
import os
import socket
import sys
import threading
import time
from typing import Callable, Dict, Optional

from .metrics import MetricsRegistry
from .metrics import registry as default_registry

logger = logging.getLogger("fabric_tpu.ops_plane.resources")

__all__ = ["ResourceCollector", "read_rss_bytes", "count_open_fds",
           "provenance", "register_routes"]


def read_rss_bytes() -> Optional[float]:
    """VmRSS from /proc/self/status, bytes; None off-Linux."""
    try:
        with open("/proc/self/status") as f:
            for line in f:
                if line.startswith("VmRSS:"):
                    return float(line.split()[1]) * 1024.0
    except OSError:
        pass
    return None


def count_open_fds() -> Optional[int]:
    try:
        return len(os.listdir("/proc/self/fd"))
    except OSError:
        return None


def _initialized_jax():
    """The jax module if this process has already initialized a backend,
    else None.  Asking jax.devices() or jax.live_arrays() initializes
    one, and on a TPU host that takes the chip from the process that
    owns it — so a process that merely imported jax is left alone."""
    jax = sys.modules.get("jax")
    if jax is None:
        return None
    from jax._src import xla_bridge
    return jax if xla_bridge.backends_are_initialized() else None


def provenance() -> dict:
    """Where a measurement ran: {platform, device_kind, n_devices,
    hostname}.  `platform` is "tpu" only on real TPU devices; any other
    initialized backend (host-platform virtual meshes included) is
    "cpu-virtual", and a process that initialized none is "none".
    Never initializes a backend itself: callers that measure devices
    already have."""
    out = {"platform": "none", "device_kind": "unknown",
           "n_devices": 0, "hostname": socket.gethostname()}
    jax = _initialized_jax()
    if jax is not None:
        devs = jax.devices()
        out["n_devices"] = len(devs)
        out["device_kind"] = str(devs[0].device_kind)
        out["platform"] = ("tpu" if devs[0].platform == "tpu"
                           else "cpu-virtual")
    return out


class ResourceCollector:
    """Samples process/runtime resources into registry gauges.

    Config keys (the node's `resources` sub-dict):
      enabled       node-level gate (read by the node, not here)
      interval_s    sampling cadence (default 5.0)
      jax_buffers   include jax_live_buffer_bytes (default True; only
                    ever read when jax is already imported)
    """

    def __init__(self, cfg: Optional[dict] = None, *,
                 registry: Optional[MetricsRegistry] = None,
                 clock=None):
        cfg = dict(cfg or {})
        self.registry = registry or default_registry
        self._clock = clock or time.monotonic
        self.interval_s = max(0.05, float(cfg.get("interval_s", 5.0)))
        self.jax_buffers = bool(cfg.get("jax_buffers", True))
        self._sources: Dict[str, Callable[[], float]] = {}
        self._g_rss = self.registry.gauge(
            "process_resident_memory_bytes", "VmRSS of this process")
        self._g_fds = self.registry.gauge(
            "process_open_fds", "open file descriptors")
        self._g_threads = self.registry.gauge(
            "process_threads", "live Python threads")
        self._g_blocks = self.registry.gauge(
            "process_allocated_blocks",
            "sys.getallocatedblocks() — live interpreter allocations")
        self._g_gc_coll = self.registry.gauge(
            "process_gc_collections_total", "GC runs per generation")
        self._g_gc_unc = self.registry.gauge(
            "process_gc_uncollectable_total",
            "uncollectable objects per generation")
        self._g_jax = None
        self._stop = threading.Event()
        self._thread: Optional[threading.Thread] = None

    def add_source(self, name: str, fn: Callable[[], float]) -> None:
        """Register an extra series: fn() -> float, sampled each
        collect into a gauge named `name` (exceptions skip the tick)."""
        self.registry.gauge(name, "resource collector source")
        self._sources[name] = fn

    # -- one sweep -----------------------------------------------------------

    def collect(self) -> dict:
        """Sample every source into its gauge; returns the snapshot."""
        snap: dict = {}
        rss = read_rss_bytes()
        if rss is not None:
            self._g_rss.set(rss)
            snap["process_resident_memory_bytes"] = rss
        fds = count_open_fds()
        if fds is not None:
            self._g_fds.set(float(fds))
            snap["process_open_fds"] = fds
        nthreads = float(threading.active_count())
        self._g_threads.set(nthreads)
        snap["process_threads"] = nthreads
        try:
            blocks = float(sys.getallocatedblocks())
            self._g_blocks.set(blocks)
            snap["process_allocated_blocks"] = blocks
        except Exception:
            pass
        try:
            for gen, st in enumerate(gc.get_stats()):
                self._g_gc_coll.set(float(st.get("collections", 0)),
                                    generation=str(gen))
                self._g_gc_unc.set(float(st.get("uncollectable", 0)),
                                   generation=str(gen))
            snap["process_gc_collections_total"] = sum(
                st.get("collections", 0) for st in gc.get_stats())
        except Exception:
            pass
        self._collect_native(snap)
        if self.jax_buffers:
            self._collect_jax(snap)
        for name, fn in self._sources.items():
            try:
                v = float(fn())
            except Exception:
                continue
            self.registry.gauge(name).set(v)
            snap[name] = v
        return snap

    def _collect_native(self, snap: dict) -> None:
        """Arena-pool occupancy from the native parser's counters —
        the parse-path's reuse economics, absent when _fastparse isn't
        built (the gauges simply never register)."""
        try:
            from fabric_tpu.native import _fastparse
            stats = _fastparse.stats()
        except Exception:
            return
        for key, metric in (("pool_free", "native_arena_pool_free"),
                            ("pool_hit", "native_arena_pool_hit_total"),
                            ("pool_miss", "native_arena_pool_miss_total"),
                            ("pool_drop", "native_arena_pool_drop_total")):
            if key in stats:
                self.registry.gauge(metric).set(float(stats[key]))
                snap[metric] = float(stats[key])

    def _collect_jax(self, snap: dict) -> None:
        """Live device-buffer bytes — only when jax is ALREADY loaded
        (sampling must never initialize a backend)."""
        jax = _initialized_jax()
        if jax is None:
            return
        try:
            total = float(sum(getattr(a, "nbytes", 0)
                              for a in jax.live_arrays()))
        except Exception:
            return
        if self._g_jax is None:
            self._g_jax = self.registry.gauge(
                "jax_live_buffer_bytes", "bytes held by live jax arrays")
        self._g_jax.set(total)
        snap["jax_live_buffer_bytes"] = total

    # -- lifecycle -----------------------------------------------------------

    def _loop(self) -> None:
        while not self._stop.wait(self.interval_s):
            try:
                self.collect()
            except Exception:       # keep the collector alive
                logger.exception("resource collect failed")

    def start(self) -> "ResourceCollector":
        if self._thread is None:
            self._stop.clear()
            self.collect()          # first point lands immediately
            self._thread = threading.Thread(
                target=self._loop, name="resource-collector", daemon=True)
            self._thread.start()
        return self

    def stop(self) -> None:
        self._stop.set()
        if self._thread is not None:
            self._thread.join(timeout=5.0)
            self._thread = None


def register_routes(ops, collector: ResourceCollector) -> None:
    """Mount GET /resources: one fresh snapshot as JSON (the same
    numbers the gauges carry, without parsing exposition text)."""

    def _resources(path: str, body: bytes):
        return 200, collector.collect()

    ops.register_route("GET", "/resources", _resources)
