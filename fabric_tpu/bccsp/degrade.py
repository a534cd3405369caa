"""Graceful degradation wrapper for the BCCSP plane.

`DegradingProvider` fronts a primary (device) provider with a circuit
breaker and a software fallback that is guaranteed to produce identical
validation flags (both implement the same malformed-item-is-False
batch_verify contract, and the chaos suite asserts flag identity):

  HEALTHY    batches go to the primary; exceptions from enqueue or
             resolve — AND silent per-batch fallbacks the JAXTPU
             provider performs internally (its `fallbacks` counter
             moving) — count against the breaker
  DEGRADED   the breaker tripped: batches route straight to the SW
             fallback, skipping the cost of a doomed device attempt;
             a cooldown timer (exponential per trip) arms a probe
  PROBE      first batch after cooldown goes to the primary again —
             success restores HEALTHY, failure re-trips with a longer
             cooldown

Every transition emits `bccsp_degraded` (gauge), a
`bccsp_breaker_transitions_total` count, a jlog line, and a span event
on the ambient trace.  Signing, key-gen, and hashing are host-side in
every provider and always delegate to the primary.

The ops plane reads `.backend` — "jaxtpu" while healthy,
"sw(degraded)" while tripped — which the peer's `/healthz` bccsp
checker surfaces.
"""

from __future__ import annotations

import logging
import threading
import time
from typing import Optional, Sequence

import numpy as np

from fabric_tpu.ops_plane import tracing
from fabric_tpu.ops_plane.logging import jlog

from .provider import Provider, VerifyItem, as_list

logger = logging.getLogger("fabric_tpu.bccsp.degrade")


class DegradingProvider(Provider):
    def __init__(self, primary: Provider, fallback: Provider,
                 failure_threshold: int = 2,
                 cooldown_base_s: float = 1.0,
                 cooldown_max_s: float = 30.0,
                 watch_silent_fallbacks: bool = True):
        self.primary = primary
        self.sw = fallback
        self.name = primary.name
        self.failure_threshold = int(failure_threshold)
        self.cooldown_base_s = cooldown_base_s
        self.cooldown_max_s = cooldown_max_s
        # the JAXTPU provider absorbs device errors per batch by running
        # the batch on ITS OWN sw fallback without raising; watching its
        # `fallbacks` counter lets the breaker see that sickness too
        self.watch_silent_fallbacks = bool(watch_silent_fallbacks)
        self._lock = threading.Lock()
        self._degraded = False
        self._consec_fails = 0
        self._trips = 0
        self._probe_at = 0.0

    # -- breaker --------------------------------------------------------

    @property
    def degraded(self) -> bool:
        return self._degraded

    @property
    def backend(self) -> str:
        return (f"{self.sw.name}(degraded)" if self._degraded
                else self.primary.name)

    def _use_primary(self) -> bool:
        """Route the next batch to the primary?  True also arms the
        post-cooldown probe."""
        if not self._degraded:
            return True
        with self._lock:
            if self._degraded and time.monotonic() >= self._probe_at:
                # push the next probe out so concurrent batches don't
                # stampede a sick device; success clears everything
                self._probe_at = time.monotonic() + self.cooldown_base_s
                return True
            return False

    def _on_success(self) -> None:
        with self._lock:
            self._consec_fails = 0
            if not self._degraded:
                return
            self._degraded = False
            self._trips_observe("restored")

    def _on_failure(self, why: str) -> None:
        with self._lock:
            self._consec_fails += 1
            if self._degraded:
                # failed probe: back off harder
                self._trips += 1
                self._probe_at = time.monotonic() + self._cooldown()
                return
            if self._consec_fails < self.failure_threshold:
                return
            self._degraded = True
            self._trips += 1
            self._probe_at = time.monotonic() + self._cooldown()
            self._trips_observe(why)

    def _cooldown(self) -> float:
        return min(self.cooldown_max_s,
                   self.cooldown_base_s * (2 ** min(self._trips - 1, 16)))

    def _trips_observe(self, reason: str) -> None:
        """Caller holds self._lock; everything here is best-effort."""
        state = "degraded" if self._degraded else "healthy"
        try:
            from fabric_tpu.ops_plane import registry
            registry.gauge(
                "bccsp_degraded",
                "1 while the crypto provider runs on the SW fallback"
            ).set(1.0 if self._degraded else 0.0)
            registry.counter(
                "bccsp_breaker_transitions_total",
                "crypto-provider breaker state changes").add(
                    1, to=state, reason=reason)
            jlog(logger, "bccsp.breaker",
                 level=logging.WARNING if self._degraded else logging.INFO,
                 state=state, reason=reason, trips=self._trips,
                 backend=self.backend)
            tracing.event("bccsp." + state, reason=reason,
                          backend=self.backend)
        except Exception:
            pass

    # -- verification ---------------------------------------------------

    def _silent_fallbacks(self) -> int:
        if not self.watch_silent_fallbacks:
            return 0
        stats = getattr(self.primary, "stats", None)
        if isinstance(stats, dict):
            return int(stats.get("fallbacks", 0))
        return 0

    def batch_verify_async(self, items: Sequence[VerifyItem]):
        return self._guarded(as_list(items), self.primary.batch_verify_async,
                             self.sw.batch_verify_async)

    def batch_verify_packed_async(self, batch):
        """The packed verb behind the same breaker (owned here:
        `__getattr__` would hand the table straight to the primary).
        The software fallback builds the items from the table when, and
        only when, it is the one that verifies."""
        packed = getattr(self.primary, "batch_verify_packed_async", None)
        if packed is None:      # a primary that knows items only
            return self.batch_verify_async(list(batch))
        return self._guarded(batch, packed,
                             self.sw.batch_verify_packed_async)

    def _guarded(self, items, primary, fallback):
        """One verb — `primary(items)`, in its place `fallback(items)`
        — behind the breaker; `items` is a list, or a signature table
        for the packed verb."""
        if not self._use_primary():
            return fallback(items)
        fb0 = self._silent_fallbacks()
        try:
            resolve = primary(items)
        except Exception as exc:
            self._on_failure("enqueue:" + type(exc).__name__)
            logger.warning("primary bccsp enqueue failed (%r); "
                           "falling back to %s", exc, self.sw.name)
            return fallback(items)

        def _resolve():
            try:
                out = resolve()
            except Exception as exc:
                self._on_failure("resolve:" + type(exc).__name__)
                logger.warning("primary bccsp resolve failed (%r); "
                               "re-verifying %d items on %s",
                               exc, len(items), self.sw.name)
                return fallback(items)()
            if self._silent_fallbacks() > fb0:
                # results are correct (primary already re-ran on its own
                # sw path) but the device is sick: tell the breaker
                self._on_failure("silent_fallback")
            else:
                self._on_success()
            return out

        return _resolve

    def batch_verify(self, items: Sequence[VerifyItem]) -> np.ndarray:
        return self.batch_verify_async(items)()

    # -- host-side verbs ------------------------------------------------

    def key_gen(self, scheme: str):
        return self.primary.key_gen(scheme)

    def sign(self, private_key, payload: bytes) -> bytes:
        return self.primary.sign(private_key, payload)

    def hash(self, data: bytes, algo: str = "sha256") -> bytes:
        return self.primary.hash(data, algo)

    def stats_snapshot(self):
        snap = getattr(self.primary, "stats_snapshot", None)
        return snap() if callable(snap) else None

    def __getattr__(self, name):
        # anything this wrapper doesn't own (stats, idemix probes,
        # device labels, ...) belongs to the primary — callers must not
        # have to care whether the provider is breaker-fronted
        return getattr(self.primary, name)
