"""The device provider's account of its dispatches: who asked, which
program, how long the batch was packed, how long the dispatch waited for
the chip and how long it held it.

One record per call of a lane's compiled program, stamped on
`perf_counter` where it happens:

  t_call   the caller entered batch_verify_async (or the previous
           dispatch of the same batch returned)
  t_enq0   just before the program's call
  t_enq1   just after it (jax enqueues and returns)
  t_ready  a waiter that was already blocked on the output saw it ready

One chip runs its dispatches in order, so

  start      = max(t_enq1, t_ready of the dispatch observed before)
  queue wait = start - t_enq1
  held       = t_ready - start      program + transfers: how long this
                                    dispatch kept the chip from the next
  pack       = t_enq0 - t_call      host work before the call

A dispatch whose output was already ready when somebody first looked
gives only an upper bound for its end: it is counted as unobserved and
adds no held time of its own.  If the chip went straight on to an
observed dispatch, that one's held time covers both (its start is the
last *observed* end), so the device's total stays whole.

Always on: a few float adds and registry updates per dispatch of >= 2 ms.
"""

from __future__ import annotations

import threading

_HELD_BUCKETS = (0.001, 0.0025, 0.005, 0.01, 0.025, 0.05, 0.1, 0.25, 0.5,
                 1.0, 2.5, float("inf"))


class DispatchRecord:
    __slots__ = ("lane", "program", "site", "sigs", "t_call", "t_enq0",
                 "t_enq1", "t_ready", "observed", "queue_wait_s", "held_s")

    def __init__(self, lane, program, site, sigs, t_call, t_enq0, t_enq1):
        self.lane = lane
        self.program = program
        self.site = site
        self.sigs = sigs
        self.t_call = t_call
        self.t_enq0 = t_enq0
        self.t_enq1 = t_enq1
        self.t_ready = None
        self.observed = False
        self.queue_wait_s = None
        self.held_s = None

    @property
    def pack_s(self) -> float:
        return max(0.0, self.t_enq0 - self.t_call)

    def as_attribute(self) -> dict:
        """The record as a span attribute (milliseconds)."""
        def ms(v):
            return None if v is None else round(v * 1e3, 3)
        return {"lane": self.lane, "program": self.program,
                "site": self.site, "sigs": self.sigs,
                "pack_ms": ms(self.pack_s),
                "enqueue_ms": ms(self.t_enq1 - self.t_enq0),
                "queue_wait_ms": ms(self.queue_wait_s),
                "held_ms": ms(self.held_s), "observed": self.observed}


class DispatchAccount:
    """The account of one provider, whose dispatches all run on the
    devices `device_labels` names (one chip, or one mesh working as
    one)."""

    def __init__(self, device_labels, registry=None):
        self.device_labels = tuple(device_labels)
        self._registry = registry
        self._lock = threading.Lock()
        self._prev_ready = float("-inf")

    def _reg(self):
        if self._registry is None:
            from fabric_tpu.ops_plane import registry
            self._registry = registry
        return self._registry

    def enqueued(self, lane: str, program: str, site: str, sigs: int,
                 t_call: float, t_enq0: float,
                 t_enq1: float) -> DispatchRecord:
        """The program was called: count the dispatch and its packing."""
        rec = DispatchRecord(lane, program, site, sigs, t_call, t_enq0,
                             t_enq1)
        try:
            reg = self._reg()
            reg.counter(
                "provider_dispatch_total",
                "calls of a lane's compiled program").add(
                    1, lane=lane, program=program, site=site)
            reg.counter(
                "provider_dispatch_sigs_total",
                "real signatures in those calls").add(
                    sigs, lane=lane, program=program, site=site)
            reg.histogram(
                "provider_dispatch_pack_seconds",
                "host time from the batch's entry (or its previous "
                "dispatch) to the program's call").observe(
                    rec.pack_s, lane=lane, site=site)
        except Exception:
            pass                 # the account never breaks a dispatch
        return rec

    def ready(self, rec: DispatchRecord, t_ready: float,
              observed: bool) -> None:
        """Somebody looked at the output at `t_ready`; `observed` when
        they were already waiting and saw it become ready."""
        with self._lock:
            start = min(max(rec.t_enq1, self._prev_ready), t_ready)
            rec.t_ready = t_ready
            rec.observed = observed
            rec.queue_wait_s = max(0.0, start - rec.t_enq1)
            rec.held_s = (t_ready - start) if observed else 0.0
            if observed and t_ready > self._prev_ready:
                self._prev_ready = t_ready
        try:
            reg = self._reg()
            reg.histogram(
                "provider_dispatch_queue_wait_seconds",
                "time a dispatch waited for the chip behind earlier "
                "ones").observe(rec.queue_wait_s, lane=rec.lane,
                                site=rec.site)
            if not observed:
                reg.counter(
                    "provider_dispatch_unobserved_total",
                    "dispatches already ready when first looked at: "
                    "no held time of their own").add(1, lane=rec.lane)
                return
            reg.histogram(
                "provider_dispatch_held_seconds",
                "time a dispatch kept the chip from the next (program "
                "+ transfers)", buckets=_HELD_BUCKETS).observe(
                    rec.held_s, lane=rec.lane, program=rec.program)
            held = reg.counter(
                "provider_device_held_seconds_total",
                "seconds the device was held by observed dispatches")
            for dev in self.device_labels:
                held.add(rec.held_s, device=dev)
        except Exception:
            pass
