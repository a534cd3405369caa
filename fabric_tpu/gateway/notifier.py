"""Commit-status notifier: txid -> validation code, push not poll.

Rides the committer's post-commit listener hook (committer.py calls
fn(block, final_flags) after every ledger commit) and keeps the outcome
of the transactions **someone here waits for**: those submitted through
this gateway and not yet committed (`watch`, called at admission) and
those a `commit_status` call is blocked on (`wait`).  A block is read
only against those — off its lane table (`wire.lane_table`: the one the
ledger's MVCC walk and the block store's index read; a tx the table does
not speak for, and a block without a table, is decoded envelope by
envelope) — so a block that brings nothing anyone waits for, as every
block of a catching-up peer does, costs one lock and no walk.
This is the event plane the reference builds from peer/deliveryservice
block events + gateway/commit.go — here it is in-process because the
gateway is peer-co-located.

Every other transaction's code is the block store's to give
(blkstorage keeps the authoritative record forever): `commit_status`
looks there when the notifier knows nothing, and `wait` looks there
once more after its waiter is registered, so that a commit between the
two is not missed.  The history window is bounded.

With each outcome the notifier keeps the block's stamps — its frame
received, the committer took it (both travel on the block,
`block.intake`), the code held here — on `perf_counter`: the gateway's
account of a request's wait by stage reads them.
"""

from __future__ import annotations

import logging
import threading
import time
from collections import OrderedDict
from typing import Callable, Dict, List, NamedTuple, Optional, Tuple

from fabric_tpu.ops_plane import tracing
from fabric_tpu.protocol import Envelope, wire

logger = logging.getLogger("fabric_tpu.gateway")


class Committed(NamedTuple):
    """What is known of a committed transaction."""
    code: int                       # validation code
    block: int                      # block number; -1 from the block store
    trace: Optional[str] = None     # the block's trace id, if traced
    # (frame received, committer took the block, code held here), or None
    stamps: Optional[Tuple[float, float, float]] = None


class CommitNotifier:
    def __init__(self, channel_id: str, window: int = 4096):
        self.channel_id = channel_id
        self.window = int(window)
        self._lock = threading.Lock()
        self._history: "OrderedDict[str, Committed]" = OrderedDict()
        self._waiters: Dict[str, List[threading.Event]] = {}
        # txids admitted by this gateway whose block has not come; bounded
        # like the history (an envelope the orderers lose never commits)
        self._watched: "OrderedDict[str, None]" = OrderedDict()

    def watch(self, txid: str) -> None:
        """A transaction enters the ordering service through this
        gateway: keep its outcome when its block comes."""
        with self._lock:
            self._watched[txid] = None
            while len(self._watched) > self.window:
                self._watched.popitem(last=False)

    # committer hook ----------------------------------------------------

    def on_block(self, block, flags) -> None:
        notified = []
        with self._lock:
            watched, waiters, history = (self._watched, self._waiters,
                                         self._history)
            if not watched and not waiters:
                return              # nobody waits: the block is not read
            found: Dict[str, int] = {}
            for i, (txid, code) in enumerate(zip(wire.lane_txids(block),
                                                 flags.codes())):
                if txid is None:
                    try:
                        txid = Envelope.deserialize(
                            block.data[i]).header().channel_header.txid
                    except Exception:
                        continue
                if txid in watched or txid in waiters:
                    found[txid] = code      # a repeated txid: the last
            if not found:
                return
            # listeners run inside committer.store_block's span, so the
            # ambient trace id here IS the block trace — remember it so
            # commit_status can link the request trace to the block trace
            block_trace = tracing.tracer.current_trace_id()
            number = int(block.header.number)
            intake = getattr(block, "intake", None)
            stamps = ((intake[0], intake[1], time.perf_counter())
                      if intake else None)
            for txid, code in found.items():
                history.pop(txid, None)
                history[txid] = Committed(code, number, block_trace, stamps)
                watched.pop(txid, None)
                notified.extend(waiters.pop(txid, ()))
            while len(history) > self.window:
                history.popitem(last=False)
        for ev in notified:
            ev.set()

    # client side -------------------------------------------------------

    def peek(self, txid: str) -> Optional[Committed]:
        with self._lock:
            return self._history.get(txid)

    def wait(self, txid: str, timeout: float,
             recheck: Optional[Callable[[], Optional[Committed]]] = None
             ) -> Optional[Committed]:
        """Block until the txid commits or the timeout lapses.
        `recheck` is asked once, after the waiter stands: a block that
        committed before that told no one."""
        ev = threading.Event()
        with self._lock:
            got = self._history.get(txid)
            if got is not None:
                return got
            self._waiters.setdefault(txid, []).append(ev)
        try:
            if recheck is not None:
                got = recheck()
                if got is not None:
                    return got
            if not ev.wait(timeout):
                return None
            with self._lock:
                return self._history.get(txid)
        finally:
            with self._lock:
                evs = self._waiters.get(txid)
                if evs and ev in evs:
                    evs.remove(ev)
                    if not evs:
                        del self._waiters[txid]
