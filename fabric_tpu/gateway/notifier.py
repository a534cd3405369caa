"""Commit-status notifier: txid -> validation code, push not poll.

Rides the committer's post-commit listener hook (committer.py calls
fn(block, final_flags) after every ledger commit), reads each tx's txid
off the block's lane table (`wire.lane_table`: the one the ledger's MVCC
walk and the block store's index read; a tx the table does not speak
for, and a block without a table, is decoded envelope by envelope), and
wakes any blocked commit_status waiters.
This is the event plane the reference builds from peer/deliveryservice
block events + gateway/commit.go — here it is in-process because the
gateway is peer-co-located.

The history window is bounded: clients that ask about a txid committed
more than `window` txs ago fall back to the gateway's ledger lookup
path (blkstorage keeps the authoritative record forever).
"""

from __future__ import annotations

import itertools
import logging
import threading
from collections import OrderedDict
from typing import Dict, List, Optional, Tuple

from fabric_tpu.ops_plane import tracing
from fabric_tpu.protocol import Envelope, wire

logger = logging.getLogger("fabric_tpu.gateway")


class CommitNotifier:
    def __init__(self, channel_id: str, window: int = 4096):
        self.channel_id = channel_id
        self.window = int(window)
        self._lock = threading.Lock()
        # txid -> (validation code int, block number, block trace id|None)
        self._history: "OrderedDict[str, Tuple[int, int, Optional[str]]]" \
            = OrderedDict()
        self._waiters: Dict[str, List[threading.Event]] = {}

    # committer hook ----------------------------------------------------

    def on_block(self, block, flags) -> None:
        notified = []
        # listeners run inside committer.store_block's span, so the
        # ambient trace id here IS the block trace — remember it so
        # commit_status can link the request trace to the block trace
        block_trace = tracing.tracer.current_trace_id()
        number = int(block.header.number)
        txids = wire.lane_txids(block)
        # what this block adds past the history: txid -> entry, ordered
        # by first appearance, the last appearance's entry (a dict's
        # rule, and `_history`'s)
        fresh: Dict[str, Tuple[int, int, Optional[str]]] = {}
        with self._lock:
            history = self._history
            for i, (txid, code) in enumerate(zip(txids, flags.codes())):
                if txid is None:
                    try:
                        txid = Envelope.deserialize(
                            block.data[i]).header().channel_header.txid
                    except Exception:
                        continue
                if not txid:
                    continue
                if txid in history:
                    history[txid] = (code, number, block_trace)
                else:
                    fresh[txid] = (code, number, block_trace)
                if self._waiters:
                    evs = self._waiters.pop(txid, None)
                    if evs:
                        notified.extend(evs)
            # the window evicts from the front: first what was there,
            # then the head of what this block brings, which is never
            # inserted
            over = len(history) + len(fresh) - self.window
            gone = max(0, min(over, len(history)))
            for _ in range(gone):
                history.popitem(last=False)
            history.update(itertools.islice(
                fresh.items(), max(0, over - gone), None))
        for ev in notified:
            ev.set()

    # client side -------------------------------------------------------

    def peek(self, txid: str) -> Optional[Tuple[int, int, Optional[str]]]:
        with self._lock:
            return self._history.get(txid)

    def wait(self, txid: str,
             timeout: float) -> Optional[Tuple[int, int, Optional[str]]]:
        """Block until the txid commits or the timeout lapses."""
        ev = threading.Event()
        with self._lock:
            got = self._history.get(txid)
            if got is not None:
                return got
            self._waiters.setdefault(txid, []).append(ev)
        try:
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
