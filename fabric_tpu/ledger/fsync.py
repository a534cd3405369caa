"""The ledger's per-block fsyncs, timed.

A committed block is three appends made durable one after another: the
block file, the state database's WAL and the history database's WAL.
Each goes through `flush_and_sync`, which observes the seconds the
flush + fsync took in `ledger_fsync_seconds{store}` (always on: one
observation of a plain float per call).
"""

from __future__ import annotations

import os
import time


def flush_and_sync(f, store: str) -> None:
    """f.flush() + os.fsync, timed under `store` ("blocks" | "state" |
    "history")."""
    t0 = time.perf_counter()
    f.flush()
    os.fsync(f.fileno())
    seconds = time.perf_counter() - t0
    from fabric_tpu.ops_plane.metrics import registry
    registry.histogram(
        "ledger_fsync_seconds", "flush + fsync of one per-block append, "
        "by the store that made it").observe(seconds, store=store)
