"""The peer process's collector policy at the block boundary.

A committing peer's heap is mostly its ledger: state entries, version
and history indexes, the block store's txid index, the verdict cache.
These survive every block by construction, yet each full (generation-2)
pass of the cyclic collector walks all of them — time that grows with
the ledger and lands on whichever thread allocates.

`block_boundary()` is called by the committer after every block:

* it moves what is alive into the permanent generation (`gc.freeze()`,
  a list splice), so the passes that follow walk only what later blocks
  allocate.  Thresholds stay as they are; reference counts still free
  everything that is not part of a cycle, frozen or not;
* a cycle dropped among frozen objects is reclaimed only by a thaw.
  When the heap has doubled since the last thaw (and on the first
  boundary of the process) the boundary is `gc.unfreeze();
  gc.collect(); gc.freeze()` instead: one whole-heap pass per doubling,
  O(1) amortised per block, unreclaimed cycles bounded by the live
  heap.

The only input is what the process observes, so several channels — and,
in tests, several nodes — share one policy.  The heap's size is read as
`sys.getallocatedblocks()` (a sum over the allocator's pools, under a
millisecond for gigabytes), not as `gc.get_freeze_count()`: that one
walks the permanent generation object by object, ~0.1 us each, which at
every block is the cost that grows with the ledger all over again.  For
the same reason the account gives the frozen count as of the last thaw,
where a whole-heap pass is paid anyway: read at every exposition it
held a scrape for 0.15 s in a serving peer.

`install()` adds the account of full passes: a `gc.callbacks` hook that
touches no lock (a collection runs on a thread that may hold the
tracer's or a histogram's non-re-entrant lock), only module floats and
an int.  `account()` reads them; the metrics registry stamps them at
each exposition (`ops_plane/metrics.py`).
"""

from __future__ import annotations

import gc
import sys
import threading
from time import perf_counter

_full_seconds = 0.0     # time inside generation-2 passes, thaws included
_full_count = 0
_full_started = 0.0

_thaws = 0
_blocks_at_thaw = 0     # allocated blocks right after the last thaw; 0 = none yet
_frozen_at_thaw = 0     # objects in the permanent generation, counted then
_boundary_lock = threading.Lock()    # the boundary's own; the hook never takes it


def _on_collection(phase: str, info: dict) -> None:
    global _full_seconds, _full_count, _full_started
    if info["generation"] != 2:
        return
    if phase == "start":
        _full_started = perf_counter()
    else:
        _full_seconds += perf_counter() - _full_started
        _full_count += 1


def install() -> None:
    """Start the account of full passes (idempotent)."""
    if _on_collection not in gc.callbacks:
        gc.callbacks.append(_on_collection)


def block_boundary() -> None:
    """Freeze what a committed block left alive; thaw by doubling."""
    global _thaws, _blocks_at_thaw, _frozen_at_thaw
    with _boundary_lock:
        gc.freeze()
        if sys.getallocatedblocks() < 2 * _blocks_at_thaw:
            return
        gc.unfreeze()
        gc.collect()
        gc.freeze()
        _thaws += 1
        _blocks_at_thaw = sys.getallocatedblocks()
        _frozen_at_thaw = gc.get_freeze_count()


def account() -> dict:
    """The four `runtime_gc_*` series as they stand: name -> (value,
    help).  Empty in a process where no node installed the account."""
    if _on_collection not in gc.callbacks:
        return {}
    return {
        "runtime_gc_full_seconds_sum": (
            _full_seconds, "seconds inside full (generation-2) passes "
            "of the collector, thaws included"),
        "runtime_gc_full_seconds_count": (
            _full_count, "full (generation-2) passes of the collector"),
        "runtime_gc_frozen_objects": (
            _frozen_at_thaw, "objects the block boundary had moved out "
            "of the collector's passes, as counted at the last thaw"),
        "runtime_gc_thaws_total": (
            _thaws, "whole-heap passes the block boundary made, one "
            "per doubling of the heap"),
    }
