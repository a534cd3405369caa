"""Device-resident per-key comb-table banks (HBM slot allocator).

The round-4 fast lane rebuilt and re-shipped its key-table bank from
host to device on EVERY dispatch — per-key tables padded to a
power-of-two bucket, ~124 MB per dispatch on the realistic 67-key block
workload — which made the lane slower than the generic ladder it was
built to beat.  This module is the fix: each key's comb table is
uploaded to the device ONCE when it is built (or restored after
eviction), into a fixed-shape f32 bank held in HBM, and dispatches
carry only int32 slot indices.  The bank shape never changes, so it
also leaves the compiled-program signature: one XLA program per row
bucket instead of one per (row bucket x bank bucket).

The reference analogue is msp/cache (msp/cache/cache.go) — identities
repeat, so per-identity work is cached; here the cached artifact lives
in device memory because that is where it is consumed.

Capacity economics: a P-256 comb table is (8192, 44) f32 = 1.44 MB;
the default 256 slots hold ~370 MB of HBM — far more distinct *hot*
keys than any real channel has endorsing orgs or enrolled clients, and
~2% of a v5e chip's 16 GB.  (CPU test backends default to far fewer
slots — the zeros bank is host RAM there.)  Eviction is LRU over whole
slots; an evicted key's next qualifying batch simply rebuilds (host,
~150 ms) and re-uploads (1.4 MB) its table.
"""

from __future__ import annotations

import threading
from collections import OrderedDict
from typing import Callable, Optional, Tuple

import numpy as np


def bank_update(bank, table, slot):
    """One key's table into its slot.  A named function, so that the
    program it compiles to (`jit_bank_update`) is told apart in a trace
    from the lanes' programs."""
    return bank.at[slot].set(table)


class DeviceBank:
    """Fixed-capacity slot allocator over one device-resident f32 bank.

    build_fn(pubkey) -> np.ndarray of `entry_shape` (host comb table),
    or None for malformed/off-curve keys (the single on-curve gate of
    the fast path).  Thread-safe; the bank array itself is immutable
    jax data — in-flight dispatches that captured an older version stay
    valid, updates swap the reference under the lock.
    """

    def __init__(self, max_keys: int, entry_shape: Tuple[int, ...],
                 build_fn: Callable[[bytes], Optional[np.ndarray]],
                 mesh=None, device=None):
        """`mesh`: the bank is replicated over it.  `device` (meshless
        only): the bank lives on that device; None is jax's default."""
        self.max_keys = int(max_keys)
        self.entry_shape = tuple(entry_shape)
        self.build_fn = build_fn
        self.mesh = mesh
        self.device = device
        self._slots: "OrderedDict[bytes, int]" = OrderedDict()
        self._free = list(range(self.max_keys - 1, -1, -1))
        self._bank = None
        self._upd = None
        self._lock = threading.RLock()
        # refcounted pins: a slot claimed by an in-flight batch (from
        # lane choice until its dispatch captured the bank array) must
        # not be evicted — by THIS batch's later builds or by a
        # CONCURRENT batch on another thread (the provider is shared
        # across channels).  Callers pin via lookup/get_or_build
        # (pin=True) and release with unpin() after dispatching.
        self._pinned: dict = {}
        self.stats = {"hits": 0, "builds": 0, "rejects": 0,
                      "evictions": 0, "pinned_spills": 0, "h2d_bytes": 0}

    def __contains__(self, pubkey: bytes) -> bool:
        with self._lock:
            return pubkey in self._slots

    def __len__(self) -> int:
        with self._lock:
            return len(self._slots)

    # -- device plumbing ----------------------------------------------------

    def _ensure_bank(self):
        if self._bank is not None:
            return
        import jax
        import jax.numpy as jnp

        shape = (self.max_keys,) + self.entry_shape
        zeros = np.zeros(shape, np.float32)
        if self.mesh is not None:
            from jax.sharding import NamedSharding, PartitionSpec
            sharding = NamedSharding(self.mesh, PartitionSpec())
            self._bank = jax.device_put(zeros, sharding)
            self._upd = jax.jit(bank_update, out_shardings=sharding)
        else:
            # committed to `device` when one is named: the update and
            # every dispatch that takes the bank then run there
            self._bank = (jnp.asarray(zeros) if self.device is None
                          else jax.device_put(zeros, self.device))
            # no donation: in-flight dispatches may still hold the old
            # bank; the on-device copy (~tens of MB at HBM bandwidth)
            # is negligible at table-build frequency
            self._upd = jax.jit(bank_update)

    def array(self):
        """The device-resident (max_keys, *entry_shape) f32 bank."""
        with self._lock:
            self._ensure_bank()
            return self._bank

    # -- slot allocation ----------------------------------------------------

    def lookup(self, pubkey: bytes, pin: bool = False) -> Optional[int]:
        """Slot index for a resident key (refreshes LRU), else None.
        pin=True atomically pins the returned slot against eviction."""
        with self._lock:
            slot = self._slots.get(pubkey)
            if slot is not None:
                self._slots.move_to_end(pubkey)
                self.stats["hits"] += 1
                if pin:
                    self._pinned[slot] = self._pinned.get(slot, 0) + 1
            return slot

    def unpin(self, slots) -> None:
        """Release pins taken via lookup/get_or_build(pin=True)."""
        with self._lock:
            for s in slots:
                n = self._pinned.get(s, 0) - 1
                if n <= 0:
                    self._pinned.pop(s, None)
                else:
                    self._pinned[s] = n

    def get_or_build(self, pubkey: bytes,
                     pin: bool = False) -> Optional[int]:
        """Slot index for the key, building + uploading its table if
        needed; None for malformed/off-curve keys or when every
        evictable slot is pinned by an in-flight batch (the new key
        spills to the generic lane instead)."""
        slot = self.lookup(pubkey, pin=pin)
        if slot is not None:
            return slot
        tab = self.build_fn(pubkey)
        if tab is None:
            self.stats["rejects"] += 1
            return None
        tab = np.ascontiguousarray(tab, dtype=np.float32)
        if tab.shape != self.entry_shape:
            raise ValueError(
                f"table shape {tab.shape} != bank entry {self.entry_shape}")
        import jax.numpy as jnp
        with self._lock:
            # lost race: another thread built it while we were building
            got = self._slots.get(pubkey)
            if got is not None:
                if pin:
                    self._pinned[got] = self._pinned.get(got, 0) + 1
                return got
            self._ensure_bank()
            if self._free:
                slot = self._free.pop()
            else:
                slot = None
                for old_pk, s in self._slots.items():      # LRU order
                    if not self._pinned.get(s):
                        slot = s
                        del self._slots[old_pk]
                        break
                if slot is None:
                    self.stats["pinned_spills"] += 1
                    return None
                self.stats["evictions"] += 1
            self.stats["builds"] += 1
            self.stats["h2d_bytes"] += tab.nbytes
            self._bank = self._upd(self._bank, jnp.asarray(tab),
                                   np.int32(slot))
            self._slots[pubkey] = slot
            if pin:
                self._pinned[slot] = self._pinned.get(slot, 0) + 1
        return slot
