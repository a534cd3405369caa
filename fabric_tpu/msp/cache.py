"""LRU-cached MSP wrapper.

Parity: /root/reference/msp/cache/cache.go (caches DeserializeIdentity,
Validate and SatisfiesPrincipal with LRU size 100, sitting in front of the
per-tx hot path so repeated cert-chain checks are deduped).

Every look-up is booked in `msp_cache_total{msp, op, result}` (always
on: one counter add a call): `op` is the cache asked — `deserialize`,
`validate`, `principal` — and `result` whether it answered (`hit`) or
the wrapped MSP had to (`miss`).  A channel with more live identities
than CACHE_SIZE reads as misses here."""

from __future__ import annotations

from collections import OrderedDict

from fabric_tpu.ops_plane.metrics import registry

from .identity import Identity
from .msp import MSP, MSPValidationError, Principal

CACHE_SIZE = 100  # msp/cache/cache.go:24


class _LRU:
    def __init__(self, size: int = CACHE_SIZE):
        self.size = size
        self._d = OrderedDict()

    def get(self, key):
        if key in self._d:
            self._d.move_to_end(key)
            return True, self._d[key]
        return False, None

    def put(self, key, value):
        self._d[key] = value
        self._d.move_to_end(key)
        if len(self._d) > self.size:
            self._d.popitem(last=False)


class CachedMSP:
    """Wraps an MSP with deserialize/validate/principal caches."""

    def __init__(self, inner: MSP, size: int = CACHE_SIZE):
        self.inner = inner
        self.mspid = inner.mspid
        self._deser = _LRU(size)
        self._valid = _LRU(size)
        self._princ = _LRU(size)
        self._lookups = registry.counter(
            "msp_cache_total",
            "look-ups of an MSP's LRU caches, by the cache asked "
            "(deserialize, validate, principal) and whether it answered "
            "(hit) or the MSP did (miss)")

    def _note(self, op: str, hit: bool) -> None:
        self._lookups.add(1, msp=self.mspid, op=op,
                          result="hit" if hit else "miss")

    def deserialize_identity(self, data: bytes) -> Identity:
        hit, v = self._deser.get(data)
        self._note("deserialize", hit)
        if hit:
            if isinstance(v, Exception):
                raise v
            return v
        try:
            ident = self.inner.deserialize_identity(data)
        except Exception as e:
            self._deser.put(data, e)
            raise
        self._deser.put(data, ident)
        return ident

    def validate(self, ident: Identity) -> None:
        key = ident
        hit, err = self._valid.get(key)
        self._note("validate", hit)
        if hit:
            if err is not None:
                raise err
            return
        try:
            self.inner.validate(ident)
        except MSPValidationError as e:
            self._valid.put(key, e)
            raise
        self._valid.put(key, None)

    def is_valid(self, ident: Identity) -> bool:
        try:
            self.validate(ident)
            return True
        except MSPValidationError:
            return False

    def satisfies_principal(self, ident: Identity, p: Principal) -> bool:
        key = (ident, p)
        hit, v = self._princ.get(key)
        self._note("principal", hit)
        if hit:
            return v
        v = self.inner.satisfies_principal(ident, p)
        self._princ.put(key, v)
        return v
