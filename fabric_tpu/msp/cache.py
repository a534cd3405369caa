"""LRU-cached MSP wrapper.

Parity: /root/reference/msp/cache/cache.go (caches DeserializeIdentity,
Validate and SatisfiesPrincipal with LRU size 100, sitting in front of the
per-tx hot path so repeated cert-chain checks are deduped).

Every look-up is booked in `msp_cache_total{msp, op, result}` (always
on: one counter add a call): `op` is the cache asked — `deserialize`,
`validate`, `principal` — and `result` whether it answered (`hit`) or
the wrapped MSP had to (`miss`).  A channel with more live identities
than CACHE_SIZE reads as misses here.

`validate_many` asks for a block's identities in one call — same cache,
same size, same law: it makes a miss cheap, not rare.  Where the block
brings enough misses under one CA for a provider's rows lane, their
leaf links' signatures are deferred (`MSP.validate_deferred`) and come
back as items for the caller's batch; `DeferredChains.settle` stores
each verdict as `validate` would have."""

from __future__ import annotations

from collections import OrderedDict
from typing import List, Optional, Tuple

from fabric_tpu.ops_plane.metrics import registry

from .identity import Identity
from .msp import MSP, MSPValidationError, Principal

CACHE_SIZE = 100  # msp/cache/cache.go:24

# What `_valid` holds for an identity whose leaf link a batch has not
# settled yet: it takes the identity's place in the LRU order at the
# look-up, as `validate`'s `put` would, and reads as a miss meanwhile.
_PENDING = object()


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

    def __contains__(self, key):
        return key in self._d

    def settle(self, key, value):
        """`value` in the place of a pending entry, the order as it is;
        nothing for a key that was evicted or answered meanwhile."""
        if self._d.get(key) is _PENDING:
            self._d[key] = value


class DeferredChains:
    """The identities of one `validate_many` whose chains stand but for
    the leaf link's signature: `items[k]` is the k-th's, for a provider;
    `settle` takes the verdicts in that order."""

    def __init__(self, cmsp: "CachedMSP", at: List[int],
                 idents: List[Identity], links: list):
        self._cmsp = cmsp
        self._at = at
        self._idents = idents
        self._links = links
        self.items = [link.item for link in links]

    def settle(self, verdicts) -> List[Tuple[int, MSPValidationError]]:
        """Each verdict into the MSP's account and into the cache, as
        `validate` would have stored it -> (position in the call's
        identities, error) for the ones refused."""
        cmsp = self._cmsp
        errors = cmsp.inner.settle_many(self._idents, self._links, verdicts)
        refused = []
        for pos, ident, err in zip(self._at, self._idents, errors):
            cmsp._valid.settle(ident, err)
            if err is not None:
                refused.append((pos, err))
        return refused


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

    def _note(self, op: str, hit: bool, n: int = 1) -> None:
        self._lookups.add(n, msp=self.mspid, op=op,
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
        hit = hit and err is not _PENDING
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

    def validate_many(self, idents: List[Identity],
                      min_batch: Optional[int] = None
                      ) -> Tuple[list, Optional[DeferredChains]]:
        """`validate` for a block's identities, in their order ->
        (errors, deferred): `errors[i]` is None or what `validate` would
        have raised for `idents[i]`, each look-up booked and each result
        stored as there.

        Where the block brings at least `min_batch` cache-missing
        identities under one CA (the caller's provider's count for a
        key to earn its rows lane; None: it has no such lane), those are
        validated by `MSP.validate_deferred`: the host checks decide
        first, and the ones that pass read None in `errors` and come
        back in `deferred` — their chains stand if the provider says
        yes to `deferred.items`, and the caller owes `deferred.settle`
        the verdicts.  Anything less stays `MSP.validate`, on the host:
        a handful of certificates would cost a `generic@128` program
        (22 ms of the chip) against 0.2 ms each here, and where blocks
        are served, not replayed, the chip is the scarce side.  An MSP
        whose `validate` was replaced on the instance (a fault
        injection: the benchmark's blind control) is asked through it,
        one identity at a time."""
        inner = self.inner
        under = {}              # position -> the CA key its link defers to
        if (min_batch is not None and "validate" not in vars(inner)
                and hasattr(inner, "validate_deferred")):
            counts = {}
            for i, ident in enumerate(idents):
                if ident not in self._valid:
                    key = inner.deferrable_under(ident)
                    if key is not None:
                        under[i] = key
                        counts[key] = counts.get(key, 0) + 1
            under = {i: key for i, key in under.items()
                     if counts[key] >= min_batch}
        errors: list = [None] * len(idents)
        at, pending, links = [], [], []
        hits = 0
        for i, ident in enumerate(idents):
            hit, err = self._valid.get(ident)
            if hit and err is not _PENDING:
                hits += 1
                errors[i] = err
                continue
            link = None
            try:
                if i in under:
                    link = inner.validate_deferred(ident)
                else:
                    inner.validate(ident)
            except Exception as e:
                # kept without its traceback: that holds this frame,
                # which holds `errors`, which holds it — a cycle with a
                # block's identities in it, and the collector is not
                # let at what a committed block leaves (utils/heap.py)
                errors[i] = e.with_traceback(None)
                if isinstance(e, MSPValidationError):
                    self._valid.put(ident, e)
                continue    # else: a certificate the checks choke on
            if link is None:
                self._valid.put(ident, None)
            else:
                self._valid.put(ident, _PENDING)
                at.append(i)
                pending.append(ident)
                links.append(link)
        self._note("validate", True, hits)
        self._note("validate", False, len(idents) - hits)
        return errors, (DeferredChains(self, at, pending, links)
                        if links else None)

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
