"""Provider interface and item types for the batch-first BCCSP plane.

Reference parity: bccsp.BCCSP (bccsp/bccsp.go:121-133) exposes KeyGen /
KeyImport / Hash / Sign / Verify.  Here the same verbs exist, plus the
batch verb that the verify-then-gate pipeline (SURVEY.md §7) is built on.
Signing always stays on the host CPU — private keys never touch the TPU.
"""

from __future__ import annotations

import contextlib
import hashlib
import threading
from dataclasses import dataclass, field
from typing import NamedTuple, Optional, Sequence

import numpy as np

SCHEME_P256 = "ecdsa-p256"
SCHEME_ED25519 = "ed25519"
SCHEME_IDEMIX = "idemix-bbs"

HASH_SHA256 = "sha256"
HASH_SHA384 = "sha384"
HASH_SHA3_256 = "sha3_256"
HASH_SHA3_384 = "sha3_384"


class VerifyItem(NamedTuple):
    """One signature-verification work item.

    scheme  : SCHEME_P256 | SCHEME_ED25519
    pubkey  : SEC1 uncompressed point (65B, 0x04||X||Y) for p256;
              raw 32B for ed25519
    signature: ASN.1/DER (r,s) for p256; raw 64B (R||S) for ed25519
    payload : the 32-byte *digest* for p256 (hashing happened upstream,
              mirroring msp/identities.go:178); the full *message* for
              ed25519 (RFC 8032 signs the message itself)

    A NamedTuple on purpose: items are created and hashed 4x per tx on
    the validator's pass-1 hot loop (they ARE their own dedup keys —
    Verify is a pure function of these four fields), and C-level tuple
    construction/hash measurably beats the frozen-dataclass forms.
    """
    scheme: str
    pubkey: bytes
    signature: bytes
    payload: bytes


def hash_payload(data: bytes, algo: str = HASH_SHA256) -> bytes:
    """The provider Hash verb (bccsp.Hash equivalent)."""
    try:
        return hashlib.new(algo, data).digest()
    except ValueError as e:
        raise ValueError(f"unsupported hash {algo!r}") from e


# Who asked: the site a verification is made for, read by a device
# provider when it accounts for a dispatch.  Ambient on the calling
# thread, so the wrapping providers (verify_plane.cache.CachingProvider,
# degrade.DegradingProvider) carry it without an argument of their own.
DISPATCH_SITES = ("handshake", "endorser", "gateway_ingress", "speculative",
                  "validator", "block_sig", "warmup", "other")
_site = threading.local()


@contextlib.contextmanager
def dispatch_site(name: str):
    """Verifications made on this thread inside the block are `name`'s
    (one of DISPATCH_SITES).  The innermost block wins."""
    if name not in DISPATCH_SITES:
        raise ValueError(f"unknown dispatch site {name!r}")
    outer = current_site()
    _site.name = name
    try:
        yield
    finally:
        _site.name = outer


def current_site() -> str:
    return getattr(_site, "name", "other")


def as_list(items) -> list:
    """The batch a verify verb keeps until its resolve: a list is taken
    as it is — the caller built it for this call and leaves it alone —
    anything else is copied into one."""
    return items if isinstance(items, list) else list(items)


class DeviceError(RuntimeError):
    """A device provider failed and was not asked to degrade: nothing
    verified the batch in its place.  The original error is the cause."""


class Provider:
    """Abstract BCCSP provider. Concrete: sw.SoftwareProvider, jaxtpu.JaxTpuProvider."""

    name = "abstract"

    # -- keys / signing (host-side in every provider) -----------------------

    def key_gen(self, scheme: str):
        raise NotImplementedError

    def sign(self, private_key, payload: bytes) -> bytes:
        raise NotImplementedError

    # -- verification -------------------------------------------------------

    def verify(self, item: VerifyItem) -> bool:
        return bool(self.batch_verify([item])[0])

    def batch_verify(self, items: Sequence[VerifyItem]) -> np.ndarray:
        """Verify a batch; returns bool[N] aligned to `items`.

        Malformed items (bad lengths, undecodable DER/points) yield False —
        they never raise, so one bad signature cannot fail a whole block
        (policy.go:390-393 semantics)."""
        raise NotImplementedError

    def batch_verify_async(self, items: Sequence[VerifyItem]):
        """Start verifying a batch; returns resolve() -> bool[N].

        Device providers override this to ENQUEUE the work and return
        immediately, letting the caller overlap further host-side
        collection with device compute (SURVEY.md §7 hard-part #3).  The
        default is lazy-but-correct: work happens at resolve()."""
        from fabric_tpu.ops_plane import tracing
        items = as_list(items)
        span = tracing.tracer.start_span(
            "bccsp.batch_verify", require_parent=True,
            attributes={"provider": self.name, "batch_size": len(items)})

        def resolve():
            import time as _t
            t0 = _t.perf_counter()
            try:
                out = self.batch_verify(items)
            except BaseException as exc:
                span.set_attribute("error", repr(exc))
                span.end(status="ERROR")
                raise
            span.set_attribute("block_until_ready_s",
                               round(_t.perf_counter() - t0, 6))
            span.end()
            return out

        return resolve

    def batch_verify_packed_async(self, batch):
        """`batch_verify_async` for a block's signature table
        (`native/fastcollect.c` SigTable, what the validator's deep tail
        collects): a sequence of VerifyItems in dispatch order whose
        P-256 items exist only as rows of flat buffers — `digest`,
        `rs`, `ok`, `key` into `keys`, `pos` — with the items of any
        other shape in `rest` / `rest_pos`.  resolve() -> bool[len(batch)]
        aligned with the positions.  A device provider packs from the
        buffers; this default builds the items, once, and verifies them
        as items.  A wrapper that forwards unknown attributes must own
        this verb beside `batch_verify_async`, or a table would go round
        what it wraps the items in."""
        return self.batch_verify_async(list(batch))

    def hash(self, data: bytes, algo: str = HASH_SHA256) -> bytes:
        return hash_payload(data, algo)
