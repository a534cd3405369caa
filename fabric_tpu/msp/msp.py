"""The MSP implementation: setup, deserialization, validation, principals.

Reference parity map:
- setup from config            -> msp/mspimplsetup.go
- deserialize + validate chain -> msp/mspimpl.go, mspimplvalidate.go:21-139
- principal evaluation         -> msp/mspimpl.go satisfiesPrincipal
- manager (mspid routing)      -> msp/mspmgrimpl.go

Chain validation is host-side X.509 (OpenSSL via `cryptography`); the
signatures *inside* certificates are CA signatures checked once per
identity and cached (see cache.py), so they are off the per-block hot
path — exactly like the reference, where msp/cache sits in front of the
per-tx flow (SURVEY.md §2 msp/cache row).  A channel with more live
identities than the cache holds pays a chain validation an identity a
block: `msp_validate_seconds{msp, result}` times every one (always on,
one observation a call), `result` being `ok` or why the chain failed —
`revoked`, `untrusted` (no trusted issuer, an issuer that is no CA),
`expired` (a certificate outside its validity period).
"""

from __future__ import annotations

import datetime
import time
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Sequence

from fabric_tpu.crypto import x509
from fabric_tpu.crypto import NameOID
from fabric_tpu.ops_plane.metrics import registry

from .identity import Identity

MAX_CHAIN_DEPTH = 6

# principal kinds (common/msp MSPPrincipal equivalents)
ROLE_MEMBER = "member"
ROLE_ADMIN = "admin"


@dataclass(frozen=True)
class Principal:
    """MSPPrincipal: role / OU / exact-identity matching."""
    kind: str                    # "role" | "org_unit" | "identity"
    mspid: str = ""
    role: str = ROLE_MEMBER      # for kind == "role"
    org_unit: str = ""           # for kind == "org_unit"
    identity_bytes: bytes = b""  # for kind == "identity"

    @staticmethod
    def member(mspid: str) -> "Principal":
        return Principal("role", mspid=mspid, role=ROLE_MEMBER)

    @staticmethod
    def admin(mspid: str) -> "Principal":
        return Principal("role", mspid=mspid, role=ROLE_ADMIN)


@dataclass
class MSPConfig:
    """FabricMSPConfig equivalent (msp/mspimplsetup.go inputs)."""
    mspid: str
    root_certs_pem: List[bytes] = field(default_factory=list)
    intermediate_certs_pem: List[bytes] = field(default_factory=list)
    admin_certs_pem: List[bytes] = field(default_factory=list)
    crls_pem: List[bytes] = field(default_factory=list)


class MSPValidationError(Exception):
    """`reason`: how a chain failed, as `msp_validate_seconds` labels
    it (`revoked` | `untrusted` | `expired`)."""

    def __init__(self, message: str, reason: str = "untrusted"):
        super().__init__(message)
        self.reason = reason


_VALIDATE_BUCKETS = (0.00005, 0.0001, 0.0002, 0.0005, 0.001, 0.005, 0.025,
                     float("inf"))


class MSP:
    """An org's membership provider (bccspmsp equivalent)."""

    def __init__(self, config: MSPConfig):
        self.mspid = config.mspid
        self.roots = [x509.load_pem_x509_certificate(p) for p in config.root_certs_pem]
        self.intermediates = [x509.load_pem_x509_certificate(p)
                              for p in config.intermediate_certs_pem]
        if not self.roots:
            raise MSPValidationError(f"MSP {self.mspid}: no root CAs")
        self._by_subject: Dict[bytes, List[x509.Certificate]] = {}
        for c in self.roots + self.intermediates:
            self._by_subject.setdefault(c.subject.public_bytes(), []).append(c)
        self._root_ids = {(c.subject.public_bytes(), c.serial_number)
                          for c in self.roots}
        self.admin_certs = [x509.load_pem_x509_certificate(p)
                            for p in config.admin_certs_pem]
        self._revoked = set()  # (issuer_subject_der, serial)
        for crl_pem in config.crls_pem:
            crl = x509.load_pem_x509_crl(crl_pem)
            for rev in crl:
                self._revoked.add((crl.issuer.public_bytes(), rev.serial_number))
        self._validations = registry.histogram(
            "msp_validate_seconds",
            "one certificate-chain validation (chain building with each "
            "link's CA signature, validity periods, CRLs), by its result",
            buckets=_VALIDATE_BUCKETS)

    # -- deserialization ---------------------------------------------------

    def deserialize_identity(self, data: bytes) -> Identity:
        ident = Identity.deserialize(data)
        if ident.mspid != self.mspid:
            raise MSPValidationError(
                f"identity mspid {ident.mspid!r} != MSP {self.mspid!r}")
        return ident

    # -- validation (mspimplvalidate.go) -----------------------------------

    def validate(self, ident: Identity,
                 at_time: Optional[datetime.datetime] = None) -> None:
        """Raises MSPValidationError unless the identity chains to our roots,
        is within its validity period, and is not revoked."""
        t0 = time.perf_counter()
        result = "ok"
        try:
            self._check_chain(ident, at_time)
        except MSPValidationError as e:
            result = e.reason
            raise
        except Exception:
            result = "untrusted"     # a certificate the checks choke on
            raise
        finally:
            self._validations.observe(time.perf_counter() - t0,
                                      msp=self.mspid, result=result)

    def _check_chain(self, ident: Identity,
                     at_time: Optional[datetime.datetime]) -> None:
        now = at_time or datetime.datetime.now(datetime.timezone.utc)
        chain = self._build_chain(ident.cert)
        for depth, cert in enumerate(chain):
            if not (cert.not_valid_before_utc <= now <= cert.not_valid_after_utc):
                raise MSPValidationError(
                    f"cert at depth {depth} outside validity period",
                    reason="expired")
            if depth > 0:
                # issuers must be CAs
                try:
                    bc = cert.extensions.get_extension_for_class(
                        x509.BasicConstraints).value
                    if not bc.ca:
                        raise MSPValidationError(
                            f"issuer at depth {depth} is not a CA")
                except x509.ExtensionNotFound:
                    raise MSPValidationError(
                        f"issuer at depth {depth} lacks BasicConstraints")
            issuer_sub = cert.issuer.public_bytes()
            if (issuer_sub, cert.serial_number) in self._revoked:
                raise MSPValidationError(f"cert at depth {depth} is revoked",
                                         reason="revoked")

    def is_valid(self, ident: Identity) -> bool:
        try:
            self.validate(ident)
            return True
        except MSPValidationError:
            return False

    def _build_chain(self, cert: x509.Certificate) -> List[x509.Certificate]:
        """leaf -> ... -> root (root included). Signature of each link is
        checked via the issuer's public key."""
        chain = [cert]
        current = cert
        for _ in range(MAX_CHAIN_DEPTH):
            if (current.subject.public_bytes(), current.serial_number) in self._root_ids:
                return chain
            candidates = self._by_subject.get(current.issuer.public_bytes(), [])
            parent = None
            for cand in candidates:
                try:
                    current.verify_directly_issued_by(cand)
                    parent = cand
                    break
                except Exception:
                    continue
            if parent is None:
                raise MSPValidationError(
                    f"no trusted issuer for {current.subject.rfc4514_string()!r}")
            chain.append(parent)
            current = parent
        raise MSPValidationError("cert chain too deep")

    # -- principals ---------------------------------------------------------

    def satisfies_principal(self, ident: Identity, p: Principal) -> bool:
        try:
            if p.kind == "role":
                if p.mspid != self.mspid or ident.mspid != self.mspid:
                    return False
                self.validate(ident)
                if p.role == ROLE_MEMBER:
                    return True
                if p.role == ROLE_ADMIN:
                    return any(ident.cert == a for a in self.admin_certs)
                return False
            if p.kind == "org_unit":
                if p.mspid != self.mspid:
                    return False
                self.validate(ident)
                ous = ident.cert.subject.get_attributes_for_oid(
                    NameOID.ORGANIZATIONAL_UNIT_NAME)
                return any(a.value == p.org_unit for a in ous)
            if p.kind == "identity":
                return ident.serialize() == p.identity_bytes
            return False
        except MSPValidationError:
            return False


class MSPManager:
    """Channel-level mspid -> MSP routing (mspmgrimpl.go)."""

    def __init__(self, msps: Sequence[MSP]):
        self._msps: Dict[str, MSP] = {m.mspid: m for m in msps}

    def get_msp(self, mspid: str) -> MSP:
        if mspid not in self._msps:
            raise MSPValidationError(f"unknown MSP {mspid!r}")
        return self._msps[mspid]

    def msps(self) -> Dict[str, MSP]:
        return dict(self._msps)

    def deserialize_identity(self, data: bytes) -> Identity:
        ident = Identity.deserialize(data)
        return self.get_msp(ident.mspid).deserialize_identity(data)


def deserialize_from_msps(msps: Dict[str, "MSP"], ident_bytes: bytes,
                          validate: bool = False) -> Optional[Identity]:
    """Shared lenient identity deserialization used by every plane that
    routes a wire identity to its MSP (txvalidator, msgprocessor, block
    signature verification).  Returns None — never raises — on unknown
    mspid, undecodable bytes, or (when validate=True) failed cert-chain
    validation, mirroring how the reference callers treat deserialization
    failures as 'identity contributes nothing' (policies/policy.go:372-383).
    """
    from fabric_tpu.utils import serde
    try:
        mspid = serde.decode(ident_bytes).get("mspid")
        msp = msps.get(mspid)
        if msp is None:
            return None
        ident = msp.deserialize_identity(ident_bytes)
        if validate and not msp.is_valid(ident):
            return None
        return ident
    except Exception:
        return None
