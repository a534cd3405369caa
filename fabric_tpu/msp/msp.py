"""The MSP implementation: setup, deserialization, validation, principals.

Reference parity map:
- setup from config            -> msp/mspimplsetup.go
- deserialize + validate chain -> msp/mspimpl.go, mspimplvalidate.go:21-139
- principal evaluation         -> msp/mspimpl.go satisfiesPrincipal
- manager (mspid routing)      -> msp/mspmgrimpl.go

Chain validation is two parts, written once (`_check_chain`).  The
**host checks**: the issuer looked up by its name, every certificate's
validity period, `BasicConstraints` of every issuer, the CRLs, and the
CA -> CA links above the leaf (OpenSSL via `cryptography`: they are the
MSP's own few certificates).  And **the leaf link's signature** — the
org CA's over the identity's certificate.  `validate` checks that one at
once, on the host, and is what every caller but one knows.
`validate_deferred` runs the host checks, which decide first, and hands
the signature back as a P-256 `VerifyItem` for the caller's batch where
the link is eligible (`_deferrable`): the validator's deep tail sends a
block's unseen creators to the device in one dispatch and gates each
creator on the verdict (`settle_many`).  What a trusted CA certificate
brings to every validation is read once an MSP (`_Issuer`).

The verdicts are cached (see cache.py), so they are off the per-block
hot path wherever a channel's identities fit the cache — exactly like
the reference, where msp/cache sits in front of the per-tx flow
(SURVEY.md §2 msp/cache row).  A channel with more live identities than
the cache holds pays a chain validation an identity a block:
`msp_validate_seconds{msp, result}` times every one (always on, one
observation a validation: the host's part where the signature was
deferred, booked when the verdict is known), `result` being `ok` or why
the chain failed — `revoked`, `untrusted` (no trusted issuer, an issuer
that is no CA, a signature algorithm no MSP takes), `expired` (a
certificate outside its validity period).
`msp_chain_signatures_total{msp, where}` counts the leaf links by where
their signature was checked: `host` (here) or `device` (deferred).
"""

from __future__ import annotations

import datetime
import hashlib
import time
from dataclasses import dataclass, field
from typing import Dict, List, NamedTuple, Optional, Sequence, Tuple

from fabric_tpu.bccsp.provider import SCHEME_P256, VerifyItem
from fabric_tpu.bccsp.sw import low_s
from fabric_tpu.crypto import x509
from fabric_tpu.crypto import NameOID
from fabric_tpu.crypto import decode_dss_signature, encode_dss_signature
from fabric_tpu.ops_plane.metrics import registry

from .identity import Identity, pubkey_wire_bytes, scheme_of_cert

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

# The signature algorithms a link of a chain may carry, by OID: ECDSA
# over SHA-256 / 384 / 512 and Ed25519 — what `msp/ca.py` issues and the
# reference's MSP takes (ECDSA certificates only; v3.0 adds Ed25519).
# The first is the one a provider can check as a P-256 item.
ECDSA_SHA256 = "1.2.840.10045.4.3.2"
LINK_ALGORITHMS = frozenset({ECDSA_SHA256, "1.2.840.10045.4.3.3",
                             "1.2.840.10045.4.3.4", "1.3.101.112"})


def _link_algorithm(cert) -> Optional[str]:
    """The OID of the algorithm `cert` was signed with — the one field
    both the refusal of an unsupported algorithm and the eligibility of
    a link for a provider read — or None for a certificate that states
    none (`crypto/lite_x509`)."""
    oid = getattr(cert, "signature_algorithm_oid", None)
    return None if oid is None else oid.dotted_string


class _Issuer(NamedTuple):
    """What a trusted CA certificate brings to the validation of every
    certificate under it, read once an MSP.  Its validity period is not
    here: that is compared with the clock every time."""
    cert: x509.Certificate
    subject: bytes               # its name, DER
    issuer: bytes                # its issuer's
    serial: int
    is_root: bool
    fault: Optional[str]         # why it may issue nothing, or None
    wire_key: Optional[bytes]    # SEC1 point where its key is P-256

    @staticmethod
    def of(cert: x509.Certificate, is_root: bool) -> "_Issuer":
        try:
            bc = cert.extensions.get_extension_for_class(
                x509.BasicConstraints).value
            fault = None if bc.ca else "is not a CA"
        except x509.ExtensionNotFound:
            fault = "lacks BasicConstraints"
        try:
            p256 = scheme_of_cert(cert) == SCHEME_P256
        except ValueError:
            p256 = False
        return _Issuer(cert, cert.subject.public_bytes(),
                       cert.issuer.public_bytes(), cert.serial_number,
                       is_root, fault,
                       pubkey_wire_bytes(cert) if p256 else None)


class ChainLink(NamedTuple):
    """A leaf link whose signature `validate_deferred` left to the
    caller: the item a provider verifies, and the seconds the host's
    part took (`settle_many` books them under the verdict)."""
    item: VerifyItem
    seconds: float


def _link_item(cert, wire_key: bytes) -> Optional[VerifyItem]:
    """The CA's signature over `cert` as a P-256 item: the issuer's
    wire key, SHA-256 of the to-be-signed bytes, and (r, min(s, n - s))
    encoded again.  X.509 puts no low-S rule on a CA's signature and
    OpenSSL signs as it comes, so about half of all certificates carry
    s > n/2, which the providers refuse (`require_low_s`, the rule for
    transaction signatures); (r, n - s) is the same signature by
    ECDSA's symmetry.  None for a signature that is no DER pair: the
    host decides that one."""
    try:
        r, s = low_s(*decode_dss_signature(cert.signature))
        sig = encode_dss_signature(r, s)
    except (ValueError, TypeError):
        return None
    return VerifyItem(SCHEME_P256, wire_key, sig,
                      hashlib.sha256(cert.tbs_certificate_bytes).digest())


class MSP:
    """An org's membership provider (bccspmsp equivalent)."""

    def __init__(self, config: MSPConfig):
        self.mspid = config.mspid
        self.roots = [x509.load_pem_x509_certificate(p) for p in config.root_certs_pem]
        self.intermediates = [x509.load_pem_x509_certificate(p)
                              for p in config.intermediate_certs_pem]
        if not self.roots:
            raise MSPValidationError(f"MSP {self.mspid}: no root CAs")
        self._by_subject: Dict[bytes, List[_Issuer]] = {}
        for n, c in enumerate(self.roots + self.intermediates):
            ca = _Issuer.of(c, is_root=n < len(self.roots))
            self._by_subject.setdefault(ca.subject, []).append(ca)
        self._root_ids = {(ca.subject, ca.serial)
                          for cas in self._by_subject.values()
                          for ca in cas if ca.is_root}
        self._root_serials = {serial for _, serial in self._root_ids}
        self.admin_certs = [x509.load_pem_x509_certificate(p)
                            for p in config.admin_certs_pem]
        self._revoked = set()  # (issuer_subject_der, serial)
        for crl_pem in config.crls_pem:
            crl = x509.load_pem_x509_crl(crl_pem)
            for rev in crl:
                self._revoked.add((crl.issuer.public_bytes(), rev.serial_number))
        self._validations = registry.histogram(
            "msp_validate_seconds",
            "one certificate-chain validation (chain building, validity "
            "periods, CRLs, and each link's CA signature unless the leaf "
            "link's was deferred to the caller's batch), by its result",
            buckets=_VALIDATE_BUCKETS)
        self._link_sigs = registry.counter(
            "msp_chain_signatures_total",
            "leaf links of the chains validated (the CA's signature over "
            "an identity's certificate), by where the signature was "
            "checked: host (OpenSSL, inside the validation) or device "
            "(deferred as a P-256 item to the caller's batch)")

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
        self._validate(ident, at_time, defer=False)

    def validate_deferred(self, ident: Identity,
                          at_time: Optional[datetime.datetime] = None
                          ) -> Optional[ChainLink]:
        """`validate` with the leaf link's signature left to the
        caller's batch.  The host checks run here and decide first: a
        revoked or expired identity raises as in `validate` and sends
        nothing anywhere.  -> None where the chain is whole (a link that
        is not eligible was checked here, as `validate` does), else the
        link: the chain stands if a provider says yes to its item, and
        the caller owes `settle_many` the verdict.  One order differs
        from `validate`, which checks the signature before the CRL: a
        certificate both forged and listed by serial is `untrusted`
        there and `revoked` here."""
        return self._validate(ident, at_time, defer=True)

    def deferrable_under(self, ident: Identity) -> Optional[bytes]:
        """The wire key of the CA under which `validate_deferred` would
        defer the identity's leaf link, or None where it would check it
        here (`_deferrable`, the eligibility rule)."""
        ca = self._deferrable(ident)
        return None if ca is None else ca.wire_key

    def settle_many(self, idents: Sequence[Identity],
                    links: Sequence[ChainLink],
                    verdicts) -> List[Optional["MSPValidationError"]]:
        """The verdicts of deferred links, in: per identity None or the
        error `validate` would have raised, its observation booked in
        `msp_validate_seconds` under the result (the host part's
        seconds), and the links into `msp_chain_signatures_total` once
        for all of them."""
        errors = []
        for ident, link, ok in zip(idents, links, verdicts):
            err = None if ok else MSPValidationError(
                f"no trusted issuer for {ident.subject!r}")
            self._validations.observe(
                link.seconds, msp=self.mspid,
                result="ok" if err is None else err.reason)
            errors.append(err)
        self._link_sigs.add(len(errors), msp=self.mspid, where="device")
        return errors

    def _validate(self, ident: Identity, at_time, defer: bool):
        t0 = time.perf_counter()
        result = "ok"
        item = None
        try:
            item = self._check_chain(ident, at_time, defer)
        except MSPValidationError as e:
            result = e.reason
            raise
        except Exception:
            result = "untrusted"     # a certificate the checks choke on
            raise
        finally:
            seconds = time.perf_counter() - t0
            if item is None:
                self._validations.observe(seconds, msp=self.mspid,
                                          result=result)
        return None if item is None else ChainLink(item, seconds)

    def _check_chain(self, ident: Identity,
                     at_time: Optional[datetime.datetime],
                     defer: bool = False) -> Optional[VerifyItem]:
        """Both parts of a validation.  The chain is built first, each
        link's signature checked here but the leaf's where `defer` is
        set and the link eligible; then the host checks over every
        certificate of it; then, deferred, the leaf link's item is
        handed back unverified."""
        now = at_time or datetime.datetime.now(datetime.timezone.utc)
        cert = ident.cert
        issuers, deferred = self._build_chain(ident, defer)
        chain = [(cert, ident.issuer_der, cert.serial_number, None)]
        chain += [(ca.cert, ca.issuer, ca.serial, ca.fault) for ca in issuers]
        for depth, (c, issuer_sub, serial, fault) in enumerate(chain):
            if not (c.not_valid_before_utc <= now <= c.not_valid_after_utc):
                raise MSPValidationError(
                    f"cert at depth {depth} outside validity period",
                    reason="expired")
            if fault is not None:        # issuers must be CAs
                raise MSPValidationError(f"issuer at depth {depth} {fault}")
            if (issuer_sub, serial) in self._revoked:
                raise MSPValidationError(f"cert at depth {depth} is revoked",
                                         reason="revoked")
        if not deferred:
            return None
        item = _link_item(cert, issuers[0].wire_key)
        if item is None:
            self._signed_by(cert, issuers[:1])
        return item

    def is_valid(self, ident: Identity) -> bool:
        try:
            self.validate(ident)
            return True
        except MSPValidationError:
            return False

    def _deferrable(self, ident: Identity) -> Optional[_Issuer]:
        """The CA whose signature over the identity's certificate a
        provider can check as a P-256 item, read off the certificate:
        signed with ecdsa-with-SHA256, by a P-256 key, under exactly
        one trusted candidate of the issuer's name (two — a CA's key
        rolled over — need "any of", so they stay here).  A lite
        certificate states no algorithm, and gives no to-be-signed
        bytes either: it stays here too."""
        candidates = self._by_subject.get(ident.issuer_der, ())
        if (len(candidates) != 1 or candidates[0].wire_key is None
                or _link_algorithm(ident.cert) != ECDSA_SHA256):
            return None
        return candidates[0]

    def _build_chain(self, ident: Identity,
                     defer: bool = False) -> Tuple[List[_Issuer], bool]:
        """The identity's issuers, leaf's first, root last — and
        whether the leaf link's signature was left unchecked (`defer`
        and `_deferrable`).  Every other link's is checked here via the
        issuer's public key."""
        cert = ident.cert
        serial = cert.serial_number
        if serial in self._root_serials and (
                cert.subject.public_bytes(), serial) in self._root_ids:
            return [], False
        parent = self._deferrable(ident) if defer else None
        deferred = parent is not None
        if parent is None:
            self._link_sigs.add(1, msp=self.mspid, where="host")
            parent = self._signed_by(
                cert, self._by_subject.get(ident.issuer_der, ()))
        issuers = [parent]
        while not parent.is_root:
            if len(issuers) == MAX_CHAIN_DEPTH - 1:
                raise MSPValidationError("cert chain too deep")
            parent = self._signed_by(
                parent.cert, self._by_subject.get(parent.issuer, ()))
            issuers.append(parent)
        return issuers, deferred

    @staticmethod
    def _signed_by(cert: x509.Certificate,
                   candidates: Sequence[_Issuer]) -> _Issuer:
        """The trusted candidate whose key signed `cert`, checked on
        the host; a candidate the check chokes on is the wrong one."""
        alg = _link_algorithm(cert)
        if alg is not None and alg not in LINK_ALGORITHMS:
            raise MSPValidationError(
                f"{cert.subject.rfc4514_string()!r} is signed with "
                f"{alg}, which no MSP takes")
        for cand in candidates:
            try:
                cert.verify_directly_issued_by(cand.cert)
                return cand
            except Exception:
                continue
        raise MSPValidationError(
            f"no trusted issuer for {cert.subject.rfc4514_string()!r}")

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
