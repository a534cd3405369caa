"""Dev certificate authority — the reference's `cryptogen` equivalent.

Parity: /root/reference/internal/cryptogen/ca/ca.go (NewCA, SignCertificate)
and internal/cryptogen/msp/generator.go — generates org CA hierarchies and
per-identity MSP material for tests / dev networks.  Supports both ECDSA
P-256 (reference parity) and ed25519 (this framework's new capability).
"""

from __future__ import annotations

import datetime
from typing import List, Optional, Tuple

from fabric_tpu.crypto import x509
from fabric_tpu.crypto import hashes, serialization
from fabric_tpu.crypto import ec, ed25519
from fabric_tpu.crypto import NameOID

from fabric_tpu.bccsp import SCHEME_P256, SCHEME_ED25519
from fabric_tpu.bccsp.sw import SigningKey
from .identity import Identity, SigningIdentity
from .msp import MSP, MSPConfig

VALIDITY = datetime.timedelta(days=3650)


def _gen_key(scheme: str):
    if scheme == SCHEME_P256:
        return ec.generate_private_key(ec.SECP256R1())
    if scheme == SCHEME_ED25519:
        return ed25519.Ed25519PrivateKey.generate()
    raise ValueError(f"unsupported scheme {scheme!r}")


def _sign_alg(key):
    return hashes.SHA256() if isinstance(key, ec.EllipticCurvePrivateKey) else None


class CA:
    """A (root or intermediate) certificate authority."""

    def __init__(self, name: str, scheme: str = SCHEME_P256,
                 parent: Optional["CA"] = None):
        self.name = name
        self.scheme = scheme
        self.parent = parent
        self._key = _gen_key(scheme)
        now = datetime.datetime.now(datetime.timezone.utc)
        subject = x509.Name([
            x509.NameAttribute(NameOID.COMMON_NAME, name),
            x509.NameAttribute(NameOID.ORGANIZATION_NAME, name),
        ])
        issuer = parent.cert.subject if parent else subject
        signing_key = parent._key if parent else self._key
        builder = (x509.CertificateBuilder()
                   .subject_name(subject)
                   .issuer_name(issuer)
                   .public_key(self._key.public_key())
                   .serial_number(x509.random_serial_number())
                   .not_valid_before(now - datetime.timedelta(minutes=5))
                   .not_valid_after(now + VALIDITY)
                   .add_extension(x509.BasicConstraints(ca=True, path_length=None),
                                  critical=True)
                   .add_extension(x509.KeyUsage(
                       digital_signature=True, key_cert_sign=True, crl_sign=True,
                       content_commitment=False, key_encipherment=False,
                       data_encipherment=False, key_agreement=False,
                       encipher_only=False, decipher_only=False), critical=True))
        self.cert = builder.sign(signing_key, _sign_alg(signing_key))

    @classmethod
    def load(cls, cert_pem: bytes, key_pem: bytes) -> "CA":
        """An issuing CA from its certificate and key (a pool's worker
        that issues a share of an org's enrolments)."""
        ca = cls.__new__(cls)
        ca.cert = x509.load_pem_x509_certificate(cert_pem)
        ca._key = serialization.load_pem_private_key(key_pem, password=None)
        ca.name = ca.cert.subject.rfc4514_string()
        ca.scheme = (SCHEME_P256
                     if isinstance(ca._key, ec.EllipticCurvePrivateKey)
                     else SCHEME_ED25519)
        ca.parent = None
        return ca

    def cert_pem(self) -> bytes:
        return self.cert.public_bytes(serialization.Encoding.PEM)

    def issue(self, common_name: str, scheme: Optional[str] = None,
              org_units: Tuple[str, ...] = (), ca: bool = False,
              not_after=None):
        """Issue an end-entity (or intermediate-CA) cert.

        Returns (cert, private_key_object)."""
        scheme = scheme or self.scheme
        key = _gen_key(scheme)
        now = datetime.datetime.now(datetime.timezone.utc)
        attrs = [x509.NameAttribute(NameOID.COMMON_NAME, common_name)]
        for ou in org_units:
            attrs.append(x509.NameAttribute(NameOID.ORGANIZATIONAL_UNIT_NAME, ou))
        builder = (x509.CertificateBuilder()
                   .subject_name(x509.Name(attrs))
                   .issuer_name(self.cert.subject)
                   .public_key(key.public_key())
                   .serial_number(x509.random_serial_number())
                   .not_valid_before(now - datetime.timedelta(minutes=5))
                   .not_valid_after(not_after or (now + VALIDITY))
                   .add_extension(x509.BasicConstraints(ca=ca, path_length=None),
                                  critical=True))
        cert = builder.sign(self._key, _sign_alg(self._key))
        return cert, key

    def crl(self, revoked_certs: List[x509.Certificate]) -> bytes:
        """Issue a CRL revoking the given certs (PEM)."""
        now = datetime.datetime.now(datetime.timezone.utc)
        builder = (x509.CertificateRevocationListBuilder()
                   .issuer_name(self.cert.subject)
                   .last_update(now)
                   .next_update(now + datetime.timedelta(days=365)))
        for c in revoked_certs:
            builder = builder.add_revoked_certificate(
                x509.RevokedCertificateBuilder()
                .serial_number(c.serial_number)
                .revocation_date(now).build())
        crl = builder.sign(self._key, _sign_alg(self._key))
        return crl.public_bytes(serialization.Encoding.PEM)


class DevOrg:
    """An org with a root CA and helpers to mint MSP config + identities
    (the cryptogen 'organization' unit)."""

    def __init__(self, mspid: str, scheme: str = SCHEME_P256,
                 with_intermediate: bool = False):
        self.mspid = mspid
        self.scheme = scheme
        self.root = CA(mspid + "-root", scheme)
        self.intermediate = CA(mspid + "-ica", scheme, parent=self.root) \
            if with_intermediate else None
        self.issuer = self.intermediate or self.root
        admin_cert, admin_key = self.issuer.issue("admin@" + mspid,
                                                  org_units=("admin",))
        self.admin = SigningIdentity(mspid, admin_cert,
                                     SigningKey(scheme, admin_key))
        self._admin_cert = admin_cert

    def msp_config(self, crls_pem: Optional[List[bytes]] = None) -> MSPConfig:
        return MSPConfig(
            mspid=self.mspid,
            root_certs_pem=[self.root.cert_pem()],
            intermediate_certs_pem=(
                [self.intermediate.cert_pem()] if self.intermediate else []),
            admin_certs_pem=[self._admin_cert.public_bytes(
                serialization.Encoding.PEM)],
            crls_pem=crls_pem or [])

    def msp(self, crls_pem: Optional[List[bytes]] = None) -> MSP:
        return MSP(self.msp_config(crls_pem))

    def new_identity(self, name: str, org_units: Tuple[str, ...] = (),
                     not_after=None) -> SigningIdentity:
        cert, key = self.issuer.issue(name + "@" + self.mspid,
                                      org_units=org_units,
                                      not_after=not_after)
        return SigningIdentity(self.mspid, cert, SigningKey(self.scheme, key))
