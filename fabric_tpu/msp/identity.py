"""Identities: X.509-certificate-backed signers/verifiers.

Reference parity: msp/identities.go — identity{} / signingidentity{}.
Key semantic preserved: Verify(msg, sig) hashes the message host-side and
hands the fixed-size digest to the crypto provider
(identities.go:178 hashes, :188 calls bccsp.Verify).  The TPU-native
addition is `verify_item`, which returns the VerifyItem for batch
collection instead of verifying immediately.
"""

from __future__ import annotations

from dataclasses import dataclass
from functools import cached_property
from typing import Optional

from fabric_tpu.crypto import x509
from fabric_tpu.crypto import serialization

from fabric_tpu.bccsp import VerifyItem, SCHEME_P256, SCHEME_ED25519
from fabric_tpu.bccsp.factory import get_default
from fabric_tpu.utils import serde


def scheme_of_cert(cert: x509.Certificate) -> str:
    from fabric_tpu.crypto import ec, ed25519
    pub = cert.public_key()
    if isinstance(pub, ec.EllipticCurvePublicKey):
        if pub.curve.name != "secp256r1":
            raise ValueError(f"unsupported EC curve {pub.curve.name}")
        return SCHEME_P256
    if isinstance(pub, ed25519.Ed25519PublicKey):
        return SCHEME_ED25519
    raise ValueError(f"unsupported key type {type(pub).__name__}")


def pubkey_wire_bytes(cert: x509.Certificate) -> bytes:
    """Provider wire format: SEC1 uncompressed (p256) or raw 32B (ed25519)."""
    from fabric_tpu.crypto import ec
    pub = cert.public_key()
    if isinstance(pub, ec.EllipticCurvePublicKey):
        return pub.public_bytes(serialization.Encoding.X962,
                                serialization.PublicFormat.UncompressedPoint)
    return pub.public_bytes(serialization.Encoding.Raw,
                            serialization.PublicFormat.Raw)


class Identity:
    """A deserialized, possibly-unvalidated identity (cert + msp id)."""

    def __init__(self, mspid: str, cert: x509.Certificate):
        self.mspid = mspid
        self.cert = cert
        self.scheme = scheme_of_cert(cert)
        self._pub_wire = pubkey_wire_bytes(cert)

    # -- serialization (SerializedIdentity equivalent, protoutil/signeddata) --

    def serialize(self) -> bytes:
        pem = self.cert.public_bytes(serialization.Encoding.PEM)
        return serde.encode({"mspid": self.mspid, "cert_pem": pem})

    @staticmethod
    def deserialize(data: bytes) -> "Identity":
        d = serde.decode(data)
        cert = x509.load_pem_x509_certificate(d["cert_pem"])
        return Identity(d["mspid"], cert)

    # -- verification ------------------------------------------------------

    def _payload_for(self, msg: bytes) -> bytes:
        """p256 signs the SHA-256 digest; ed25519 signs the message."""
        if self.scheme == SCHEME_P256:
            return get_default().hash(msg)
        return msg

    def verify_item(self, msg: bytes, sig: bytes) -> VerifyItem:
        """Collect-don't-verify: the batch-pipeline's unit of work."""
        return VerifyItem(self.scheme, self._pub_wire, sig, self._payload_for(msg))

    def verify(self, msg: bytes, sig: bytes) -> bool:
        """Immediate verification through the default provider (compat path)."""
        return get_default().verify(self.verify_item(msg, sig))

    @property
    def subject(self) -> str:
        return self.cert.subject.rfc4514_string()

    @cached_property
    def issuer_der(self) -> bytes:
        """The certificate's issuer name as DER, what an MSP looks its
        CAs and its CRLs up by: encoded once an identity."""
        return self.cert.issuer.public_bytes()

    def expires_at(self):
        return self.cert.not_valid_after_utc

    def __eq__(self, other):
        return (isinstance(other, Identity) and self.mspid == other.mspid
                and self.cert == other.cert)

    def __hash__(self):
        return hash((self.mspid, self._pub_wire,
                     self.cert.serial_number))


class SigningIdentity(Identity):
    """Identity + private key (msp signingidentity, identities.go:252)."""

    def __init__(self, mspid: str, cert: x509.Certificate, signing_key):
        super().__init__(mspid, cert)
        self._key = signing_key  # bccsp SigningKey

    def sign(self, msg: bytes) -> bytes:
        payload = self._payload_for(msg)
        return get_default().sign(self._key, payload)
