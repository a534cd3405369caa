"""MSP identity-plane tests: serialization, chain validation, CRLs,
principals, caching (reference parity: msp/ tests + mspimplvalidate.go)."""
import datetime

import pytest

from fabric_tpu.bccsp import SCHEME_P256, SCHEME_ED25519
from fabric_tpu.bccsp.factory import init_factories, FactoryOpts
from fabric_tpu.msp import MSP, MSPManager, Principal, CachedMSP
from fabric_tpu.msp.msp import MSPValidationError
from fabric_tpu.msp.ca import DevOrg


@pytest.fixture(scope="module", autouse=True)
def sw_provider():
    # identity-plane tests don't need a device
    init_factories(FactoryOpts(default="SW"))


@pytest.fixture(scope="module")
def org():
    return DevOrg("Org1MSP", with_intermediate=True)


@pytest.fixture(scope="module")
def msp(org):
    return org.msp()


def test_identity_roundtrip_and_sign_verify(org, msp):
    user = org.new_identity("alice")
    data = user.serialize()
    ident = msp.deserialize_identity(data)
    assert ident.mspid == "Org1MSP"
    sig = user.sign(b"hello world")
    assert ident.verify(b"hello world", sig)
    assert not ident.verify(b"hello worlD", sig)


def test_chain_validation_with_intermediate(org, msp):
    user = org.new_identity("bob")
    msp.validate(user)  # should not raise


def test_foreign_identity_rejected(msp):
    other = DevOrg("EvilMSP")
    mallory = other.new_identity("mallory")
    with pytest.raises(MSPValidationError):
        msp.validate(mallory)
    with pytest.raises(MSPValidationError):
        msp.deserialize_identity(mallory.serialize())


def test_crl_revocation(org):
    user = org.new_identity("carol")
    crl = org.issuer.crl([user.cert])
    msp2 = org.msp(crls_pem=[crl])
    with pytest.raises(MSPValidationError, match="revoked"):
        msp2.validate(user)
    # others still fine
    msp2.validate(org.new_identity("dave"))


def test_principals(org, msp):
    user = org.new_identity("erin", org_units=("ops",))
    assert msp.satisfies_principal(user, Principal.member("Org1MSP"))
    assert not msp.satisfies_principal(user, Principal.member("OtherMSP"))
    assert not msp.satisfies_principal(user, Principal.admin("Org1MSP"))
    assert msp.satisfies_principal(org.admin, Principal.admin("Org1MSP"))
    assert msp.satisfies_principal(
        user, Principal("org_unit", mspid="Org1MSP", org_unit="ops"))
    assert not msp.satisfies_principal(
        user, Principal("org_unit", mspid="Org1MSP", org_unit="dev"))
    assert msp.satisfies_principal(
        user, Principal("identity", identity_bytes=user.serialize()))


def test_ed25519_org():
    org = DevOrg("EdOrg", scheme=SCHEME_ED25519)
    msp = org.msp()
    user = org.new_identity("frank")
    msp.validate(user)
    sig = user.sign(b"ed msg")
    ident = msp.deserialize_identity(user.serialize())
    assert ident.scheme == SCHEME_ED25519
    assert ident.verify(b"ed msg", sig)
    assert not ident.verify(b"ed msg2", sig)


def test_cached_msp(org):
    from fabric_tpu.ops_plane import registry
    lookups = registry.counter("msp_cache_total")

    def asked(result):
        return sum(lookups.value(msp="Org1MSP", op=op, result=result)
                   for op in ("deserialize", "validate", "principal"))

    hits0, misses0 = asked("hit"), asked("miss")
    cmsp = CachedMSP(org.msp())
    user = org.new_identity("gina")
    data = user.serialize()
    for _ in range(5):
        ident = cmsp.deserialize_identity(data)
        cmsp.validate(ident)
        assert cmsp.satisfies_principal(ident, Principal.member("Org1MSP"))
    assert asked("hit") - hits0 == 12
    assert asked("miss") - misses0 == 3


def test_msp_manager(org):
    org2 = DevOrg("Org2MSP")
    mgr = MSPManager([org.msp(), org2.msp()])
    u1 = org.new_identity("u1")
    ident = mgr.deserialize_identity(u1.serialize())
    assert ident.mspid == "Org1MSP"
    with pytest.raises(MSPValidationError):
        mgr.get_msp("NopeMSP")
