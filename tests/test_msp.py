"""MSP identity-plane tests: serialization, chain validation, CRLs,
principals, caching (reference parity: msp/ tests + mspimplvalidate.go)."""
import datetime

import pytest

from fabric_tpu.bccsp import SCHEME_P256, SCHEME_ED25519
from fabric_tpu.bccsp.factory import init_factories, FactoryOpts
from fabric_tpu.bccsp.sw import SoftwareProvider
from fabric_tpu.crypto import ec, hashes, serialization, x509
from fabric_tpu.msp import MSP, MSPManager, Principal, CachedMSP
from fabric_tpu.msp import msp as mspmod
from fabric_tpu.msp.identity import Identity
from fabric_tpu.msp.msp import MSPValidationError
from fabric_tpu.msp.ca import DevOrg
from test_enrolment import forge


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


# -- the split: host checks + the leaf link's signature ------------------------------


# what `MSP.validate` said of each before chain validation was split in
# two (read on the parent commit), and what it and the deferred entry
# say now: (validate, validate_deferred), "link" where the deferred
# entry hands the signature back
CHAINS = {
    "sound": ("ok", "link"),
    "under_intermediate": ("ok", "link"),
    "revoked": ("revoked", "revoked"),
    "expired": ("expired", "expired"),
    "forged": ("untrusted", "link"),
    "forged_and_listed": ("untrusted", "revoked"),
    "forged_and_expired": ("untrusted", "expired"),
    "foreign_org": ("untrusted", "untrusted"),
    "issued_by_a_member": ("untrusted", "untrusted"),
    "ed25519_org": ("ok", "ok"),
    "the_root_itself": ("ok", "ok"),
    "revoked_intermediate": ("revoked", "revoked"),
}


def _issued(org, name, algorithm=None, issuer_key=None, issuer_name=None,
            not_before=None, not_after=None, public_key=None):
    """A certificate under `org`'s CA's name made by hand: signed by
    `issuer_key` (the CA's own unless given) with `algorithm`."""
    now = datetime.datetime.now(datetime.timezone.utc)
    return (x509.CertificateBuilder()
            .subject_name(x509.Name([x509.NameAttribute(
                x509.oid.NameOID.COMMON_NAME, name)]))
            .issuer_name(issuer_name or org.issuer.cert.subject)
            .public_key(public_key
                        or ec.generate_private_key(ec.SECP256R1()).public_key())
            .serial_number(x509.random_serial_number())
            .not_valid_before(not_before or now - datetime.timedelta(minutes=5))
            .not_valid_after(not_after or now + datetime.timedelta(days=1))
            .sign(issuer_key or org.issuer._key, algorithm or hashes.SHA256()))


def _chain_case(case):
    """-> (msp, identity) of one row of CHAINS."""
    past = (datetime.datetime.now(datetime.timezone.utc)
            - datetime.timedelta(minutes=1))
    if case == "ed25519_org":
        org = DevOrg("EdOrg2", scheme=SCHEME_ED25519)
        return org.msp(), org.new_identity("frank")
    org = DevOrg("SplitMSP", with_intermediate=(
        case in ("under_intermediate", "revoked_intermediate")))
    user = org.new_identity("user")
    if case in ("sound", "under_intermediate"):
        return org.msp(), user
    if case == "revoked":
        return org.msp(crls_pem=[org.issuer.crl([user.cert])]), user
    if case == "revoked_intermediate":
        return org.msp(crls_pem=[org.root.crl([org.intermediate.cert])]), user
    if case == "expired":
        return org.msp(), org.new_identity("late", not_after=past)
    if case == "forged":
        return org.msp(), forge(user)
    if case == "forged_and_listed":
        return (org.msp(crls_pem=[org.issuer.crl([user.cert])]),
                forge(user, serial=user.cert.serial_number))
    if case == "forged_and_expired":
        rogue = ec.generate_private_key(ec.SECP256R1())
        return org.msp(), Identity("SplitMSP", _issued(
            org, "late", issuer_key=rogue, not_after=past,
            not_before=past - datetime.timedelta(minutes=5)))
    if case == "foreign_org":
        return org.msp(), DevOrg("EvilMSP").new_identity("mallory")
    if case == "issued_by_a_member":
        # a member's certificate (no CA) listed as an intermediate, and
        # an identity it signed
        config = org.msp_config()
        config.intermediate_certs_pem.append(
            user.cert.public_bytes(serialization.Encoding.PEM))
        return MSP(config), Identity("SplitMSP", _issued(
            org, "child", issuer_key=user._key.key,
            issuer_name=user.cert.subject))
    if case == "the_root_itself":
        return org.msp(), Identity("SplitMSP", org.root.cert)
    raise AssertionError(case)


@pytest.mark.parametrize("case", sorted(CHAINS))
def test_validate_says_what_it_said_before_the_split(case):
    msp, ident = _chain_case(case)
    want, want_deferred = CHAINS[case]
    for _ in range(2):                      # and says it again
        if want == "ok":
            msp.validate(ident)
        else:
            with pytest.raises(MSPValidationError) as refused:
                msp.validate(ident)
            assert refused.value.reason == want
    # the same two parts, the signature handed back where it is eligible
    if want_deferred in ("ok", "link"):
        link = msp.validate_deferred(ident)
        assert (link is not None) == (want_deferred == "link")
        if link is not None:
            ok = SoftwareProvider().verify(link.item)
            assert ok == (want == "ok")
            err, = msp.settle_many([ident], [link], [ok])
            assert (err.reason if err else "ok") == want
    else:
        with pytest.raises(MSPValidationError) as refused:
            msp.validate_deferred(ident)
        assert refused.value.reason == want_deferred


def test_an_unsupported_signature_algorithm_is_refused_by_name():
    """A link signed with an algorithm no MSP takes is `untrusted`
    because the host part says so, reading the field the eligibility
    test reads — not because the library choked somewhere."""
    org = DevOrg("AlgMSP")
    msp = org.msp()
    odd = Identity("AlgMSP", _issued(org, "odd", hashes.SHA224()))
    assert mspmod._link_algorithm(odd.cert) not in mspmod.LINK_ALGORITHMS
    assert msp.deferrable_under(odd) is None
    for entry in (msp.validate, msp.validate_deferred):
        with pytest.raises(MSPValidationError, match="signed with") as refused:
            entry(odd)
        assert refused.value.reason == "untrusted"
    # SHA-384 under the same key is a link the host checks, not the device
    fine = Identity("AlgMSP", _issued(org, "fine", hashes.SHA384()))
    assert msp.deferrable_under(fine) is None
    msp.validate(fine)
    assert msp.validate_deferred(fine) is None
