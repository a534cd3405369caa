"""aclmgmt: resource-name -> policy registry, config-driven.

Reference parity: core/aclmgmt/aclmgmt.go:15 + resources.go — an ACL
entry committed in the channel config retargets authorization for the
named API resource with no code change.
"""
import pytest

from fabric_tpu.bccsp.factory import FactoryOpts, init_factories
from fabric_tpu.config import (Bundle, BundleSource, ChannelConfig,
                               OrgConfig, default_policies)
from fabric_tpu.msp.ca import DevOrg
from fabric_tpu.policy import ACLError, ACLProvider, SignedData
from fabric_tpu.policy.dsl import parse_policy


@pytest.fixture(scope="module")
def world():
    provider = init_factories(FactoryOpts(default="SW"))
    org = DevOrg("Org1")
    mc = org.msp_config()
    orgs = (OrgConfig(mspid="Org1", root_certs=tuple(mc.root_certs_pem),
                      admins=tuple(mc.admin_certs_pem)),)
    return provider, org, orgs


def _bundle_source(org, orgs, acls=None):
    pols = default_policies(["Org1"])
    cfg = ChannelConfig(channel_id="ch", sequence=0, orgs=orgs,
                        policies=pols, acls=dict(acls or {}))
    return BundleSource(Bundle(cfg))


def test_default_acls_member_vs_admin(world):
    provider, org, orgs = world
    src = _bundle_source(org, orgs)
    acl = ACLProvider(src, provider)
    member = org.new_identity("m1")
    payload = b"query"
    sd = SignedData(payload, member.serialize(), member.sign(payload))
    # Readers default: any member passes
    acl.check_acl("qscc/GetBlockByNumber", sd)
    # Admins default: member fails, admin passes
    with pytest.raises(ACLError):
        acl.check_acl("cscc/JoinChain", sd)
    admin = org.admin
    sd_admin = SignedData(payload, admin.serialize(), admin.sign(payload))
    acl.check_acl("cscc/JoinChain", sd_admin)
    # unknown resource fails closed
    with pytest.raises(ACLError):
        acl.check_acl("no/SuchResource", sd_admin)


def test_config_acl_change_retargets_resource(world):
    """An ACL override in the channel config changes behavior for the
    SAME caller at the SAME call site."""
    provider, org, orgs = world
    src = _bundle_source(org, orgs)
    acl = ACLProvider(src, provider)
    member = org.new_identity("m2")
    sd = SignedData(b"q", member.serialize(), member.sign(b"q"))
    acl.check_acl("qscc/GetBlockByNumber", sd)      # Readers: allowed

    # config update: qscc/GetBlockByNumber now requires Admins
    pols = default_policies(["Org1"])
    cfg2 = ChannelConfig(channel_id="ch", sequence=1, orgs=orgs,
                         policies=pols,
                         acls={"qscc/GetBlockByNumber": "Admins"})
    src.update(Bundle(cfg2))
    with pytest.raises(ACLError):
        acl.check_acl("qscc/GetBlockByNumber", sd)  # member now denied
    admin = org.admin
    acl.check_acl("qscc/GetBlockByNumber",
                  SignedData(b"q", admin.serialize(), admin.sign(b"q")))


def test_handshake_identity_check(world):
    provider, org, orgs = world
    src = _bundle_source(org, orgs)
    acl = ACLProvider(src, provider)
    member = org.new_identity("m3")
    acl.check("qscc/GetChainInfo", member)          # identity object
    with pytest.raises(ACLError):
        acl.check("participation/Join", member)     # Admins
    acl.check("participation/Join", org.admin)
    with pytest.raises(ACLError):
        acl.check("qscc/GetChainInfo", None)
    # foreign-org identity: unknown to the channel MSPs -> denied
    org2 = DevOrg("Evil")
    with pytest.raises(ACLError):
        acl.check("qscc/GetChainInfo", org2.new_identity("x"))


def test_qscc_consumes_acl(world):
    """Qscc routes each query through its own named resource."""
    from fabric_tpu.ledger.blkstorage import BlockStore
    from fabric_tpu.scc.qscc import Qscc

    provider, org, orgs = world
    src = _bundle_source(org, orgs,
                         acls={"qscc/GetChainInfo": "Admins"})
    acl = ACLProvider(src, provider)
    qscc = Qscc("ch", BlockStore(), acl=acl)
    member = org.new_identity("m4")
    with pytest.raises(ACLError):
        qscc.get_chain_info(member)                 # Admins override
    qscc.get_chain_info(org.admin)
    # a DIFFERENT qscc resource keeps its Readers default
    with pytest.raises(Exception):
        qscc.get_block_by_number(0, member)         # Readers ok, but
                                                    # empty store raises
    qscc.get_chain_info(org.admin)


def _signed(ident, payload=b"query", signed=None):
    return SignedData(payload, ident.serialize(),
                      ident.sign(payload if signed is None else signed))


@pytest.mark.parametrize("case", ["valid", "invalid", "no_signed_data"])
def test_check_acl_with_held_verdicts_dispatches_nothing(world, counting,
                                                         case):
    """`check_acl(resource, sd, verified)` decides as `check_acl(resource,
    sd)` does, from the caller's verdict and without the provider."""
    provider, org, orgs = world
    member = org.new_identity("h1")
    sd = {"valid": _signed(member),
          "invalid": _signed(member, signed=b"other bytes"),
          "no_signed_data": None}[case]
    src = _bundle_source(org, orgs)
    own = ACLProvider(src, provider)
    counted = counting(provider)
    acl = ACLProvider(src, counted)
    held = {}
    if sd is not None:
        item = src.current().msps["Org1"].deserialize_identity(
            sd.identity).verify_item(sd.data, sd.signature)
        held[item] = provider.verify(item)

    def outcome(check):
        try:
            check()
        except ACLError as e:
            return str(e)
        return "allowed"

    for resource in ("qscc/GetBlockByNumber", "cscc/JoinChain",
                     "no/SuchResource"):
        assert outcome(lambda: acl.check_acl(resource, sd, held)) \
            == outcome(lambda: own.check_acl(resource, sd))
    assert outcome(lambda: acl.check_acl("qscc/GetBlockByNumber", sd, held)) \
        == {"valid": "allowed",
            "invalid": "qscc/GetBlockByNumber: signed data does not "
                       "satisfy policy 'Readers'",
            "no_signed_data": "qscc/GetBlockByNumber: no signed data"}[case]
    assert counted.calls == 0


def test_check_acl_verifies_what_the_held_verdicts_do_not_cover(world,
                                                                counting):
    provider, org, orgs = world
    src = _bundle_source(org, orgs)
    counted = counting(provider)
    acl = ACLProvider(src, counted)
    member = org.new_identity("h2")
    sd, other = _signed(member), _signed(member, b"another query")
    msp = src.current().msps["Org1"]
    item = msp.deserialize_identity(sd.identity).verify_item(
        sd.data, sd.signature)
    # a verdict for this signer's other message does not answer this one
    acl.check_acl("qscc/GetBlockByNumber", other, {item: True})
    assert counted.calls == 1 and counted.items[0] != item
    # nor does it let a forged signature through
    with pytest.raises(ACLError):
        acl.check_acl("qscc/GetBlockByNumber",
                      _signed(member, signed=b"x"), {item: True})
    assert counted.calls == 2
    # no verdicts at all: today's call, one verify
    acl.check_acl("qscc/GetBlockByNumber", sd)
    assert counted.calls == 3 and counted.items[-1] == item
