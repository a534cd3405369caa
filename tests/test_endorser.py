"""Endorsement plane: proposal -> simulate -> endorse -> assemble ->
order -> validate -> commit (reference: core/endorser, core/chaincode,
core/chaincode/lifecycle)."""
import pytest

from fabric_tpu.bccsp.factory import (FactoryOpts, init_factories,
                                      set_default)
from fabric_tpu.chaincode import (
    ChaincodeDefinition,
    ChaincodeRegistry,
    ChaincodeStub,
    LIFECYCLE_NS,
    LifecycleContract,
    LifecyclePolicyProvider,
    SimulationError,
)
from fabric_tpu.chaincode.runtime import FuncContract
from fabric_tpu.committer import Committer, TxValidator
from fabric_tpu.config import (Bundle, BundleSource, ChannelConfig,
                               OrgConfig, default_policies)
from fabric_tpu.endorser import (
    Endorser,
    ProposalResponse,
    ResponseMismatchError,
    assemble_transaction,
    signed_proposal,
)
from fabric_tpu.ledger import KVLedger, LedgerConfig
from fabric_tpu.msp import CachedMSP
from fabric_tpu.msp.ca import DevOrg
from fabric_tpu.policy import ACLProvider, parse_policy
from fabric_tpu.protocol import ValidationCode, build


@pytest.fixture(scope="module", autouse=True)
def sw_provider():
    return init_factories(FactoryOpts(default="SW"))


def kv_contract():
    def put(stub, key, value):
        stub.put_state(key.decode(), value)
        return b"ok"

    def get(stub, key):
        v = stub.get_state(key.decode())
        if v is None:
            raise SimulationError("no such key")
        return v

    def transfer(stub, frm, to, amt):
        a = int(stub.get_state(frm.decode()) or b"0")
        b = int(stub.get_state(to.decode()) or b"0")
        n = int(amt)
        if a < n:
            raise SimulationError("insufficient funds")
        stub.put_state(frm.decode(), str(a - n).encode())
        stub.put_state(to.decode(), str(b + n).encode())
        return b"ok"

    def scan(stub, start, end):
        rows = stub.get_state_by_range(start.decode(), end.decode())
        return str(len(rows)).encode()

    def call_other(stub, cc, fn, *args):
        return stub.invoke_chaincode(cc.decode(), fn.decode(), list(args))

    return FuncContract(put=put, get=get, transfer=transfer, scan=scan,
                        call_other=call_other)


class World:
    def __init__(self, provider, n_orgs=2):
        self.orgs = [DevOrg(f"Org{i+1}") for i in range(n_orgs)]
        self.msps = {o.mspid: CachedMSP(o.msp()) for o in self.orgs}
        self.ledger = KVLedger("ch", LedgerConfig())
        self.registry = ChaincodeRegistry()
        self.registry.install(ChaincodeDefinition("cc", "1.0"), kv_contract())
        self.registry.install(
            ChaincodeDefinition(LIFECYCLE_NS, "1.0"),
            LifecycleContract([o.mspid for o in self.orgs]))
        self.policies = LifecyclePolicyProvider(
            self.ledger.statedb,
            default=parse_policy("OR('Org1.member', 'Org2.member')"))
        self.policies.set_policy(LIFECYCLE_NS,
                                 parse_policy("OR('Org1.member')"))
        self.policies.set_policy("cc", parse_policy(
            "AND('Org1.member', 'Org2.member')"))
        self.endorsers = [
            Endorser("ch", self.ledger.statedb, self.registry, self.msps,
                     provider, o.new_identity(f"peer{o.mspid}"))
            for o in self.orgs]
        self.committer = Committer(
            self.ledger, TxValidator("ch", self.msps, provider, self.policies))
        self.client = self.orgs[0].new_identity("client")

    def roundtrip(self, cc, fn, args, expect=ValidationCode.VALID,
                  endorsers=None):
        sp = signed_proposal("ch", cc, fn, args, self.client)
        resps = [e.process_proposal(sp) for e in (endorsers or self.endorsers)]
        env = assemble_transaction(sp, resps, self.client)
        lg = self.ledger
        prev = (lg.blockstore.chain_info().current_hash
                if lg.height else b"\x00" * 32)
        block = build.new_block(lg.height, prev, [env])
        res = self.committer.store_block(block)
        code = ValidationCode(res.validation.flags.flag(0))
        # MVCC may flip flags later; read the final bitmap from the store
        from fabric_tpu.protocol import TxFlags
        from fabric_tpu.protocol.types import META_TXFLAGS
        final = TxFlags.from_bytes(
            lg.blockstore.get_by_number(block.header.number)
            .metadata.items[META_TXFLAGS])
        assert final.flag(0) == expect, \
            f"expected {expect.name}, got {ValidationCode(final.flag(0)).name}"
        return resps


@pytest.fixture()
def world(sw_provider):
    return World(sw_provider)


def test_full_lifecycle_roundtrip(world):
    world.roundtrip("cc", "put", [b"a", b"100"])
    world.roundtrip("cc", "put", [b"b", b"50"])
    world.roundtrip("cc", "transfer", [b"a", b"b", b"30"])
    assert world.ledger.get_state("cc", "a") == b"70"
    assert world.ledger.get_state("cc", "b") == b"80"


def test_failed_simulation_not_endorsed(world):
    sp = signed_proposal("ch", "cc", "transfer",
                         [b"nobody", b"a", b"1"], world.client)
    resp = world.endorsers[0].process_proposal(sp)
    assert resp.status == 500 and "insufficient" in resp.message
    assert resp.endorsement is None
    with pytest.raises(ResponseMismatchError):
        assemble_transaction(sp, [resp], world.client)


def test_single_endorsement_fails_and_policy(world):
    # AND(Org1, Org2) policy but only Org1 endorses
    world.roundtrip("cc", "put", [b"x", b"1"],
                    expect=ValidationCode.ENDORSEMENT_POLICY_FAILURE,
                    endorsers=[world.endorsers[0]])
    assert world.ledger.get_state("cc", "x") is None


def test_bad_proposal_signature(world):
    sp = signed_proposal("ch", "cc", "put", [b"k", b"v"], world.client)
    tampered = type(sp)(sp.proposal_bytes, sp.signature[:-2] + b"\x00\x01")
    resp = world.endorsers[0].process_proposal(tampered)
    assert resp.status == 500 and "signature" in resp.message


def test_proposal_acl(world, sw_provider):
    world.endorsers[0].proposal_acl = parse_policy("OR('Org2.member')")
    sp = signed_proposal("ch", "cc", "put", [b"k", b"v"], world.client)
    resp = world.endorsers[0].process_proposal(sp)  # client is Org1
    assert resp.status == 500 and "ACL" in resp.message


def test_divergent_responses_rejected(world):
    sp = signed_proposal("ch", "cc", "put", [b"k", b"v"], world.client)
    r1 = world.endorsers[0].process_proposal(sp)
    r2 = world.endorsers[1].process_proposal(sp)
    forged = ProposalResponse(200, "", r2.payload[:-1] + b"\x00",
                              r2.endorsement)
    with pytest.raises(ResponseMismatchError):
        assemble_transaction(sp, [r1, forged], world.client)


def test_mvcc_conflict_between_endorse_and_commit(world):
    world.roundtrip("cc", "put", [b"m", b"100"])
    # two transfers simulate against the same committed version of "m"
    world.roundtrip("cc", "put", [b"n", b"0"])
    sp1 = signed_proposal("ch", "cc", "transfer", [b"m", b"n", b"10"],
                          world.client)
    sp2 = signed_proposal("ch", "cc", "transfer", [b"m", b"n", b"20"],
                          world.client)
    r1 = [e.process_proposal(sp1) for e in world.endorsers]
    r2 = [e.process_proposal(sp2) for e in world.endorsers]
    env1 = assemble_transaction(sp1, r1, world.client)
    env2 = assemble_transaction(sp2, r2, world.client)
    lg = world.ledger
    prev = lg.blockstore.chain_info().current_hash
    block = build.new_block(lg.height, prev, [env1, env2])
    world.committer.store_block(block)
    # both read the same version of "m": first wins, second MVCC-conflicts
    from fabric_tpu.protocol import TxFlags
    from fabric_tpu.protocol.types import META_TXFLAGS
    final = TxFlags.from_bytes(
        lg.blockstore.get_by_number(block.header.number)
        .metadata.items[META_TXFLAGS])
    assert final.codes() == [int(ValidationCode.VALID),
                             int(ValidationCode.MVCC_READ_CONFLICT)]
    assert lg.get_state("cc", "m") == b"90"
    assert lg.get_state("cc", "n") == b"10"


def test_phantom_read_detection(world):
    world.roundtrip("cc", "put", [b"r1", b"1"])
    world.roundtrip("cc", "put", [b"r2", b"1"])
    # scan records a range query; then a conflicting insert lands first
    sp_scan = signed_proposal("ch", "cc", "scan", [b"r", b"s"], world.client)
    r_scan = [e.process_proposal(sp_scan) for e in world.endorsers]
    env_scan = assemble_transaction(sp_scan, r_scan, world.client)
    world.roundtrip("cc", "put", [b"r3", b"1"])  # phantom inserted + committed
    lg = world.ledger
    prev = lg.blockstore.chain_info().current_hash
    block = build.new_block(lg.height, prev, [env_scan])
    world.committer.store_block(block)
    from fabric_tpu.protocol import TxFlags
    from fabric_tpu.protocol.types import META_TXFLAGS
    final = TxFlags.from_bytes(
        lg.blockstore.get_by_number(block.header.number)
        .metadata.items[META_TXFLAGS])
    assert final.flag(0) == ValidationCode.PHANTOM_READ_CONFLICT


def test_cc2cc_writes_both_namespaces(world):
    world.registry.install(ChaincodeDefinition("cc2", "1.0"), kv_contract())
    world.policies.set_policy("cc2", parse_policy(
        "AND('Org1.member', 'Org2.member')"))
    world.roundtrip("cc", "call_other", [b"cc2", b"put", b"zz", b"9"])
    assert world.ledger.get_state("cc2", "zz") == b"9"
    assert world.ledger.get_state("cc", "zz") is None


def test_lifecycle_approve_commit_policy(world):
    # both orgs approve a definition for "newcc" with an OR policy
    pol = parse_policy("OR('Org2.member')").serialize()
    for org_i in (0, 1):
        client = world.orgs[org_i].new_identity("admin")
        sp = signed_proposal("ch", LIFECYCLE_NS, "approve_for_org",
                             [b"newcc", b"1.0", b"1", pol], client)
        resps = [e.process_proposal(sp) for e in world.endorsers]
        env = assemble_transaction(sp, resps, client)
        lg = world.ledger
        prev = (lg.blockstore.chain_info().current_hash
                if lg.height else b"\x00" * 32)
        world.committer.store_block(
            build.new_block(lg.height, prev, [env]))
    # commit the definition
    client = world.orgs[0].new_identity("admin")
    sp = signed_proposal("ch", LIFECYCLE_NS, "commit",
                         [b"newcc", b"1.0", b"1", pol], client)
    resps = [e.process_proposal(sp) for e in world.endorsers]
    env = assemble_transaction(sp, resps, client)
    lg = world.ledger
    prev = lg.blockstore.chain_info().current_hash
    world.committer.store_block(build.new_block(lg.height, prev, [env]))
    # the committed policy now gates "newcc": Org2 alone suffices
    got = world.policies.policy_for("newcc")
    assert got is not None and got.to_dict() == \
        parse_policy("OR('Org2.member')").to_dict()
    world.registry.install(ChaincodeDefinition("newcc", "1.0"), kv_contract())
    world.roundtrip("newcc", "put", [b"q", b"1"],
                    endorsers=[world.endorsers[1]])  # Org2 endorser only
    assert world.ledger.get_state("newcc", "q") == b"1"


def test_lifecycle_insufficient_approvals(world):
    pol = b""
    client = world.orgs[0].new_identity("admin")
    sp = signed_proposal("ch", LIFECYCLE_NS, "approve_for_org",
                         [b"solo", b"1.0", b"1", pol], client)
    resps = [e.process_proposal(sp) for e in world.endorsers]
    env = assemble_transaction(sp, resps, client)
    lg = world.ledger
    prev = (lg.blockstore.chain_info().current_hash
            if lg.height else b"\x00" * 32)
    world.committer.store_block(build.new_block(lg.height, prev, [env]))
    # only 1/2 orgs approved -> commit simulation fails
    sp = signed_proposal("ch", LIFECYCLE_NS, "commit",
                         [b"solo", b"1.0", b"1", pol], client)
    resp = world.endorsers[0].process_proposal(sp)
    assert resp.status == 500 and "insufficient approvals" in resp.message


def test_read_your_writes_and_version_pinning(world):
    world.roundtrip("cc", "put", [b"p", b"1"])
    stub = ChaincodeStub(world.ledger.statedb, "cc")
    assert stub.get_state("p") == b"1"
    stub.put_state("p", b"2")
    assert stub.get_state("p") == b"2"  # read-your-writes
    rw = stub.rwset()
    ns = rw.ns_rwsets[0]
    assert ns.reads[0].key == "p" and ns.reads[0].version is not None
    assert ns.writes[0].value == b"2"


def test_lifecycle_approval_cannot_be_forged(world):
    """An extra arg to approve_for_org must NOT let one org record
    another org's approval (approvals bind to the submitter's MSP)."""
    pol = b""
    client = world.orgs[0].new_identity("mallory")  # Org1
    for forged_org in (b"Org2", b"Org1"):
        sp = signed_proposal("ch", LIFECYCLE_NS, "approve_for_org",
                             [b"victim", b"1.0", b"1", pol, forged_org],
                             client)
        resp = world.endorsers[0].process_proposal(sp)
        assert resp.status == 500  # extra arg rejected outright


def test_malformed_proposal_returns_500_not_crash(world):
    from fabric_tpu.endorser.proposal import SignedProposal
    from fabric_tpu.utils import serde
    # header with a non-bytes nonce: compute_txid would TypeError
    raw = serde.encode({
        "header": {"channel_header": {"type": "endorser_transaction",
                                      "channel_id": "ch", "txid": "x",
                                      "epoch": 0, "timestamp": 0},
                   "signature_header": {"creator": b"junk", "nonce": 7}},
        "chaincode_id": "cc", "fn": "put", "args": []})
    resp = world.endorsers[0].process_proposal(SignedProposal(raw, b"sig"))
    assert resp.status == 500


def test_all_endorsers_must_succeed(world):
    """A single failed response aborts assembly client-side."""
    sp = signed_proposal("ch", "cc", "get", [b"never-set-key"], world.client)
    good = ProposalResponse(200, "", b"x", None)
    bad = world.endorsers[0].process_proposal(sp)
    assert bad.status == 500
    with pytest.raises(ResponseMismatchError):
        assemble_transaction(sp, [good, bad], world.client)


# -- one verify per proposal: collect -> one verify -> gate -----------------

WIRINGS = ("acl", "proposal_acl", "neither")
BAD_SIGNATURE = "bad proposal signature"


@pytest.fixture()
def counted(sw_provider, counting):
    """The node's provider, counting — and the process default too, so
    that a verify made through `get_default()` would be counted as well."""
    provider = counting(sw_provider)
    set_default(provider)
    yield provider
    set_default(sw_provider)


def _bundle(world, propose=None, orgs=None, sequence=0):
    """The channel's config over the world's orgs (or `orgs` of them);
    `propose`: a policy of its own for `peer/Propose`, named "Propose"
    (the default is Writers: any member)."""
    orgs = world.orgs if orgs is None else orgs
    cfgs = []
    for o in orgs:
        mc = o.msp_config()
        cfgs.append(OrgConfig(mspid=o.mspid,
                              root_certs=tuple(mc.root_certs_pem),
                              admins=tuple(mc.admin_certs_pem)))
    policies = default_policies([o.mspid for o in orgs])
    if propose:
        policies["Propose"] = parse_policy(propose)
    return Bundle(ChannelConfig(
        channel_id="ch", sequence=sequence, orgs=tuple(cfgs),
        policies=policies,
        acls={"peer/Propose": "Propose"} if propose else {}))


def _wired(world, provider, wiring, admits_org1=True):
    """Org1's endorser on `provider` under one of the three wirings of
    the proposal gate; -> (endorser, the bundle source or None)."""
    src = None
    kw = {}
    if wiring == "acl":
        src = BundleSource(_bundle(
            world, None if admits_org1 else "OR('Org2.member')"))
        kw["acl"] = ACLProvider(src, provider)
    elif wiring == "proposal_acl":
        kw["proposal_acl"] = parse_policy(
            "OR('Org1.member')" if admits_org1 else "OR('Org2.member')")
    endorser = Endorser("ch", world.ledger.statedb, world.registry,
                        world.msps, provider,
                        world.orgs[0].new_identity("peerOrg1"), **kw)
    return endorser, src


def _put(world, signer=None, channel="ch"):
    return signed_proposal(channel, "cc", "put", [b"k", b"v"],
                           signer or world.client)


def _tamper(sp):
    return type(sp)(sp.proposal_bytes, sp.signature[:-2] + b"\x00\x01")


@pytest.mark.parametrize("wiring", WIRINGS)
def test_valid_proposal_costs_exactly_one_verify(world, counted, wiring):
    endorser, _ = _wired(world, counted, wiring)
    sp = _put(world)
    resp = endorser.process_proposal(sp)
    assert (resp.status, resp.message) == (200, "")
    creator = world.msps["Org1"].deserialize_identity(world.client.serialize())
    assert counted.items == [
        creator.verify_item(sp.proposal_bytes, sp.signature)]
    assert counted.calls == 1


@pytest.mark.parametrize("wiring", WIRINGS)
def test_tampered_signature_stops_before_filters_and_acl(
        world, counted, wiring):
    endorser, _ = _wired(world, counted, wiring)
    ran = []
    endorser.auth_filters = [lambda prop, creator: ran.append("filter")]
    if endorser.acl is not None:
        real = endorser.acl.check_acl
        endorser.acl.check_acl = lambda *a, **kw: (ran.append("acl"),
                                                   real(*a, **kw))
    resp = endorser.process_proposal(_tamper(_put(world)))
    assert (resp.status, resp.message) == (500, BAD_SIGNATURE)
    assert resp.endorsement is None and ran == []
    assert len(counted.items) == 1 and counted.calls == 1
    # the same endorser, a sound proposal: filter, then the gate
    assert endorser.process_proposal(_put(world)).status == 200
    assert ran == ["filter"] + ["acl"] * (endorser.acl is not None)


@pytest.mark.parametrize("wiring,message", [
    ("acl", "peer/Propose: signed data does not satisfy policy 'Propose'"),
    ("proposal_acl", "creator fails proposal ACL policy"),
])
def test_well_signed_creator_outside_the_policy(world, counted, wiring,
                                                message):
    endorser, _ = _wired(world, counted, wiring, admits_org1=False)
    resp = endorser.process_proposal(_put(world))
    assert (resp.status, resp.message) == (500, message)
    assert resp.endorsement is None
    # its signature was sound, verified once, and not asked about again
    assert len(counted.items) == 1 and counted.calls == 1


@pytest.mark.parametrize("case,message", [
    ("bad_signature", BAD_SIGNATURE),
    ("unknown_creator", "unknown or invalid creator identity"),
    ("wrong_channel", "proposal for channel 'other', serving 'ch'"),
])
@pytest.mark.parametrize("wiring", WIRINGS)
def test_rejections_a_client_sees_are_unchanged(world, counted, wiring, case,
                                                message):
    endorser, _ = _wired(world, counted, wiring)
    if case == "bad_signature":
        sp = _tamper(_put(world))
    elif case == "unknown_creator":
        sp = _put(world, DevOrg("Org1").new_identity("stranger"))
    else:
        sp = _put(world, channel="other")
    resp = endorser.process_proposal(sp)
    assert (resp.status, resp.message, resp.payload, resp.endorsement) == \
        (500, message, b"", None)
    # only a proposal that reached the signature check cost a verify
    assert counted.calls == (1 if case == "bad_signature" else 0)


def test_config_update_judges_the_next_proposal(world, counted):
    """`peer/Propose` tightened, then an org taken off the channel: each
    takes effect on the next proposal, and each proposal still costs one
    verify — the verdict is carried, the policy and the MSPs are not."""
    endorser, src = _wired(world, counted, "acl")
    admin = world.orgs[0].admin
    assert endorser.process_proposal(_put(world)).status == 200
    src.update(_bundle(world, "OR('Org1.admin')", sequence=1))
    resp = endorser.process_proposal(_put(world))
    assert (resp.status, resp.message) == (
        500, "peer/Propose: signed data does not satisfy policy 'Propose'")
    assert endorser.process_proposal(_put(world, admin)).status == 200
    # Org1 leaves the bundle; the endorser's own MSP table still knows it,
    # so the signature check passes and the bundle's MSPs decide
    src.update(_bundle(world, orgs=world.orgs[1:], sequence=2))
    resp = endorser.process_proposal(_put(world))
    assert (resp.status, resp.message) == (
        500, "peer/Propose: signed data does not satisfy policy 'Writers'")
    assert counted.calls == len(counted.items) == 4
