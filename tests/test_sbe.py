"""Key-level (state-based) endorsement — validator_keylevel.go semantics.

Covers:
  - a key's validation parameter replaces the chaincode policy for txs
    writing that key (stricter AND looser directions),
  - keys without parameters still need the chaincode policy,
  - the policy transition takes effect for later blocks (committed
    metadata) AND for later txs in the same block when the updater tx is
    valid (intra-block ordering),
  - removing the parameter falls back to the chaincode policy.
"""
import pytest

from fabric_tpu.bccsp.factory import FactoryOpts, init_factories
from fabric_tpu.chaincode.stub import ChaincodeStub
from fabric_tpu.committer import sbe
from fabric_tpu.committer.committer import Committer
from fabric_tpu.committer.txvalidator import PolicyRegistry, TxValidator
from fabric_tpu.ledger import KVLedger
from fabric_tpu.ledger.statedb import StateDB
from fabric_tpu.msp import CachedMSP
from fabric_tpu.msp.ca import DevOrg
from fabric_tpu.policy import parse_policy
from fabric_tpu.protocol import build
from fabric_tpu.protocol.txflags import ValidationCode


@pytest.fixture(scope="module", autouse=True)
def provider():
    return init_factories(FactoryOpts(default="SW"))


@pytest.fixture()
def world(provider):
    o1, o2 = DevOrg("Org1"), DevOrg("Org2")
    msps = {"Org1": CachedMSP(o1.msp()), "Org2": CachedMSP(o2.msp())}
    ledger = KVLedger("ch")
    cc_policy = parse_policy("OR('Org1.member')")   # default: Org1 alone
    validator = TxValidator(
        "ch", msps, provider, PolicyRegistry(cc_policy),
        sbe_lookup=sbe.statedb_lookup(ledger.statedb))
    committer = Committer(ledger, validator)
    return o1, o2, committer, ledger


def tx(org_client, endorsers, writes=(), sbe_set=(), sbe_del=()):
    stub = ChaincodeStub(StateDB(), "cc", channel_id="ch")
    for k, v in writes:
        stub.put_state(k, v)
    for k, pol in sbe_set:
        stub.set_state_validation_parameter(k, pol)
    for k in sbe_del:
        stub.set_state_validation_parameter(k, None)
    return build.endorser_tx("ch", "cc", "1.0", stub.rwset(),
                             org_client.new_identity("client"),
                             endorsers)


def commit(committer, envs):
    lg = committer.ledger
    prev = (lg.blockstore.chain_info().current_hash
            if lg.height else b"\x00" * 32)
    return committer.store_block(build.new_block(lg.height, prev, envs))


def codes(result):
    return [int(c) for c in result.validation.flags.codes()]


def test_key_policy_overrides_and_transitions(world):
    o1, o2, committer, ledger = world
    e1 = [o1.new_identity("e1")]
    e2 = [o2.new_identity("e2")]
    both = parse_policy("AND('Org1.member','Org2.member')")

    # block 0: Org1 writes k normally (cc policy: Org1) + sets SBE=AND(both)
    r = commit(committer, [
        tx(o1, e1, writes=[("k", b"v0")], sbe_set=[("k", both)]),
    ])
    assert codes(r) == [ValidationCode.VALID]

    # block 1: Org1-only endorsement on k now FAILS (key policy overrides);
    # an Org1-only write to another key still passes (cc policy)
    r = commit(committer, [
        tx(o1, e1, writes=[("k", b"v1")]),
        tx(o1, e1, writes=[("other", b"x")]),
        tx(o1, e1 + e2, writes=[("k", b"v2")]),   # both orgs: satisfies SBE
    ])
    assert codes(r)[:2] == [ValidationCode.ENDORSEMENT_POLICY_FAILURE,
                            ValidationCode.VALID]
    # third tx writes the same key as tx 0 in this block: MVCC decides it,
    # but the ENDORSEMENT gate must pass; it can only be VALID or
    # MVCC_READ_CONFLICT, never ENDORSEMENT_POLICY_FAILURE
    assert codes(r)[2] != ValidationCode.ENDORSEMENT_POLICY_FAILURE


def test_same_block_transition(world):
    o1, o2, committer, ledger = world
    e1 = [o1.new_identity("e1")]
    org2_only = parse_policy("OR('Org2.member')")

    # one block: tx0 sets SBE(k2)=Org2; tx1 (Org1-endorsed) writes k2 ->
    # must FAIL under the NEW policy (intra-block transition); tx2
    # endorsed by Org2 writes k2 -> endorsement-valid
    r = commit(committer, [
        tx(o1, e1, sbe_set=[("k2", org2_only)]),
        tx(o1, e1, writes=[("k2", b"a")]),
        tx(o1, [o2.new_identity("e2")], writes=[("k2", b"b")]),
    ])
    c = codes(r)
    assert c[0] == ValidationCode.VALID
    assert c[1] == ValidationCode.ENDORSEMENT_POLICY_FAILURE
    assert c[2] != ValidationCode.ENDORSEMENT_POLICY_FAILURE


def test_delete_falls_back_to_cc_policy(world):
    o1, o2, committer, ledger = world
    e1 = [o1.new_identity("e1")]
    org2_only = parse_policy("OR('Org2.member')")
    r = commit(committer, [tx(o1, e1, sbe_set=[("k3", org2_only)])])
    assert codes(r) == [ValidationCode.VALID]
    r = commit(committer, [tx(o1, e1, writes=[("k3", b"x")])])
    assert codes(r) == [ValidationCode.ENDORSEMENT_POLICY_FAILURE]
    # Org2 removes the parameter; Org1 writes again under the cc policy
    r = commit(committer, [tx(o1, [o2.new_identity("e2")],
                              sbe_del=["k3"])])
    assert codes(r) == [ValidationCode.VALID]
    r = commit(committer, [tx(o1, e1, writes=[("k3", b"y")])])
    assert codes(r) == [ValidationCode.VALID]


def test_sbe_gated_by_channel_capability(provider):
    """A channel whose config lacks V1_3_KeyLevelEndorsement skips SBE
    deterministically: validation parameters become inert and keys fall
    back to the namespace policy (common/capabilities/application.go)."""
    from fabric_tpu.config import (
        Bundle, BundleSource, CAP_V2_0, ChannelConfig, OrgConfig,
        default_policies)

    o1, o2 = DevOrg("Org1"), DevOrg("Org2")

    def make_world(caps):
        orgs = []
        for o in (o1, o2):
            mc = o.msp_config()
            orgs.append(OrgConfig(mspid=o.mspid,
                                  root_certs=tuple(mc.root_certs_pem),
                                  admins=tuple(mc.admin_certs_pem)))
        cfg = ChannelConfig(channel_id="ch", sequence=0, orgs=tuple(orgs),
                            policies=default_policies(["Org1", "Org2"]),
                            capabilities=caps)
        src = BundleSource(Bundle(cfg))
        ledger = KVLedger("ch")
        validator = TxValidator(
            "ch", None, provider,
            PolicyRegistry(parse_policy("OR('Org1.member')")),
            bundle_source=src,
            sbe_lookup=sbe.statedb_lookup(ledger.statedb))
        return Committer(ledger, validator, bundle_source=src,
                         provider=provider)

    both = parse_policy("AND('Org1.member','Org2.member')")
    e1 = [o1.new_identity("e1")]

    # capability ON: the round-trip from test_key_policy_overrides
    com = make_world((CAP_V2_0, "V1_3_KeyLevelEndorsement"))
    r = commit(com, [tx(o1, e1, writes=[("k", b"v")], sbe_set=[("k", both)])])
    assert codes(r) == [ValidationCode.VALID]
    r = commit(com, [tx(o1, e1, writes=[("k", b"v1")])])
    assert codes(r) == [ValidationCode.ENDORSEMENT_POLICY_FAILURE]

    # capability OFF: the same sequence passes — the key policy is inert
    com = make_world((CAP_V2_0,))
    r = commit(com, [tx(o1, e1, writes=[("k", b"v")], sbe_set=[("k", both)])])
    assert codes(r) == [ValidationCode.VALID]
    r = commit(com, [tx(o1, e1, writes=[("k", b"v1")])])
    assert codes(r) == [ValidationCode.VALID]


def test_two_key_policies_one_tx_no_eval_cross_talk(world):
    """One tx writes TWO keys whose key-level policies differ (OR vs
    AND) under the SAME endorser set: each key must be judged by ITS
    policy.  Regression for the gate's per-block evaluation memo: a
    fresh-decoded policy object freed between checks could have its
    id() reused by the next policy, letting the first verdict answer
    for the second — SbeOverlay now interns decoded policies per block
    so identity keys are stable."""
    o1, o2, committer, ledger = world
    e1 = [o1.new_identity("e1")]
    loose = parse_policy("OR('Org1.member')")
    strict = parse_policy("AND('Org1.member','Org2.member')")

    r = commit(committer, [
        tx(o1, e1, writes=[("ka", b"v"), ("kb", b"v")],
           sbe_set=[("ka", loose), ("kb", strict)]),
    ])
    assert codes(r) == [ValidationCode.VALID]

    # Org1-only endorsement: ka's OR policy passes, kb's AND policy
    # must FAIL the tx — if the loose verdict leaked into kb's check
    # the tx would wrongly be VALID (key-level endorsement bypass)
    r = commit(committer, [
        tx(o1, e1, writes=[("ka", b"v1"), ("kb", b"v1")]),
        tx(o1, e1, writes=[("ka", b"v2")]),            # loose key alone: ok
    ])
    assert codes(r)[0] == ValidationCode.ENDORSEMENT_POLICY_FAILURE
    assert codes(r)[1] != ValidationCode.ENDORSEMENT_POLICY_FAILURE


# -- which tail a block takes (txvalidator._tail_of) -------------------------
#
# A validator built AS node/peer.py BUILDS IT — the committed-parameter
# lookup and, beside it, the state's own count of parameters — takes the
# deep C tail for every block key-level endorsement cannot touch and the
# classic one from the first parameter on.  `validator_tail_total` says
# which, in transactions, and why.

def tail_counts():
    from fabric_tpu.ops_plane import registry
    c = registry.counter("validator_tail_total")
    pairs = [("deep", "no_sbe")] + [("classic", r) for r in (
        "state_meta", "block_meta", "inflight_meta", "no_native", "forced")]
    return {p: c.value(channel="ch", tail=p[0], reason=p[1]) for p in pairs}


def moved(before):
    """{(tail, reason): transactions} that moved since `before`."""
    return {p: int(v - before[p]) for p, v in tail_counts().items()
            if v != before[p]}


def node_world(provider, ledger=None, bundle_source=None, state=True,
               orgs=None):
    o1, o2 = orgs or (DevOrg("Org1"), DevOrg("Org2"))
    msps = (None if bundle_source is not None else
            {"Org1": CachedMSP(o1.msp()), "Org2": CachedMSP(o2.msp())})
    ledger = ledger or KVLedger("ch")
    validator = TxValidator(
        "ch", msps, provider, PolicyRegistry(parse_policy("OR('Org1.member')")),
        bundle_source=bundle_source,
        sbe_lookup=sbe.statedb_lookup(ledger.statedb),
        sbe_state=ledger.statedb.meta_keys if state else None)
    return o1, o2, Committer(ledger, validator, bundle_source=bundle_source,
                             provider=provider if bundle_source else None)


def commit_counted(committer, envs):
    """Commit one block; -> (its codes, what `validator_tail_total`
    moved by): deep + classic transactions = the block's tx count."""
    before = tail_counts()
    r = commit(committer, envs)
    by_tail = moved(before)
    assert sum(by_tail.values()) == len(envs), by_tail
    return codes(r), by_tail


ORG2_ONLY = "OR('Org2.member')"


def _plain_blocks_take_the_deep_tail(provider, tmp_path):
    o1, o2, com = node_world(provider)
    e1 = [o1.new_identity("e1")]
    c, by_tail = commit_counted(com, [
        tx(o1, e1, writes=[("a", b"1")]),
        tx(o1, [o2.new_identity("e2")], writes=[("b", b"1")]),   # cc: Org1
        tx(o1, e1, writes=[("c", b"1")])])
    assert c == [ValidationCode.VALID,
                 ValidationCode.ENDORSEMENT_POLICY_FAILURE,
                 ValidationCode.VALID]
    assert by_tail == {("deep", "no_sbe"): 3}
    assert com.ledger.statedb.meta_keys() == (0, 0)


def _parameter_set_in_the_block_then_in_state(provider, tmp_path):
    o1, o2, com = node_world(provider)
    e1 = [o1.new_identity("e1")]
    # tx 0 sets the parameter of k2; tx 1, Org1-endorsed, writes k2 and is
    # judged under it; tx 2, Org2-endorsed, passes the gate
    c, by_tail = commit_counted(com, [
        tx(o1, e1, sbe_set=[("k2", parse_policy(ORG2_ONLY))]),
        tx(o1, e1, writes=[("k2", b"a")]),
        tx(o1, [o2.new_identity("e2")], writes=[("k2", b"b")])])
    assert c[:2] == [ValidationCode.VALID,
                     ValidationCode.ENDORSEMENT_POLICY_FAILURE]
    assert c[2] != ValidationCode.ENDORSEMENT_POLICY_FAILURE
    assert by_tail == {("classic", "block_meta"): 3}
    # the next block: the parameter is committed state
    assert com.ledger.statedb.meta_keys() == (0, 1)
    c, by_tail = commit_counted(com, [
        tx(o1, e1, writes=[("k2", b"c")]),
        tx(o1, e1, writes=[("other", b"x")])])
    assert c == [ValidationCode.ENDORSEMENT_POLICY_FAILURE,
                 ValidationCode.VALID]
    assert by_tail == {("classic", "state_meta"): 2}


def _reopened_ledger_with_a_parameter(provider, tmp_path, source="wal"):
    from fabric_tpu.ledger import LedgerConfig
    root = str(tmp_path / "ledger")
    o1, o2, com = node_world(
        provider, KVLedger("ch", LedgerConfig(root=root)))
    e1 = [o1.new_identity("e1")]
    commit(com, [tx(o1, e1, writes=[("k", b"0")],
                    sbe_set=[("k", parse_policy(ORG2_ONLY))])])
    if source == "checkpoint":
        assert com.ledger.statedb.checkpoint() is not None
    ledger = KVLedger("ch", LedgerConfig(root=root))
    assert ledger.statedb.last_recovery["source"] != "fresh"
    assert ledger.statedb.last_recovery["wal_blocks"] == (source == "wal")
    assert ledger.statedb.meta_keys() == (0, 1)
    # a fresh validator, no block seen: the state's count alone decides
    _o1, _o2, com2 = node_world(provider, ledger, orgs=(o1, o2))
    c, by_tail = commit_counted(com2, [tx(o1, e1, writes=[("k", b"1")])])
    assert c == [ValidationCode.ENDORSEMENT_POLICY_FAILURE]
    assert by_tail == {("classic", "state_meta"): 1}


def _parameter_deleted_and_deep_again(provider, tmp_path):
    o1, o2, com = node_world(provider)
    e1, e2 = [o1.new_identity("e1")], [o2.new_identity("e2")]
    _, by_tail = commit_counted(com, [
        tx(o1, e1, sbe_set=[("k3", parse_policy(ORG2_ONLY))])])
    assert by_tail == {("classic", "block_meta"): 1}
    # Org2 removes it: judged under the parameter, on the classic tail
    c, by_tail = commit_counted(com, [tx(o1, e2, sbe_del=["k3"])])
    assert c == [ValidationCode.VALID]
    assert by_tail == {("classic", "state_meta"): 1}
    assert com.ledger.statedb.meta_keys() == (1, 0)
    c, by_tail = commit_counted(com, [tx(o1, e1, writes=[("k3", b"y")])])
    assert c == [ValidationCode.VALID]
    assert by_tail == {("deep", "no_sbe"): 1}


def _begun_before_the_parameters_block_commits(provider, tmp_path):
    from fabric_tpu.protocol import block_header_hash
    o1, o2, com = node_world(provider)
    v, ledger = com.validator, com.ledger
    e1 = [o1.new_identity("e1")]
    b0 = build.new_block(0, b"\x00" * 32, [
        tx(o1, e1, sbe_set=[("k4", parse_policy(ORG2_ONLY))])])
    b1 = build.new_block(1, block_header_hash(b0.header), [
        tx(o1, e1, writes=[("k4", b"x")]), tx(o1, e1, writes=[("z", b"x")])])
    before = tail_counts()
    s0 = v.validate_begin(b0)
    s1 = v.validate_begin(b1)          # block 0 validated, not committed
    assert moved(before) == {("classic", "block_meta"): 1,
                             ("classic", "inflight_meta"): 2}
    assert not s0.get("deep") and not s1.get("deep")
    v.validate_finish(s0)
    ledger.commit(b0)
    # begun again once block 0 is state: the count answers, and says yes
    before = tail_counts()
    r1 = v.validate(b1)
    assert moved(before) == {("classic", "state_meta"): 2}
    assert [int(c) for c in r1.flags.codes()] == [
        ValidationCode.ENDORSEMENT_POLICY_FAILURE, ValidationCode.VALID]
    v.validate_finish(s1)


def _no_capability_deep_with_parameters_in_state(provider, tmp_path):
    """On a channel without V1_3_KeyLevelEndorsement a parameter is inert:
    the deep tail even with `#meta` keys in state and in the block, and
    the flags the classic tail gives (taken here by force)."""
    from fabric_tpu.config import (
        Bundle, BundleSource, CAP_V2_0, ChannelConfig, OrgConfig,
        default_policies)
    orgs = DevOrg("Org1"), DevOrg("Org2")
    cfg = ChannelConfig(
        channel_id="ch", sequence=0, capabilities=(CAP_V2_0,),
        policies=default_policies(["Org1", "Org2"]),
        orgs=tuple(OrgConfig(mspid=o.mspid,
                             root_certs=tuple(o.msp_config().root_certs_pem),
                             admins=tuple(o.msp_config().admin_certs_pem))
                   for o in orgs))
    o1, o2 = orgs
    e1 = [o1.new_identity("e1")]
    blocks = [
        [tx(o1, e1, writes=[("k", b"v")],
            sbe_set=[("k", parse_policy(ORG2_ONLY))]),
         tx(o1, e1, writes=[("k", b"w")])],
        [tx(o1, e1, writes=[("k", b"v1")]),
         tx(o1, [o2.new_identity("e2")], writes=[("j", b"v1")])]]
    got = {}
    for forced in (False, True):
        _, _, com = node_world(provider, orgs=orgs,
                               bundle_source=BundleSource(Bundle(cfg)))
        com.validator.force_python_collect = forced
        got[forced] = [commit_counted(com, envs) for envs in blocks]
        assert com.ledger.statedb.meta_keys() == (1, 1)
    assert [t for _, t in got[False]] == [{("deep", "no_sbe"): 2}] * 2
    assert [t for _, t in got[True]] == [{("classic", "forced"): 2}] * 2
    assert [c for c, _ in got[False]] == [c for c, _ in got[True]]
    assert got[False][1][0] == [ValidationCode.VALID,
                                ValidationCode.ENDORSEMENT_POLICY_FAILURE]


def _lookup_alone_stays_classic(provider, tmp_path):
    """A validator handed the lookup and not the state's question cannot
    know that no key has a parameter: classic, as before the rule."""
    o1, _o2, com = node_world(provider, state=False)
    _, by_tail = commit_counted(com, [
        tx(o1, [o1.new_identity("e1")], writes=[("a", b"1")])])
    assert by_tail == {("classic", "state_meta"): 1}
    com.validator.sbe_lookup = None
    _, by_tail = commit_counted(com, [
        tx(o1, [o1.new_identity("e1")], writes=[("a", b"2")])])
    assert by_tail == {("deep", "no_sbe"): 1}


def _forced_and_no_native_are_counted(provider, tmp_path):
    from fabric_tpu.committer import txvalidator as tv
    o1, _o2, com = node_world(provider)
    e1 = [o1.new_identity("e1")]
    com.validator.force_python_collect = True
    _, by_tail = commit_counted(com, [tx(o1, e1, writes=[("a", b"1")])])
    assert by_tail == {("classic", "forced"): 1}
    com.validator.force_python_collect = False
    real, tv._fastcollect = tv._fastcollect, None
    try:
        _, by_tail = commit_counted(com, [tx(o1, e1, writes=[("a", b"2")])])
    finally:
        tv._fastcollect = real
    assert by_tail == {("classic", "no_native"): 1}


TAIL_SCENARIOS = {
    "plain_blocks_deep": _plain_blocks_take_the_deep_tail,
    "set_in_block_then_in_state": _parameter_set_in_the_block_then_in_state,
    "reopened_from_wal": _reopened_ledger_with_a_parameter,
    "reopened_from_checkpoint": lambda p, t: _reopened_ledger_with_a_parameter(
        p, t, source="checkpoint"),
    "deleted_then_deep_again": _parameter_deleted_and_deep_again,
    "begun_before_commit": _begun_before_the_parameters_block_commits,
    "no_capability_deep": _no_capability_deep_with_parameters_in_state,
    "lookup_alone_classic": _lookup_alone_stays_classic,
    "forced_and_no_native": _forced_and_no_native_are_counted,
}


@pytest.mark.parametrize("scenario", sorted(TAIL_SCENARIOS))
def test_tail_is_read_off_the_state_and_the_block(scenario, provider,
                                                  tmp_path):
    from fabric_tpu.committer import txvalidator as tv
    if tv._fastcollect is None or not hasattr(tv._fastcollect, "digest"):
        pytest.skip("deep native tail unavailable")
    TAIL_SCENARIOS[scenario](provider, tmp_path)
