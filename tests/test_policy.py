"""Policy plane tests: DSL parsing, NOutOf semantics, verify-then-gate."""
import numpy as np
import pytest

from fabric_tpu.bccsp.factory import init_factories, FactoryOpts
from fabric_tpu.msp import Principal, CachedMSP
from fabric_tpu.msp.ca import DevOrg
from fabric_tpu.policy import (SignedData, PolicyError, parse_policy,
                               signed_by, n_out_of, PolicyEvaluator)


@pytest.fixture(scope="module", autouse=True)
def sw_provider():
    return init_factories(FactoryOpts(default="SW"))


@pytest.fixture(scope="module")
def world(sw_provider):
    org1, org2, org3 = DevOrg("Org1"), DevOrg("Org2"), DevOrg("Org3")
    msps = {o.mspid: CachedMSP(o.msp()) for o in (org1, org2, org3)}
    ev = PolicyEvaluator(msps, sw_provider)
    return org1, org2, org3, ev


def sd(ident, data=b"payload"):
    return SignedData(data, ident.serialize(), ident.sign(data))


def test_parse_policy_shapes():
    p = parse_policy("AND('Org1.member', 'Org2.member')")
    assert p.kind == "n_out_of" and p.n == 2 and len(p.rules) == 2
    p = parse_policy("OR('Org1.admin', 'Org2.member')")
    assert p.n == 1
    p = parse_policy("OutOf(2, 'Org1.member', 'Org2.member', 'Org3.member')")
    assert p.n == 2 and len(p.rules) == 3
    assert p.serialize() and p.deserialize(p.serialize()) == p
    for bad in ["", "XOR('a.b')", "AND()", "OutOf('x', 'Org1.member')",
                "'Org1.superuser'", "'no-dot'"]:
        with pytest.raises(PolicyError):
            parse_policy(bad)


def test_and_or_outof_evaluation(world):
    org1, org2, org3, ev = world
    u1, u2, u3 = (o.new_identity("u") for o in (org1, org2, org3))
    and_p = parse_policy("AND('Org1.member', 'Org2.member')")
    or_p = parse_policy("OR('Org1.member', 'Org2.member')")
    two_of = parse_policy("OutOf(2, 'Org1.member', 'Org2.member', 'Org3.member')")

    assert ev.evaluate_signed_data(and_p, [sd(u1), sd(u2)])
    assert not ev.evaluate_signed_data(and_p, [sd(u1)])
    assert ev.evaluate_signed_data(or_p, [sd(u2)])
    assert ev.evaluate_signed_data(two_of, [sd(u1), sd(u3)])
    assert not ev.evaluate_signed_data(two_of, [sd(u3)])


def test_bad_signature_excludes_but_not_fatal(world):
    org1, org2, _, ev = world
    u1, u2 = org1.new_identity("a"), org2.new_identity("b")
    or_p = parse_policy("OR('Org1.member', 'Org2.member')")
    good = sd(u2)
    forged = SignedData(b"payload", u1.serialize(), u1.sign(b"other data"))
    # forged sig excludes u1, but u2 still satisfies OR (policy.go:390-393)
    assert ev.evaluate_signed_data(or_p, [forged, good])
    and_p = parse_policy("AND('Org1.member', 'Org2.member')")
    assert not ev.evaluate_signed_data(and_p, [forged, good])


def test_dedup_same_identity_counted_once(world):
    org1, _, _, ev = world
    u1 = org1.new_identity("dup")
    p = parse_policy("AND('Org1.member', 'Org1.member')")
    # same identity twice: dedup (policy.go:385) + used-once (cauthdsl)
    assert not ev.evaluate_signed_data(p, [sd(u1), sd(u1)])
    u1b = org1.new_identity("dup2")
    assert ev.evaluate_signed_data(p, [sd(u1), sd(u1b)])


def test_admin_role(world):
    org1, _, _, ev = world
    p = parse_policy("OR('Org1.admin')")
    member = org1.new_identity("pleb")
    assert not ev.evaluate_signed_data(p, [sd(member)])
    assert ev.evaluate_signed_data(p, [sd(org1.admin)])


def test_foreign_and_garbage_identities_skipped(world):
    org1, _, _, ev = world
    evil = DevOrg("EvilOrg")
    e1 = evil.new_identity("eve")
    p = parse_policy("OR('Org1.member')")
    u1 = org1.new_identity("ok")
    assert ev.evaluate_signed_data(p, [sd(e1), sd(u1)])
    garbage = SignedData(b"payload", b"\x00\x01garbage", b"sig")
    assert ev.evaluate_signed_data(p, [garbage, sd(u1)])
    assert not ev.evaluate_signed_data(p, [garbage, sd(e1)])


def test_collect_gate_split(world):
    """The split API: collect -> batch_verify -> gate -> evaluate."""
    org1, org2, _, ev = world
    u1, u2 = org1.new_identity("c1"), org2.new_identity("c2")
    sds = [sd(u1), sd(u2), sd(u1)]  # dup identity collapses
    collected = ev.collect(sds)
    assert len(collected) == 2
    verdicts = ev.provider.batch_verify(collected.items)
    valid = ev.gate(collected, verdicts)
    assert len(valid) == 2
    assert ev.evaluate(parse_policy("AND('Org1.member','Org2.member')"), valid)


def test_or_consumes_all_branches_like_reference(world):
    """cauthdsl.go:44-58: NOutOf evaluates ALL rules and each satisfied
    branch consumes its identity.  AND(OR(Org1,Org2), Org2) with one Org1
    member and one Org2 member must FAIL: the OR consumes both."""
    org1, org2, _, ev = world
    u1, u2 = org1.new_identity("x1"), org2.new_identity("x2")
    p = parse_policy("AND(OR('Org1.member','Org2.member'), 'Org2.member')")
    assert not ev.evaluate_signed_data(p, [sd(u1), sd(u2)])
    # with a second Org2 member it passes
    u2b = org2.new_identity("x3")
    assert ev.evaluate_signed_data(p, [sd(u1), sd(u2), sd(u2b)])


# -- verdicts the caller already holds ---------------------------------------

def _held_cases(org1, org2):
    u1, u2 = org1.new_identity("h1"), org2.new_identity("h2")
    forged = SignedData(b"payload", u1.serialize(), u1.sign(b"other data"))
    return {
        "valid": [sd(u1), sd(u2)],
        "invalid": [forged, sd(u2)],
        "duplicated": [sd(u1), sd(u1), sd(u2)],
        "empty": [],
    }


@pytest.mark.parametrize("case", ["valid", "invalid", "duplicated", "empty"])
def test_held_verdicts_dispatch_nothing_and_gate_the_same(world, counting,
                                                          case):
    """`evaluate_signed_data(..., verified)` with a verdict for every
    collected item asks the provider nothing and decides as its own
    verify does, under a policy that needs every signer and one that
    needs any."""
    org1, org2, _, ev = world
    sds = _held_cases(org1, org2)[case]
    collected = ev.collect(sds)
    held = dict(zip(collected.items,
                    map(bool, ev.provider.batch_verify(collected.items))
                    if collected.items else ()))
    assert list(held.values()) == {"valid": [True, True],
                                   "invalid": [False, True],
                                   "duplicated": [True, True],
                                   "empty": []}[case]
    counted = counting(ev.provider)
    ev2 = PolicyEvaluator(ev.msps, counted)
    for expr in ("AND('Org1.member', 'Org2.member')",
                 "OR('Org1.member', 'Org2.member')"):
        p = parse_policy(expr)
        assert ev2.evaluate_signed_data(p, sds, held) \
            == ev.evaluate_signed_data(p, sds)
    assert counted.calls == 0 and counted.items == []


def test_item_the_held_verdicts_do_not_cover_is_verified(world, counting):
    """A held verdict answers only the item equal to it in scheme, key,
    signature and payload; any other collected item is dispatched, alone,
    and a held verdict of True for some other item admits nobody."""
    org1, org2, _, ev = world
    u1, u2 = org1.new_identity("p1"), org2.new_identity("p2")
    forged = SignedData(b"payload", u2.serialize(), u2.sign(b"other data"))
    and_p = parse_policy("AND('Org1.member', 'Org2.member')")
    counted = counting(ev.provider)
    ev2 = PolicyEvaluator(ev.msps, counted)
    s1, s2 = sd(u1), sd(u2)     # ECDSA signs with fresh randomness: sign once
    item1, item2 = ev.collect([s1, s2]).items
    assert ev2.evaluate_signed_data(and_p, [s1, s2], {item1: True})
    assert counted.items == [item2] and counted.calls == 1
    # the same signer over other bytes: another payload, so not covered
    other = ev.collect([sd(u1, b"other")]).items[0]
    assert other.pubkey == item1.pubkey and other != item1
    assert ev2.evaluate_signed_data(and_p, [s1, s2], {other: True})
    assert counted.items == [item2, item1, item2] and counted.calls == 2
    # a forged signature is found out here even beside a held True
    assert not ev2.evaluate_signed_data(and_p, [s1, forged], {item1: True})
    assert counted.calls == 3 and counted.items[-1].pubkey == item2.pubkey
    # and a held False is believed: nothing is asked, nobody admitted
    assert not ev2.evaluate_signed_data(and_p, [s1, s2],
                                        {item1: False, item2: True})
    assert counted.calls == 3
