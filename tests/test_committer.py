"""Verify-then-gate block validation + end-to-end commit pipeline."""
import numpy as np
import pytest

from fabric_tpu.bccsp.factory import init_factories, FactoryOpts
from fabric_tpu.committer import Committer, PolicyRegistry, TxValidator
from fabric_tpu.ledger import KVLedger, LedgerConfig
from fabric_tpu.msp import CachedMSP
from fabric_tpu.msp.ca import DevOrg
from fabric_tpu.policy import parse_policy
from fabric_tpu.protocol import (Envelope, KVRead, KVWrite, NsRwSet, TxFlags,
                                 TxRwSet, ValidationCode, Version)
from fabric_tpu.protocol import build
from fabric_tpu.protocol.types import META_TXFLAGS


@pytest.fixture(scope="module", autouse=True)
def sw_provider():
    return init_factories(FactoryOpts(default="SW"))


@pytest.fixture()
def world(sw_provider):
    org1, org2 = DevOrg("Org1"), DevOrg("Org2")
    msps = {o.mspid: CachedMSP(o.msp()) for o in (org1, org2)}
    policies = PolicyRegistry()
    policies.set_policy("cc", parse_policy("AND('Org1.member', 'Org2.member')"))
    ledger = KVLedger("ch", LedgerConfig())
    validator = TxValidator("ch", msps, sw_provider, policies)
    return org1, org2, Committer(ledger, validator)


def rw(reads=(), writes=(), ns="cc"):
    return TxRwSet((NsRwSet(ns, reads=tuple(reads), writes=tuple(writes)),))


def make_tx(org1, org2, rwset, endorsers=None, creator=None):
    endorsers = endorsers or [org1.new_identity("e1"), org2.new_identity("e2")]
    return build.endorser_tx("ch", "cc", "1.0", rwset,
                             creator or org1.new_identity("client"), endorsers)


def next_block(committer, envs):
    lg = committer.ledger
    prev = (lg.blockstore.chain_info().current_hash
            if lg.height else b"\x00" * 32)
    return build.new_block(lg.height, prev, envs)


def test_happy_path_commit(world):
    org1, org2, committer = world
    envs = [make_tx(org1, org2, rw(writes=[KVWrite(f"k{i}", b"v")]))
            for i in range(5)]
    block = next_block(committer, envs)
    res = committer.store_block(block)
    assert res.validation.flags.valid_count() == 5
    assert res.validation.n_unique_items > 0
    assert committer.ledger.get_state("cc", "k3") == b"v"


def test_policy_failure_and_bad_sigs(world):
    org1, org2, committer = world
    good = make_tx(org1, org2, rw(writes=[KVWrite("a", b"1")]))
    # only Org1 endorses an AND(Org1,Org2) policy -> policy failure
    only1 = make_tx(org1, org2, rw(writes=[KVWrite("b", b"1")]),
                    endorsers=[org1.new_identity("e")])
    # corrupt creator signature
    bad_creator = make_tx(org1, org2, rw(writes=[KVWrite("c", b"1")]))
    bad_creator = Envelope(bad_creator.payload,
                           bad_creator.signature[:-2] + b"\x00\x01")
    block = next_block(committer, [good, only1, bad_creator])
    res = committer.store_block(block)
    assert res.validation.flags.codes() == [
        int(ValidationCode.VALID),
        int(ValidationCode.ENDORSEMENT_POLICY_FAILURE),
        int(ValidationCode.BAD_CREATOR_SIGNATURE)]
    assert committer.ledger.get_state("cc", "a") == b"1"
    assert committer.ledger.get_state("cc", "b") is None


def test_tampered_endorsement_excluded_not_fatal(world):
    """A bad endorsement signature only excludes that identity
    (policy.go:390-393) — OR policies still pass via the good one."""
    org1, org2, committer = world
    committer.validator.policies.set_policy(
        "cc", parse_policy("OR('Org1.member', 'Org2.member')"))
    env = make_tx(org1, org2, rw(writes=[KVWrite("x", b"1")]))
    # tamper org2's endorsement signature in-place
    from fabric_tpu.protocol import Transaction
    payload = env.payload_dict()
    tx = payload["data"]
    e2 = tx["actions"][0]["endorsements"][1]
    e2["signature"] = e2["signature"][:-2] + b"\x00\x01"
    from fabric_tpu.utils import serde
    # rebuild envelope with same creator signature -> creator sig now stale;
    # instead re-sign with the original creator to isolate the endorsement
    creator = org1.new_identity("fresh")
    env2 = build.signed_envelope("endorser_transaction", "ch", tx, creator)
    block = next_block(committer, [env2])
    res = committer.store_block(block)
    assert res.validation.flags.is_valid(0)


def test_duplicate_txid_within_block_and_ledger(world):
    org1, org2, committer = world
    env = make_tx(org1, org2, rw(writes=[KVWrite("d", b"1")]))
    block = next_block(committer, [env, env])
    res = committer.store_block(block)
    assert res.validation.flags.codes() == [
        int(ValidationCode.VALID), int(ValidationCode.DUPLICATE_TXID)]
    # replaying the same tx in a later block: duplicate against the ledger
    block2 = next_block(committer, [env])
    res2 = committer.store_block(block2)
    assert res2.validation.flags.codes() == [int(ValidationCode.DUPLICATE_TXID)]


def test_mvcc_after_gate(world):
    org1, org2, committer = world
    setup = make_tx(org1, org2, rw(writes=[KVWrite("m", b"v0")]))
    committer.store_block(next_block(committer, [setup]))
    v = Version(0, 0)
    t1 = make_tx(org1, org2, rw(reads=[KVRead("m", v)],
                                writes=[KVWrite("m", b"v1")]))
    t2 = make_tx(org1, org2, rw(reads=[KVRead("m", v)],
                                writes=[KVWrite("m", b"v2")]))
    res = committer.store_block(next_block(committer, [t1, t2]))
    assert res.validation.flags.valid_count() == 2  # sig/policy pass
    final = TxFlags.from_bytes(
        committer.ledger.blockstore.get_by_number(1)
        .metadata.items[META_TXFLAGS])
    assert final.codes() == [int(ValidationCode.VALID),
                             int(ValidationCode.MVCC_READ_CONFLICT)]
    assert committer.ledger.get_state("cc", "m") == b"v1"


def test_structural_rejects(world):
    org1, org2, committer = world
    good = make_tx(org1, org2, rw(writes=[KVWrite("s", b"1")]))
    garbage = b"\xde\xad\xbe\xef"
    wrong_channel = build.endorser_tx(
        "other-ch", "cc", "1.0", rw(), org1.new_identity("c"),
        [org1.new_identity("e")])
    block = next_block(committer, [good])
    block.data.append(garbage)
    block.data.append(wrong_channel.serialize())
    res = committer.store_block(block)
    assert res.validation.flags.codes() == [
        int(ValidationCode.VALID),
        int(ValidationCode.BAD_PAYLOAD),
        int(ValidationCode.TARGET_CHAIN_NOT_FOUND)]


def test_unknown_namespace_policy_rejected(world):
    org1, org2, committer = world
    committer.validator.policies = PolicyRegistry()  # no default, no entries
    committer.validator.policies.set_policy(
        "cc", parse_policy("OR('Org1.member')"))
    env = make_tx(org1, org2, rw(writes=[KVWrite("q", b"1")], ns="unknown_ns"))
    res = committer.store_block(next_block(committer, [env]))
    assert res.validation.flags.codes() == [
        int(ValidationCode.INVALID_CHAINCODE)]


# -- commit-time config-tx validation (ADVICE r2: unauthorized config txs
# must be recorded INVALID, never committed as VALID) ------------------------

def _config_world(sw_provider, tmp_path):
    from fabric_tpu.config import (Bundle, BundleSource, ChannelConfig,
                                   OrgConfig, default_policies)
    org1 = DevOrg("Org1")
    mc = org1.msp_config()
    cfg0 = ChannelConfig(
        channel_id="ch", sequence=0,
        orgs=(OrgConfig(mspid="Org1", root_certs=tuple(mc.root_certs_pem),
                        admins=tuple(mc.admin_certs_pem)),),
        policies=default_policies(["Org1"]))
    src = BundleSource(Bundle(cfg0))
    policies = PolicyRegistry(parse_policy("OR('Org1.member')"))
    ledger = KVLedger("ch", LedgerConfig(root=str(tmp_path)))
    validator = TxValidator("ch", None, sw_provider, policies,
                            bundle_source=src)
    committer = Committer(ledger, validator, bundle_source=src,
                          provider=sw_provider)
    return org1, cfg0, src, committer


def _new_cfg(org1, cfg0, sequence):
    from dataclasses import replace
    return replace(cfg0, sequence=sequence)


def test_unauthorized_config_tx_flagged_invalid_at_commit(sw_provider,
                                                          tmp_path):
    from fabric_tpu.config import build_config_envelope
    org1, cfg0, src, committer = _config_world(sw_provider, tmp_path)

    # wrong sequence (5 != 1): must be committed INVALID, bundle unchanged
    bad = build_config_envelope(_new_cfg(org1, cfg0, 5), [org1.admin])
    res = committer.store_block(next_block(committer, [bad]))
    assert not res.final_flags.is_valid(0)
    assert (res.final_flags.flag(0)
            == ValidationCode.INVALID_CONFIG_TRANSACTION)
    assert src.current().sequence == 0

    # non-admin signer: Admins policy unsatisfied -> INVALID
    member_signed = build_config_envelope(_new_cfg(org1, cfg0, 1),
                                          [org1.new_identity("m")])
    res = committer.store_block(next_block(committer, [member_signed]))
    assert (res.final_flags.flag(0)
            == ValidationCode.INVALID_CONFIG_TRANSACTION)
    assert src.current().sequence == 0

    # a correct update still applies
    good = build_config_envelope(_new_cfg(org1, cfg0, 1), [org1.admin])
    res = committer.store_block(next_block(committer, [good]))
    assert res.final_flags.is_valid(0)
    assert src.current().sequence == 1


def test_config_tx_in_multi_tx_block_invalid(sw_provider, tmp_path):
    """A config tx smuggled into a multi-tx block by a byzantine orderer is
    flagged invalid outright (config txs must ride alone)."""
    from fabric_tpu.config import build_config_envelope
    org1, cfg0, src, committer = _config_world(sw_provider, tmp_path)

    normal = build.endorser_tx("ch", "cc", "1.0",
                               rw(writes=[KVWrite("k", b"v")]),
                               org1.new_identity("client"),
                               [org1.new_identity("e1")])
    cfg_env = build_config_envelope(_new_cfg(org1, cfg0, 1), [org1.admin])
    res = committer.store_block(next_block(committer, [normal, cfg_env]))
    assert res.final_flags.is_valid(0)
    assert (res.final_flags.flag(1)
            == ValidationCode.INVALID_CONFIG_TRANSACTION)
    assert src.current().sequence == 0


def test_config_block_replay_keeps_valid_flags(sw_provider, tmp_path):
    """A peer bootstrapped at a later config catching up through an old
    config block must NOT re-judge it against the current bundle (that
    would permanently flag a historically-valid config tx invalid)."""
    from fabric_tpu.config import build_config_envelope
    org1, cfg0, src, committer = _config_world(sw_provider, tmp_path / "a")
    good = build_config_envelope(_new_cfg(org1, cfg0, 1), [org1.admin])
    block = next_block(committer, [good])
    res = committer.store_block(block)
    assert res.final_flags.is_valid(0) and src.current().sequence == 1

    # fresh peer provisioned directly at sequence 1 replays the chain
    org1b, cfg0b, src2, committer2 = _config_world(sw_provider,
                                                   tmp_path / "b")
    from fabric_tpu.config import Bundle
    src2.update(Bundle(_new_cfg(org1, cfg0, 1)))
    import dataclasses
    replay = dataclasses.replace(block)
    res2 = committer2.store_block(replay)
    assert res2.final_flags.is_valid(0)          # flags match the tip peer
    assert src2.current().sequence == 1          # nothing re-applied


def test_fast_collect_differential(world):
    """C pass-1 (native/fastcollect.c) vs pure-Python pass-1: identical
    flags and identical deduplicated item sets over a block mixing valid
    txs, structural rejects, duplicates, meta writes, and foreign-org
    endorsements."""
    from fabric_tpu.committer.txvalidator import _fastcollect
    if _fastcollect is None:
        pytest.skip("native fastcollect unavailable")
    org1, org2, committer = world
    v = committer.validator
    v.policies.set_policy("cc", parse_policy(
        "OR('Org1.member', 'Org2.member')"))
    envs = []
    for i in range(40):
        rwset = TxRwSet((
            NsRwSet("cc", reads=(KVRead("r", Version(0, 1)),),
                    writes=(KVWrite(f"k{i}", b"v"),)),
            NsRwSet("cc#meta",
                    writes=(KVWrite(f"k{i}", b"POL", i % 3 == 0),))))
        env = make_tx(org1, org2, rwset)
        raw = env.serialize()
        kind = i % 8
        if kind == 1:
            raw = raw[:-3]
        elif kind == 2:
            raw = b""
        elif kind == 3:
            raw = make_tx(org1, org2, rwset,
                          creator=org2.new_identity("c2")).serialize()
        elif kind == 5 and i > 8:
            raw = envs[i - 8]
        envs.append(raw)
    from fabric_tpu.protocol.types import Block, BlockHeader, BlockMetadata

    def run(force_py):
        v.force_python_collect = force_py
        blk = Block(BlockHeader(9, b"p", b"d"), list(envs), BlockMetadata())
        vr = v.validate(blk)
        return vr.flags.codes(), vr.n_unique_items

    try:
        fast = run(False)
        slow = run(True)
    finally:
        v.force_python_collect = False
    assert fast == slow


def test_fast_collect_late_error_parity_and_deep_nesting(world):
    """Post-registration failures (unknown type, nil action, late
    malformed body) must register their txid BEFORE flagging on BOTH
    collect paths — otherwise C-path and fallback peers produce
    divergent DUPLICATE_TXID bitmaps.  Also: a deeply nested envelope
    (C-stack attack) degrades to BAD_PAYLOAD, never a crash."""
    from fabric_tpu.committer.txvalidator import _fastcollect
    if _fastcollect is None:
        pytest.skip("native fastcollect unavailable")
    from fabric_tpu.protocol.types import Block, BlockHeader, BlockMetadata
    from fabric_tpu.utils import serde

    org1, org2, committer = world
    v = committer.validator
    creator = org1.new_identity("late")
    nonce = b"fixed-nonce-late"
    env_unknown = build.signed_envelope("weird_type", "ch", {"x": b"y"},
                                        creator, nonce=nonce)
    env_dup = make_tx(org1, org2, rw(writes=[KVWrite("lk", b"v")]),
                      creator=creator)
    # same (nonce, creator) => same txid as env_unknown
    env_dup2 = build.signed_envelope(
        "endorser_transaction", "ch",
        env_dup.payload_dict()["data"], creator, nonce=nonce)
    deep = (b"L" + (1).to_bytes(4, "big")) * 60000 + b"N"
    evil = serde.encode({"payload": deep, "signature": b"s"})
    envs = [env_unknown.serialize(), env_dup2.serialize(), evil]

    def run(force_py):
        v.force_python_collect = force_py
        blk = Block(BlockHeader(7, b"p", b"d"), list(envs),
                    BlockMetadata())
        return v.validate(blk).flags.codes()

    try:
        fast = run(False)
        slow = run(True)
    finally:
        v.force_python_collect = False
    assert fast == slow
    assert fast[0] == int(ValidationCode.UNKNOWN_TX_TYPE)
    assert fast[1] == int(ValidationCode.DUPLICATE_TXID)
    assert fast[2] == int(ValidationCode.BAD_PAYLOAD)


# transactions a fuzzed block: 330-390 unique verify items (`run`
# asserts it), more than the validator's PROBE, so that the deep tail
# hands its signature table over as arrays — the path the fuzz must
# compare
FUZZ_TXS = 400


class _NoDigest:
    """Hide `digest` so the validator takes the classic
    C-walker + Python-tail path."""
    def __init__(self, mod):
        self._mod = mod

    def __getattr__(self, name):
        if name == "digest":
            raise AttributeError(name)
        return getattr(self._mod, name)


def _fuzz_kit(world, handed_over):
    """(corpus, run, dup_raw) of the state-fork fuzz: randomized
    adversarial corpora — intra-block txid collisions, carry collisions
    across PIPELINED blocks, ledger-oracle duplicates, unknown-org
    creators, config txs, wrong-channel headers, and non-canonical
    envelope bytes (truncations, junk, bitflips) — and `run(mode, ...)`,
    which validates two pipelined blocks of them with a validator built
    for one of four modes: `deep` (no key-level lookup), `classic` (the
    C walker + the Python tail), `python` (the pure-Python mirror),
    `node` (built as node/peer.py builds it: the lookup and the state's
    question, over an empty state)."""
    from fabric_tpu.committer import sbe, txvalidator as tv
    if tv._fastcollect is None or not hasattr(tv._fastcollect, "digest"):
        pytest.skip("deep native tail unavailable")
    from fabric_tpu.bccsp.factory import get_default
    from fabric_tpu.ledger.statedb import StateDB
    from fabric_tpu.protocol.types import Block, BlockHeader, BlockMetadata

    org1, org2, _committer = world
    stranger = DevOrg("OrgX")        # mspid absent from the validator MSPs
    provider = get_default()
    msps = {o.mspid: CachedMSP(o.msp()) for o in (org1, org2)}
    policies = PolicyRegistry()
    policies.set_policy("cc", parse_policy(
        "OR('Org1.member', 'Org2.member')"))

    # one tx whose txid the "ledger" already holds (oracle duplicate)
    led_nonce = b"oracle-nonce-0001"
    led_creator = org1.new_identity("led")
    led_txid = build.compute_txid(led_nonce, led_creator.serialize())
    led_raw = build.endorser_tx(
        "ch", "cc", "1.0", rw(writes=[KVWrite("led", b"1")]), led_creator,
        [org1.new_identity("e1"), org2.new_identity("e2")],
        nonce=led_nonce).serialize()

    def corpus(rng, n=FUZZ_TXS):
        raws = []
        for _ in range(n):
            kind = rng.randrange(10)
            if kind == 0 and raws:
                raws.append(rng.choice(raws))          # intra-block dup
                continue
            creator = (stranger.new_identity("ghost") if kind == 1 else
                       (org1 if rng.random() < 0.5 else
                        org2).new_identity("c"))
            if kind == 6:
                raws.append(build.signed_envelope(
                    "config", "ch", {"config": {"sequence": 1}},
                    creator).serialize())
                continue
            ends = ([org1.new_identity("e1")] if kind == 2 else
                    [org1.new_identity("e1"), org2.new_identity("e2")])
            chan = "other" if kind == 7 else "ch"
            rwset = rw(writes=[KVWrite(f"k{rng.random()}", b"v")])
            raw = build.endorser_tx(chan, "cc", "1.0", rwset, creator,
                                    ends).serialize()
            if kind == 3 and len(raw) > 4:
                raw = raw[:rng.randrange(1, len(raw))]  # truncated
            elif kind == 4:
                raw = rng.randbytes(rng.randrange(0, 48))   # junk
            elif kind == 5:
                mut = bytearray(raw)
                mut[rng.randrange(len(mut))] ^= 0xFF        # bitflip
                raw = bytes(mut)
            raws.append(raw)
        return raws

    def run(mode, b1raws, b2raws, dup_raw):
        wiring = {}
        if mode == "node":
            state = StateDB()
            wiring = dict(sbe_lookup=sbe.statedb_lookup(state),
                          sbe_state=state.meta_keys)
        v = TxValidator("ch", msps, provider, policies,
                        ledger_has_txid=lambda t: t == led_txid, **wiring)
        real = tv._fastcollect
        if mode == "python":
            v.force_python_collect = True
        elif mode == "classic":
            tv._fastcollect = _NoDigest(real)
        try:
            b1 = Block(BlockHeader(5, b"p", b"d"),
                       list(b1raws) + [dup_raw], BlockMetadata())
            b2 = Block(BlockHeader(6, b"p", b"d"),
                       list(b2raws) + [dup_raw, led_raw], BlockMetadata())
            before = handed_over()
            s1 = v.validate_begin(b1)
            s2 = v.validate_begin(b2)   # pipelined: b1 carry, not ledger
            assert (bool(s1.get("deep")) == bool(s2.get("deep"))
                    == (mode in ("deep", "node")))
            r1 = v.validate_finish(s1)
            r2 = v.validate_finish(s2)
            # per block arrays + items = its unique items; a deep block
            # above PROBE goes as arrays, every other as items
            moved = handed_over(before)
            n_unique = r1.n_unique_items + r2.n_unique_items
            assert sum(moved.values()) == n_unique
            assert min(r1.n_unique_items, r2.n_unique_items) > tv.PROBE
            assert moved == ({("arrays", "bypassed"): n_unique}
                             if mode in ("deep", "node") else
                             {("items", "classic_tail"): n_unique})
            return (r1.flags.codes(), r2.flags.codes(),
                    r1.n_unique_items, r2.n_unique_items)
        finally:
            tv._fastcollect = real

    def dup_raw(seed):
        return build.endorser_tx(
            "ch", "cc", "1.0", rw(writes=[KVWrite("dup", b"1")]),
            org1.new_identity("dupc"),
            [org1.new_identity("e1"), org2.new_identity("e2")],
            nonce=bytes([seed]) * 20).serialize()

    return corpus, run, dup_raw


def test_deep_collect_three_way_differential_fuzz(world, handed_over):
    """State-fork invariant fuzz: the deep C tail (digest/assemble/gate),
    the classic C-walker + Python-tail, and the pure-Python mirror must
    produce bit-identical TxFlags and item counts over `_fuzz_kit`'s
    corpora."""
    import random
    corpus, run, dup_raw_of = _fuzz_kit(world, handed_over)
    for seed in (11, 22, 33):
        rng = random.Random(seed)
        dup_raw = dup_raw_of(seed)
        b1raws, b2raws = corpus(rng), corpus(rng)
        deep = run("deep", b1raws, b2raws, dup_raw)
        classic = run("classic", b1raws, b2raws, dup_raw)
        pure = run("python", b1raws, b2raws, dup_raw)
        assert deep == classic == pure, f"state fork at seed {seed}"
        # the corpus really exercised the dedup layers: first sighting
        # valid, carry copy + ledger-oracle copy both flagged
        assert deep[0][len(b1raws)] == int(ValidationCode.VALID)
        assert deep[1][len(b2raws)] == int(ValidationCode.DUPLICATE_TXID)
        assert deep[1][len(b2raws) + 1] == \
            int(ValidationCode.DUPLICATE_TXID)


@pytest.mark.parametrize("seed", [11, 22, 33, 44])
def test_node_wired_validator_takes_the_deep_tail_on_the_fuzz(world, seed,
                                                              handed_over):
    """A validator built as node/peer.py builds it — the key-level lookup
    and the state's count beside it — takes the deep tail on a state and
    blocks that hold no validation parameter (`run` asserts which tail
    each mode took), and its flags and item counts are the classic
    tail's and the pure-Python mirror's."""
    import random
    corpus, run, dup_raw_of = _fuzz_kit(world, handed_over)
    rng = random.Random(seed)
    dup_raw = dup_raw_of(seed)
    b1raws, b2raws = corpus(rng), corpus(rng)
    node = run("node", b1raws, b2raws, dup_raw)
    assert node == run("classic", b1raws, b2raws, dup_raw)
    assert node == run("python", b1raws, b2raws, dup_raw)


def test_pipelined_inflight_duplicate_txid(world):
    """A txid duplicated across two PIPELINED blocks (begin N+1 before
    block N commits) is flagged in the later block: the in-flight carry
    covers the window the ledger oracle cannot see yet."""
    org1, org2, committer = world
    validator = committer.validator
    env = make_tx(org1, org2, rw(writes=[KVWrite("p", b"1")]))
    other = make_tx(org1, org2, rw(writes=[KVWrite("q", b"2")]))

    h = committer.ledger.height
    prev = (committer.ledger.blockstore.chain_info().current_hash
            if h else b"\x00" * 32)
    b1 = build.new_block(h, prev, [env])
    b2 = build.new_block(h + 1, b"\x00" * 32, [env, other])

    s1 = validator.validate_begin(b1)
    s2 = validator.validate_begin(b2)          # b1 not yet finished
    r1 = validator.validate_finish(s1)
    r2 = validator.validate_finish(s2)
    assert r1.flags.codes() == [int(ValidationCode.VALID)]
    assert r2.flags.codes() == [int(ValidationCode.DUPLICATE_TXID),
                                int(ValidationCode.VALID)]

    # the carry survives validate_finish (commit hasn't happened): a
    # third begin still sees b1's txid...
    b3 = build.new_block(h + 2, b"\x00" * 32, [env])
    r3 = validator.validate(b3)
    assert r3.flags.codes() == [int(ValidationCode.DUPLICATE_TXID)]

    # ...but a REPLAY of the same block number is not its own duplicate
    # (catch-up/crash-recovery semantics prune entries >= the number)
    r1b = validator.validate(build.new_block(h, prev, [env]))
    assert r1b.flags.codes() == [int(ValidationCode.VALID)]


# -- the block's lane table, opened while the validator waits -----------------


def _stored_with_spans(committer, block):
    """store_block(block) under the tracer -> (result, the spans of its
    trace by name)."""
    from fabric_tpu.ops_plane import tracing
    t = tracing.tracer
    was = t.enabled
    t.configure({"enabled": True})
    try:
        result = committer.store_block(block)
        spans = t.recorder.get(result.trace.trace_id)["spans"]
    finally:
        t.enabled = was
    by_name = {}
    for s in spans:
        by_name.setdefault(s["name"], []).append(s)
    return result, by_name


@pytest.mark.parametrize("tail", ["deep", "classic", "python"])
def test_the_wait_for_the_device_opens_the_lane_table_for_the_commit(
        world, tail, monkeypatch, lanes_opened):
    """On either tail the validator, once the block's items are enqueued,
    opens the view's lane table (not its strings: they hold the
    interpreter lock, and are the commit's); the commit that follows
    extracts nothing again, and the counter says where."""
    from fabric_tpu.committer import txvalidator as tv
    from fabric_tpu.protocol import wire
    org1, org2, committer = world
    if tail == "python":
        committer.validator.force_python_collect = True
    elif tail == "classic":
        monkeypatch.setattr(tv, "_fastcollect", _NoDigest(tv._fastcollect))
    envs = [make_tx(org1, org2, rw(writes=[KVWrite(f"k{i}", b"v")]))
            for i in range(6)]
    view = wire.parse_block(next_block(committer, envs).serialize())
    assert isinstance(view, wire.BlockView) and view._table is None
    extracted = wire._fastparse.stats()["rw_accept"]

    # validate alone leaves the table open, its strings undecoded
    state = committer.validator.validate_begin(view)
    assert bool(state.get("deep")) == (tail == "deep")
    assert view._table is None            # the collect asks for no table
    committer.validator.validate_finish(state)
    table = view._table
    assert table.opened_at == "validator_wait" and table.n_tx == 6
    assert table._txids is None and table._key_strs is None
    assert wire._fastparse.stats()["rw_accept"] == extracted + 1

    # ... and the whole path, on a fresh view of the same bytes
    view = wire.parse_block(bytes(view.raw))
    before = lanes_opened()
    result, spans = _stored_with_spans(committer, view)
    assert result.final_flags.valid_count() == 6
    assert view._table.opened_at == "validator_wait"
    assert wire._fastparse.stats()["rw_accept"] == extracted + 2
    assert lanes_opened(before) == {"validator_wait": 6}
    (wait,), (prepare,) = (spans["validator.dispatch_wait"],
                           spans["validator.lanes_prepare"])
    assert prepare["parent_id"] == wait["span_id"]
    assert prepare["attributes"] == {"block": int(view.header.number),
                                     "txs": 6}
    assert wait["start"] <= prepare["start"]
    assert (prepare["start"] + prepare["duration_s"]
            <= wait["start"] + wait["duration_s"])
    (mvcc,) = spans["ledger.mvcc"]
    assert mvcc["attributes"] == {"source": "lanes", "walk": "arrays"}
    assert mvcc["start"] >= wait["start"] + wait["duration_s"]


@pytest.mark.parametrize("form", ["plain_block", "open_already",
                                  "preparation_raises"])
def test_the_wait_prepares_nothing_where_there_is_nothing_to_prepare(
        world, form, monkeypatch, lanes_opened):
    """A Block that is no view has no table; a view whose table somebody
    opened first keeps it: no span, no second extraction, and the counter
    names the first opener.  A preparation that raises is the validator's
    to log, not the block's end: the commit opens the table itself."""
    from fabric_tpu.protocol import wire
    org1, org2, committer = world
    envs = [make_tx(org1, org2, rw(writes=[KVWrite(f"k{i}", b"v")]))
            for i in range(3)]
    block = next_block(committer, envs)
    if form != "plain_block":
        block = wire.parse_block(block.serialize())
    if form == "open_already":
        table, _ = wire.lane_table(block)
        assert table.opened_at == "commit"
    if form == "preparation_raises":
        def boom(block, at):
            raise MemoryError("no room for the arena")
        monkeypatch.setattr(wire, "prepare_lanes", boom)
    extracted = wire._fastparse.stats()["rw_accept"]
    before = lanes_opened()
    result, spans = _stored_with_spans(committer, block)
    monkeypatch.undo()
    assert result.final_flags.valid_count() == 3
    assert "validator.lanes_prepare" not in spans
    assert len(spans["validator.dispatch_wait"]) == 1
    assert wire._fastparse.stats()["rw_accept"] - extracted == (
        form == "preparation_raises")
    assert lanes_opened(before) == (
        {} if form == "plain_block" else {"commit": 3})
    assert wire.prepare_lanes(block, at="validator_wait") is None
