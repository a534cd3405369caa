"""An asset registry indexed by colour — the shim's composite keys and
the contract `asset_queries` (fabric-samples'
asset-transfer-ledger-queries) against its plain model
(`fabric_tpu/testing/asset_queries_model.py`): the key vectors; what the
endorser's simulate records, function by function, range query included;
seeded chains of a load phase + 25 blocks of the mix from wire bytes
through the committer — flags (PHANTOM_READ_CONFLICT and
MVCC_READ_CONFLICT told apart), final state, the source every block's
walk took and the range counters; the phantom cases each alone; and a
by-colour hand-over that meets a create of its colour in one block of a
live three-org network.
"""

import json
import os
import time

os.environ.setdefault("JAX_PLATFORMS", "cpu")

import pytest

from fabric_tpu.bccsp.factory import FactoryOpts, init_factories
from fabric_tpu.chaincode import (ChaincodeDefinition, ChaincodeRegistry,
                                  ChaincodeStub, SimulationError,
                                  asset_queries)
from fabric_tpu.chaincode.stub import (create_composite_key,
                                       split_composite_key)
from fabric_tpu.committer import Committer, PolicyRegistry, TxValidator
from fabric_tpu.endorser import Endorser, signed_proposal
from fabric_tpu.ledger import KVLedger, LedgerConfig
from fabric_tpu.ledger.statedb import StateDB, UpdateBatch
from fabric_tpu.msp import CachedMSP
from fabric_tpu.msp.ca import DevOrg
from fabric_tpu.ops_plane import registry
from fabric_tpu.policy import parse_policy
from fabric_tpu.protocol import Version, wire
from fabric_tpu.protocol.types import META_TXFLAGS, ChaincodeAction
from fabric_tpu.testing import asset_queries_model as model
from fabric_tpu.utils import serde

CC = "assets"
ORGS = ("Org1", "Org2", "Org3")
AND3 = "AND('Org1.member', 'Org2.member', 'Org3.member')"
GENESIS = b"\x00" * 32
V, POLICY, MVCC, PHANTOM = (model.VALID, model.POLICY_FAILURE,
                            model.MVCC_CONFLICT, model.PHANTOM_CONFLICT)
TOP = "\U0010ffff"
# 400 assets, 16 colours of 25, in load blocks of 60; 1,500 transactions
# of the mix in 25 blocks of 60; 12 clients, one envelope in 10 tampered
SIZES = (400, 16, 1500, 60, 12, 10)
SEEDS = (2**31 + 45, 7, 2**32 + 1)


@pytest.fixture(scope="module", autouse=True)
def sw_provider():
    return init_factories(FactoryOpts(default="SW"))


class World:
    """Three orgs under AND, one endorsing peer each, twelve clients
    enrolled org by org in turn."""

    def __init__(self):
        self.orgs = [DevOrg(o) for o in ORGS]
        self.msps = {o.mspid: CachedMSP(o.msp()) for o in self.orgs}
        self.endorsers = [o.new_identity(f"peer{o.mspid}")
                          for o in self.orgs]
        self.creators = [self.orgs[i % 3].new_identity(f"client{i}")
                         for i in range(12)]

    def raw_blocks(self, plan, prev=GENESIS) -> list:
        raws = []
        for block in plan:
            raw, prev = model.build_block(block, prev, "ch", CC,
                                          self.endorsers, self.creators)
            raws.append(raw)
        return raws

    def committer(self, provider) -> Committer:
        """A validator built as node/peer.py builds it."""
        policies = PolicyRegistry()
        policies.set_policy(CC, parse_policy(AND3))
        return Committer(KVLedger("ch", LedgerConfig()),
                         TxValidator("ch", self.msps, provider, policies))


@pytest.fixture(scope="module")
def world():
    return World()


def stored_flags(ledger, number: int) -> list:
    return list(ledger.blockstore.get_by_number(number)
                .metadata.items[META_TXFLAGS])


def mvcc_span(ledger) -> dict:
    return ledger.last_stats.span_attrs["ledger.mvcc"]


RANGE_SERIES = [("ledger_mvcc_range_queries_total", {"result": "held"}),
                ("ledger_mvcc_range_queries_total", {"result": "phantom"}),
                ("ledger_mvcc_range_reads_total", {}),
                ("ledger_commit_source_total", {"source": "envelopes"}),
                ("ledger_commit_source_total", {"source": "lanes"}),
                ("ledger_mvcc_walk_total", {"walk": "python",
                                            "reason": "range"})]


def range_counters() -> list:
    seen = [registry.counter(name).value(channel="ch", **labels)
            for name, labels in RANGE_SERIES]
    _, seconds, blocks = registry.histogram(
        "ledger_mvcc_range_seconds").state()
    return seen + [blocks, seconds]


# -- composite keys ------------------------------------------------------------

def test_composite_key_vectors():
    assert create_composite_key("color~name", ["blue", "asset1"]) == \
        "\x00color~name\x00blue\x00asset1\x00"
    assert create_composite_key("t", []) == "\x00t\x00"
    assert create_composite_key("t", ["", "x"]) == "\x00t\x00\x00x\x00"
    for parts in [("color~name", ["blue", "asset1"]), ("t", []),
                  ("t", ["", "x"]), ("t", ["café", "\U0001f600"])]:
        assert split_composite_key(create_composite_key(*parts)) == parts
        assert create_composite_key(*parts) == model.composite_key(*parts)
    for bad in [("", ["a"]), ("t\x00", ["a"]), ("t", ["a\x00b"]),
                ("t", [TOP]), (TOP, [])]:
        with pytest.raises(SimulationError):
            create_composite_key(*bad)
        with pytest.raises(model.Rejected):
            model.composite_key(*bad)
    for not_one in ["", "asset1", "\x00t", "t\x00", "\x00\x00"]:
        with pytest.raises(SimulationError):
            split_composite_key(not_one)


def small_state() -> StateDB:
    """Simple keys a, b, c and four entries of two object types."""
    db = StateDB(None)
    batch = UpdateBatch()
    keys = ["a", "b", "c",
            create_composite_key("color~name", ["blue", "a"]),
            create_composite_key("color~name", ["blue", "c"]),
            create_composite_key("color~name", ["bluer", "b"]),
            create_composite_key("owner~name", ["blue", "a"])]
    for n, key in enumerate(keys):
        batch.put(CC, key, b"v", Version(0, n))
    db.apply_updates(batch, 0)
    return db


def test_partial_composite_key_is_the_range_between_its_two_ends():
    stub = ChaincodeStub(small_state(), CC)
    rows = stub.get_state_by_partial_composite_key("color~name", ["blue"])
    assert [split_composite_key(k) for k, _ in rows] == [
        ("color~name", ["blue", "a"]), ("color~name", ["blue", "c"])]
    whole = stub.get_state_by_partial_composite_key("color~name", [])
    assert len(whole) == 3
    rq_one, rq_all = stub.rwset().ns_rwsets[0].range_queries
    assert (rq_one.start_key, rq_one.end_key, rq_one.itr_exhausted) == (
        "\x00color~name\x00blue\x00", "\x00color~name\x00blue\x00" + TOP, True)
    assert [(r.key, r.version) for r in rq_one.reads] == [
        (k, Version(0, 3 + n)) for n, (k, _) in enumerate(rows)]
    assert (rq_all.start_key, rq_all.end_key) == (
        "\x00color~name\x00", "\x00color~name\x00" + TOP)


def test_a_partial_key_scan_sees_committed_state_only_and_counts_a_limit():
    stub = ChaincodeStub(small_state(), CC)
    stub.put_state(create_composite_key("color~name", ["blue", "b"]), b"v")
    stub.del_state(create_composite_key("color~name", ["blue", "a"]))
    rows = stub.get_state_by_partial_composite_key("color~name", ["blue"],
                                                   limit=1)
    assert [split_composite_key(k)[1] for k, _ in rows] == [["blue", "a"]]
    (rq,) = stub.rwset().ns_rwsets[0].range_queries
    assert not rq.itr_exhausted and len(rq.reads) == 1


def test_a_scan_of_the_simple_keys_never_returns_a_composite_key():
    stub = ChaincodeStub(small_state(), CC)
    assert [k for k, _ in stub.get_state_by_range("", "")] == ["a", "b", "c"]
    assert [k for k, _ in stub.get_state_by_range("b", "")] == ["b", "c"]
    first, _ = stub.rwset().ns_rwsets[0].range_queries
    assert (first.start_key, first.end_key) == ("\x01", "")
    for bounds in [("\x00", ""), ("", "\x00color~name\x00"),
                   ("\x00color~name\x00", "\x00color~name\x00" + TOP)]:
        with pytest.raises(SimulationError):
            ChaincodeStub(small_state(), CC).get_state_by_range(*bounds)
    # point reads, writes and deletes take composite keys as they are
    stub = ChaincodeStub(small_state(), CC)
    key = create_composite_key("color~name", ["blue", "a"])
    assert stub.get_state(key) == b"v"
    stub.put_state(key, b"w")
    stub.del_state(create_composite_key("owner~name", ["blue", "a"]))
    ns = stub.rwset().ns_rwsets[0]
    assert [r.key for r in ns.reads] == [key]
    assert [(w.key, w.is_delete) for w in ns.writes] == [
        (key, False), (create_composite_key("owner~name", ["blue", "a"]),
                       True)]


# -- the chain is what the issue says it is ------------------------------------

@pytest.fixture(scope="module")
def plans():
    return {seed: model.plan_chain(seed, *SIZES) for seed in SEEDS}


def test_the_chain_is_a_pure_function_of_the_seed_and_holds_every_case(plans):
    plan = plans[SEEDS[0]]
    assert plan == model.plan_chain(SEEDS[0], *SIZES)
    assert plan != plans[SEEDS[1]]
    load, run = plan[:7], plan[7:]
    assert [len(b["txs"]) for b in load] == [60] * 6 + [40]
    assert all(b["phase"] == "load" and b["codes"] == [V] * len(b["txs"])
               for b in load)
    assert len(run) == 25 and all(b["phase"] == "run" and len(b["txs"]) == 60
                                  for b in run)
    world = model.replay_plan(load)
    by_color = {}
    for asset_id, (color, *_rest) in world.assets.items():
        by_color.setdefault(color, []).append(asset_id)
    assert len(by_color) == 16 and {len(v) for v in by_color.values()} == {25}
    assert len(world.index) == 400
    shapes = {(tx["kind"], len(tx["reads"]), len(tx["ranges"]),
               len(tx["writes"]))
              for b in plan for tx in b["txs"] if tx["kind"] != "bycolor"}
    assert shapes == {("create", 1, 0, 2), ("transfer", 1, 0, 1),
                      ("delete", 1, 0, 2)}
    for b in run:
        for tx in b["txs"]:
            # simulated against the state committed before the block
            assert all(v is None or v[0] < b["number"]
                       for k, v in tx["reads"])
            if tx["kind"] == "bycolor":
                (rq,) = tx["ranges"]
                assert len(tx["reads"]) == len(rq["reads"]) == len(tx["writes"])
                assert rq["end"] == rq["start"] + TOP and rq["exhausted"]
    for seed in SEEDS:
        seen = model.counts(plans[seed][7:])
        for what in ("ranges_held", "phantoms_by_create",
                     "bycolor_mvcc_by_transfer", "bycolor_mvcc_by_delete",
                     "creates", "deletes"):
            assert seen[what] > 0, (seed, what)
        # reads are judged before ranges: a delete in the colour is the
        # read's conflict, never a phantom, with this contract
        assert seen["phantoms_by_delete"] == 0
        assert seen["phantoms"] == seen["phantoms_by_create"]
        assert seen["ranges_replayed"] == seen["ranges_held"] + seen["phantoms"]
        assert seen["largest_range"] <= 50


# -- the endorser's simulate against the model's -------------------------------

ACCEPTED = [
    ("CreateAsset", ["asset900", "color0003", "7", "client@Org2", "1200"], 1),
    ("CreateAsset", ["asset901", "magenta", "0", "someone", "0"], 2),
    ("ReadAsset", ["asset1"], 0),
    ("AssetExists", ["asset1"], 0),
    ("AssetExists", ["asset77"], 0),
    ("DeleteAsset", ["asset2"], 2),
    ("TransferAsset", ["asset3", "client1@Org3"], 1),
    ("TransferAssetByColor", ["color0001", "client2@Org1"], 0),
    ("TransferAssetByColor", ["color0000", "client@Org2"], 1),
    ("TransferAssetByColor", ["nocolor", "client@Org2"], 1),  # an empty class
    ("GetAssetsByRange", ["asset2", "asset5"], 0),
    ("GetAssetsByRange", ["", ""], 0),          # every asset, no index entry
    ("GetAssetsByRange", ["asset5", ""], 2),
]
REJECTED = [
    ("CreateAsset", ["asset1", "color0001", "5", "someone", "9"], 0),
    ("CreateAsset", ["asset902", "color0001", "five", "someone", "9"], 0),
    ("CreateAsset", ["asset902", "color\x000001", "5", "someone", "9"], 0),
    ("CreateAsset", ["asset902", "color0001", "5", "someone"], 0),
    ("ReadAsset", ["asset77"], 0),
    ("DeleteAsset", ["asset77"], 0),
    ("TransferAsset", ["asset77", "someone"], 0),
    ("TransferAssetByColor", ["color\x00", "someone"], 0),
    ("GetAssetsByRange", ["\x00color~name\x00", ""], 0),
    ("GetAssetsByRange", ["", "\x00"], 0),
    ("QueryAssets", ['{"selector":{}}'], 0),     # no rich query here
]


@pytest.fixture(scope="module")
def endorsing(world, sw_provider):
    """(endorser, the model's registry): twelve assets of four colours
    created in block 0, on a ledger and in the model alike."""
    reg_model = model.Registry()
    txs = [dict(reg_model.simulate(
                    "CreateAsset", [model.asset_key(i),
                                    model.color_name(i % 4), 10 + i,
                                    model.enrolment_name(i, ORGS), 100 * i]),
                kind="create", creator=i, tampered=False, nonce="%048x" % i)
           for i in range(12)]
    assert reg_model.commit_block(0, txs) == [V] * 12
    committer = world.committer(sw_provider)
    for raw in world.raw_blocks([{"number": 0, "txs": txs}]):
        committer.store_block(wire.parse_block(raw))
    assert stored_flags(committer.ledger, 0) == [V] * 12
    reg = ChaincodeRegistry()
    reg.install(ChaincodeDefinition(CC, "1.0"), asset_queries.contract())
    endorser = Endorser("ch", committer.ledger.statedb, reg, world.msps,
                        sw_provider, world.endorsers[0])
    return endorser, reg_model


def case_id(case) -> str:
    fn, args, client = case
    return f"{fn}({','.join(args)})by{client}".replace("\x00", "<0>")


@pytest.mark.parametrize("case", ACCEPTED, ids=case_id)
def test_simulated_rwset_equals_the_models(endorsing, world, case):
    fn, args, client = case
    endorser, reg_model = endorsing
    sp = signed_proposal("ch", CC, fn, [a.encode() for a in args],
                         world.creators[client])
    resp = endorser.process_proposal(sp)
    assert resp.status == 200, resp.message
    want = reg_model.simulate(fn, args)
    action = ChaincodeAction(CC, "1.0", model.rwset_of(want, CC),
                             response_payload=want["payload"].encode())
    got = serde.decode(resp.payload)["action"]
    assert serde.encode(got) == serde.encode(action.to_dict())
    if fn == "TransferAssetByColor" and args[0] == "color0001":
        (rq,) = want["ranges"]
        assert [k for k, _ in rq["reads"]] == [
            model.index_key("color0001", model.asset_key(i))
            for i in (1, 5, 9)]
        assert [k for k, _ in want["writes"]] == ["asset1", "asset5",
                                                  "asset9"]


@pytest.mark.parametrize("case", REJECTED, ids=case_id)
def test_what_the_model_rejects_the_contract_rejects(endorsing, world, case):
    fn, args, client = case
    endorser, reg_model = endorsing
    with pytest.raises(model.Rejected):
        reg_model.simulate(fn, args)
    sp = signed_proposal("ch", CC, fn, [a.encode() for a in args],
                         world.creators[client])
    resp = endorser.process_proposal(sp)
    assert resp.status == 500 and resp.endorsement is None


# -- seeded chains from wire bytes through the committer -----------------------

def held_state(ledger, world_model) -> tuple:
    """(what the ledger holds under every key the model knows or knew of,
    what the model holds): asset records and index entries, and the
    index as one scan returns it."""
    ids = [model.asset_key(i) for i in range(SIZES[0] + SIZES[2])]
    got = {k: ledger.get_state(CC, k) for k in ids}
    want = {k: (world_model.record_of(k).encode()
                if k in world_model.assets else None) for k in ids}
    prefix = "\x00" + model.INDEX + "\x00"
    scanned = [(k, vv.value) for k, vv in
               ledger.range_query(CC, prefix, prefix + TOP)]
    index = [(k, model.INDEX_VALUE.encode())
             for k in sorted(world_model.index)]
    return (got, scanned), (want, index)


@pytest.mark.parametrize("seed", SEEDS)
def test_chain_through_the_committer_equals_the_model(world, sw_provider,
                                                      plans, seed):
    plan = plans[seed]
    committer = world.committer(sw_provider)
    before = range_counters()
    for block, raw in zip(plan, world.raw_blocks(plan)):
        committer.store_block(wire.parse_block(raw))
        assert stored_flags(committer.ledger, block["number"]) == \
            block["codes"], block["number"]
        span = mvcc_span(committer.ledger)
        at_gate = [tx for tx in block["txs"]
                   if tx["ranges"] and not tx["tampered"]]
        if at_gate:
            assert (span["source"], span["reason"], span["walk"]) == (
                "envelopes", "range", "python")
        else:
            assert (span["source"], span["walk"]) == ("lanes", "arrays")
        replayed = [tx for tx in at_gate if "replayed" in tx]
        assert span.get("range_queries", 0) == len(replayed)
        assert span.get("range_reads", 0) == sum(tx["replayed"]
                                                 for tx in replayed)
        assert ("range_ms" in span) == bool(replayed)
    final = model.replay_plan(plan)
    got, want = held_state(committer.ledger, final)
    assert got == want
    assert len(final.index) == len(final.assets)      # no entry alone
    # the always-on counters are the model's counts
    seen = model.counts(plan)
    txs = sum(len(b["txs"]) for b in plan)
    moved = [a - b for a, b in zip(range_counters(), before)]
    assert moved[:7] == [
        seen["ranges_held"], seen["phantoms"],
        seen["range_results_replayed"], seen["envelope_source_txs"],
        txs - seen["envelope_source_txs"], seen["envelope_source_txs"],
        sum(1 for b in plan if any("replayed" in tx for tx in b["txs"]))]
    assert moved[7] > 0
    assert seen["phantoms"] > 0 and seen["bycolor_mvcc"] > 0
    codes = {c for b in plan for c in b["codes"]}
    assert codes == {V, POLICY, MVCC, PHANTOM}


# -- the phantom cases, each alone ---------------------------------------------

def run_case(world, provider, calls):
    """Nine assets of three colours in block 0; `calls` — (fn, args) —
    simulated against that state and committed as block 1, on a ledger
    and in the model.  -> (block 1's codes, the committer, the model's
    transactions)."""
    reg_model = model.Registry()
    opening = [dict(reg_model.simulate(
                        "CreateAsset", [model.asset_key(i),
                                        model.color_name(i % 3), 1, "o", 1]),
                    kind="create", creator=i, tampered=False,
                    nonce="%048x" % i) for i in range(9)]
    assert reg_model.commit_block(0, opening) == [V] * 9
    txs = [dict(reg_model.simulate(fn, args), kind=fn, creator=n,
                tampered=False, nonce="%048x" % (100 + n))
           for n, (fn, args) in enumerate(calls)]
    want = reg_model.commit_block(1, txs)
    committer = world.committer(provider)
    for raw in world.raw_blocks([{"number": 0, "txs": opening},
                                 {"number": 1, "txs": txs}]):
        committer.store_block(wire.parse_block(raw))
    got = stored_flags(committer.ledger, 1)
    assert got == want
    return got, committer, txs


BY_COLOR_0 = ("TransferAssetByColor", ["color0000", "new"])   # assets 0, 3, 6

PHANTOM_CASES = {
    "a create in the colour": (
        [("CreateAsset", ["asset9", "color0000", 1, "o", 1]), BY_COLOR_0],
        [V, PHANTOM], "create"),
    "a create in another colour": (
        [("CreateAsset", ["asset9", "color0001", 1, "o", 1]), BY_COLOR_0],
        [V, V], None),
    # the hand-over read the deleted asset: reads are judged first
    "a delete in the colour": (
        [("DeleteAsset", ["asset3"]), BY_COLOR_0], [V, MVCC], "DeleteAsset"),
    "a transfer of a member": (
        [("TransferAsset", ["asset3", "x"]), BY_COLOR_0], [V, MVCC],
        "TransferAsset"),
    "a transfer of a non-member": (
        [("TransferAsset", ["asset4", "x"]), BY_COLOR_0], [V, V], None),
    "a hand-over, then a transfer of a member": (
        [BY_COLOR_0, ("TransferAsset", ["asset3", "x"])], [V, MVCC],
        "TransferAssetByColor"),
    "two hand-overs of one colour": (
        [BY_COLOR_0, BY_COLOR_0], [V, MVCC], "TransferAssetByColor"),
    # a range alone has no read to lose first: both kinds are phantoms
    "a range alone, a delete inside": (
        [("DeleteAsset", ["asset3"]),
         ("GetAssetsByRange", ["asset2", "asset5"])], [V, PHANTOM], "delete"),
    "a range alone, a create inside": (
        [("CreateAsset", ["asset31", "color0000", 1, "o", 1]),
         ("GetAssetsByRange", ["asset2", "asset5"])], [V, PHANTOM], "create"),
    "a range alone, a rewrite inside": (
        [("TransferAsset", ["asset3", "x"]),
         ("GetAssetsByRange", ["asset2", "asset5"])], [V, PHANTOM],
        "rewrite"),
    "a range alone, a create beyond its end": (
        [("CreateAsset", ["asset9", "color0000", 1, "o", 1]),
         ("GetAssetsByRange", ["asset2", "asset5"])], [V, V], None),
    "a create that loses, then the hand-over": (
        [("CreateAsset", ["asset9", "color0000", 1, "o", 1]),
         ("CreateAsset", ["asset9", "color0000", 2, "o", 2]), BY_COLOR_0,
         ("TransferAssetByColor", ["color0001", "new"])],
        [V, MVCC, PHANTOM, V], "create"),
}


@pytest.mark.parametrize("name", PHANTOM_CASES)
def test_phantom_case(world, sw_provider, name):
    calls, codes, cause = PHANTOM_CASES[name]
    got, committer, txs = run_case(world, sw_provider, calls)
    assert got == codes
    lost = [tx for tx, code in zip(txs, got) if code in (MVCC, PHANTOM)]
    assert [tx["cause"] for tx in lost[-1:]] == ([cause] if cause else [])
    span = mvcc_span(committer.ledger)
    assert (span["source"], span["reason"]) == ("envelopes", "range")
    if got == [V, V] and calls[1] == BY_COLOR_0:
        for i in (0, 3, 6):
            assert json.loads(committer.ledger.get_state(
                CC, model.asset_key(i)))["owner"] == "new"


def test_a_block_whose_range_transactions_all_failed_the_gate_takes_the_lanes(
        world, sw_provider):
    reg_model = model.Registry()
    opening = [dict(reg_model.simulate(
                        "CreateAsset", [model.asset_key(i), "color0000", 1,
                                        "o", 1]),
                    kind="create", creator=i, tampered=False,
                    nonce="%048x" % i) for i in range(3)]
    reg_model.commit_block(0, opening)
    txs = [dict(reg_model.simulate(*call), kind="case", creator=n,
                tampered=tampered, nonce="%048x" % (50 + n))
           for n, (call, tampered) in enumerate([
               (BY_COLOR_0, True), (("TransferAsset", ["asset1", "x"]), False),
               (BY_COLOR_0, True)])]
    assert reg_model.commit_block(1, txs) == [POLICY, V, POLICY]
    committer = world.committer(sw_provider)
    before = range_counters()
    for raw in world.raw_blocks([{"number": 0, "txs": opening},
                                 {"number": 1, "txs": txs}]):
        committer.store_block(wire.parse_block(raw))
    assert stored_flags(committer.ledger, 1) == [POLICY, V, POLICY]
    assert mvcc_span(committer.ledger) == {"source": "lanes",
                                           "walk": "arrays"}
    moved = [a - b for a, b in zip(range_counters(), before)]
    assert moved == [0, 0, 0, 0, 6, 0, 0, 0]


# -- gateway -> endorse -> order -> commit, a live three-org network ----------

@pytest.fixture(scope="module")
def net(tmp_path_factory):
    """One orderer + Org1/Org2/Org3 peers in this process, the contract
    under AND of the three; a block is two transactions or a second."""
    from fabric_tpu.config import BatchConfig
    from fabric_tpu.node.orderer import OrdererNode
    from fabric_tpu.node.peer import PeerNode
    from fabric_tpu.node.provision import provision_network
    base = str(tmp_path_factory.mktemp("asset_queries_gw"))
    paths = provision_network(
        base, n_orderers=1, peer_orgs=list(ORGS),
        batch=BatchConfig(max_message_count=2, timeout_s=1.0),
        chaincodes=[{"name": CC, "version": "1.0",
                     "contract": "asset_queries", "policy": AND3}])
    orderers, peers = [], []
    try:
        for p in paths["orderers"]:
            with open(p) as f:
                cfg = json.load(f)
            orderers.append(OrdererNode(cfg, data_dir=cfg["data_dir"]).start())
        for p in paths["peers"]:
            with open(p) as f:
                cfg = json.load(f)
            cfg["gateway"] = {"linger_s": 0.002, "max_batch": 8,
                              "broadcast_deadline_s": 20.0}
            peers.append(PeerNode(cfg, data_dir=cfg["data_dir"]).start())
        deadline = time.time() + 60
        while not any(o.support.chain.node.role == "leader"
                      for o in orderers):
            assert time.time() < deadline, "no raft leader elected"
            time.sleep(0.2)
        yield {"paths": paths, "peers": peers}
    finally:
        for n in peers + orderers:
            try:
                n.stop()
            except Exception:
                pass


def test_a_create_in_the_colour_makes_the_later_handover_a_phantom(net):
    """Both endorsed against the same state and ordered into one block,
    the create first: the client of the hand-over reads
    PHANTOM_READ_CONFLICT, and no asset changed hands."""
    from fabric_tpu.endorser import assemble_transaction
    from fabric_tpu.gateway import GatewayClient
    from fabric_tpu.node.orderer import load_signing_identity
    with open(net["paths"]["clients"]["Org1"]) as f:
        cc = json.load(f)
    signer = load_signing_identity(cc["mspid"], cc["cert_pem"].encode(),
                                   cc["key_pem"].encode())
    peer = net["peers"][0]
    ledgers = [p.channels["ch"].ledger for p in net["peers"]]
    gw = GatewayClient(peer.rpc.addr, signer, peer.msps, channel_id="ch")

    def settled():
        """Every peer holds the same chain, writes applied: the three
        endorsers will simulate against one state."""
        deadline = time.time() + 30
        height = max(lg.height for lg in ledgers)
        while time.time() < deadline and any(
                lg.height != height or lg.statedb.savepoint != height - 1
                for lg in ledgers):
            time.sleep(0.05)
            height = max(lg.height for lg in ledgers)
        assert [lg.height for lg in ledgers] == [height] * 3

    def endorsed(fn, args):
        sp, responses = gw.endorse(CC, fn, [a.encode() for a in args])
        assert len(responses) == 3
        env = assemble_transaction(sp, responses, signer)
        return env, env.header().channel_header.txid

    try:
        settled()
        pair = [endorsed("CreateAsset", [f"lot{i}", "teal", "1", "ann", "5"])
                for i in (1, 2)]
        for env, _ in pair:
            gw.submit_envelope(env, timeout_s=60.0)
        assert [gw.commit_status(txid, timeout_s=60.0)[0]
                for _, txid in pair] == [V, V]
        settled()
        create = endorsed("CreateAsset", ["lot3", "teal", "1", "ann", "5"])
        handover = endorsed("TransferAssetByColor", ["teal", "bob"])
        other = endorsed("TransferAssetByColor", ["none-of-this", "bob"])
        for env, _ in (create, handover):
            gw.submit_envelope(env, timeout_s=60.0)
        code_create, block_create = gw.commit_status(create[1], timeout_s=60.0)
        code_handover, block_handover = gw.commit_status(handover[1],
                                                         timeout_s=60.0)
        assert block_create == block_handover
        assert (code_create, code_handover) == (V, PHANTOM)
        gw.submit_envelope(other[0], timeout_s=60.0)
        assert gw.commit_status(other[1], timeout_s=60.0)[0] == V
        settled()
        for lg in ledgers:
            assert [json.loads(lg.get_state(CC, f"lot{i}"))["owner"]
                    for i in (1, 2, 3)] == ["ann"] * 3
        # endorsed again on the new state, the hand-over takes all three
        again = endorsed("TransferAssetByColor", ["teal", "bob"])
        gw.submit_envelope(again[0], timeout_s=60.0)
        assert gw.commit_status(again[1], timeout_s=60.0)[0] == V
        settled()
        for lg in ledgers:
            assert [json.loads(lg.get_state(CC, f"lot{i}"))["owner"]
                    for i in (1, 2, 3)] == ["bob"] * 3
        assert ledgers[0].commit_hash == ledgers[1].commit_hash \
            == ledgers[2].commit_hash
    finally:
        gw.close()
