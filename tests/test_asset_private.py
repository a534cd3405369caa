"""Private data collections — the shim's transient map, hashed read and
member-only checks, the contract `asset_private` (fabric-samples'
asset-transfer-private-data) against its plain model
(`fabric_tpu/testing/asset_private_model.py`) rw-set for rw-set, a
collection's own endorsement policy at the validator, the ledger's expiry
of hashed keys (block-to-live), and seeded chains of a load phase + the
mix from wire bytes through three committers — a member (Org1), a second
member (Org2) and a peer that is a member of nothing (Org3): flags,
commit hash and hashed state equal, each private store its org's view.
"""

import hashlib
import json
import os

os.environ.setdefault("JAX_PLATFORMS", "cpu")

import pytest

from fabric_tpu.bccsp.factory import FactoryOpts, init_factories
from fabric_tpu.chaincode import (ChaincodeDefinition, ChaincodeRegistry,
                                  ChaincodeStub, SimulationError,
                                  asset_private)
from fabric_tpu.committer import Committer, PolicyRegistry, TxValidator
from fabric_tpu.endorser import Endorser, signed_proposal
from fabric_tpu.endorser.proposal import Proposal, assemble_transaction
from fabric_tpu.ledger import KVLedger, LedgerConfig
from fabric_tpu.ledger.pvtexpiry import EXPIRY_NS
from fabric_tpu.ledger.statedb import StateDB, UpdateBatch
from fabric_tpu.msp import CachedMSP
from fabric_tpu.msp.ca import DevOrg
from fabric_tpu.ops_plane import registry
from fabric_tpu.policy import parse_policy
from fabric_tpu.privdata import (CollectionConfig, CollectionRegistry,
                                 Coordinator, PvtDataStore, TransientStore,
                                 pvt_namespace)
from fabric_tpu.protocol import (KVRead, KVWrite, NsRwSet, TxRwSet, Version,
                                 build, wire)
from fabric_tpu.protocol.types import META_TXFLAGS, ChaincodeAction
from fabric_tpu.testing import asset_private_model as model
from fabric_tpu.utils import serde

CC = "assets"
ORGS = ("Org1", "Org2", "Org3")
TRADERS = ("Org1", "Org2")
OR2 = "OR('Org1.peer', 'Org2.peer')"
GENESIS = b"\x00" * 32
V, POLICY, MVCC = model.VALID, model.POLICY_FAILURE, model.MVCC_CONFLICT
A = model.ASSET_COLLECTION
# 240 assets in load blocks of 60; 1,800 draws of the mix in blocks of 60;
# 12 clients (8 of the trading orgs), one envelope in 25 tampered
SIZES = (240, 1800, 60, 12, 25)
SEEDS = (2**31 + 48, 7, 2**32 + 3)


def node_collections() -> list:
    """The sample's collections_config.json as a node's `collections`."""
    out = []
    for name, c in model.collections(TRADERS).items():
        out.append({
            "ns": CC, "name": name, "members": list(c["members"]),
            "btl": c["btl"], "member_only_read": c["member_only_read"],
            "member_only_write": c["member_only_write"],
            "required_peer_count": 1 if name == A else 0,
            "max_peer_count": 1,
            "endorsement_policy": (f"OR('{c['policy_org']}.member')"
                                   if c["policy_org"] else "")})
    return out


@pytest.fixture(scope="module", autouse=True)
def sw_provider():
    return init_factories(FactoryOpts(default="SW"))


class Peer:
    """One org's committing peer, wired as node/peer.py wires a channel:
    the collections' registry, their policies at the validator, their
    block-to-live at the ledger, a transient and a private store behind
    the coordinator."""

    def __init__(self, world, org: str, provider, root=None):
        self.org = org
        self.collections = CollectionRegistry()
        policies = PolicyRegistry()
        policies.set_policy(CC, parse_policy(OR2))
        for col in node_collections():
            self.collections.define(col["ns"],
                                    CollectionConfig.from_node_config(col))
            if col["endorsement_policy"]:
                policies.set_policy(pvt_namespace(CC, col["name"]),
                                    parse_policy(col["endorsement_policy"]))
        self.ledger = KVLedger("ch", LedgerConfig(
            root=root, pvt_btl=self.collections.block_to_live()))
        self.transient, self.pvt = TransientStore(), PvtDataStore()
        self.coordinator = Coordinator(
            Committer(self.ledger,
                      TxValidator("ch", world.msps, provider, policies)),
            self.collections, self.transient, self.pvt, mspid=org)

    def store(self, world, block: dict, raw: bytes) -> list:
        """Stage what this org's peer was pushed at endorsement, then
        the block from its wire bytes.  -> its stored flags."""
        for tx in block["txs"]:
            sets = model.private_sets(tx, CC, self.org, TRADERS)
            if sets:
                self.transient.persist(model.txid_of(tx, world.creators),
                                       self.ledger.height, sets)
        self.coordinator.store_block(wire.parse_block(raw))
        return stored_flags(self.ledger, block["number"])

    def hashed_state(self) -> dict:
        return {(coll, k): (vv.value.hex(), [vv.version.block_num,
                                            vv.version.tx_num])
                for coll in model.collections(TRADERS)
                for k, vv in self.ledger.range_query(
                    pvt_namespace(CC, coll), "", "")}

    def private_view(self) -> dict:
        return {(coll, key): self.pvt.get(ns, coll, key).decode()
                for ns, coll, key in self.pvt.keys()}


class World:
    """Three orgs, one endorsing peer each, twelve clients enrolled org
    by org in turn under the names the model gives them."""

    def __init__(self):
        self.orgs = {o: DevOrg(o) for o in ORGS}
        self.msps = {o: CachedMSP(org.msp()) for o, org in self.orgs.items()}
        self.endorsers = {o: org.new_identity(f"peer{o}")
                          for o, org in self.orgs.items()}
        self.creators = [
            self.orgs[ORGS[i % 3]].new_identity(
                model.enrolment_name(i, ORGS).split("@")[0])
            for i in range(12)]

    def raw_blocks(self, plan, prev=GENESIS) -> list:
        raws = []
        for block in plan:
            raw, prev = model.build_block(block, prev, "ch", CC,
                                          self.endorsers, self.creators)
            raws.append(raw)
        return raws


@pytest.fixture(scope="module")
def world():
    return World()


def stored_flags(ledger, number: int) -> list:
    return list(ledger.blockstore.get_by_number(number)
                .metadata.items[META_TXFLAGS])


def name_of(client: int) -> str:
    return model.enrolment_name(client, ORGS)


def tx_of(world_model, kind, fn, transient, client, endorser=None,
          nonce=0, args=()):
    """A model transaction as `Chain` would have drawn it."""
    org = ORGS[client % 3]
    tx = world_model.simulate(fn, args, transient, name_of(client), org)
    if endorser:
        tx["endorser"] = endorser
    return dict(tx, kind=kind, creator=client, tampered=False,
                nonce="%048x" % nonce)


def create(asset, value=500, color="blue", size=5) -> dict:
    return {"asset_properties": model.compact({
        "objectType": "asset", "assetID": asset, "color": color,
        "size": size, "appraisedValue": value})}


def agree(asset, value=500) -> dict:
    return {"asset_value": model.details_record(asset, value)}


def transfer(asset, buyer_org="Org2") -> dict:
    return {"asset_owner": model.compact({"assetID": asset,
                                          "buyerMSP": buyer_org})}


def delete(asset) -> dict:
    return {"asset_delete": model.compact({"assetID": asset})}


def unagree(asset) -> dict:
    return {"agreement_delete": model.compact({"assetID": asset})}


# -- the chain -----------------------------------------------------------------

@pytest.fixture(scope="module")
def plans():
    return {seed: model.plan_chain(seed, *SIZES) for seed in SEEDS}


def test_the_chain_is_a_pure_function_of_the_seed_and_holds_every_case(plans):
    plan = plans[SEEDS[0]]
    assert plan == model.plan_chain(SEEDS[0], *SIZES)
    assert plan != plans[SEEDS[1]]
    load, run = plan[:4], plan[4:]
    assert [len(b["txs"]) for b in load] == [60] * 4
    assert all(b["phase"] == "load" and b["codes"] == [V] * 60 for b in load)
    assert all(b["phase"] == "run" for b in run)
    assert sum(len(b["txs"]) for b in run) == SIZES[1]
    # owners alternate between the trading orgs; Org3's clients never submit
    assert [tx["org"] for tx in load[0]["txs"][:4]] == ["Org1", "Org2"] * 2
    assert {tx["org"] for b in plan for tx in b["txs"]} == set(TRADERS)
    # the load phase's appraisals expire from block 4 = 0 + 3 + 1 on
    assert [len(b["expired"]) for b in plan[:5]] == [0, 0, 0, 0, 60]
    shapes = {(tx["kind"], len(tx["reads"]), len(tx["writes"]))
              for b in plan for tx in b["txs"]}
    assert shapes == {("create", 1, 2), ("agree", 1, 2), ("wrong_org", 1, 2),
                      ("transfer", 4, 3), ("late", 4, 3), ("delete", 2, 2)}
    for b in run:
        late = [tx for tx in b["txs"] if tx["kind"] == "late"]
        assert b["txs"][:len(late)] == late    # held back into the front
        for tx in b["txs"]:
            # simulated against the state committed before its block; a
            # late one a block earlier still
            before = b["number"] - (tx["kind"] == "late")
            assert all(v is None or v[0] < before for _, _, v in tx["reads"])
            assert "appraisedValue" not in json.dumps(
                [tx["reads"], tx["writes"], tx["args"], tx["payload"]])
    for seed in SEEDS:
        seen = model.counts(plans[seed][4:])
        for what in ("tampered", "collection_policy", "conflict", "expired",
                     "creates", "agrees", "transfers", "deletes",
                     "expired_keys"):
            assert seen[what] > 0, (seed, what)
        causes = {(tx["kind"], tx["cause"]) for b in plans[seed]
                  for tx in b["txs"] if "cause" in tx}
        assert ("late", "expired") in causes
        assert ("wrong_org", "collection_policy") in causes
        assert {k for k, c in causes if c == "expired"} == {"late"}
        assert {k for k, c in causes if c == "collection_policy"} == {
            "wrong_org"}


# -- the endorser's simulate against the model's -------------------------------

@pytest.fixture(scope="module")
def endorsing(world, sw_provider):
    """Org1's and Org2's endorsing peers and the model's world after two
    blocks: block 0 creates asset0..5 (owners client 0 of Org1 and client
    1 of Org2 in turn, appraised at 100 * (n + 1)); block 1 has Org2's
    client 1 agree to buy asset0 at its price and asset2 at another, and
    Org1's client 0 agree to buy asset1."""
    wm = model.World(ORGS, TRADERS)
    block0 = [tx_of(wm, "create", "CreateAsset",
                    create(model.asset_key(n), 100 * (n + 1)), n % 2, nonce=n)
              for n in range(6)]
    assert wm.commit_block(0, block0) == [V] * 6
    block1 = [
        tx_of(wm, "agree", "AgreeToTransfer", agree("asset0", 100), 1,
              nonce=10),
        tx_of(wm, "agree", "AgreeToTransfer", agree("asset2", 999), 1,
              nonce=11),
        tx_of(wm, "agree", "AgreeToTransfer", agree("asset1", 200), 0,
              nonce=12)]
    assert wm.commit_block(1, block1) == [V] * 3
    plan = [{"number": 0, "txs": block0}, {"number": 1, "txs": block1}]
    raws = world.raw_blocks(plan)
    out = {}
    for org in TRADERS:
        peer = Peer(world, org, sw_provider)
        for block, raw in zip(plan, raws):
            assert peer.store(world, block, raw) == [V] * len(block["txs"])
        reg = ChaincodeRegistry()
        reg.install(ChaincodeDefinition(CC, "1.0"), asset_private.contract())
        out[org] = Endorser("ch", peer.ledger.statedb, reg, world.msps,
                            sw_provider, world.endorsers[org],
                            transient_store=peer.transient,
                            pvt_store=peer.pvt,
                            collections=peer.collections)
        out[org].peer = peer
    return out, wm


# (fn, args, transient, client, the org whose peer simulates)
ACCEPTED = [
    ("CreateAsset", [], create("asset900", 12345, "magenta", 1), 0, "Org1"),
    ("CreateAsset", [], create("asset901"), 4, "Org2"),
    ("AgreeToTransfer", [], agree("asset3", 400), 3, "Org1"),
    ("AgreeToTransfer", [], agree("asset2", 300), 4, "Org2"),  # another buyer
    ("TransferAsset", [], transfer("asset0", "Org2"), 0, "Org1"),
    ("TransferAsset", [], transfer("asset1", "Org1"), 1, "Org2"),
    ("DeleteAsset", [], delete("asset4"), 0, "Org1"),
    ("DeleteAsset", [], delete("asset5"), 4, "Org2"),  # any client of the org
    # the sample asks only that the client's org holds an appraisal of it:
    # a buyer that agreed passes
    ("DeleteAsset", [], delete("asset1"), 0, "Org1"),
    ("DeleteTransferAgreement", [], unagree("asset0"), 1, "Org2"),
    ("ReadAsset", ["asset0"], {}, 0, "Org1"),
    ("ReadAsset", ["asset0"], {}, 1, "Org2"),
    ("ReadAssetPrivateDetails", ["Org1PrivateCollection", "asset0"], {}, 3,
     "Org1"),
    ("ReadTransferAgreement", ["asset0"], {}, 0, "Org1"),
]
REJECTED = [
    # a client of an org that is no member writes to assetCollection
    ("CreateAsset", [], create("asset902"), 2, "Org3"),
    ("CreateAsset", [], create("asset902"), 2, "Org1"),
    # the client's org is not the endorsing peer's
    ("CreateAsset", [], create("asset902"), 0, "Org2"),
    ("AgreeToTransfer", [], agree("asset3", 400), 0, "Org2"),
    ("TransferAsset", [], transfer("asset0", "Org2"), 0, "Org2"),
    ("DeleteAsset", [], delete("asset4"), 0, "Org2"),
    ("DeleteTransferAgreement", [], unagree("asset0"), 1, "Org1"),
    # the agreement is at another price than the owner's appraisal
    ("TransferAsset", [], transfer("asset2", "Org2"), 0, "Org1"),
    # not the owner's transfer (client 3 is of the owner's org)
    ("TransferAsset", [], transfer("asset0", "Org2"), 3, "Org1"),
    # no agreement; no such asset; an asset that exists already
    ("TransferAsset", [], transfer("asset4", "Org2"), 0, "Org1"),
    ("TransferAsset", [], transfer("asset77", "Org2"), 0, "Org1"),
    ("AgreeToTransfer", [], agree("asset77", 1), 1, "Org2"),
    ("CreateAsset", [], create("asset0"), 0, "Org1"),
    ("DeleteAsset", [], delete("asset3"), 0, "Org1"),   # the other org's
    ("DeleteTransferAgreement", [], unagree("asset4"), 1, "Org2"),
    # inputs: not in the transient map, not JSON, a field missing or <= 0
    ("CreateAsset", [], {}, 0, "Org1"),
    ("CreateAsset", [], {"asset_properties": "{"}, 0, "Org1"),
    ("CreateAsset", [], create("asset902", 0), 0, "Org1"),
    ("CreateAsset", [], create("asset902", 5, ""), 0, "Org1"),
    ("CreateAsset", [], create("", 5), 0, "Org1"),
    ("AgreeToTransfer", [], {"asset_value": '{"assetID":"asset3"}'}, 3,
     "Org1"),
    ("CreateAsset", ["asset902"], create("asset902"), 0, "Org1"),
    # member-only read: Org3's client, and Org2's of Org1's collection
    ("ReadAsset", ["asset0"], {}, 2, "Org1"),
    ("ReadAssetPrivateDetails", ["Org1PrivateCollection", "asset0"], {}, 1,
     "Org1"),
    # a member's peer holds no cleartext of the other org's collection
    ("ReadAssetPrivateDetails", ["Org2PrivateCollection", "asset0"], {}, 1,
     "Org1"),
    ("ReadAsset", ["asset77"], {}, 0, "Org1"),
    ("ReadTransferAgreement", ["asset4"], {}, 0, "Org1"),
    ("PurgeAsset", [], delete("asset4"), 0, "Org1"),     # left out
]


def case_id(case) -> str:
    fn, args, transient, client, peer_org = case
    what = ",".join(args) or ",".join(
        f"{k}={v}" for k, v in transient.items())
    return f"{fn}({what})by{client}at{peer_org}"[:90]


def propose(endorsers, world, case):
    fn, args, transient, client, peer_org = case
    sp = signed_proposal("ch", CC, fn, [a.encode() for a in args],
                         world.creators[client],
                         transient={k: v.encode()
                                    for k, v in transient.items()})
    return sp, endorsers[peer_org if peer_org in endorsers
                         else "Org1"].process_proposal(sp)


@pytest.mark.parametrize("case", ACCEPTED, ids=case_id)
def test_simulated_rwset_equals_the_models(endorsing, world, case):
    fn, args, transient, client, peer_org = case
    endorsers, wm = endorsing
    endorser = endorsers[peer_org]
    staged = len(endorser.peer.transient)
    sp, resp = propose(endorsers, world, case)
    assert resp.status == 200, resp.message
    want = wm.simulate(fn, args, transient, name_of(client),
                       ORGS[client % 3], peer_org)
    action = ChaincodeAction(CC, "1.0", model.rwset_of(want, CC),
                             response_payload=want["payload"].encode())
    got = serde.decode(resp.payload)["action"]
    assert serde.encode(got) == serde.encode(action.to_dict())
    # the private write-sets are staged for the commit, whole, in the
    # endorser's transient store; a read stages nothing
    txid = sp.proposal().header.channel_header.txid
    sets = endorser.peer.transient.get(txid)
    if want["private"]:
        pushed = {}
        for coll, key, value in want["private"]:
            pushed.setdefault((CC, coll), {})[key] = (
                None if value is None else value.encode())
        assert sets == [pushed]
    else:
        assert sets == [] and len(endorser.peer.transient) == staged
    endorser.peer.transient.purge_by_txids([txid])
    if fn == "TransferAsset":
        # the buyer's appraisal is read by its hash alone, at a peer
        # that is no member of the buyer's collection
        buyer = model.org_collection(
            json.loads(transient["asset_owner"])["buyerMSP"])
        assert [c for c, _, _ in want["reads"]].count(buyer) == 1
        assert not endorser.peer.pvt.has_collection(CC, buyer)


@pytest.mark.parametrize("case", REJECTED, ids=case_id)
def test_what_the_model_rejects_the_contract_rejects(endorsing, world, case):
    fn, args, transient, client, peer_org = case
    endorsers, wm = endorsing
    with pytest.raises(model.Rejected):
        wm.simulate(fn, args, transient, name_of(client), ORGS[client % 3],
                    peer_org)
    if peer_org not in endorsers:
        # Org3 runs no endorser here: its client at its own org's peer is
        # refused by the member-only write before any peer matters
        return
    before = len(endorsers[peer_org].peer.transient)
    _, resp = propose(endorsers, world, case)
    assert resp.status == 500 and resp.endorsement is None
    assert len(endorsers[peer_org].peer.transient) == before


# -- the shim ------------------------------------------------------------------

def small_stub(world, client: int, collections=None, **kwargs):
    db = StateDB()
    batch = UpdateBatch()
    batch.put(pvt_namespace(CC, "c"), hashlib.sha256(b"k").hexdigest(),
              hashlib.sha256(b"secret").digest(), Version(3, 1))
    db.apply_updates(batch, 3)
    return ChaincodeStub(db, CC, creator=world.creators[client].serialize(),
                         collections=collections, **kwargs)


def test_private_data_hash_needs_no_membership(world):
    reg = CollectionRegistry()
    reg.define(CC, CollectionConfig("c", member_orgs=("Org1",),
                                    member_only_read=True,
                                    member_only_write=True))
    stub = small_stub(world, 2, reg)             # Org3's client
    assert stub.creator_mspid() == "Org3"
    assert stub.get_private_data_hash("c", "k") == \
        hashlib.sha256(b"secret").digest()
    assert stub.get_private_data_hash("c", "absent") is None
    for refused in (lambda: stub.get_private_data("c", "k"),
                    lambda: stub.put_private_data("c", "k", b"v"),
                    lambda: stub.del_private_data("c", "k")):
        with pytest.raises(SimulationError, match="member-only"):
            refused()
    (ns_set,) = stub.rwset().ns_rwsets
    hk = hashlib.sha256(b"k").hexdigest()
    assert ns_set.namespace == "assets$c" and ns_set.writes == ()
    assert ns_set.reads == tuple(sorted(
        [KVRead(hk, Version(3, 1)),
         KVRead(hashlib.sha256(b"absent").hexdigest(), None)],
        key=lambda r: r.key))
    # a member's client, and anyone where the flags are off
    member = small_stub(world, 0, reg)
    member.put_private_data("c", "k", b"v")
    assert member.get_private_data("c", "k") == b"v"
    reg.define(CC, CollectionConfig("open", member_orgs=("Org1",)))
    small_stub(world, 2, reg).put_private_data("open", "k", b"v")
    small_stub(world, 2).put_private_data("c", "k", b"v")    # no registry


def test_the_transient_map_reaches_the_stub_and_no_envelope(endorsing, world):
    endorsers, _ = endorsing
    secret = create("asset950", 424242)
    sp = signed_proposal("ch", CC, "CreateAsset", [], world.creators[0],
                         transient={k: v.encode()
                                    for k, v in secret.items()})
    prop = sp.proposal()
    assert prop.transient == {"asset_properties":
                              secret["asset_properties"].encode()}
    # the proposal's hash, which the endorsement binds, leaves it out
    bare = Proposal(prop.header, prop.chaincode_id, prop.fn, prop.args)
    assert prop.hash() == bare.hash() and b"transient" not in bare.to_bytes()
    resp = endorsers["Org1"].process_proposal(sp)
    assert resp.status == 200, resp.message
    env = assemble_transaction(sp, [resp], world.creators[0])
    for hidden in (b"424242", b"appraisedValue", b"asset_properties",
                   b"transient"):
        assert hidden in sp.proposal_bytes
        assert hidden not in env.serialize() and hidden not in resp.payload
    txid = prop.header.channel_header.txid
    endorsers["Org1"].peer.transient.purge_by_txids([txid])
    stub = ChaincodeStub(StateDB(), CC, transient={"a": b"1"},
                         peer_mspid="Org9")
    assert stub.get_transient() == {"a": b"1"} and stub.peer_mspid == "Org9"
    stub.get_transient()["a"] = b"2"             # a copy
    assert stub.get_transient() == {"a": b"1"}
    assert ChaincodeStub(StateDB(), CC).get_transient() == {}


# -- a collection's own endorsement policy --------------------------------------

def hand_made(world, collection_writes: dict, client: int, endorser: str,
              nonce: int, public: bool = False, reads=()) -> bytes:
    """An envelope writing one hashed key under each named collection
    (and, with `public`, one key of the chaincode's own namespace),
    endorsed by `endorser`'s peer alone."""
    sets = [NsRwSet(pvt_namespace(CC, coll),
                    reads=tuple(KVRead(k, v) for c, k, v in reads
                                if c == coll),
                    writes=tuple(KVWrite(key, b"\x01" * 32)
                                 for c, key in collection_writes.items()
                                 if c == coll))
            for coll in sorted(set(collection_writes)
                               | {c for c, _, _ in reads})]
    if public:
        sets.insert(0, NsRwSet(CC, writes=(KVWrite("pub", b"1"),)))
    return build.endorser_tx(
        "ch", CC, "1.0", TxRwSet(tuple(sets)), world.creators[client],
        [world.endorsers[endorser]], nonce=b"%024d" % nonce).serialize()


def raw_block(number: int, prev: bytes, data: list):
    from fabric_tpu.protocol import block_header_hash
    from fabric_tpu.protocol.types import (Block, BlockHeader, BlockMetadata,
                                           block_data_hash)
    header = BlockHeader(number, prev, block_data_hash(data))
    return (Block(header, data, BlockMetadata()).serialize(),
            block_header_hash(header))


@pytest.mark.parametrize("tail", ["deep", "classic"])
def test_the_collections_policy_decides_a_write_to_it(world, sw_provider,
                                                      tail):
    peer = Peer(world, "Org3", sw_provider)
    if tail == "classic":
        peer.coordinator.validator.force_python_collect = True
    o1, o2 = "Org1PrivateCollection", "Org2PrivateCollection"
    cases = [
        ({o1: "a"}, "Org1", V),          # its own org's peer
        ({o1: "b"}, "Org2", POLICY),     # the chaincode's OR would pass
        ({o2: "c"}, "Org2", V),
        ({o2: "d"}, "Org1", POLICY),
        ({A: "e"}, "Org1", V),           # no policy of its own:
        ({A: "f"}, "Org2", V),           # the chaincode's
        ({A: "g"}, "Org3", POLICY),
        ({A: "h", o1: "i"}, "Org1", V),
        ({A: "j", o1: "k"}, "Org2", POLICY),     # one collection fails it
        ({o1: "l", o2: "m"}, "Org1", POLICY),
        ({}, "Org2", V),                 # the chaincode's namespace alone
        ({}, "Org3", POLICY),
    ]
    data = [hand_made(world, writes, 0, endorser, n, public=not writes)
            for n, (writes, endorser, _) in enumerate(cases)]
    raw, _ = raw_block(0, GENESIS, data)
    before = registry.counter("validator_tail_total").value(
        channel="ch", tail=tail, reason="no_sbe" if tail == "deep"
        else "forced")
    peer.coordinator.store_block(wire.parse_block(raw))
    assert stored_flags(peer.ledger, 0) == [code for _, _, code in cases]
    assert registry.counter("validator_tail_total").value(
        channel="ch", tail=tail, reason="no_sbe" if tail == "deep"
        else "forced") - before == len(cases)
    held = peer.hashed_state()
    assert sorted(k for _, k in held) == list("acefhi")
    # a registry without the collection's policy falls to the chaincode's,
    # and one that knows neither to its default
    plain = PolicyRegistry(parse_policy("OR('Org3.member')"))
    assert plain.policy_for("assets$" + o1) is plain.policy_for("other")
    plain.set_policy(CC, parse_policy(OR2))
    assert plain.policy_for("assets$" + o1) is plain.policy_for(CC)


# -- expiry --------------------------------------------------------------------

class Expiring:
    """A ledger whose collection `short` lives 2 blocks and `forever`
    has no block-to-live, fed hand-made blocks."""

    BTL = {"assets$short": 2}

    def __init__(self, world, provider, root=None):
        self.world, self.provider, self.root = world, provider, root
        self.prev, self.n = GENESIS, 0
        self.open()

    def open(self):
        policies = PolicyRegistry(parse_policy(OR2))
        self.ledger = KVLedger("ch", LedgerConfig(root=self.root,
                                                  pvt_btl=dict(self.BTL)))
        self.committer = Committer(
            self.ledger, TxValidator("ch", self.world.msps, self.provider,
                                     policies))

    def block(self, *txs) -> list:
        """Each tx: ({collection: key}, reads).  -> the block's flags."""
        data = [hand_made(self.world, writes, 0, "Org1", 100 * self.n + i,
                          public=not writes, reads=reads)
                for i, (writes, reads) in enumerate(txs)]
        raw, self.prev = raw_block(self.n, self.prev, data)
        self.committer.store_block(wire.parse_block(raw))
        self.n += 1
        return stored_flags(self.ledger, self.n - 1)

    def has(self, coll: str, key: str) -> bool:
        return self.ledger.get_state("assets$" + coll, key) is not None

    def pending(self) -> list:
        return [k for k, _ in self.ledger.range_query(EXPIRY_NS, "", "")]


def expired_total() -> float:
    return registry.counter("ledger_pvt_expired_keys_total").value(
        channel="ch")


def test_a_hashed_key_is_gone_at_n_plus_btl_plus_1_and_not_before(
        world, sw_provider):
    led = Expiring(world, sw_provider)
    before = expired_total()
    assert led.block(({"short": "a", "forever": "z"}, ())) == [V]   # 0
    assert led.pending() == ["%016x%016x" % (3, 0)]
    assert led.block(({"short": "b"}, ())) == [V]                   # 1
    assert led.block(({}, ())) == [V]                               # 2
    assert led.has("short", "a") and led.has("short", "b")
    assert expired_total() == before
    span = led.ledger.last_stats.span_attrs["ledger.pvt_expiry"]
    assert span == {"expired": 0}
    # block 3 = 0 + 2 + 1: a transaction of it still reads `a` at its
    # version; the key leaves with the block's commit
    read_a = [("short", "a", Version(0, 0))]
    assert led.block(({"forever": "y"}, read_a)) == [V]             # 3
    assert not led.has("short", "a") and led.has("short", "b")
    assert led.has("forever", "z") and led.has("forever", "y")
    assert expired_total() == before + 1
    assert led.ledger.last_stats.span_attrs["ledger.pvt_expiry"] == {
        "expired": 1}
    assert ("ledger.pvt_expiry" in
            [name for name, _, _ in led.ledger.last_stats.phase_spans])
    assert led.ledger.last_stats.pvt_expiry_s > 0
    # ordered after the purge, the same read is a conflict
    assert led.block(({"forever": "x"}, read_a),                    # 4
                     ({"forever": "w"}, [("short", "a", None)])) == [MVCC, V]
    assert not led.has("short", "b") and not led.has("forever", "x")
    assert led.pending() == []
    assert expired_total() == before + 2
    # the deletes are no transaction's: the key's history is its one write
    assert len(led.ledger.get_history("assets$short", "a")) == 1
    # a collection without a block-to-live never expires
    for _ in range(4):
        led.block(({}, ()))
    assert led.has("forever", "z") and led.has("forever", "w")
    _, seconds, blocks = registry.histogram(
        "ledger_pvt_expiry_seconds").state()
    assert blocks >= led.n and seconds > 0


def test_a_rewrite_moves_the_expiry_and_the_expiring_blocks_own_write_stands(
        world, sw_provider):
    led = Expiring(world, sw_provider)
    led.block(({"short": "a"}, ()), ({"short": "c"}, ()))           # 0
    led.block(({"short": "a"}, ()))                 # 1: written again
    led.block(({}, ()))                                             # 2
    # block 3 expires block 0's writes: `c` goes, `a` (now block 1's)
    # stays; and block 3 itself writes `c`, which therefore stands
    assert led.block(({"short": "c"}, ())) == [V]                   # 3
    assert led.has("short", "a") and led.has("short", "c")
    assert led.ledger.statedb.get_version("assets$short", "c") == \
        Version(3, 0)
    led.block(({}, ()))                             # 4 = 1 + 2 + 1
    assert not led.has("short", "a") and led.has("short", "c")
    led.block(({}, ()))                                             # 5
    led.block(({}, ()))                             # 6 = 3 + 2 + 1
    assert not led.has("short", "c") and led.pending() == []
    # the private store's own purge, by the same rule
    pvt = PvtDataStore()
    btl = {("assets", "short"): 2}
    pvt.commit(0, {("assets", "short"): {"a": b"1", "c": b"1"}}, btl)
    pvt.commit(1, {("assets", "short"): {"a": b"2"}}, btl)
    assert [pvt.process_purges(n) for n in (1, 2)] == [0, 0]
    pvt.commit(3, {("assets", "short"): {"c": b"3"}}, btl)
    assert pvt.process_purges(3) == 0
    assert pvt.get("assets", "short", "a") == b"2"
    assert pvt.process_purges(4) == 1 and pvt.has_collection("assets",
                                                             "short")
    assert pvt.process_purges(5) == 0 and pvt.process_purges(6) == 1
    assert not pvt.has_collection("assets", "short") and pvt.keys() == []


def test_the_expiry_index_survives_a_reopen(world, sw_provider, tmp_path):
    led = Expiring(world, sw_provider, root=str(tmp_path))
    led.block(({"short": "a"}, ()))                                 # 0
    led.block(({"short": "b"}, ()))                                 # 1
    pending = led.pending()
    assert len(pending) == 2
    led.open()                   # a restart: state and index from disk
    assert led.pending() == pending
    led.block(({}, ()))                                             # 2
    led.block(({}, ()))                                             # 3
    assert not led.has("short", "a") and led.has("short", "b")
    # a state lost behind the blocks: recovery replays the expiry too
    import shutil
    shutil.rmtree(os.path.join(str(tmp_path), "ch", "state"))
    led.open()
    assert led.ledger.last_recovery["replayed_blocks"] == 4
    assert not led.has("short", "a") and led.has("short", "b")
    assert led.pending() == ["%016x%016x" % (4, 1)]
    led.block(({}, ()))                                             # 4
    assert not led.has("short", "b") and led.pending() == []


def test_a_channel_without_collections_has_no_expiry_step(world,
                                                         sw_provider):
    policies = PolicyRegistry(parse_policy(OR2))
    ledger = KVLedger("ch", LedgerConfig())
    committer = Committer(ledger, TxValidator("ch", world.msps, sw_provider,
                                              policies))
    raw, _ = raw_block(0, GENESIS, [hand_made(world, {"short": "a"}, 0,
                                              "Org1", 1)])
    before = expired_total()
    committer.store_block(wire.parse_block(raw))
    assert stored_flags(ledger, 0) == [V]
    assert "ledger.pvt_expiry" not in ledger.last_stats.span_attrs
    assert ledger.last_stats.pvt_expiry_s == 0.0
    assert list(ledger.range_query(EXPIRY_NS, "", "")) == []
    assert expired_total() == before


# -- seeded chains from wire bytes through three committers ---------------------

SERIES = [("ledger_pvt_expired_keys_total", {}),
          ("privdata_txs_total", {"result": "resolved"}),
          ("privdata_txs_total", {"result": "not_member"}),
          ("privdata_txs_total", {"result": "missing"}),
          ("privdata_decoded_txs_total", {}),
          ("privdata_purged_keys_total", {}),
          ("privdata_fetch_total", {})]


def counters() -> list:
    return [registry.counter(name).value(channel="ch", **labels)
            for name, labels in SERIES]


@pytest.mark.parametrize("seed", SEEDS)
def test_chain_through_three_committers_equals_the_model(world, sw_provider,
                                                         plans, seed):
    plan = plans[seed]
    raws = world.raw_blocks(plan)
    for raw in raws:
        assert b"appraisedValue" not in raw      # no private value on chain
    final = model.replay_plan(plan, orgs=ORGS)
    want_hashed = {k: (vh, ver) for k, (vh, ver) in final.hashed.items()}
    seen = {org: model.counts(plan, org, TRADERS) for org in ORGS}
    assert seen["Org3"]["sets_resolved"] == 0
    peers = {}
    for org in ORGS:
        peer = peers[org] = Peer(world, org, sw_provider)
        before = counters()
        _, resolve_s0, resolves0 = registry.histogram(
            "privdata_resolve_seconds").state()
        purged = 0
        for block, raw in zip(plan, raws):
            assert peer.store(world, block, raw) == block["codes"], (
                org, block["number"])
            assert peer.ledger.last_stats.span_attrs[
                "ledger.pvt_expiry"] == {"expired": len(block["expired"])}
        moved = [a - b for a, b in zip(counters(), before)]
        _, resolve_s, resolves = registry.histogram(
            "privdata_resolve_seconds").state()
        want = seen[org]
        # what each org's private store dropped: the expired keys of the
        # collections it is a member of
        purged = sum(1 for b in plan for coll, _ in b["expired"]
                     if org in model.collections(TRADERS)[coll]["members"])
        assert moved == [want["expired_keys"], want["sets_resolved"],
                         want["sets_not_member"], 0,
                         want["private_writers"], purged, 0]
        assert resolves - resolves0 == want["sets_resolved"]
        assert (resolve_s > resolve_s0) == bool(want["sets_resolved"])
        assert peer.coordinator.missing == []
        assert peer.hashed_state() == want_hashed
        assert peer.private_view() == final.views[org]
        # the transient store keeps no committed transaction's entry
        valid = {model.txid_of(tx, world.creators) for b in plan
                 for tx, code in zip(b["txs"], b["codes"]) if code == V}
        assert not any(peer.transient.get(txid) for txid in valid)
    assert peers["Org3"].pvt.keys() == [] and len(peers["Org3"].transient) == 0
    assert len({p.ledger.commit_hash for p in peers.values()}) == 1
    # the views: every live asset and agreement with both members, an
    # org's appraisals with it alone, none outliving its block-to-live
    v1, v2 = final.views["Org1"], final.views["Org2"]
    assert {k: v for k, v in v1.items() if k[0] == A} == \
        {k: v for k, v in v2.items() if k[0] == A}
    assert {c for c, _ in v1} == {A, "Org1PrivateCollection"}
    assert {c for c, _ in v2} == {A, "Org2PrivateCollection"}
    last = plan[-1]["number"]
    for view in (v1, v2):
        for (coll, key), _ in view.items():
            if coll != A:
                written = final.hashed[coll, model.hash_key(key)][1][0]
                assert last - written <= 3
    codes = {c for b in plan for c in b["codes"]}
    assert codes == {V, POLICY, MVCC}


def test_a_member_that_was_pushed_nothing_records_the_sets_missing(
        world, sw_provider, plans):
    plan = plans[SEEDS[1]][:2]
    raws = world.raw_blocks(plan)
    peer = Peer(world, "Org1", sw_provider)
    fetched = []
    peer.coordinator.fetch = lambda *a: fetched.append(a)
    before = counters()
    for block, raw in zip(plan, raws):
        peer.coordinator.store_block(wire.parse_block(raw))
    want = model.counts(plan, "Org1", TRADERS)
    moved = [a - b for a, b in zip(counters(), before)]
    assert moved[1:4] == [0, want["sets_not_member"], want["sets_resolved"]]
    assert moved[6] == len(fetched) == want["sets_resolved"]
    assert len(peer.coordinator.missing) == want["sets_resolved"]
    assert peer.pvt.keys() == []
    # the hashes are on the chain all the same
    assert len(peer.hashed_state()) == 2 * 120
