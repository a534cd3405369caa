"""Assets that carry their owner's endorsement policy — the contract
`asset_sbe` (fabric-samples' asset-transfer-sbe) against its plain model
(`fabric_tpu/testing/asset_sbe_model.py`): what the endorser's simulate
records, function by function; a seeded chain of load + 6 run-phase
blocks through a peer in library form under both providers — flags,
state, parameters, commit hash, the tail every block took and the
key-level counters; and, case by case on every supplier of the commit's
batch, what a delete does to a key's validation parameter.
"""

import hashlib
import json
import os

os.environ.setdefault("JAX_PLATFORMS", "cpu")

import pytest

from fabric_tpu.bccsp.factory import FactoryOpts, init_factories
from fabric_tpu.chaincode import (ChaincodeDefinition, ChaincodeRegistry,
                                  asset_sbe)
from fabric_tpu.committer import Committer, PolicyRegistry, TxValidator
from fabric_tpu.committer import sbe
from fabric_tpu.endorser import Endorser, signed_proposal
from fabric_tpu.ledger import KVLedger, LedgerConfig, mvcc
from fabric_tpu.ledger.kvledger import _safe_envelopes
from fabric_tpu.msp import CachedMSP
from fabric_tpu.msp.ca import DevOrg
from fabric_tpu.ops_plane import registry, tracing
from fabric_tpu.policy import parse_policy
from fabric_tpu.protocol import (Block, KVRead, KVWrite, NsRwSet, TxFlags,
                                 TxRwSet, build, wire)
from fabric_tpu.protocol.types import META_TXFLAGS, ChaincodeAction
from fabric_tpu.testing import asset_sbe_model as model
from fabric_tpu.utils import serde

CC = "asset_sbe"
META = CC + "#meta"
ORGS = ("Org1", "Org2", "Org3")
AND3 = "AND('Org1.member', 'Org2.member', 'Org3.member')"
GENESIS = b"\x00" * 32
V, POLICY, MVCC = model.VALID, model.POLICY_FAILURE, model.MVCC_CONFLICT
# 200 assets in two load blocks, six blocks of 100 of the mix, 12
# clients, one envelope in 10 tampered
CHAIN = (2**31 + 39, 200, 6, 100, 12, 10)


@pytest.fixture(scope="module", autouse=True)
def sw_provider():
    return init_factories(FactoryOpts(default="SW"))


class World:
    """Three orgs under AND, one endorsing peer each, twelve clients
    enrolled org by org in turn."""

    def __init__(self):
        self.orgs = [DevOrg(o) for o in ORGS]
        self.msps = {o.mspid: CachedMSP(o.msp()) for o in self.orgs}
        self.endorsers = {o.mspid: o.new_identity(f"peer{o.mspid}")
                          for o in self.orgs}
        self.creators = [self.orgs[i % 3].new_identity(f"client{i}")
                         for i in range(12)]

    def raw_blocks(self, plan, prev=GENESIS) -> list:
        raws = []
        for block in plan:
            raw, prev = model.build_block(block, prev, "ch", CC,
                                          self.endorsers, self.creators)
            raws.append(raw)
        return raws

    def committer(self, provider, blind=False) -> Committer:
        """A validator built as node/peer.py builds it."""
        policies = PolicyRegistry()
        policies.set_policy(CC, parse_policy(AND3))
        ledger = KVLedger("ch", LedgerConfig())
        return Committer(ledger, TxValidator(
            "ch", self.msps, provider, policies,
            sbe_lookup=None if blind else sbe.statedb_lookup(ledger.statedb),
            sbe_state=ledger.statedb.meta_keys))


@pytest.fixture(scope="module")
def world():
    return World()


def stored_flags(ledger, number: int) -> list:
    return list(ledger.blockstore.get_by_number(number)
                .metadata.items[META_TXFLAGS])


def held(get_state, ids) -> dict:
    """{id: (record text | None, parameter bytes | None)} as a state
    holds them."""
    out = {}
    for key in ids:
        raw, param = get_state(CC, key), get_state(META, key)
        out[key] = (None if raw is None else raw.decode(), param)
    return out


def models_state(world_model, ids) -> dict:
    out = {}
    for key in ids:
        a = world_model.assets.get(key)
        org = world_model.params.get(key)
        out[key] = (
            None if a is None else model.record(a["ID"], a["Value"],
                                                a["Owner"], a["OwnerOrg"]),
            None if org is None else model.parameter_bytes(org))
    return out


def all_ids(plan) -> list:
    return sorted({tx["args"][0] for b in plan for tx in b["txs"]})


SBE_SERIES = [("validator_sbe_keys_total", {"judged": j})
              for j in ("parameter", "namespace", "overlay")] + [
    ("validator_sbe_failures_total", {}), ("validator_sbe_policies_total", {})]


def sbe_counters() -> list:
    return [registry.counter(name).value(channel="ch", **labels)
            for name, labels in SBE_SERIES]


def tails() -> dict:
    c = registry.counter("validator_tail_total")
    return {(t, r): c.value(channel="ch", tail=t, reason=r)
            for t, r in [("deep", "no_sbe"), ("classic", "state_meta"),
                         ("classic", "block_meta")]}


# -- the chain is what the issue says it is -----------------------------------

def test_the_chain_is_a_pure_function_of_the_seed_and_holds_every_case():
    plan = model.plan_chain(*CHAIN)
    assert plan == model.plan_chain(*CHAIN)
    assert plan != model.plan_chain(CHAIN[0] + 1, *CHAIN[1:])
    assert [b["codes"] for b in plan[:2]] == [[V] * 100] * 2
    run = plan[2:]
    kinds = {tx["kind"] for b in run for tx in b["txs"]}
    assert kinds == {k for k, _ in model.MIX}
    shapes = {(tx["kind"], len(tx["reads"]), len(tx["writes"]),
               tx["param"] is not None, len(tx["endorsers"]))
              for b in plan for tx in b["txs"]}
    assert shapes == {("create", 1, 1, True, 3), ("update", 1, 1, False, 1),
                      ("wrong_org", 1, 1, False, 1),
                      ("transfer", 1, 1, True, 1),
                      ("delete", 1, 1, False, 1)}
    seen = model.counts(run)
    for what in ("wrong_org_failures", "overlay_failures", "mvcc_conflicts",
                 "deletes", "recreates"):
        assert seen[what] > 0, what
    # every wrong-org attempt fails, with every signature valid
    for b in run:
        for tx, code in zip(b["txs"], b["codes"]):
            if tx["kind"] == "wrong_org":
                assert code == POLICY
                assert tx["tampered"] or tx["cause"] == "wrong_org"
    # owners fall org by org in turn
    world = model.replay_plan(plan[:2], ORGS)
    by_org = [sum(1 for a in world.assets.values() if a["OwnerOrg"] == o)
              for o in ORGS]
    assert by_org == [67, 67, 66]
    assert len(world.params) == 200


def test_the_models_parameter_is_the_contracts():
    for org in ORGS:
        assert model.parameter_bytes(org) == sbe.encode_policy(
            asset_sbe.owner_policy(org))


# -- the endorser's simulate against the model's ------------------------------

# (fn, args, client): client i belongs to ORGS[i % 3]
ACCEPTED = [
    ("CreateAsset", ["asset900", "17", "client@Org2"], 1),
    ("CreateAsset", ["asset901", "0", "someone"], 2),
    ("ReadAsset", ["asset1"], 0),
    ("UpdateAsset", ["asset1", "5"], 0),
    ("UpdateAsset", ["asset1", "5"], 1),        # not the owner's org: the
                                                # contract asks nothing
    ("TransferAsset", ["asset2", "client1@Org3", "Org3"], 1),
    ("TransferAsset", ["asset2", "client@Org1", "Org1"], 2),
    ("DeleteAsset", ["asset3"], 2),
    ("DeleteAsset", ["asset3"], 0),
    ("AssetExists", ["asset3"], 0),
    ("AssetExists", ["asset77"], 0),
]
REJECTED = [
    ("CreateAsset", ["asset1", "5", "someone"], 0),          # exists
    ("CreateAsset", ["asset902", "five", "someone"], 0),     # not a value
    ("ReadAsset", ["asset77"], 0),
    ("UpdateAsset", ["asset77", "5"], 0),
    ("UpdateAsset", ["asset1", "five"], 0),
    ("TransferAsset", ["asset77", "someone", "Org2"], 0),
    ("DeleteAsset", ["asset77"], 0),
    ("BurnAsset", ["asset1"], 0),                            # no such function
]


@pytest.fixture(scope="module")
def endorsing(world, sw_provider):
    """(endorser, the model's registry): six assets created in block 0,
    on a ledger and in the model alike."""
    reg_model = model.Registry(ORGS)
    txs = [dict(reg_model.simulate(
                    "CreateAsset", [model.asset_key(i), 100 + i,
                                    model.enrolment_name(i - 1, ORGS)],
                    ORGS[(i - 1) % 3]),
                kind="create", creator=i - 1, endorsers=list(ORGS),
                tampered=False, nonce="%048x" % i)
           for i in range(1, 7)]
    assert reg_model.commit_block(0, txs) == [V] * 6
    committer = world.committer(sw_provider)
    for raw in world.raw_blocks([{"number": 0, "txs": txs}]):
        committer.store_block(wire.parse_block(raw))
    assert stored_flags(committer.ledger, 0) == [V] * 6
    reg = ChaincodeRegistry()
    reg.install(ChaincodeDefinition(CC, "1.0"), asset_sbe.contract())
    endorser = Endorser("ch", committer.ledger.statedb, reg, world.msps,
                        sw_provider, world.endorsers["Org1"])
    return endorser, reg_model


def case_id(case) -> str:
    fn, args, client = case
    return f"{fn}({','.join(args)})by{client}"


@pytest.mark.parametrize("case", ACCEPTED, ids=case_id)
def test_simulated_rwset_equals_the_models(endorsing, world, case):
    fn, args, client = case
    endorser, reg_model = endorsing
    sp = signed_proposal("ch", CC, fn, [a.encode() for a in args],
                         world.creators[client])
    resp = endorser.process_proposal(sp)
    assert resp.status == 200, resp.message
    want = reg_model.simulate(fn, args, ORGS[client % 3])
    action = ChaincodeAction(CC, "1.0", model.rwset_of(want, CC),
                             response_payload=want["payload"].encode())
    got = serde.decode(resp.payload)["action"]
    assert serde.encode(got) == serde.encode(action.to_dict())
    namespaces = [ns["namespace"] for ns in got["rwset"]["ns"]]
    assert namespaces == ([CC, META] if want["param"] else [CC])


@pytest.mark.parametrize("case", REJECTED, ids=case_id)
def test_what_the_model_rejects_the_contract_rejects(endorsing, world, case):
    fn, args, client = case
    endorser, reg_model = endorsing
    with pytest.raises(model.Rejected):
        reg_model.simulate(fn, args, ORGS[client % 3])
    sp = signed_proposal("ch", CC, fn, [a.encode() for a in args],
                         world.creators[client])
    resp = endorser.process_proposal(sp)
    assert resp.status == 500 and resp.endorsement is None


# -- the seeded chain on the normal path, both providers ----------------------

@pytest.fixture(scope="module")
def provisioned(tmp_path_factory):
    """A provisioned three-org network with the contract under AND, its
    identities, and the chain built with them, as block files."""
    from fabric_tpu.node.orderer import load_signing_identity
    from fabric_tpu.node.provision import provision_network
    base = str(tmp_path_factory.mktemp("asset_sbe_net"))
    net = provision_network(
        base, n_orderers=1, peer_orgs=list(ORGS), clients_per_org=4,
        chaincodes=[{"name": CC, "version": "1.0", "contract": "asset_sbe",
                     "policy": AND3}])

    def identity(path):
        with open(path) as f:
            cfg = json.load(f)
        return load_signing_identity(cfg["mspid"], cfg["cert_pem"].encode(),
                                     cfg["key_pem"].encode())
    peers = [identity(p) for p in net["peers"]]
    endorsers = {p.mspid: p for p in peers}
    creators = [identity(net["client_pool"][ORGS[i % 3]][i // 3])
                for i in range(12)]
    plan = model.plan_chain(*CHAIN)
    paths, prev = [], GENESIS
    for block in plan:
        raw, prev = model.build_block(block, prev, "ch", CC, endorsers,
                                      creators)
        paths.append(os.path.join(base, "block_%d.bin" % block["number"]))
        with open(paths[-1], "wb") as f:
            f.write(raw)
    return net, plan, paths


REPLAYS = {}


@pytest.mark.parametrize("bccsp", ["SW", "JAXTPU"])
def test_chain_through_replay_equals_the_model(provisioned, tmp_path, bccsp):
    """`testing/replay.py`: a peer in library form — the contract found
    by name, the validator as the node builds it — under the software
    provider and the device provider (here on the CPU backend)."""
    from fabric_tpu.testing.replay import replay
    net, plan, paths = provisioned
    with open(net["peers"][0]) as f:
        cfg = json.load(f)
    cfg.update(bccsp=bccsp, bccsp_degrade=False, data_dir=str(tmp_path))
    cfg.pop("verify_once", None)
    ids = all_ids(plan)
    seen = {}
    counters0, tails0 = sbe_counters(), tails()

    def on_block(node, i, store):
        record = store()
        if i == len(paths) - 1:
            seen["state"] = held(node.ledger.get_state, ids)
            seen["meta_keys"] = node.ledger.statedb.meta_keys()
            seen["gauge"] = registry.gauge("ledger_state_meta_keys").value(
                channel="ch")
        return record

    try:
        report = replay(cfg, paths, on_block=on_block)
    finally:
        init_factories(FactoryOpts(default="SW"))
    assert report["provider"]["name"] == bccsp.lower()
    signatures = model.counts(plan)["signatures"]
    if bccsp == "JAXTPU":       # the device provider did the verifying
        stats = report["provider"]["stats"]
        assert stats["fallbacks"] == 0
        assert stats["device_sigs"] >= signatures
    assert report["height"] == len(plan)
    for block, got in zip(plan, report["blocks"]):
        assert list(bytes.fromhex(got["flags"])) == block["codes"], \
            block["number"]
    final = model.replay_plan(plan, ORGS)
    assert seen["state"] == models_state(final, ids)
    assert seen["meta_keys"] == (len(plan) - 1, len(final.params))
    assert seen["gauge"] == len(final.params)
    # every block on the classic tail: the first because it sets the
    # channel's first parameters, the rest because the state holds some
    moved = {k: int(v - tails0[k]) for k, v in tails().items()}
    assert moved == {("deep", "no_sbe"): 0, ("classic", "block_meta"): 100,
                     ("classic", "state_meta"): 100 * (len(plan) - 1)}
    # the key-level counters are the model's tallies
    want = [sum(b["tally"][k] for b in plan)
            for k in ("parameter", "namespace", "overlay", "failures",
                      "policies")]
    assert [int(a - b) for a, b in zip(sbe_counters(), counters0)] == want
    assert want[2] > 0 and want[3] > 0
    REPLAYS[bccsp] = (report["commit_hash"],
                      [b["flags"] for b in report["blocks"]], seen["state"])


def test_both_providers_end_with_the_same_chain_and_state():
    if set(REPLAYS) != {"SW", "JAXTPU"}:
        pytest.skip("needs both replays of this module")
    assert REPLAYS["SW"] == REPLAYS["JAXTPU"]


def test_gate_span_carries_the_blocks_key_level_numbers(world, sw_provider):
    plan = model.plan_chain(*CHAIN)[:4]
    committer = world.committer(sw_provider)
    was = tracing.tracer.enabled
    tracing.tracer.enabled = True
    try:
        for raw in world.raw_blocks(plan):
            committer.store_block(wire.parse_block(raw))
        gates = [s for rec in tracing.tracer.recorder.list()["recent"]
                 for s in tracing.tracer.recorder.get(rec["trace_id"])["spans"]
                 if s["name"] == "validator.gate"
                 and "sbe_keys" in s["attributes"]]
    finally:
        tracing.tracer.enabled = was
    by_block = {s["attributes"]["block"]: s["attributes"] for s in gates}
    for block in plan:
        t, a = block["tally"], by_block[block["number"]]
        assert (a["sbe_keys"], a["sbe_overlay"], a["sbe_failures"],
                a["sbe_policies"]) == (
            t["parameter"] + t["namespace"] + t["overlay"], t["overlay"],
            t["failures"], t["policies"])


def test_a_blind_validator_fails_every_owner_endorsed_update(world,
                                                             sw_provider):
    """The control the verifier's answers cannot satisfy: without the
    committed-parameter lookup every single-endorser transaction fails
    AND of three, though every signature is valid."""
    plan = model.plan_chain(*CHAIN)[:3]
    committer = world.committer(sw_provider, blind=True)
    for raw in world.raw_blocks(plan):
        committer.store_block(wire.parse_block(raw))
    got = stored_flags(committer.ledger, 2)
    single = [n for n, tx in enumerate(plan[2]["txs"])
              if len(tx["endorsers"]) == 1]
    assert len(single) > 80
    assert all(got[n] == POLICY for n in single)
    assert got != plan[2]["codes"]


# -- case by case, on every supplier of the commit's batch --------------------

def txs_of(reg_model, calls, start=0):
    """[(fn, args, client, endorsing orgs)] simulated against the
    model's CURRENT state, as a block cut under load holds them."""
    return [dict(reg_model.simulate(fn, args, ORGS[client % 3]),
                 kind="case", creator=client, endorsers=list(orgs),
                 tampered=False, nonce="%048x" % (start + n))
            for n, (fn, args, client, orgs) in enumerate(calls)]


SOURCES = ["lanes", "lanes-python", "envelopes"]


@pytest.fixture(autouse=True)
def the_walk_its_source_names(request, monkeypatch):
    """"lanes-python": the lane table walked one Python iteration a
    transaction, through the rule's seam (`mvcc.walk_of`: what a
    `native/fastmvcc.c` that did not build leaves) — "lanes" is walked as
    arrays."""
    params = getattr(getattr(request.node, "callspec", None), "params", {})
    if params.get("source") == "lanes-python":
        monkeypatch.setattr(mvcc, "_fastmvcc", None)


def block_for(source: str, raw: bytes):
    """The same bytes as the deliver loop hands them (a BlockView: the
    lane source) or decoded whole (the envelope source)."""
    return wire.parse_block(raw) if source.startswith("lanes") \
        else Block.deserialize(raw)


def run_blocks(world, provider, source, reg_model, stream):
    """Commit each [(fn, args, client, orgs)] list as one block on a
    fresh ledger and in the model; -> (committer, [codes a block])."""
    committer = world.committer(provider)
    prev, out, nonce = GENESIS, [], 0
    for number, calls in enumerate(stream):
        txs = txs_of(reg_model, calls, nonce)
        nonce += len(txs)
        want = reg_model.commit_block(number, txs)
        raw, prev = model.build_block({"number": number, "txs": txs}, prev,
                                      "ch", CC, world.endorsers,
                                      world.creators)
        committer.store_block(block_for(source, raw))
        got = stored_flags(committer.ledger, number)
        assert got == want, number
        span = committer.ledger.last_stats.span_attrs["ledger.mvcc"]
        assert (span["source"], span["walk"]) == {
            "lanes": ("lanes", "arrays"), "lanes-python": ("lanes", "python"),
            "envelopes": ("envelopes", "python")}[source]
        out.append(got)
    return committer, out


CREATE_A = ("CreateAsset", ["a", "1", "client@Org1"], 0, ORGS)   # Org1's


@pytest.mark.parametrize("source", SOURCES)
def test_same_block_transfer_then_the_old_owners_update_fails(
        world, sw_provider, source):
    reg_model = model.Registry(ORGS)
    committer, codes = run_blocks(world, sw_provider, source, reg_model, [
        [CREATE_A, ("CreateAsset", ["b", "1", "client@Org1"], 0, ORGS)],
        [("TransferAsset", ["a", "client@Org2", "Org2"], 0, ["Org1"]),
         ("UpdateAsset", ["a", "2"], 0, ["Org1"]),    # judged under Org2's
         ("UpdateAsset", ["a", "3"], 1, ["Org2"]),    # passes; MVCC decides
         ("UpdateAsset", ["b", "4"], 0, ["Org1"])]])
    assert codes[1] == [V, POLICY, MVCC, V]
    assert held(committer.ledger.get_state, ["a"])["a"] == (
        model.record("a", 1, "client@Org2", "Org2"),
        model.parameter_bytes("Org2"))
    # the next block: the roles have swapped
    txs = txs_of(reg_model, [("UpdateAsset", ["a", "5"], 0, ["Org1"]),
                             ("UpdateAsset", ["a", "6"], 1, ["Org2"])], 50)
    raw, _ = model.build_block(
        {"number": 2, "txs": txs},
        committer.ledger.blockstore.chain_info().current_hash, "ch", CC,
        world.endorsers, world.creators)
    committer.store_block(block_for(source, raw))
    assert stored_flags(committer.ledger, 2) == [POLICY, V]


@pytest.mark.parametrize("source", SOURCES)
def test_wrong_org_update_fails_with_every_signature_valid(
        world, sw_provider, source):
    reg_model = model.Registry(ORGS)
    failures = registry.counter("validator_sbe_failures_total")
    before = failures.value(channel="ch")
    _, codes = run_blocks(world, sw_provider, source, reg_model, [
        [CREATE_A],
        [("UpdateAsset", ["a", "2"], 1, ["Org2"]),
         ("UpdateAsset", ["a", "3"], 2, ["Org2", "Org3"]),
         ("UpdateAsset", ["a", "4"], 0, ["Org1"])]])
    assert codes[1] == [POLICY, POLICY, V]
    assert failures.value(channel="ch") - before == 2


@pytest.mark.parametrize("source", SOURCES)
def test_delete_drops_the_parameter_and_recreate_is_under_the_chaincode_policy(
        world, sw_provider, source):
    reg_model = model.Registry(ORGS)
    committer, codes = run_blocks(world, sw_provider, source, reg_model, [
        [CREATE_A, ("CreateAsset", ["b", "1", "client@Org2"], 1, ORGS)],
        [("DeleteAsset", ["a"], 0, ["Org1"]),
         # later in the block the key has no parameter any more: its old
         # owner's update fails AND of three before MVCC looks at it
         ("UpdateAsset", ["a", "2"], 0, ["Org1"])],
        # re-created by another org, under the chaincode policy: one
        # endorsement is not enough, three are
        [("CreateAsset", ["a", "7", "client@Org3"], 2, ["Org3"]),
         ("CreateAsset", ["a", "7", "client@Org3"], 2, ORGS)],
        [("UpdateAsset", ["a", "8"], 0, ["Org1"]),
         ("UpdateAsset", ["a", "9"], 2, ["Org3"])]])
    assert codes[1:] == [[V, POLICY], [POLICY, V], [POLICY, V]]
    db = committer.ledger.statedb
    assert held(committer.ledger.get_state, ["a"])["a"] == (
        model.record("a", 9, "client@Org3", "Org3"),
        model.parameter_bytes("Org3"))
    assert db.meta_keys() == (3, 2)
    assert reg_model.params == {"a": "Org3", "b": "Org2"}


@pytest.mark.parametrize("source", SOURCES)
def test_meta_keys_falls_with_a_delete(world, sw_provider, source):
    reg_model = model.Registry(ORGS)
    committer, _ = run_blocks(world, sw_provider, source, reg_model, [
        [CREATE_A, ("CreateAsset", ["b", "1", "client@Org2"], 1, ORGS)],
        [("DeleteAsset", ["a"], 0, ["Org1"])]])
    db = committer.ledger.statedb
    assert db.meta_keys() == (1, 1)
    assert db.get(META, "a") is None and db.get(CC, "a") is None
    assert db.get(META, "b") is not None
    assert db._scan_meta_keys() == 1
    assert registry.gauge("ledger_state_meta_keys").value(channel="ch") == 1


def hand_block(world, number, prev, rwsets, endorser_orgs):
    envs = [build.endorser_tx(
        "ch", CC, "1.0", rwset, world.creators[0],
        [world.endorsers[o] for o in endorser_orgs]) for rwset in rwsets]
    block = build.new_block(number, prev, envs)
    return block.serialize(), block.hash()


@pytest.mark.parametrize("source", SOURCES)
@pytest.mark.parametrize("says", ["nothing_of_meta", "sets_meta_too"])
def test_the_commit_decides_not_the_rwset(world, sw_provider, source, says):
    """An endorser cannot keep a parameter alive: a rw-set that deletes
    the key and leaves `#meta` alone — or even sets it again in the same
    transaction — ends with no parameter."""
    reg_model = model.Registry(ORGS)
    committer, _ = run_blocks(world, sw_provider, source, reg_model,
                              [[CREATE_A]])
    db = committer.ledger.statedb
    version = db.get(CC, "a").version
    sets = [NsRwSet(CC, reads=(KVRead("a", version),),
                    writes=(KVWrite("a", is_delete=True),))]
    if says == "sets_meta_too":
        sets.append(NsRwSet(META, writes=(
            KVWrite("a", model.parameter_bytes("Org1")),)))
    raw, _ = hand_block(world, 1,
                        committer.ledger.blockstore.chain_info().current_hash,
                        [TxRwSet(tuple(sets))], ["Org1"])
    committer.store_block(block_for(source, raw))
    assert stored_flags(committer.ledger, 1) == [V]
    assert db.get(CC, "a") is None and db.get(META, "a") is None
    assert db.meta_keys() == (1, 0)
    # no parameter in state: the next block is the deep tail's again
    before = tails()
    txs = txs_of(model.Registry(ORGS),
                 [("CreateAsset", ["z", "1", "client@Org1"], 0, ORGS)], 90)
    txs[0]["param"] = None          # a create that sets no parameter
    raw, _ = model.build_block(
        {"number": 2, "txs": txs},
        committer.ledger.blockstore.chain_info().current_hash, "ch", CC,
        world.endorsers, world.creators)
    committer.store_block(block_for(source, raw))
    assert stored_flags(committer.ledger, 2) == [V]
    assert tails()[("deep", "no_sbe")] - before[("deep", "no_sbe")] == 1


def test_the_three_walks_drop_the_parameter_alike(world, sw_provider):
    """The contract's own deletes through the walk's three forms, on a
    block that deletes a key with a parameter, one without, and a key
    whose delete loses MVCC."""
    reg_model = model.Registry(ORGS)
    committer, _ = run_blocks(world, sw_provider, "lanes", reg_model, [
        [CREATE_A, ("CreateAsset", ["b", "1", "client@Org2"], 1, ORGS),
         ("CreateAsset", ["c", "1", "client@Org3"], 2, ORGS)]])
    db = committer.ledger.statedb
    txs = txs_of(reg_model, [("DeleteAsset", ["a"], 0, ["Org1"]),
                             ("UpdateAsset", ["b", "2"], 1, ["Org2"]),
                             ("DeleteAsset", ["b"], 1, ["Org2"]),
                             ("DeleteAsset", ["c"], 2, ["Org3"])], 70)
    raw, _ = model.build_block(
        {"number": 1, "txs": txs},
        committer.ledger.blockstore.chain_info().current_hash, "ch", CC,
        world.endorsers, world.creators)
    gate = bytes([V, V, V, V])
    batches = {}
    for source in SOURCES:
        block = block_for(source, raw)
        flags = TxFlags.from_bytes(gate)
        supplier = (mvcc.lane_source_of(block, flags)[0]
                    if source.startswith("lanes")
                    else _safe_envelopes(block))
        with pytest.MonkeyPatch.context() as seam:
            if source == "lanes-python":
                seam.setattr(mvcc, "_fastmvcc", None)
            tally = mvcc.MvccTally()
            batch, history = mvcc.validate_and_prepare_batch(
                db, 1, supplier, flags, tally)
        assert tally.walk == ("arrays" if source == "lanes" else "python")
        batches[source] = (list(flags.to_bytes()), _batch(batch), history)
    assert batches["lanes"] == batches["envelopes"] == batches["lanes-python"]
    final, staged, history = batches["lanes"]
    assert final == [V, V, MVCC, V]
    assert staged[(META, "a")] is None and staged[(META, "c")] is None
    assert (META, "b") not in staged        # its delete lost MVCC
    assert all(ns == CC for _, _, ns, _, _, _ in history)


def _batch(batch) -> dict:
    return {k: None if vv is None else
            (vv.value, vv.version.block_num, vv.version.tx_num)
            for k, vv in batch.items()}


def test_state_digest_of_an_asset_is_record_and_parameter(world, sw_provider):
    """What the benchmark's cell compares a peer with the model by: one
    SHA-256 an asset over its record and its parameter."""
    plan = model.plan_chain(*CHAIN)[:4]
    committer = world.committer(sw_provider)
    for raw in world.raw_blocks(plan):
        committer.store_block(wire.parse_block(raw))
    ids = all_ids(plan)
    final = model.replay_plan(plan, ORGS)

    def digest(pair):
        return hashlib.sha256((pair[0] or "").encode() + b"|"
                              + (pair[1] or b"")).hexdigest()
    ours = {k: digest(v) for k, v in
            held(committer.ledger.get_state, ids).items()}
    theirs = {k: digest(v) for k, v in models_state(final, ids).items()}
    assert ours == theirs
    assert len(set(ours.values())) > len(ids) // 2
