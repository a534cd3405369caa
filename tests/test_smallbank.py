"""SmallBank, the second contract, against its plain model
(`fabric_tpu/testing/smallbank_model.py`): what the endorser's simulate
records, a seeded chain of hot-account blocks through the committer
under both providers and through the walk's three forms, a short mix
through gateway → orderers → peers, and the ledger's counters.
"""

import json
import os
import threading
import time

import pytest

from fabric_tpu.bccsp.factory import FactoryOpts, init_factories
from fabric_tpu.chaincode import (ChaincodeDefinition, ChaincodeRegistry,
                                  smallbank)
from fabric_tpu.committer import Committer, PolicyRegistry, TxValidator
from fabric_tpu.endorser import Endorser, signed_proposal
from fabric_tpu.ledger import KVLedger, LedgerConfig
from fabric_tpu.msp import CachedMSP
from fabric_tpu.msp.ca import DevOrg
from fabric_tpu.ops_plane import registry
from fabric_tpu.policy import parse_policy
from fabric_tpu.protocol import Block, wire
from fabric_tpu.protocol.types import META_TXFLAGS, ChaincodeAction
from fabric_tpu.testing import smallbank_model as model
from fabric_tpu.utils import serde
from test_commit_lanes import WALKS, walking_as

CC = "smallbank"
ACCOUNTS = 40
GENESIS = b"\x00" * 32


@pytest.fixture(scope="module", autouse=True)
def sw_provider():
    return init_factories(FactoryOpts(default="SW"))


class World:
    """Two orgs under AND, one endorser each, six creators."""

    def __init__(self):
        self.orgs = [DevOrg("Org1"), DevOrg("Org2")]
        self.msps = {o.mspid: CachedMSP(o.msp()) for o in self.orgs}
        self.endorsers = [o.new_identity(f"peer{o.mspid}") for o in self.orgs]
        self.creators = [self.orgs[i % 2].new_identity(f"client{i}")
                         for i in range(6)]

    def blocks(self, plan) -> list:
        raws, prev = [], GENESIS
        for block in plan:
            raw, prev = model.build_block(block, prev, "ch", CC,
                                          self.endorsers, self.creators)
            raws.append(raw)
        return raws

    def committer(self, provider):
        policies = PolicyRegistry()
        policies.set_policy(CC, parse_policy(
            "AND('Org1.member', 'Org2.member')"))
        return Committer(KVLedger("ch", LedgerConfig()), TxValidator(
            "ch", self.msps, provider, policies))


@pytest.fixture(scope="module")
def world():
    return World()


@pytest.fixture(scope="module")
def chain(world):
    """(plan, serialized blocks): one opening block of 40 accounts, then
    8 blocks x 64 tx of the mix at s = 1.0, one envelope in 9 tampered."""
    plan = model.plan_chain(2**31 + 27, ACCOUNTS, 8, 64, 6, 9)
    return plan, world.blocks(plan)


def stored_flags(ledger, number: int) -> list:
    return list(ledger.blockstore.get_by_number(number)
                .metadata.items[META_TXFLAGS])


def balances_of(get_state) -> dict:
    """{key: int} of every account key the state holds."""
    out = {}
    for i in range(1, ACCOUNTS + 1):
        for key in (model.savings(i), model.checking(i)):
            raw = get_state(CC, key)
            if raw is not None:
                out[key] = int(raw)
    return out


def assert_equals_model(plan, flags_by_block, balances) -> None:
    bank = model.replay_plan(plan)
    for block, got in zip(plan, flags_by_block):
        assert got == block["codes"], block["number"]
    assert balances == bank.balance
    assert bank.money_balances()
    m = bank.money
    assert sum(balances.values()) == (m["opened"] + m["deposited"]
                                      - m["checks"] - m["penalties"])


def test_the_chain_is_a_pure_function_of_the_seed_and_has_chains(chain):
    plan, _ = chain
    assert plan == model.plan_chain(2**31 + 27, ACCOUNTS, 8, 64, 6, 9)
    assert plan != model.plan_chain(2**31 + 28, ACCOUNTS, 8, 64, 6, 9)
    assert plan[0]["codes"] == [model.VALID] * ACCOUNTS
    codes = [c for b in plan[1:] for c in b["codes"]]
    assert codes.count(model.POLICY_FAILURE) == 8 * 7
    # hot accounts: most of a block conflicts, and a tx whose first
    # writer was itself invalid goes through (a chain, not a repeat)
    assert codes.count(model.MVCC_CONFLICT) > codes.count(model.VALID) > 0
    fns = {tx["fn"] for b in plan[1:] for tx in b["txs"]}
    assert fns == set(model.MIX)
    shapes = {(tx["fn"], len(tx["reads"]), len(tx["writes"]))
              for b in plan for tx in b["txs"]}
    assert shapes == {("create_account", 2, 2), ("transact_savings", 1, 1),
                      ("deposit_checking", 1, 1), ("send_payment", 2, 2),
                      ("write_check", 2, 1), ("amalgamate", 3, 3),
                      ("query", 2, 0)}
    survivors = 0
    for block in plan[1:]:
        dead_writers = set()
        for tx, code in zip(block["txs"], block["codes"]):
            keys = {k for k, _ in tx["reads"]}
            if code == model.VALID and keys & dead_writers:
                survivors += 1
            if code != model.VALID:
                dead_writers |= {k for k, _ in tx["writes"]}
    assert survivors > 0


# -- the endorser's simulate against the model's ------------------------------

ACCEPTED = [
    ("create_account", ["99", "someone", "5", "7"]),
    ("transact_savings", ["25", "1"]),
    ("transact_savings", ["-10000", "2"]),
    ("deposit_checking", ["40", "3"]),
    ("deposit_checking", ["0", "3"]),
    ("send_payment", ["10000", "2", "1"]),
    ("write_check", ["50", "4"]),
    ("write_check", ["20001", "4"]),            # overdrawn: one more
    ("amalgamate", ["5", "6"]),
    ("query", ["1"]),
]
REJECTED = [
    ("create_account", ["1", "someone", "5", "7"]),      # exists
    ("transact_savings", ["-10001", "2"]),               # below zero
    ("transact_savings", ["5", "77"]),                   # unknown account
    ("deposit_checking", ["-1", "3"]),
    ("deposit_checking", ["5", "77"]),
    ("send_payment", ["10001", "2", "1"]),               # insufficient funds
    ("send_payment", ["5", "77", "1"]),
    ("send_payment", ["5", "1", "77"]),
    ("send_payment", ["-5", "2", "1"]),
    ("send_payment", ["5", "1", "1"]),
    ("write_check", ["5", "77"]),
    ("write_check", ["-5", "1"]),
    ("amalgamate", ["77", "1"]),
    ("amalgamate", ["1", "77"]),
    ("amalgamate", ["1", "1"]),
    ("query", ["77"]),
    ("close_account", ["1"]),                            # no such function
]


@pytest.fixture(scope="module")
def endorsing(world, sw_provider):
    """(endorser, bank): six accounts opened in block 0 and a few moved
    in block 1, on a ledger and in the model alike."""
    bank = model.Bank()
    txs0 = [dict(bank.simulate("create_account", [i, "n", 10000, 10000]),
                 tampered=False, creator=0, nonce="%048x" % i)
            for i in range(1, 7)]
    plan = [{"number": 0, "txs": txs0}]
    bank.commit_block(0, txs0)
    txs1 = [dict(bank.simulate(fn, args), tampered=False, creator=1,
                 nonce="%048x" % (100 + n))
            for n, (fn, args) in enumerate([("deposit_checking", [7, 3]),
                                            ("send_payment", [9, 5, 4])])]
    plan.append({"number": 1, "txs": txs1})
    bank.commit_block(1, txs1)
    committer = world.committer(sw_provider)
    for raw in world.blocks(plan):
        committer.store_block(wire.parse_block(raw))
    assert stored_flags(committer.ledger, 1) == [model.VALID] * 2
    reg = ChaincodeRegistry()
    reg.install(ChaincodeDefinition(CC, "1.0"), smallbank.contract())
    endorser = Endorser("ch", committer.ledger.statedb, reg, world.msps,
                        sw_provider, world.endorsers[0])
    return endorser, bank


def invoked(fn: str, status: str) -> float:
    return registry.counter("chaincode_invoke_total").value(
        chaincode=CC, function=fn, status=status)


@pytest.mark.parametrize("fn,args", ACCEPTED,
                         ids=[f"{f}({','.join(a)})" for f, a in ACCEPTED])
def test_simulated_rwset_equals_the_models(endorsing, world, fn, args):
    endorser, bank = endorsing
    before = invoked(fn, "200")
    sp = signed_proposal("ch", CC, fn, [a.encode() for a in args],
                         world.creators[0])
    resp = endorser.process_proposal(sp)
    assert resp.status == 200, resp.message
    want = bank.simulate(fn, args)
    action = ChaincodeAction(CC, "1.0", model.rwset_of(want, CC),
                             response_payload=want["payload"].encode())
    got = serde.decode(resp.payload)["action"]
    assert serde.encode(got) == serde.encode(action.to_dict())
    assert (len(want["reads"]), len(want["writes"])) == {
        "create_account": (2, 2), "transact_savings": (1, 1),
        "deposit_checking": (1, 1), "send_payment": (2, 2),
        "write_check": (2, 1), "amalgamate": (3, 3), "query": (2, 0)}[fn]
    assert invoked(fn, "200") == before + 1


@pytest.mark.parametrize("fn,args", REJECTED,
                         ids=[f"{f}({','.join(a)})" for f, a in REJECTED])
def test_what_the_model_rejects_the_contract_rejects(endorsing, world, fn,
                                                     args):
    endorser, bank = endorsing
    label = fn if fn in smallbank.contract().functions() else "other"
    before = invoked(label, "500")
    with pytest.raises(model.Rejected):
        bank.simulate(fn, args)
    sp = signed_proposal("ch", CC, fn, [a.encode() for a in args],
                         world.creators[0])
    resp = endorser.process_proposal(sp)
    assert resp.status == 500 and resp.endorsement is None
    assert invoked(label, "500") == before + 1


# -- the seeded chain through the committer -----------------------------------

@pytest.fixture(scope="module")
def provisioned(tmp_path_factory):
    """A provisioned two-org network with the contract under AND, its
    identities, and the chain built with them, as block files."""
    from fabric_tpu.node.orderer import load_signing_identity
    from fabric_tpu.node.provision import provision_network
    base = str(tmp_path_factory.mktemp("smallbank_net"))
    net = provision_network(
        base, n_orderers=1, peer_orgs=["Org1", "Org2"], clients_per_org=3,
        chaincodes=[{"name": CC, "version": "1.0", "contract": "smallbank",
                     "policy": "AND('Org1.member', 'Org2.member')"}])

    def identity(path):
        with open(path) as f:
            cfg = json.load(f)
        return load_signing_identity(cfg["mspid"], cfg["cert_pem"].encode(),
                                     cfg["key_pem"].encode())
    endorsers = [identity(p) for p in net["peers"]]
    creators = [identity(p) for org in ("Org1", "Org2")
                for p in net["client_pool"][org]]
    plan = model.plan_chain(2**31 + 27, ACCOUNTS, 8, 64, 6, 9)
    paths, prev = [], GENESIS
    for block in plan:
        raw, prev = model.build_block(block, prev, "ch", CC, endorsers,
                                      creators)
        paths.append(os.path.join(base, "block_%d.bin" % block["number"]))
        with open(paths[-1], "wb") as f:
            f.write(raw)
    return net, plan, paths


@pytest.mark.parametrize("bccsp", ["SW", "JAXTPU"])
def test_chain_through_replay_equals_the_model(provisioned, tmp_path, bccsp):
    """`testing/replay.py`: a peer in library form, the software provider
    and the device provider (here on the CPU backend) alike."""
    from fabric_tpu.testing.replay import replay
    net, plan, paths = provisioned
    with open(net["peers"][0]) as f:
        cfg = json.load(f)
    cfg.update(bccsp=bccsp, bccsp_degrade=False, data_dir=str(tmp_path))
    cfg.pop("verify_once", None)
    seen = {}

    def on_block(node, i, store):
        record = store()
        if i == len(paths) - 1:
            seen.update(balances_of(node.ledger.get_state))
        return record

    try:
        report = replay(cfg, paths, on_block=on_block)
    finally:
        init_factories(FactoryOpts(default="SW"))
    assert report["provider"]["name"] == bccsp.lower()
    if bccsp == "JAXTPU":       # the device provider did the verifying
        stats = report["provider"]["stats"]
        assert stats["fallbacks"] == 0
        assert stats["device_sigs"] >= 3 * sum(len(b["txs"]) for b in plan)
    assert report["height"] == len(plan)
    assert_equals_model(
        plan, [list(bytes.fromhex(b["flags"])) for b in report["blocks"]],
        seen)


@pytest.mark.parametrize("form", list(WALKS))
def test_chain_through_the_serial_walk_equals_the_model(world, chain,
                                                        sw_provider, form):
    """Conflict chains over hot keys — most of a block aborting, a tx
    whose first writer was itself invalid going through — by each form
    of the walk: the lane table as arrays, the lane table in Python, the
    envelopes decoded again.  The same flags, balances and commit hash,
    which are the model's; and the reads and conflicts counted alike."""
    plan, raws = chain
    span = WALKS[form]
    source, walk, reason = (span["source"], span["walk"],
                            span.get("reason", "none"))
    committer = world.committer(sw_provider)
    walked = registry.counter("ledger_mvcc_walk_total")
    conflicts = registry.counter("ledger_mvcc_conflicts_total")
    before = (walked.value(channel="ch", walk=walk, reason=reason),
              conflicts.value(channel="ch", path="serial", against="block"))
    for raw in raws:
        with walking_as(form):
            committer.store_block(wire.parse_block(raw) if source == "lanes"
                                  else Block.deserialize(raw))
        assert committer.ledger.last_stats.span_attrs["ledger.mvcc"] == span
    codes = [c for b in plan for c in b["codes"]]
    assert (walked.value(channel="ch", walk=walk, reason=reason) - before[0]
            == len(codes))
    # every tx is simulated on the state before its block: what fails,
    # fails against the block
    assert (conflicts.value(channel="ch", path="serial", against="block")
            - before[1] == codes.count(model.MVCC_CONFLICT) > 200)
    flags = [stored_flags(committer.ledger, b["number"]) for b in plan]
    assert_equals_model(plan, flags, balances_of(committer.ledger.get_state))


# -- the counters ---------------------------------------------------------------

def test_ledger_counters_are_exposed_and_add_up(world, chain, sw_provider):
    plan, raws = chain
    channel = "ch"

    def reading():
        txs = registry.counter("ledger_tx_total")
        conflicts = registry.counter("ledger_mvcc_conflicts_total")
        return {
            "by_code": txs.breakdown("code", channel=channel),
            "reads": registry.counter("ledger_mvcc_reads_total").value(
                channel=channel, path="serial"),
            "block": conflicts.value(channel=channel, path="serial",
                                     against="block"),
            "state": conflicts.value(channel=channel, path="serial",
                                     against="state"),
            "writes": registry.counter("ledger_state_writes_total").value(
                channel=channel)}
    committer = world.committer(sw_provider)
    committer.store_block(wire.parse_block(raws[0]))
    before = reading()
    for raw in raws[1:]:
        committer.store_block(wire.parse_block(raw))
    after = reading()
    codes = [c for b in plan[1:] for c in b["codes"]]

    def moved(name):
        return after["by_code"].get(name, 0) - before["by_code"].get(name, 0)
    assert moved("VALID") == codes.count(model.VALID)
    assert moved("MVCC_READ_CONFLICT") == codes.count(model.MVCC_CONFLICT)
    assert (moved("ENDORSEMENT_POLICY_FAILURE")
            == codes.count(model.POLICY_FAILURE))
    assert (sum(after["by_code"].values()) - sum(before["by_code"].values())
            == len(codes))
    # every tx of a block was simulated before the block: each conflict
    # is with an earlier tx of the same block, none with the state
    assert after["block"] - before["block"] == codes.count(model.MVCC_CONFLICT)
    assert after["state"] == before["state"]
    valid = [tx for b in plan[1:] for tx, c in zip(b["txs"], b["codes"])
             if c == model.VALID]
    assert (after["writes"] - before["writes"]
            == sum(len(tx["writes"]) for tx in valid))
    # reads validated: all of a valid tx's, a conflicting tx's up to and
    # including the one that failed, none of a tampered tx's
    assert (after["reads"] - before["reads"]
            >= sum(len(tx["reads"]) for tx in valid)
            + codes.count(model.MVCC_CONFLICT))
    text = registry.expose_text()
    for line in ('ledger_tx_total{channel="ch",code="VALID"}',
                 'ledger_mvcc_conflicts_total{against="block",channel="ch",'
                 'path="serial"}',
                 'ledger_mvcc_conflicts_total{against="state",channel="ch",'
                 'path="serial"}',
                 'ledger_mvcc_reads_total{channel="ch",path="serial"}',
                 'ledger_state_writes_total{channel="ch"}'):
        assert line in text, line


def test_a_stale_read_conflicts_against_the_state(world, sw_provider):
    """A tx simulated before an earlier block committed: the state
    answers, and the counter says so."""
    bank = model.Bank()
    opened = [dict(bank.simulate("create_account", [1, "n", 10, 10]),
                   tampered=False, creator=0, nonce="%048x" % 1)]
    early = dict(bank.simulate("create_account", [1, "n", 3, 3]),
                 tampered=False, creator=1, nonce="%048x" % 2)
    bank.commit_block(0, opened)
    plan = [{"number": 0, "txs": opened}, {"number": 1, "txs": [early]}]
    conflicts = registry.counter("ledger_mvcc_conflicts_total")
    before = conflicts.value(channel="ch", path="serial", against="state")
    committer = world.committer(sw_provider)
    for raw in world.blocks(plan):
        committer.store_block(wire.parse_block(raw))
    assert stored_flags(committer.ledger, 1) == [model.MVCC_CONFLICT]
    assert bank.commit_block(1, [early]) == [model.MVCC_CONFLICT]
    assert conflicts.value(channel="ch", path="serial",
                           against="state") == before + 1


# -- gateway -> endorse -> order -> commit, in process ------------------------

@pytest.fixture(scope="module")
def net(tmp_path_factory):
    """3 orderers + Org1/Org2 peers in this process, both contracts."""
    from fabric_tpu.config import BatchConfig
    from fabric_tpu.node.orderer import OrdererNode
    from fabric_tpu.node.peer import PeerNode
    from fabric_tpu.node.provision import provision_network
    base = str(tmp_path_factory.mktemp("smallbank_gw"))
    policy = "AND('Org1.member', 'Org2.member')"
    paths = provision_network(
        base, n_orderers=3, peer_orgs=["Org1", "Org2"],
        batch=BatchConfig(max_message_count=8, timeout_s=0.1),
        chaincodes=[{"name": "assets", "version": "1.0",
                     "contract": "asset_demo", "policy": policy},
                    {"name": CC, "version": "1.0", "contract": "smallbank",
                     "policy": policy}])
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


def test_a_mix_through_the_gateway_commits_the_models_balances(net):
    """Three clients at once over six accounts: whatever order and
    conflicts the run takes, every peer holds what the model holds
    after the chain's VALID transactions, run again in commit order."""
    import random

    from fabric_tpu.endorser import assemble_transaction
    from fabric_tpu.gateway import GatewayClient, GatewayError
    from fabric_tpu.node.orderer import load_signing_identity
    from fabric_tpu.protocol.txflags import ValidationCode
    with open(net["paths"]["clients"]["Org1"]) as f:
        cc = json.load(f)
    signer = load_signing_identity(cc["mspid"], cc["cert_pem"].encode(),
                                   cc["key_pem"].encode())
    peer = net["peers"][0]
    calls, errors = {}, []           # txid -> (fn, args)

    def client(tag: int, work: list) -> None:
        gw = GatewayClient(peer.rpc.addr, signer, peer.msps, channel_id="ch")
        try:
            for fn, args in work:
                try:
                    sp, responses = gw.endorse(
                        CC, fn, [str(a).encode() for a in args])
                except GatewayError as exc:   # the contract refused
                    if "insufficient" not in str(exc):
                        errors.append((tag, fn, args, exc))
                    continue
                if len(responses) < 2:
                    continue      # an endorser was at another height
                env = assemble_transaction(sp, responses, signer)
                txid = env.header().channel_header.txid
                calls[txid] = (fn, args)
                gw.submit_envelope(env, timeout_s=60.0)
                gw.commit_status(txid, timeout_s=60.0)
        except Exception as exc:
            errors.append((tag, exc))
        finally:
            gw.close()

    client(0, [("create_account", [i, f"c{i}", 100, 100])
               for i in range(1, 7)])
    rng = random.Random(5)
    draw = model.zipf_sampler(6, 1.0)
    threads = [threading.Thread(target=client, args=(
        t, [model.draw_call(rng, draw, 0.9) for _ in range(12)]))
        for t in (1, 2, 3)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=180)
    assert not errors, errors
    assert len(calls) >= 6 + 6
    ledgers = [p.channels["ch"].ledger for p in net["peers"]]
    height = ledgers[0].height
    # a block is in the block store (and counts in `height`) before its
    # writes are in the state: wait for the state, which is what is read
    deadline = time.time() + 30
    while (any(lg.statedb.savepoint != height - 1 for lg in ledgers)
           and time.time() < deadline):
        time.sleep(0.1)
    assert [lg.statedb.savepoint for lg in ledgers] == [height - 1] * 2
    assert [lg.height for lg in ledgers] == [height] * 2
    assert ledgers[0].commit_hash == ledgers[1].commit_hash
    bank, committed, codes = model.Bank(), 0, set()
    for number in range(height):
        block = ledgers[0].blockstore.get_by_number(number)
        flags = list(block.metadata.items[META_TXFLAGS])
        for n, raw in enumerate(block.data):
            txid = wire.envelope_summary(raw)[2]
            if txid not in calls:
                continue
            committed += 1
            codes.add(flags[n])
            if flags[n] == ValidationCode.VALID:
                bank.apply(number, n, *calls[txid])
    assert committed == len(calls)
    assert codes <= {ValidationCode.VALID, ValidationCode.MVCC_READ_CONFLICT}
    assert bank.money_balances()
    for lg in ledgers:
        held = {k: int(lg.get_state(CC, k)) for k in bank.balance}
        assert held == bank.balance
