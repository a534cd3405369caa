"""The SmallBank generator: a pure function of the seed, the same
answers as the program's copy of the model, and blocks whose expected
flags and balances are what a software committer gives them."""

import json
import os

from gen import backlog
from gen import smallbank as gen
from gen.deployment import Deployment

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
REPO = os.path.dirname(BENCH)
ARGS = (2**31 + 9, 90, 4, 60, 6, 7)     # seed, accounts, blocks, block_tx,
#                                         creators, tamper_every


def test_chain_is_a_pure_function_of_the_seed():
    a, b = gen.plan_chain(*ARGS), gen.plan_chain(*ARGS)
    c = gen.plan_chain(ARGS[0] + 1, *ARGS[1:])
    assert a == b
    assert a != c
    assert [len(blk["txs"]) for blk in a] == [60, 30, 60, 60, 60, 60]
    opening, window = a[:2], a[2:]
    assert all(code == gen.VALID for blk in opening for code in blk["codes"])
    assert not any(tx["tampered"] for blk in opening for tx in blk["txs"])
    codes = [code for blk in window for code in blk["codes"]]
    assert codes.count(gen.POLICY_FAILURE) == 4 * 8
    assert codes.count(gen.MVCC_CONFLICT) > 0 and codes.count(gen.VALID) > 0
    # every tx of a block read the state committed before the block
    for blk in window:
        assert all(v is None or v[0] < blk["number"]
                   for tx in blk["txs"] for _k, v in tx["reads"])
    # hot accounts: id 1 is drawn far more often than id 90
    drawn = [a for blk in window for tx in blk["txs"] for a in tx["args"][-2:]]
    assert drawn.count("1") > 5 * max(1, drawn.count("90"))


def test_the_two_copies_of_the_model_agree():
    from fabric_tpu.testing import smallbank_model as model
    ours, theirs = gen.plan_chain(*ARGS), model.plan_chain(*ARGS)
    assert ours == theirs
    bank = model.replay_plan(theirs)
    balances, money = gen.balances_after([gen.summary(b) for b in ours],
                                         ours[-1]["number"])
    assert balances == bank.balance and money == bank.money
    assert bank.money_balances()
    assert gen.replay_plan(ours).balance == bank.balance
    # a chain one copy planned, decided by the other's block rule
    decided = gen.Bank()
    for blk in theirs:
        assert decided.commit_block(blk["number"], blk["txs"]) == blk["codes"]
    # the same procedures, call by call, rejections included
    a, b = gen.replay_plan(ours), bank
    for fn, args in [("send_payment", [10**9, 1, 2]), ("query", [91]),
                     ("amalgamate", [3, 3]), ("write_check", [30000, 5]),
                     ("transact_savings", [-10**9, 4]),
                     ("deposit_checking", [5, 6]), ("amalgamate", [1, 2])]:
        try:
            want = b.simulate(fn, args)
        except model.Rejected:
            want = None
        try:
            got = a.simulate(fn, args)
        except gen.Rejected:
            got = None
        assert got == want, (fn, args)


def test_expected_flags_and_balances_equal_a_sw_committers(tmp_path):
    from fabric_tpu.config.localconfig import load_node_config
    from fabric_tpu.node.peer import PeerNode
    from fabric_tpu.protocol import wire
    from fabric_tpu.protocol.types import META_TXFLAGS

    with open(os.path.join(BENCH, "configs",
                           "smallbank-and3-cut10k.json")) as f:
        cfg = json.load(f)
    cfg["client_identities"] = 6
    dep = Deployment(str(tmp_path), cfg, REPO, {})
    plan = gen.plan_chain(*ARGS)
    endorsers, creators = backlog.load_identities(dep.file)
    node_cfg = load_node_config(dep.peer_cfg_path["Org2"], "peer")
    node = PeerNode(node_cfg, data_dir=node_cfg["data_dir"])
    try:
        prev = backlog.GENESIS_PREVIOUS_HASH
        for blk in plan:
            data = gen.build_block_data(blk, dep.channel, dep.chaincode,
                                        endorsers, creators)
            raw, prev = backlog.chain_block(data, blk["number"], prev)
            node.coordinator.store_block(wire.parse_block(raw))
            stored = node.ledger.blockstore.get_by_number(blk["number"])
            assert (bytes(stored.metadata.items[META_TXFLAGS])
                    == gen.summary(blk)["codes"])
        balances, _ = gen.balances_after([gen.summary(b) for b in plan],
                                         plan[-1]["number"])
        assert len(balances) == 2 * 90
        for key, value in balances.items():
            assert int(node.ledger.get_state(dep.chaincode, key)) == value
    finally:
        node.stop()
