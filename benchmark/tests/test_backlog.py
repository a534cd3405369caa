"""The backlog generator: a pure function of the seed, and its expected
flags are what a software committer gives the blocks it builds."""

import json
import os

from gen import backlog
from gen.deployment import Deployment

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
REPO = os.path.dirname(BENCH)


def small_config():
    with open(os.path.join(BENCH, "configs", "and3-cut10k.json")) as f:
        cfg = json.load(f)
    cfg["client_identities"] = 6
    return cfg


def test_plan_is_a_pure_function_of_the_seed():
    a = backlog.plan_backlog(2**31 + 5, 3, 50, 40, 6, 10)
    b = backlog.plan_backlog(2**31 + 5, 3, 50, 40, 6, 10)
    c = backlog.plan_backlog(2**31 + 6, 3, 50, 40, 6, 10)
    assert a == b
    assert a != c
    codes = [tx["code"] for blk in a for tx in blk["txs"]]
    assert codes.count(backlog.POLICY_FAILURE) == 15
    # 50 draws from 40 keys: repeats inside a block are certain
    assert codes.count(backlog.MVCC_CONFLICT) > 0
    # a later block reads the version an earlier block's valid tx wrote
    assert any(tx["read"] is not None for tx in a[2]["txs"])


def test_expected_flags_equal_a_sw_committers(tmp_path):
    from fabric_tpu.config.localconfig import load_node_config
    from fabric_tpu.node.peer import PeerNode
    from fabric_tpu.protocol import wire
    from fabric_tpu.protocol.types import META_TXFLAGS

    cfg = small_config()
    dep = Deployment(str(tmp_path), cfg, REPO, {})
    plan = backlog.plan_backlog(7, 3, 60, 40, 6, 10)
    endorsers, creators = backlog.load_identities(dep.file)
    datas = [backlog.build_block_data(b, dep.channel, dep.chaincode,
                                      endorsers, creators) for b in plan]
    blocks, prev = [], backlog.GENESIS_PREVIOUS_HASH
    for data, bplan in zip(datas, plan):
        raw, prev = backlog.chain_block(data, bplan["number"], prev)
        blocks.append(raw)
    node_cfg = load_node_config(dep.peer_cfg_path["Org2"], "peer")
    node = PeerNode(node_cfg, data_dir=node_cfg["data_dir"])
    try:
        for raw, bplan in zip(blocks, plan):
            block = wire.parse_block(raw)
            node.coordinator.store_block(block)
            stored = node.ledger.blockstore.get_by_number(bplan["number"])
            got = bytes(stored.metadata.items[META_TXFLAGS]).hex()
            assert got == backlog.expected_flags_hex(bplan)
        assert node.ledger.height == 3
    finally:
        node.stop()
