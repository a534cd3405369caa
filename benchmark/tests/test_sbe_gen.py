"""The key-level-endorsement generator: a pure function of the seed, the
same chain, flags and counts as the program's copy of the model, and
what the judge keeps of a block is enough to rebuild the model's state."""

import hashlib

from gen import sbe as gen

ORGS = ("Org1", "Org2", "Org3")
ARGS = (2**31 + 39, 300, 8, 100, 12, 10)    # seed, assets, blocks, block_tx,
#                                             clients, tamper_every


def test_chain_is_a_pure_function_of_the_seed():
    a, b = gen.plan_chain(*ARGS), gen.plan_chain(*ARGS)
    c = gen.plan_chain(ARGS[0] + 1, *ARGS[1:])
    assert a == b
    assert a != c
    assert [len(blk["txs"]) for blk in a] == [100] * 11
    load, window = a[:3], a[3:]
    assert all(code == gen.VALID for blk in load for code in blk["codes"])
    assert not any(tx["tampered"] for blk in load for tx in blk["txs"])
    assert all(tx["endorsers"] == list(ORGS) and tx["param"] is not None
               for blk in load for tx in blk["txs"])
    # every tx of a block read the state committed before the block
    for blk in window:
        assert all(v is None or v[0] < blk["number"]
                   for tx in blk["txs"] for _k, v in tx["reads"])
    # a transaction carries its own endorsers' signatures and no others
    kinds = {tx["kind"]: len(tx["endorsers"])
             for blk in window for tx in blk["txs"]}
    assert kinds == {"update": 1, "transfer": 1, "wrong_org": 1,
                     "delete": 1, "create": 3}
    # the rightful submitter is of the owner's org, the wrong one is not
    world = gen.Registry(ORGS)
    for blk in a:
        for tx in blk["txs"]:
            held = world.assets.get(tx["args"][0])
            if tx["kind"] in ("update", "transfer", "delete"):
                assert tx["endorsers"] == [held["OwnerOrg"]]
                assert ORGS[tx["creator"] % 3] == held["OwnerOrg"]
            elif tx["kind"] == "wrong_org":
                assert tx["endorsers"] != [held["OwnerOrg"]]
        world.commit_block(blk["number"], blk["txs"])
    seen = gen.counts(window)
    assert all(seen[k] > 0 for k in ("wrong_org_failures", "overlay_failures",
                                     "mvcc_conflicts", "deletes",
                                     "recreates"))
    assert seen["signatures"] == sum(1 + len(tx["endorsers"])
                                     for blk in window for tx in blk["txs"])


def test_the_two_copies_of_the_model_agree():
    from fabric_tpu.testing import asset_sbe_model as model
    ours, theirs = gen.plan_chain(*ARGS), model.plan_chain(*ARGS)
    assert ours == theirs
    assert gen.counts(ours) == model.counts(theirs)
    assert [b["tally"] for b in ours] == [b["tally"] for b in theirs]
    # a chain one copy planned, decided by the other's block rule
    decided = gen.Registry(ORGS)
    for blk in theirs:
        assert decided.commit_block(blk["number"], blk["txs"]) == blk["codes"]
    final = model.replay_plan(theirs, ORGS)
    assert (decided.assets, decided.params) == (final.assets, final.params)
    for org in ORGS:
        assert gen.parameter_bytes(org) == model.parameter_bytes(org)
    # the same functions, call by call, rejections included
    for fn, args, org in [("UpdateAsset", ["asset1", 5], "Org1"),
                          ("UpdateAsset", ["asset9999", 5], "Org1"),
                          ("CreateAsset", ["asset1", 5, "x"], "Org2"),
                          ("CreateAsset", ["asset9999", 5, "x"], "Org2"),
                          ("TransferAsset", ["asset2", "x", "Org3"], "Org1"),
                          ("DeleteAsset", ["asset3"], "Org3"),
                          ("ReadAsset", ["asset4"], "Org1"),
                          ("AssetExists", ["asset9999"], "Org1"),
                          ("BurnAsset", ["asset1"], "Org1")]:
        try:
            want = final.simulate(fn, args, org)
        except model.Rejected:
            want = "rejected"
        try:
            got = decided.simulate(fn, args, org)
        except gen.Rejected:
            got = "rejected"
        assert got == want, (fn, args)


def test_summaries_rebuild_the_models_state_and_its_digests():
    plan = gen.plan_chain(*ARGS)
    summaries = [gen.summary(b) for b in plan]
    ids = [gen.asset_key(i)
           for i in range(1, max(s["highest_id"] for s in summaries) + 1)]
    assert len(ids) >= 300
    for upto in (2, 5, plan[-1]["number"]):
        world = gen.replay_plan(plan, ORGS, upto)
        assets, params = gen.state_after(summaries, upto)
        assert params == world.params
        assert assets == {k: gen.record(a["ID"], a["Value"], a["Owner"],
                                        a["OwnerOrg"])
                          for k, a in world.assets.items()}
        assert set(params) == set(assets)    # a deleted key keeps none
        want = [None if k not in assets else hashlib.sha256(
                    assets[k].encode() + b"|"
                    + gen.parameter_bytes(params[k])).hexdigest()
                for k in ids]
        assert gen.digests(assets, params, ids) == want
    gone = [k for k in ids if k not in assets]
    assert gone and len(set(d for d in want if d)) == len(assets)
    assert [s["codes"] for s in summaries] == [bytes(b["codes"])
                                               for b in plan]
    assert sum(len(s["recreates"]) for s in summaries) >= \
        gen.counts(plan)["recreates"] > 0
