"""The range-query generator: a pure function of the seed and of where
its blocks are cut, the same chain, flags and counts as the program's
copy of the model, a cut that follows the program's BlockCutter, and
what the judge keeps of a block is enough to rebuild the model's state."""

from gen import queries as gen

ARGS = (2**31 + 45, 400, 16, 900, 60, 12, 10)   # seed, assets, colours,
#                                     run_tx, block_tx, clients, tamper_every
TOP = "\U0010ffff"


def test_chain_is_a_pure_function_of_the_seed():
    a, b = gen.plan_chain(*ARGS), gen.plan_chain(*ARGS)
    c = gen.plan_chain(ARGS[0] + 1, *ARGS[1:])
    assert a == b
    assert a != c
    assert [len(blk["txs"]) for blk in a] == [60] * 6 + [40] + [60] * 15
    load, run = a[:7], a[7:]
    assert all(code == gen.VALID for blk in load for code in blk["codes"])
    assert not any(tx["tampered"] for blk in load for tx in blk["txs"])
    assert sum(tx["tampered"] for blk in run for tx in blk["txs"]) == 90
    kinds = [tx["kind"] for blk in run for tx in blk["txs"]]
    share = {k: kinds.count(k) / len(kinds) for k in set(kinds)}
    for kind, want in gen.MIX:
        assert abs(share[kind] - want) < 0.04, share
    # every tx of a block read the state committed before the block
    for blk in run:
        for tx in blk["txs"]:
            reads = tx["reads"] + [r for q in tx["ranges"]
                                   for r in q["reads"]]
            assert all(v is None or v[0] < blk["number"] for _k, v in reads)
            if tx["kind"] == "bycolor":
                (rq,) = tx["ranges"]
                assert rq["end"] == rq["start"] + TOP
                assert len(rq["reads"]) == len(tx["reads"]) == len(tx["writes"])
    # fresh ids in draw order, whatever became of the draw
    created = [int(tx["args"][0][5:]) for blk in run for tx in blk["txs"]
               if tx["kind"] == "create"]
    assert created == list(range(400, 400 + len(created)))
    seen = gen.counts(run)
    assert all(seen[k] > 0 for k in (
        "phantoms_by_create", "bycolor_mvcc_by_transfer",
        "bycolor_mvcc_by_delete", "ranges_held", "creates", "deletes"))
    assert seen["phantoms_by_delete"] == 0
    assert seen["ranges_replayed"] == seen["ranges_held"] + seen["phantoms"]


def test_the_two_copies_of_the_model_agree():
    from fabric_tpu.testing import asset_queries_model as model
    ours, theirs = gen.plan_chain(*ARGS), model.plan_chain(*ARGS)
    assert ours == theirs
    assert gen.counts(ours) == model.counts(theirs)
    # a chain one copy planned, decided by the other's block rule
    decided = gen.Registry()
    for blk in theirs:
        txs = [{k: v for k, v in tx.items() if k not in ("cause", "replayed")}
               for tx in blk["txs"]]
        assert decided.commit_block(blk["number"], txs) == blk["codes"]
        assert txs == blk["txs"]             # causes and replays as well
    final = model.replay_plan(theirs)
    assert (decided.assets, decided.index, decided.version) == (
        final.assets, final.index, final.version)
    assert len(decided.index) == len(decided.assets) > 300
    # the same functions, call by call, rejections included
    for fn, args in [("TransferAsset", ["asset1", "x"]),
                     ("TransferAsset", ["asset9999", "x"]),
                     ("CreateAsset", ["asset1", "c", 5, "x", 7]),
                     ("CreateAsset", ["asset9999", "c\x00", 5, "x", 7]),
                     ("CreateAsset", ["asset9999", "color0001", 5, "x", 7]),
                     ("DeleteAsset", ["asset3"]),
                     ("ReadAsset", ["asset4"]),
                     ("AssetExists", ["asset9999"]),
                     ("TransferAssetByColor", ["color0002", "x"]),
                     ("GetAssetsByRange", ["asset1", "asset3"]),
                     ("GetAssetsByRange", ["", ""]),
                     ("GetAssetsByRange", ["\x00", ""]),
                     ("QueryAssets", ["{}"])]:
        try:
            want = final.simulate(fn, args)
        except model.Rejected:
            want = "rejected"
        try:
            got = decided.simulate(fn, args)
        except gen.Rejected:
            got = "rejected"
        assert got == want, (fn, args)


def test_a_block_ends_where_the_programs_cutter_ends_it():
    """`form_chain` with envelopes of made-up sizes: a block is what the
    BlockCutter's first cut takes, the rest is simulated again against
    the new state, and no block crosses the end of the load phase."""
    batch = {"max_message_count": 50, "absolute_max_bytes": 10 << 20,
             "preferred_max_bytes": 40_000, "timeout_s": 2.0}
    from fabric_tpu.protocol import Envelope
    built = []

    def build(txs):
        built.append(len(txs))
        return [Envelope(b"p" * (4000 if tx["kind"] == "bycolor" else 1000),
                         b"s").serialize() for tx in txs]
    chain = gen.Chain(7, 120, 8, 400, 12, 10)
    blocks = list(gen.form_chain(chain, build, batch))
    assert [b["number"] for b in blocks] == list(range(len(blocks)))
    load = [b for b in blocks if b["phase"] == "load"]
    run = [b for b in blocks if b["phase"] == "run"]
    assert sum(len(b["txs"]) for b in load) == 120
    assert sum(len(b["txs"]) for b in run) == 400
    assert [b["reason"] for b in load] == ["bytes", "bytes", "bytes", "end"]
    assert {b["reason"] for b in run[:-1]} <= {"bytes", "count"}
    assert all(len(b["txs"]) == len(b["data"]) <= 50 for b in blocks)
    assert all(sum(len(raw) for raw in b["data"]) <= 40_000 for b in blocks)
    assert max(built) <= 50
    for b in run[:-1]:
        assert b["reason"] == ("count" if len(b["txs"]) == 50 else "bytes")
    # the chain is the one the model gives for the same cuts
    again = gen.Chain(7, 120, 8, 400, 12, 10)
    for b in blocks:
        again.next_block(50)
        mine = again.commit_block(len(b["txs"]))
        assert (mine["txs"], mine["codes"]) == (b["txs"], b["codes"])


def test_summaries_rebuild_the_models_state_and_its_digests():
    import hashlib
    plan = gen.plan_chain(*ARGS)
    summaries = [gen.summary(dict(b, reason="count")) for b in plan]
    ids = 1 + max(s["highest_id"] for s in summaries)
    assert ids > 400
    for upto in (6, 12, plan[-1]["number"]):
        world = gen.replay_plan(plan, upto)
        want = []
        for n in range(ids):
            key = gen.asset_key(n)
            if key not in world.assets:
                want.append(None)
                continue
            entry = gen.index_key(world.assets[key][0], key)
            assert entry in world.index
            want.append(hashlib.sha256(
                world.record_of(key).encode() + b"|" + entry.encode()
                + b"|\x00").hexdigest())
        assert gen.digests_after(summaries, upto, ids) == want
        assert sum(d is not None for d in want) == len(world.index)
    assert [s["codes"] for s in summaries] == [bytes(b["codes"])
                                               for b in plan]
    assert all(s["ranged"] == [n for n, tx in enumerate(b["txs"])
                               if tx["kind"] == "bycolor"]
               for s, b in zip(summaries, plan))
