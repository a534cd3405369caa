"""The private-data generator: a pure function of the seed and of where
its blocks are cut, the same chain, flags and counts as the program's
copy of the model, the mix as realised, a cut that follows the program's
BlockCutter, and what the judge keeps of a block is enough to rebuild
the model's hashed state and every org's view."""

import collections

from gen import privdata as gen

ARGS = (2**31 + 48, 1000, 6000, 250, 24, 25)    # seed, assets, run_tx,
#                                          block_tx, clients, tamper_every
ORGS = ("Org1", "Org2", "Org3")


def test_chain_is_a_pure_function_of_the_seed():
    a, b = gen.plan_chain(*ARGS), gen.plan_chain(*ARGS)
    c = gen.plan_chain(ARGS[0] + 1, *ARGS[1:])
    assert a == b
    assert a != c
    load, run = a[:4], a[4:]
    assert [len(blk["txs"]) for blk in load] == [250] * 4
    assert all(code == gen.VALID for blk in load for code in blk["codes"])
    assert not any(tx["tampered"] for blk in load for tx in blk["txs"])
    assert sum(len(blk["txs"]) for blk in run) == 6000
    assert sum(tx["tampered"] for blk in run for tx in blk["txs"]) == 240
    # only the trading orgs' clients submit, each through its own org's
    # peer but for the wrong-org agreements
    for blk in a:
        for tx in blk["txs"]:
            assert ORGS[tx["creator"] % 3] == tx["org"] != "Org3"
            assert (tx["endorser"] == tx["org"]) == (tx["kind"] != "wrong_org")
    # every tx of a block read the state committed before the block, a
    # late one the state a block earlier, and leads the block
    for blk in run:
        late = [tx for tx in blk["txs"] if tx["kind"] == "late"]
        assert blk["txs"][:len(late)] == late
        for tx in blk["txs"]:
            before = blk["number"] - (tx["kind"] == "late")
            assert all(v is None or v[0] < before for _c, _k, v in tx["reads"])
    seen = gen.counts(run)
    assert all(seen[k] > 0 for k in (
        "tampered", "collection_policy", "conflict", "expired", "creates",
        "agrees", "transfers", "deletes", "expired_keys"))
    causes = collections.Counter(
        (tx["kind"], tx["cause"]) for blk in run for tx in blk["txs"]
        if "cause" in tx)
    assert {k for k, c in causes if c == "expired"} == {"late"}
    assert {k for k, c in causes if c == "collection_policy"} == {"wrong_org"}
    assert {k for k, c in causes if c == "conflict"} <= {"agree", "wrong_org",
                                                         "delete", "transfer"}


def test_the_mix_as_realised():
    run = gen.plan_chain(*ARGS)[4:]
    # the first blocks have nothing to agree on or to transfer yet: their
    # draws are drawn again as creates; from the sixth block on the mix
    # is the stated one, but for the draws that find their pool empty
    steady = [tx for blk in run[5:] for tx in blk["txs"]]
    share = collections.Counter(tx["kind"] for tx in steady)
    drawn = collections.Counter(tx["drawn"] for tx in steady)
    for kind, want in gen.MIX:
        assert abs(drawn[kind] / len(steady) - want) < 0.03, (kind, drawn)
    redrawn = sum(tx["kind"] != tx["drawn"] for tx in steady) / len(steady)
    assert redrawn < 0.05
    assert abs(share["create"] / len(steady) - 0.40) < 0.06
    assert share["agree"] > share["transfer"] > share["delete"] > 0
    early = [tx for tx in run[0]["txs"]]
    assert all(tx["kind"] in ("create", "agree", "wrong_org", "delete")
               for tx in early)


def test_the_two_copies_of_the_model_agree():
    from fabric_tpu.testing import asset_private_model as model
    ours, theirs = gen.plan_chain(*ARGS), model.plan_chain(*ARGS)
    assert ours == theirs
    for org in ORGS:
        assert gen.counts(ours, org) == model.counts(theirs, org)
    assert gen.collections() == model.collections()
    # a chain one copy planned, decided by the other's block rule
    decided = gen.World()
    for blk in theirs:
        txs = [{k: v for k, v in tx.items() if k != "cause"}
               for tx in blk["txs"]]
        assert decided.commit_block(blk["number"], txs) == blk["codes"]
        assert txs == blk["txs"]             # causes as well
        assert decided.last["expired"] == blk["expired"]
    final = model.replay_plan(theirs)
    assert decided.hashed == final.hashed and decided.views == final.views


def test_summaries_rebuild_the_state_at_any_block():
    plan = gen.plan_chain(*ARGS)
    summaries = [gen.summary(dict(blk, reason="count")) for blk in plan]
    for upto in (3, 9, len(plan) - 1):
        world = gen.replay_plan(plan, upto)
        for org in ORGS:
            hashed, view = gen.state_after(summaries, upto, org)
            assert hashed == world.hashed
            assert view == world.views[org]
    world = gen.replay_plan(plan)
    assert world.views["Org3"] == {}
    held, digest = gen.view_digest(world.views["Org1"])
    assert set(held) == {"assetCollection", "Org1PrivateCollection"}
    assert gen.view_digest(world.views["Org2"])[1] != digest
    n, _ = gen.hashed_digest(world.hashed, "assetCollection")
    assert n == held["assetCollection"]      # records and live agreements
    one = summaries[9]
    assert one["codes"] == bytes(plan[9]["codes"])
    assert one["counts"]["Org3"]["sets_resolved"] == 0
    assert one["counts"]["Org1"]["sets_resolved"] > 0


def test_form_chain_cuts_by_count_and_carries_the_txids():
    chain = gen.Chain(*ARGS[:3], *ARGS[4:])

    def build(txs):
        # envelopes of ~2 KB: far under the preferred size at 250 a block
        return ([b"\x00" * 2000 for _ in txs],
                [tx["nonce"] for tx in txs])

    class Cutter:
        def __init__(self, batch):
            self.n, self.held = int(batch["max_message_count"]), []

        def ordered(self, raw):
            self.held.append(raw)
            if len(self.held) == self.n:
                data, self.held = self.held, []
                return [(data, "count")]
            return []

        def flush(self):
            return [(self.held, "end")] if self.held else []

    from gen import ycsb
    real, ycsb.Cutter = ycsb.Cutter, Cutter
    try:
        blocks = list(gen.form_chain(chain, build,
                                     {"max_message_count": 250}))
    finally:
        ycsb.Cutter = real
    plan = gen.plan_chain(*ARGS)
    assert [b["codes"] for b in blocks] == [b["codes"] for b in plan]
    assert all(b["txids"] == [tx["nonce"] for tx in b["txs"]]
               for b in blocks)
    assert {b["reason"] for b in blocks} <= {"count", "end"}
