"""YCSB over the key-value contract `kvstore`, against its plain model
(`fabric_tpu/testing/ycsb_model.py`): the model's key naming and zipfian
against fixed vectors, what the endorser's simulate records, the
program's `BlockCutter` under Fabric's default `BatchSize`, seeded chains
of load + update blocks through the committer — the walk's three forms:
the lane table as arrays, the lane table in Python, the envelopes — and
the ledger's byte, fsync and checkpoint counters.
"""

import importlib.util
import os
import random

import pytest

from fabric_tpu.bccsp.factory import FactoryOpts, init_factories
from fabric_tpu.chaincode import (ChaincodeDefinition, ChaincodeRegistry,
                                  kvstore)
from fabric_tpu.committer import Committer, PolicyRegistry, TxValidator
from fabric_tpu.config import BatchConfig
from fabric_tpu.endorser import Endorser, signed_proposal
from fabric_tpu.ledger import KVLedger, LedgerConfig, mvcc
from fabric_tpu.msp import CachedMSP
from fabric_tpu.msp.ca import DevOrg
from fabric_tpu.node.peer import DEV_CONTRACTS
from fabric_tpu.ops_plane import registry
from fabric_tpu.orderer.blockcutter import BlockCutter
from fabric_tpu.policy import parse_policy
from fabric_tpu.protocol import (Block, Envelope, KVRead, KVWrite, NsRwSet,
                                 TxFlags, TxRwSet, Version, build, wire)
from fabric_tpu.protocol.types import META_TXFLAGS, ChaincodeAction
from fabric_tpu.testing import ycsb_model as model
from fabric_tpu.utils import serde
from test_commit_lanes import WALKS, walking_as

CC = "kvstore"
POLICY = "AND('Org1.member', 'Org2.member', 'Org3.member')"
SEED = 2**31 + 35
RECORDS, UPDATES, CREATORS, TAMPER = 60, 240, 6, 7
DEFAULT_BATCH = {"max_message_count": 500, "absolute_max_bytes": 10485760,
                 "preferred_max_bytes": 2097152, "timeout_s": 2.0}
# the same rule at a size a test can build: ~10 envelopes of ~4.3 KB a block
SMALL_BATCH = dict(DEFAULT_BATCH, preferred_max_bytes=45000)
REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(scope="module", autouse=True)
def sw_provider():
    return init_factories(FactoryOpts(default="SW"))


class World:
    """Three orgs under AND, one endorser each, six creators."""

    def __init__(self):
        self.orgs = [DevOrg("Org1"), DevOrg("Org2"), DevOrg("Org3")]
        self.msps = {o.mspid: CachedMSP(o.msp()) for o in self.orgs}
        self.endorsers = [o.new_identity(f"peer{o.mspid}") for o in self.orgs]
        self.creators = [self.orgs[i % 3].new_identity(f"client{i}")
                         for i in range(CREATORS)]

    def committer(self, provider):
        policies = PolicyRegistry()
        policies.set_policy(CC, parse_policy(POLICY))
        return Committer(KVLedger("ch", LedgerConfig()), TxValidator(
            "ch", self.msps, provider, policies))


@pytest.fixture(scope="module")
def world():
    return World()


@pytest.fixture(scope="module")
def chain(world):
    """(txs, blocks): 60 inserts then 240 updates, one update in 7
    tampered, each phase cut by bytes at ~10 transactions a block."""
    txs = model.plan_txs(SEED, RECORDS, UPDATES, CREATORS, TAMPER)
    return txs, model.build_chain(txs, SEED, SMALL_BATCH, "ch", CC,
                                  world.endorsers, world.creators)


def stored_flags(ledger, number: int) -> list:
    return list(ledger.blockstore.get_by_number(number)
                .metadata.items[META_TXFLAGS])


def records_of(get_state) -> dict:
    held = {model.key_name(n): get_state(CC, model.key_name(n))
            for n in range(RECORDS)}
    return {k: v for k, v in held.items() if v is not None}


def replay_model(blocks) -> tuple:
    """(codes per block, the model's store) of the serial block rule."""
    store = model.Store()
    return [store.commit_block(b["number"], b["txs"]) for b in blocks], store


def assert_equals_model(blocks, flags_by_block, records) -> None:
    codes, store = replay_model(blocks)
    assert flags_by_block == codes
    assert records == store.data and len(records) == RECORDS
    flat = [c for block in codes for c in block]
    assert model.MVCC_CONFLICT not in flat
    assert flat.count(model.POLICY_FAILURE) == UPDATES // TAMPER
    # the load phase: every insert VALID
    assert flat[:RECORDS] == [model.VALID] * RECORDS


# -- the model against fixed vectors ------------------------------------------

def test_key_naming_is_ycsbs_hashed_insert_order():
    # the first keys of any YCSB load under insertorder=hashed
    assert [model.key_name(n) for n in range(3)] == [
        "user6284781860667377211", "user8517097267634966620",
        "user1820151046732198393"]
    assert model.fnvhash64(0) == 6284781860667377211
    assert all(model.fnvhash64(n) >= 0 for n in range(2000))


def test_scrambled_zipfian_is_grays_with_ycsbs_constants():
    z = model.ScrambledZipfian(100000)
    # rank 0 holds 1 / zeta(n, 0.99) of the draws, rank 1 a further
    # 0.5 ** 0.99 of that; both from YCSB's precomputed zeta
    assert z.rank(0.0) == 0 and z.rank(0.0377) == 0 and z.rank(0.0378) == 1
    assert z.rank(1.0 / model.ZETAN - 1e-12) == 0
    assert z.rank(z.zeta2 / model.ZETAN - 1e-12) == 1
    assert z.rank(z.zeta2 / model.ZETAN + 1e-9) >= 2
    assert z.rank(0.999999) < model.ITEM_COUNT
    assert z.record_of(0.0) == model.fnvhash64(0) % 100000 == 77211
    assert z.record_of(0.05) == model.fnvhash64(1) % 100000 == 66620
    rng = random.Random(5)
    drawn = [z.draw(rng) for _ in range(40000)]
    assert all(0 <= n < 100000 for n in drawn)
    share = drawn.count(77211) / len(drawn)
    assert abs(share - 1.0 / model.ZETAN) < 0.004       # 3.78%
    assert drawn.count(66620) > drawn.count(98393) > 200


def test_a_record_is_ten_fields_of_a_hundred_printable_bytes():
    rec = model.record(SEED, 12345)
    assert rec == model.record(SEED, 12345) != model.record(SEED, 12346)
    assert model.record(SEED + 1, 12345) != rec
    fields = rec.split(b" ")
    assert fields.pop() == b"" and len(fields) == 10
    for i, field in enumerate(fields):
        name, _, value = field.partition(b"=")
        assert name == b"field%d" % i and len(value) == 100
        assert all(33 <= c <= 126 for c in value)
    assert len(rec) == 1080


def test_the_chain_is_a_pure_function_of_the_seed(chain):
    txs, _ = chain
    assert txs == model.plan_txs(SEED, RECORDS, UPDATES, CREATORS, TAMPER)
    assert txs != model.plan_txs(SEED + 1, RECORDS, UPDATES, CREATORS, TAMPER)
    load, run = txs[:RECORDS], txs[RECORDS:]
    assert [tx["record"] for tx in load] == list(range(RECORDS))
    assert not any(tx["tampered"] for tx in load)
    assert {tx["phase"] for tx in load} == {"load"}
    assert {tx["phase"] for tx in run} == {"run"}
    assert sum(tx["tampered"] for tx in run) == UPDATES // TAMPER
    hot = model.fnvhash64(0) % RECORDS
    assert sum(tx["record"] == hot for tx in run) > UPDATES // 40


def test_the_benchmarks_copy_gives_the_same_chain(world, chain):
    """`benchmark/gen/ycsb.py` is the yardstick's own copy: the same
    transactions, records, envelopes' shapes and cut for the same seed."""
    spec = importlib.util.spec_from_file_location(
        "bench_gen_ycsb", os.path.join(REPO, "benchmark", "gen", "ycsb.py"))
    gen = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(gen)
    txs, blocks = chain
    assert gen.plan_txs(SEED, RECORDS, UPDATES, CREATORS, TAMPER) == txs
    assert [gen.key_name(n) for n in range(50)] == [
        model.key_name(n) for n in range(50)]
    assert all(gen.record(SEED, s) == model.record(SEED, s)
               for s in (0, 1, RECORDS, 2**20))
    z, theirs = gen.ScrambledZipfian(1000), model.ScrambledZipfian(1000)
    assert all(z.record_of(u / 997.0) == theirs.record_of(u / 997.0)
               for u in range(997))
    assert [gen.code_of(tx) for tx in txs] == [
        c for codes in replay_model(blocks)[0] for c in codes]
    # the cut: the blocks the model built, fed to the copy's cutter
    raws = [(tx["phase"], raw) for b in blocks
            for tx, raw in zip(b["txs"],
                               Block.deserialize(b["raw"]).data)]
    ours = list(gen.cut_chain(iter(raws), SMALL_BATCH))
    assert [(b["number"], b["reason"], b["txs"]) for b in ours] == [
        (b["number"], b["reason"], len(b["txs"])) for b in blocks]
    assert [b["first"] for b in ours] == [
        b["txs"][0]["serial"] for b in blocks]
    assert [b["data"] for b in ours] == [
        Block.deserialize(b["raw"]).data for b in blocks]
    # the judge's records: the last VALID write of each
    written = [-1 if tx["tampered"] else tx["record"] for tx in txs]
    store = replay_model(blocks)[1]
    assert gen.records_after(written, ours, ours[-1]["number"], SEED,
                             RECORDS) == [
        gen.digest(store.data[model.key_name(n)]) for n in range(RECORDS)]
    assert gen.records_after(written, ours, -1, SEED, RECORDS) == [
        None] * RECORDS


# -- the cut -------------------------------------------------------------------

def fake_envelope(size: int) -> Envelope:
    env = Envelope(b"p" * 8, b"s" * 8)
    return Envelope(b"p" * (8 + size - len(env.serialize())), b"s" * 8)


def test_the_default_batch_cuts_ycsb_by_bytes_and_bump_by_count(chain):
    """Fabric's sample BatchSize 500 / 10 MiB / 2 MiB: ~4.5 KB envelopes
    reach the preferred size first, at ~460; ~3.4 KB `bump` envelopes the
    message count; one message over the preferred size goes alone."""
    _txs, blocks = chain
    sizes = [len(raw) for b in blocks
             for raw in Block.deserialize(b["raw"]).data]
    assert 4200 < min(sizes) and max(sizes) < 4700
    ycsb = [fake_envelope(s).serialize() for s in (sizes * 6)[:1500]]
    assert [len(r) for r in ycsb] == (sizes * 6)[:1500]
    cuts = model.cut(ycsb, DEFAULT_BATCH)
    assert [reason for _d, reason in cuts] == ["bytes"] * 3 + ["end"]
    for data, _reason in cuts[:-1]:
        assert 440 <= len(data) <= 499
        total = sum(len(r) for r in data)
        assert total <= 2097152 < total + max(sizes)
    assert sum(len(d) for d, _r in cuts) == 1500
    assert [r for d, _r in cuts for r in d] == ycsb           # in order
    bump = [fake_envelope(3410).serialize()] * 1200
    assert [(len(d), r) for d, r in model.cut(bump, DEFAULT_BATCH)] == [
        (500, "count"), (500, "count"), (200, "end")]
    # a message over the preferred size: what is pending is cut, then it
    # goes alone, and the cutter carries on
    big = fake_envelope(2097153).serialize()
    mixed = ycsb[:10] + [big] + ycsb[10:15]
    assert [(len(d), r) for d, r in model.cut(mixed, DEFAULT_BATCH)] == [
        (10, "bytes"), (1, "oversize"), (5, "end")]
    cutter = BlockCutter(BatchConfig(500, 10485760, 2097152, 2.0))
    batches, pending = cutter.ordered(Envelope.deserialize(big))
    assert batches == [[big]] and not pending


def test_the_chain_was_cut_by_bytes(chain):
    txs, blocks = chain
    load = [b for b in blocks if b["txs"][0]["phase"] == "load"]
    run = [b for b in blocks if b["txs"][0]["phase"] == "run"]
    assert [b["reason"] for b in load[:-1]] == ["bytes"] * (len(load) - 1)
    assert [b["reason"] for b in run[:-1]] == ["bytes"] * (len(run) - 1)
    assert load[-1]["reason"] == run[-1]["reason"] == "end"
    assert sum(len(b["txs"]) for b in load) == RECORDS
    assert [tx["serial"] for b in blocks for tx in b["txs"]] == list(
        range(len(txs)))
    assert all(8 <= len(b["txs"]) <= 11 for b in run[:-1])
    assert all(len(b["raw"]) <= 45000 + 400 for b in blocks)


# -- the endorser's simulate against the model's ------------------------------

@pytest.fixture(scope="module")
def endorsing(world, chain, sw_provider):
    """(endorser, store): the load phase committed, on a ledger and in
    the model alike."""
    _txs, blocks = chain
    load = [b for b in blocks if b["txs"][0]["phase"] == "load"]
    store = model.Store()
    committer = world.committer(sw_provider)
    for b in load:
        committer.store_block(wire.parse_block(b["raw"]))
        store.commit_block(b["number"], b["txs"])
    reg = ChaincodeRegistry()
    reg.install(ChaincodeDefinition(CC, "1.0"), DEV_CONTRACTS[CC]())
    endorser = Endorser("ch", committer.ledger.statedb, reg, world.msps,
                        sw_provider, world.endorsers[0])
    return endorser, store


def invoked(fn: str, status: str) -> float:
    return registry.counter("chaincode_invoke_total").value(
        chaincode=CC, function=fn, status=status)


ACCEPTED = [("write", [model.key_name(3), model.record(1, 2)]),
            ("write", ["user_new", b"anything at all"]),
            ("write", [model.key_name(4), b""]),
            ("read", [model.key_name(5)]),
            ("del", [model.key_name(6)]),
            ("del", ["user_never_written"])]
REJECTED = [("read", ["user_never_written"]), ("scan", [model.key_name(1)])]


def as_args(args) -> list:
    return [a if isinstance(a, bytes) else a.encode() for a in args]


@pytest.mark.parametrize("fn,args", ACCEPTED,
                         ids=[f"{f}-{n}" for n, (f, _a) in enumerate(ACCEPTED)])
def test_simulated_rwset_equals_the_models(endorsing, world, fn, args):
    endorser, store = endorsing
    before = invoked(fn, "200")
    sp = signed_proposal("ch", CC, fn, as_args(args), world.creators[0])
    resp = endorser.process_proposal(sp)
    assert resp.status == 200, resp.message
    want = store.simulate(fn, args)
    action = ChaincodeAction(CC, "1.0", model.rwset_of(want, CC),
                             response_payload=want["payload"])
    got = serde.decode(resp.payload)["action"]
    assert serde.encode(got) == serde.encode(action.to_dict())
    assert (len(want["reads"]), len(want["writes"])) == {
        "write": (0, 1), "read": (1, 0), "del": (0, 1)}[fn]
    assert invoked(fn, "200") == before + 1


@pytest.mark.parametrize("fn,args", REJECTED, ids=["read-absent", "scan"])
def test_what_the_model_rejects_the_contract_rejects(endorsing, world, fn,
                                                     args):
    endorser, store = endorsing
    label = fn if fn in kvstore.contract().functions() else "other"
    before = invoked(label, "500")
    with pytest.raises(model.Rejected):
        store.simulate(fn, args)
    sp = signed_proposal("ch", CC, fn, as_args(args), world.creators[0])
    resp = endorser.process_proposal(sp)
    assert resp.status == 500 and resp.endorsement is None
    assert invoked(label, "500") == before + 1


# -- the seeded chain through the committer -----------------------------------

@pytest.mark.parametrize("form", list(WALKS))
def test_chain_through_the_serial_walk_equals_the_model(world, chain,
                                                        sw_provider, form):
    """A block of blind writes takes the lane source where it arrives as
    a `BlockView` — walked as arrays, or in Python where
    `native/fastmvcc.c` did not build — and the envelope source as a
    plain `Block`: the same flags, records and commit hash, which are
    the model's."""
    _txs, blocks = chain
    span = WALKS[form]
    source, walk, reason = (span["source"], span["walk"],
                            span.get("reason", "none"))
    committer = world.committer(sw_provider)
    other = world.committer(sw_provider)
    moved = registry.counter("ledger_commit_source_total")
    walked = registry.counter("ledger_mvcc_walk_total")
    before = (moved.value(channel="ch", source=source),
              walked.value(channel="ch", walk=walk, reason=reason))
    for b in blocks:
        parsed = (wire.parse_block(b["raw"]) if source == "lanes"
                  else Block.deserialize(b["raw"]))
        if source == "lanes":
            gate = TxFlags.from_bytes(bytes(len(b["txs"])))
            table, why = mvcc.lane_source_of(
                wire.parse_block(b["raw"]), gate)
            assert why is None and isinstance(table, wire.LaneTable)
        with walking_as(form):
            committer.store_block(parsed)
        assert committer.ledger.last_stats.span_attrs["ledger.mvcc"] == span
        other.store_block(Block.deserialize(b["raw"])
                          if source == "lanes"
                          else wire.parse_block(b["raw"]))
    assert (moved.value(channel="ch", source=source) - before[0]
            == walked.value(channel="ch", walk=walk, reason=reason)
            - before[1] == RECORDS + UPDATES)
    flags = [stored_flags(committer.ledger, b["number"]) for b in blocks]
    assert_equals_model(blocks, flags, records_of(committer.ledger.get_state))
    assert committer.ledger.commit_hash == other.ledger.commit_hash
    assert (records_of(other.ledger.get_state)
            == records_of(committer.ledger.get_state))
    # the history of the hottest record: every VALID write of it, in order
    hot = model.key_name(model.fnvhash64(0) % RECORDS)
    want = [(b["number"], n) for b in blocks
            for n, tx in enumerate(b["txs"])
            if tx["key"] == hot and not tx["tampered"]]
    got = [(m.block_num, m.tx_num)
           for m in committer.ledger.get_history(CC, hot)]
    assert sorted(got) == want and len(want) > 5


def test_a_read_of_an_updated_record_conflicts(world, chain, sw_provider):
    """The model keeps the block rule's third outcome for a transaction
    that read: `read` simulated before an update of its key committed."""
    _txs, blocks = chain
    store = model.Store()
    committer = world.committer(sw_provider)
    for b in blocks[:8]:
        committer.store_block(wire.parse_block(b["raw"]))
        store.commit_block(b["number"], b["txs"])
    key = blocks[7]["txs"][0]["key"]
    stale = store.simulate("read", [key])
    fresh = dict(store.simulate("write", [key, b"newer"]), tampered=False)
    number = blocks[7]["number"] + 1
    assert store.commit_block(number, [fresh, dict(stale, tampered=False)]) \
        == [model.VALID, model.MVCC_CONFLICT]
    envs = [build.endorser_tx("ch", CC, "1.0", model.rwset_of(sim, CC),
                              world.creators[0], world.endorsers)
            for sim in (fresh, stale)]
    prev = committer.ledger.blockstore.get_by_number(number - 1).hash()
    committer.store_block(wire.parse_block(
        build.new_block(number, prev, envs).serialize()))
    assert stored_flags(committer.ledger, number) == [
        model.VALID, model.MVCC_CONFLICT]
    assert committer.ledger.get_state(CC, key) == b"newer"


# -- the counters ---------------------------------------------------------------

def hist(name: str, **labels) -> tuple:
    """(count, sum) of a histogram's series."""
    h = registry.get(name)
    if h is None:
        return 0, 0.0
    state = h.state_by(next(iter(labels)))
    _counts, total, n = state.get(next(iter(labels.values())), ((), 0.0, 0))
    return n, total


def test_bytes_fsyncs_and_checkpoints_are_counted(world, chain, sw_provider,
                                                  tmp_path):
    """On a disk-backed ledger: the bytes applied are the VALID writes'
    key + value bytes; every block is three fsyncs, one a store; a
    checkpoint moves both stores' seconds."""
    _txs, blocks = chain
    policies = PolicyRegistry()
    policies.set_policy(CC, parse_policy(POLICY))
    ledger = KVLedger("ch", LedgerConfig(root=str(tmp_path),
                                         snapshot_every=5))
    committer = Committer(ledger, TxValidator("ch", world.msps, sw_provider,
                                              policies))
    wrote = registry.counter("ledger_state_write_bytes_total")
    writes = registry.counter("ledger_state_writes_total")
    stores = ("blocks", "state", "history")

    def reading():
        return {"bytes": wrote.value(channel="ch"),
                "writes": writes.value(channel="ch"),
                "fsync": {s: hist("ledger_fsync_seconds", store=s)
                          for s in stores},
                "state": hist("state_checkpoint_seconds", channel="ch"),
                "history": hist("history_checkpoint_seconds", channel="ch"),
                "history_total": registry.counter(
                    "history_checkpoint_total").value(channel="ch")}
    before = reading()
    n = 12
    for b in blocks[:n]:
        committer.store_block(wire.parse_block(b["raw"]))
    after = reading()
    valid = [tx for b in blocks[:n] for tx in b["txs"] if not tx["tampered"]]
    assert after["writes"] - before["writes"] == len(valid)
    assert after["bytes"] - before["bytes"] == sum(
        len(tx["key"].encode()) + len(tx["writes"][0][1]) for tx in valid)
    for s in stores:
        count = after["fsync"][s][0] - before["fsync"][s][0]
        assert count == n, s
        assert after["fsync"][s][1] > before["fsync"][s][1]
    # every 5th block: two checkpoints of each store in 12 blocks
    for store in ("state", "history"):
        assert after[store][0] - before[store][0] == 2
        assert after[store][1] > before[store][1]
    assert after["history_total"] - before["history_total"] == 2
    # forced: both again
    ledger.statedb.checkpoint()
    ledger.historydb.checkpoint()
    forced = reading()
    for store in ("state", "history"):
        assert forced[store][0] - after[store][0] == 1
        assert forced[store][1] > after[store][1]
    text = registry.expose_text()
    for line in ('ledger_state_write_bytes_total{channel="ch"}',
                 'ledger_fsync_seconds_count{store="blocks"}',
                 'ledger_fsync_seconds_sum{store="state"}',
                 'ledger_fsync_seconds_count{store="history"}',
                 'history_checkpoint_total{channel="ch"}',
                 'history_checkpoint_seconds_sum{channel="ch"}',
                 'state_checkpoint_seconds_sum{channel="ch"}'):
        assert line in text, line


def test_a_memory_ledger_syncs_nothing_and_a_delete_counts_its_key(
        world, sw_provider):
    """No disk, no fsync and no checkpoint; a delete applies its key's
    bytes and no value."""
    committer = world.committer(sw_provider)
    before = {s: hist("ledger_fsync_seconds", store=s)[0]
              for s in ("blocks", "state", "history")}
    wrote = registry.counter("ledger_state_write_bytes_total")
    b0 = wrote.value(channel="ch")
    store = model.Store()
    sims = [store.simulate("write", ["user1", b"x" * 100]),
            store.simulate("del", ["user22"])]
    envs = [build.endorser_tx("ch", CC, "1.0", model.rwset_of(sim, CC),
                              world.creators[0], world.endorsers)
            for sim in sims]
    committer.store_block(wire.parse_block(
        build.new_block(0, b"\x00" * 32, envs).serialize()))
    assert stored_flags(committer.ledger, 0) == [model.VALID, model.VALID]
    assert wrote.value(channel="ch") - b0 == (5 + 100) + 6
    assert before == {s: hist("ledger_fsync_seconds", store=s)[0]
                      for s in ("blocks", "state", "history")}


NEW_SERIES = ("ledger_state_write_bytes_total", "ledger_fsync_seconds",
              "history_checkpoint_total", "history_checkpoint_seconds")


def test_a_bump_run_exposes_what_the_parent_did_and_the_new_series(
        sw_provider, tmp_path):
    """A P-256-only `bump` chain on a disk-backed ledger: beside the new
    series, the ledger's part of the exposition names exactly what it
    named before them."""
    org = DevOrg("Org1")
    creator, endorsers = org.new_identity("c"), [org.new_identity("e")]
    policies = PolicyRegistry()
    policies.set_policy("assets", parse_policy("OR('Org1.member')"))
    ledger = KVLedger("bumpch", LedgerConfig(root=str(tmp_path),
                                             snapshot_every=2))
    committer = Committer(ledger, TxValidator(
        "bumpch", {"Org1": CachedMSP(org.msp())}, sw_provider, policies))
    prev = b"\x00" * 32
    for number in range(4):
        envs = [build.endorser_tx(
            "bumpch", "assets", "1.0", TxRwSet((NsRwSet(
                "assets",
                reads=(KVRead(f"k{i}", Version(number - 1, i)
                              if number else None),),
                writes=(KVWrite(f"k{i}", str(number).encode()),)),)),
            creator, endorsers) for i in range(5)]
        block = build.new_block(number, prev, envs)
        prev = block.hash()
        committer.store_block(wire.parse_block(block.serialize()))
        assert stored_flags(ledger, number) == [0] * 5
    mine = set()
    for line in registry.expose_text().splitlines():
        if 'channel="bumpch"' in line or line.startswith("ledger_fsync"):
            mine.add(line.split("{")[0])
    suffixes = ("_bucket", "_sum", "_count")

    def family(name: str) -> str:
        for s in suffixes:
            if name.endswith(s):
                return name[:-len(s)]
        return name
    families = {family(n) for n in mine}
    new = {f for f in families if f in NEW_SERIES}
    assert new == set(NEW_SERIES)
    assert families - new == PARENT_FAMILIES


# what PR 35's parent's exposition named with this channel's label after
# the same run (read off that parent with this very chain), less the two
# twins PR 37 took out: `commit_phase_seconds` (the `ledger.*` spans and
# `validator_stage_seconds{stage="commit"}` say the same) and
# `validation_dispatch_seconds` (`validator_stage_seconds{stage=
# "dispatch"}`), plus PR 38's `validator_tail_total` (which tail each
# block took, in transactions) and PR 40's `validator_handoff_sigs_total`
# (the form the provider got each block's unique items in) and PR 41's
# `validator_creators_total` (a block's creators by whether its memo knew
# them) and PR 43's `ledger_mvcc_walk_total` (the form the serial MVCC
# walk took, in transactions) and PR 46's `state_index_update_total` and
# `state_index_changed_keys_total` (how each shard's ordered key list
# followed a batch, and how many keys came or went) and PR 47's
# `ledger_lane_table_opened_total` (where a block's lane table was first
# opened, in transactions)
PARENT_FAMILIES = {
    "commit_graph_apply_batch_size",
    "committed_blocks_total", "committed_txs_total",
    "ledger_commit_source_total", "ledger_height",
    "ledger_lane_table_opened_total",
    "ledger_mvcc_conflicts_total", "ledger_mvcc_reads_total",
    "ledger_mvcc_walk_total",
    "ledger_state_writes_total", "ledger_tx_total",
    "pipeline_collect_under_verify_frac", "state_checkpoint_height",
    "state_checkpoint_seconds", "state_checkpoint_total",
    "state_index_changed_keys_total", "state_index_update_total",
    "state_shard_keys",
    "validation_duration_seconds", "validator_stage_seconds",
    "validator_creators_total", "validator_handoff_sigs_total",
    "validator_tail_total"}
