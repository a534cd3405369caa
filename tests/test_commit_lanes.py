"""The commit path's two sources: `mvcc.validate_and_prepare_batch` fed
by the block's lane table against the same walk fed by the envelopes
decoded again, on the same bytes — flags, UpdateBatch order, history
rows, commit hash and MvccTally equal — the rule that picks one
(`mvcc.lane_source_of`) and each of its demotions, and the three readers
that take their txids from the same table: the block store's index, the
commit notifier and the private-data coordinator.  The adversarial
corpora and the seeded generators that PR 8, 11 and 17 wrote against the
wave scheduler, the commit window and the fused device validator (gone
in PR 44) are inputs here: every one of them through the walk's three
forms.
"""
import collections
import contextlib
import os
import random

os.environ.setdefault("JAX_PLATFORMS", "cpu")

import threading

import pytest

from fabric_tpu.bccsp.factory import FactoryOpts, init_factories
from fabric_tpu.gateway.notifier import CommitNotifier
from fabric_tpu.ledger import KVLedger, LedgerConfig
from fabric_tpu.ledger import mvcc
from fabric_tpu.ledger.blkstorage import BlockStore
from fabric_tpu.ledger.kvledger import _safe_envelopes
from fabric_tpu.ledger.statedb import UpdateBatch
from fabric_tpu.msp.ca import DevOrg
from fabric_tpu.ops_plane import registry, tracing
from fabric_tpu.privdata import coordinator as coordinator_mod
from fabric_tpu.protocol import (Block, Envelope, KVRead, KVWrite, NsRwSet,
                                 RangeQueryInfo, TxFlags, TxRwSet,
                                 ValidationCode, Version, build, wire)
from fabric_tpu.protocol.types import META_TXFLAGS, TX_CONFIG, TX_ENDORSER
from fabric_tpu.testing import smallbank_model as model
from fabric_tpu.utils import serde

V = int(ValidationCode.VALID)
MVCC = int(ValidationCode.MVCC_READ_CONFLICT)
PHANTOM = int(ValidationCode.PHANTOM_READ_CONFLICT)
BADSIG = int(ValidationCode.BAD_CREATOR_SIGNATURE)
POLICY = int(ValidationCode.ENDORSEMENT_POLICY_FAILURE)
BADRW = int(ValidationCode.BAD_RWSET)
GENESIS = b"\x00" * 32


@pytest.fixture(scope="module", autouse=True)
def sw_provider():
    return init_factories(FactoryOpts(default="SW"))


@pytest.fixture(scope="module")
def org():
    return DevOrg("Org1")


@pytest.fixture(scope="module")
def ids(org):
    """(creator, endorsers): MVCC and the readers verify no signature, so
    one org's identities sign everything."""
    return org.new_identity("client"), [org.new_identity("e1"),
                                        org.new_identity("e2")]


def rw(reads=(), writes=(), ranges=(), ns="cc"):
    return TxRwSet((NsRwSet(ns, reads=tuple(reads), writes=tuple(writes),
                            range_queries=tuple(ranges)),))


def tx(ids, rwset, **kw):
    creator, endorsers = ids
    return build.endorser_tx("ch", "cc", "1.0", rwset, creator, endorsers,
                             **kw)


def seed(ids, n=8):
    """Block 0: put k00..k{n-1} = b"v0"."""
    return [tx(ids, rw(writes=[KVWrite(f"k{i:02d}", b"v0")]))
            for i in range(n)]


def raw_block(number, prev, envelopes):
    """(serialized block, its header hash)."""
    block = build.new_block(number, prev, envelopes)
    return block.serialize(), block.hash()


def view_of(raw, gate=None):
    view = wire.parse_block(raw)
    assert isinstance(view, wire.BlockView)
    if gate is not None:
        view.metadata.items[META_TXFLAGS] = bytes(gate)
    return view


def plain_of(raw, gate=None):
    block = Block.deserialize(raw)
    if gate is not None:
        block.metadata.items[META_TXFLAGS] = bytes(gate)
    return block


def state_of(ledger):
    return sorted(
        (k, None if vv is None else
         (vv.value, vv.version.block_num, vv.version.tx_num))
        for k, vv in ledger.statedb._data.items())


def history_of(ledger):
    h = ledger.historydb
    return {k: [(m.block_num, m.tx_num, m.txid, m.value, m.is_delete)
                for m in h.get_history(*k)]
            for k in sorted(h._index)}


def tally_of(t):
    return (t.reads, t.conflicts_block, t.conflicts_state)


def mvcc_span(ledger):
    """The span's source, reason and walk; what a block that replayed a
    range adds beside them (`range_*`) is tests/test_asset_queries.py's."""
    return {k: v for k, v in
            ledger.last_stats.span_attrs["ledger.mvcc"].items()
            if not k.startswith("range_")}


@contextlib.contextmanager
def the_python_walk():
    """The rule's seam (`mvcc.walk_of`): what a `native/fastmvcc.c` that
    did not build leaves behind.  A lane table is then walked one Python
    iteration a transaction, as before PR 43."""
    was, mvcc._fastmvcc = mvcc._fastmvcc, None
    try:
        yield
    finally:
        mvcc._fastmvcc = was


WALKS = {"arrays": {"source": "lanes", "walk": "arrays"},
         "python": {"source": "lanes", "walk": "python",
                    "reason": "no_native"},
         "envelopes": {"source": "envelopes", "walk": "python",
                       "reason": "no_view"}}


def walking_as(walk):
    """The seam closed for the form "python", left alone for the others."""
    return the_python_walk() if walk == "python" else contextlib.nullcontext()


def walked(db, number, source, gate, python=False):
    """One walk of `source` over `db`: everything it gives back."""
    flags, tally = TxFlags.from_bytes(gate), mvcc.MvccTally()
    with (the_python_walk() if python else contextlib.nullcontext()):
        batch, history = mvcc.validate_and_prepare_batch(
            db, number, source, flags, tally)
    split = batch.items_by_shard(db.n_shards)       # warm, or hashed now
    fresh = UpdateBatch()
    fresh._updates = dict(batch.items())
    assert split == fresh.items_by_shard(db.n_shards)
    return {"flags": flags.to_bytes(), "batch": list(batch.items()),
            "history": history, "tally": tally_of(tally),
            "walk": (tally.walk, tally.reason),
            "touches_meta": batch.touches_meta,
            "namespaces": batch._namespaces}


def spans_of(reason=None):
    """What `ledger.mvcc` says on each of the three ledgers for a block
    the lane source takes, or one it refuses for `reason`: then the two
    fed views decode the envelopes again, as the one fed plain blocks
    always does."""
    if reason is None:
        return WALKS
    refused = {"source": "envelopes", "walk": "python", "reason": reason}
    return {"arrays": refused, "python": refused,
            "envelopes": WALKS["envelopes"]}


def through_three_walks(stream, config=LedgerConfig, prepared=False):
    """Feed `stream` — [(envelopes, gate codes | None[, reason])] — to
    three ledgers: two get BlockViews (the lane source: one walked as
    arrays, one by the Python walk, forced through the rule's seam), one
    plain Blocks (the envelope source).  Before each commit the three
    walk the same bytes over the same state and every output is
    compared; after it, the ledgers.  A block that comes with a `reason`
    is one `lane_source_of` must refuse for it (a still-VALID range
    query: "range"): there the views' envelopes are walked against the
    plain block's, and the span says why.  `prepared`: every view's
    table is opened ahead, by the call the validator makes in its wait
    (`wire.prepare_lanes`), and the walks and commits find it open.
    -> (final codes per block, tally per block)."""
    def view_of_raw(raw, gate=None):
        view = view_of(raw, gate)
        if prepared:
            opened = wire.prepare_lanes(view, at="validator_wait")
            assert opened is view._table
            assert wire.prepare_lanes(view, at="again") is None
        return view

    ledgers = {name: KVLedger("ch", config()) for name in WALKS}
    db = ledgers["arrays"].statedb
    prev, codes, tallies = GENESIS, [], []
    for number, (envelopes, gate, *reason) in enumerate(stream):
        reason = reason[0] if reason else None
        raw, nxt = raw_block(number, prev, envelopes)
        gate = bytes(gate if gate is not None else [V] * len(envelopes))
        view = view_of_raw(raw, gate)
        table, why = mvcc.lane_source_of(view, TxFlags.from_bytes(gate))
        assert why == reason and (table is None) == (reason is not None)
        # a table that speaks for no still-VALID range is open all the
        # same, and says who opened it
        assert view._table.opened_at == (
            "validator_wait" if prepared else "commit")
        got = {"envelopes": walked(db, number,
                                   _safe_envelopes(plain_of(raw)), gate)}
        if table is None:
            got["arrays"] = walked(db, number, _safe_envelopes(view), gate)
            got["python"] = walked(db, number,
                                   _safe_envelopes(view_of_raw(raw)), gate,
                                   python=True)
            forms = [("python", None)] * 3
        else:
            got["arrays"] = walked(db, number, table, gate)
            got["python"] = walked(db, number, table, gate, python=True)
            forms = [("arrays", None), ("python", "no_native"),
                     ("python", None)]
        assert [got[w].pop("walk") for w in WALKS] == forms
        assert got["arrays"] == got["envelopes"]     # batch: in order
        assert got["python"] == got["envelopes"]
        final, tally = got["arrays"]["flags"], got["arrays"]["tally"]
        assert tally[1] + tally[2] == sum(
            a != b and b == MVCC for a, b in zip(gate, final))

        ledgers["arrays"].commit(view)
        # no envelope list was built, unless the block store's index
        # had a tx to read for which the table does not speak
        if table is not None:
            assert (view._data is None) == all(
                st == wire.LANE_OK for st in table.status.tolist())
        with the_python_walk():
            ledgers["python"].commit(view_of_raw(raw, gate))
        ledgers["envelopes"].commit(plain_of(raw, gate))
        for walk, attrs in spans_of(reason).items():
            assert mvcc_span(ledgers[walk]) == attrs
            assert ledgers[walk].commit_hash == ledgers["arrays"].commit_hash
        assert bytes(view.metadata.items[META_TXFLAGS]) == final
        codes.append(list(final))
        tallies.append(tally)
        prev = nxt
    for ledger in ledgers.values():
        assert state_of(ledger) == state_of(ledgers["envelopes"])
        assert history_of(ledger) == history_of(ledgers["envelopes"])
        assert (ledger.statedb.status()["shard_keys"]
                == ledgers["envelopes"].statedb.status()["shard_keys"])
        for number in range(len(stream)):
            assert (ledger.blockstore.get_by_number(number).serialize()
                    == ledgers["envelopes"].blockstore.get_by_number(
                        number).serialize())
    return codes, tallies


# -- the lane source against the envelope source ------------------------------


def case_bump_repeats(ids):
    """`bump`: one versioned read + one write a tx; the second and third
    tx on a key of the block conflict against the block."""
    keys = [0, 1, 2, 1, 3, 1, 2, 4]
    block = [tx(ids, rw(reads=[KVRead(f"k{k:02d}", Version(0, k))],
                        writes=[KVWrite(f"k{k:02d}", b"v1")])) for k in keys]
    stale = [tx(ids, rw(reads=[KVRead("k00", Version(0, 0))],
                        writes=[KVWrite("k00", b"v2")]))]
    stream = [(seed(ids), None), (block, None), (stale, None)]
    want = [[V] * 8, [V, V, V, MVCC, V, MVCC, MVCC, V], [MVCC]]
    return stream, want, [(0, 0, 0), (8, 3, 0), (1, 0, 1)]


def case_deletes(ids):
    """A delete in one block and stale / absent reads after it; a delete
    inside a block and reads of that key after it in the same block, the
    last one at the version the block itself put it back at."""
    b1 = [tx(ids, rw(writes=[KVWrite("k01", b"", True)]))]
    b2 = [
        tx(ids, rw(reads=[KVRead("k01", Version(0, 1))])),     # stale
        tx(ids, rw(reads=[KVRead("k01", None)],                # gone: holds
                   writes=[KVWrite("k01", b"back")])),
        tx(ids, rw(reads=[KVRead("k02", Version(0, 2))],
                   writes=[KVWrite("k02", b"", True)])),       # deletes k02
        tx(ids, rw(reads=[KVRead("k02", Version(0, 2))])),     # ... so: block
        tx(ids, rw(reads=[KVRead("k02", None)],                # absent now
                   writes=[KVWrite("k02", b"again")])),
        tx(ids, rw(reads=[KVRead("k02", None)])),              # written: block
        tx(ids, rw(reads=[KVRead("k02", Version(2, 4))])),     # the re-put
    ]
    stream = [(seed(ids), None), (b1, None), (b2, None)]
    want = [[V] * 8, [V], [MVCC, V, V, MVCC, V, MVCC, V]]
    return stream, want, [(0, 0, 0), (0, 0, 0), (7, 2, 1)]


def case_absent_keys(ids):
    """A key the state never held, read with no version (holds) and with
    a recorded one (conflicts against the state); two namespaces in one
    tx, the second one's read failing after the first one's counted."""
    two_ns = TxRwSet((
        NsRwSet("cc", reads=(KVRead("k00", Version(0, 0)),),
                writes=(KVWrite("k00", b"x"),)),
        NsRwSet("dd", reads=(KVRead("nokey", Version(3, 3)),
                             KVRead("never-counted", None)),
                writes=(KVWrite("w", b"y"),))))
    b1 = [tx(ids, rw(reads=[KVRead("nokey", None)],
                     writes=[KVWrite("nokey2", b"1")])),
          tx(ids, rw(reads=[KVRead("nokey", Version(0, 3))])),
          tx(ids, two_ns),
          tx(ids, rw(reads=[KVRead("k00", Version(0, 0))]))]
    stream = [(seed(ids), None), (b1, None)]
    return stream, [[V] * 8, [V, MVCC, MVCC, V]], [(0, 0, 0), (5, 0, 2)]


def case_garbage_bad_and_config(ids):
    """A gate-invalid tx whose rw-set is garbage stays as the gate left
    it; the same bytes gate-valid are BAD_RWSET, and so is an envelope
    whose payload is no payload at all; a config tx among endorser txs
    is skipped; an endorser tx without actions too."""
    creator, _ = ids
    junk = build.signed_envelope(TX_ENDORSER, "ch", {"not": "a tx"}, creator)
    bomb = Envelope(b"\xde\xad\xbe\xef", b"")
    config = build.signed_envelope(TX_CONFIG, "ch", {"config": 1}, creator)
    empty = build.signed_envelope(TX_ENDORSER, "ch", {"actions": []},
                                  creator)
    good = [tx(ids, rw(reads=[KVRead("k07", Version(0, 7))],
                       writes=[KVWrite("k07", b"g")])),
            tx(ids, rw(reads=[KVRead("k07", Version(0, 7))]))]
    b1 = [junk, good[0], junk, config, empty, good[1], bomb]
    stream = [(seed(ids), None), (b1, [POLICY, V, V, V, V, V, V])]
    want = [[V] * 8, [POLICY, V, BADRW, V, V, MVCC, BADRW]]
    return stream, want, [(0, 0, 0), (2, 1, 0)]


def case_smallbank_chains(ids):
    """SmallBank at s = 1.0 over 40 accounts: chains of conflicts on the
    hot ones, rw-sets of 1-3 reads and 0-3 writes, one envelope in 9
    tampered (what the gate stamped stays)."""
    creator, endorsers = ids
    plan = model.plan_chain(2**31 + 34, 40, 4, 64, 6, 9)
    stream, want = [], []
    for block in plan:
        raw, _ = model.build_block(block, GENESIS, "ch", "smallbank",
                                   endorsers, [creator] * 6)
        envelopes = [Envelope.deserialize(b)
                     for b in Block.deserialize(raw).data]
        stream.append((envelopes, [V if c == MVCC else c
                                   for c in block["codes"]]))
        want.append(list(block["codes"]))
    assert sum(c == MVCC for codes in want for c in codes) > 20
    return stream, want, None


def case_thrice_written(ids):
    """A key written by three valid txs of a block (blind writes), other
    keys between them: the batch keeps its first position and its last
    value, the history all three."""
    b1 = [tx(ids, rw(writes=[KVWrite("k03", b"one")])),
          tx(ids, rw(writes=[KVWrite("new", b"n"), KVWrite("k03", b"two")])),
          tx(ids, rw(writes=[KVWrite("k01", b"m")])),
          tx(ids, rw(writes=[KVWrite("k03", b"three"),
                             KVWrite("k03", b"four")]))]
    stream = [(seed(ids), None), (b1, None)]
    return stream, [[V] * 8, [V] * 4], [(0, 0, 0), (0, 0, 0)]


def case_nil_after_a_staged_delete(ids):
    """nil = nil: a read without a version holds against a delete staged
    earlier in the block, and against a key the state never held; once
    the key is written again the same read fails against the block."""
    b1 = [tx(ids, rw(writes=[KVWrite("k03", b"", True)])),
          tx(ids, rw(reads=[KVRead("k03", None)],
                     writes=[KVWrite("k03", b"new")])),
          tx(ids, rw(reads=[KVRead("never", None)])),
          tx(ids, rw(reads=[KVRead("k03", None)])),
          tx(ids, rw(reads=[KVRead("k03", Version(1, 1))],   # the staged put
                     writes=[KVWrite("k03", b"", True),
                             KVWrite("gone", b"", True)])),
          tx(ids, rw(reads=[KVRead("gone", None), KVRead("k03", None)]))]
    stream = [(seed(ids), None), (b1, None)]
    return (stream, [[V] * 8, [V, V, V, MVCC, V, V]],
            [(0, 0, 0), (6, 1, 0)])


def case_bad_between_valid(ids):
    """A gate-valid tx whose rw-set does not decode, between two that
    do: BAD_RWSET, and the walk goes on with the lanes after it."""
    creator, _ = ids
    junk = build.signed_envelope(TX_ENDORSER, "ch", {"not": "a tx"}, creator)
    b1 = [tx(ids, rw(reads=[KVRead("k00", Version(0, 0))],
                     writes=[KVWrite("k00", b"a")])),
          junk,
          tx(ids, rw(reads=[KVRead("k00", Version(0, 0))],
                     writes=[KVWrite("k01", b"b")])),
          tx(ids, rw(reads=[KVRead("k01", Version(0, 1))],
                     writes=[KVWrite("k01", b"c")]))]
    stream = [(seed(ids), None), (b1, None)]
    return (stream, [[V] * 8, [V, BADRW, MVCC, V]],
            [(0, 0, 0), (3, 1, 0)])


def case_valid_txs_write_nothing(ids):
    """The only valid txs of the block read and write nothing; the one
    that writes failed the gate.  An empty batch, no history row."""
    b1 = [tx(ids, rw(reads=[KVRead("k00", Version(0, 0))])),
          tx(ids, rw(writes=[KVWrite("k00", b"never")])),
          tx(ids, rw(reads=[KVRead("k01", Version(0, 1)),
                            KVRead("k02", Version(0, 2))])),
          tx(ids, rw())]
    stream = [(seed(ids), None), (b1, [V, POLICY, V, V])]
    return stream, [[V] * 8, [V, POLICY, V, V]], [(0, 0, 0), (3, 0, 0)]


def case_one_tx(ids):
    b1 = [tx(ids, rw(reads=[KVRead("k05", Version(0, 5))],
                     writes=[KVWrite("k05", b"5")]))]
    b2 = [tx(ids, rw(reads=[KVRead("k05", Version(0, 5))],
                     writes=[KVWrite("k05", b"6")]))]
    stream = [(seed(ids), None), (b1, None), (b2, None)]
    return (stream, [[V] * 8, [V], [MVCC]],
            [(0, 0, 0), (1, 0, 0), (1, 0, 1)])


def case_parameters_dropped(ids):
    """Key-level validation parameters (`cc#meta`) and the deletes that
    take them along: one held in state and not named by the block, one
    the block names, one set and deleted by the same tx, one dropped
    twice in a block, and a read of a dropped parameter after it."""
    def meta(writes=(), reads=()):
        return NsRwSet("cc#meta", reads=tuple(reads), writes=tuple(writes))

    b1 = [tx(ids, TxRwSet((meta([KVWrite(f"k{i:02d}", b"p")
                                 for i in range(5)]),)))]
    b2 = [
        tx(ids, rw(writes=[KVWrite("k00", b"", True)])),     # held, unnamed
        tx(ids, TxRwSet((NsRwSet("cc", writes=(KVWrite("k01", b"", True),)),
                         meta([KVWrite("k01", b"again")])))),  # set + delete
        tx(ids, TxRwSet((meta(reads=[KVRead("k02", Version(1, 0))]),))),
        tx(ids, rw(writes=[KVWrite("k02", b"", True)])),     # named by a read
        tx(ids, TxRwSet((meta(reads=[KVRead("k02", Version(1, 0))]),))),
        tx(ids, TxRwSet((meta(reads=[KVRead("k02", None)]),))),
        tx(ids, rw(writes=[KVWrite("k00", b"back")])),
        tx(ids, rw(writes=[KVWrite("k00", b"", True)])),     # dropped already
        tx(ids, TxRwSet((meta([KVWrite("k03", b"", True)]),))),  # by hand
        tx(ids, rw(writes=[KVWrite("k03", b"", True),
                           KVWrite("k07", b"", True)])),     # k07: none held
        tx(ids, rw(reads=[KVRead("k04", Version(0, 3))],     # loses MVCC:
                   writes=[KVWrite("k04", b"", True)])),     # k04's stays
    ]
    b3 = [tx(ids, TxRwSet((meta(reads=[KVRead("k04", Version(1, 0)),
                                       KVRead("k00", None),
                                       KVRead("k01", None)]),)))]
    stream = [(seed(ids), None), (b1, None), (b2, None), (b3, None)]
    want = [[V] * 8, [V], [V, V, V, V, MVCC, V, V, V, V, V, MVCC], [V]]
    return stream, want, [(0, 0, 0), (0, 0, 0), (4, 1, 1), (3, 0, 0)]


def case_chain_reads_the_blocks_own_puts(ids):
    """A write-write chain on one key whose later links read the
    versions the block itself staged: the winner's put holds, a loser's
    would-be version never existed."""
    b1 = [tx(ids, rw(reads=[KVRead("k00", Version(0, 0))],
                     writes=[KVWrite("k00", b"a")])),
          tx(ids, rw(reads=[KVRead("k00", Version(0, 0))],    # tx0 won
                     writes=[KVWrite("k00", b"b")])),
          tx(ids, rw(reads=[KVRead("k00", Version(1, 0))],    # tx0's put
                     writes=[KVWrite("k00", b"c")])),
          tx(ids, rw(reads=[KVRead("k00", Version(1, 2))])),  # tx2's put
          tx(ids, rw(reads=[KVRead("k00", Version(1, 1))]))]  # tx1 lost
    stream = [(seed(ids), None), (b1, None)]
    return stream, [[V] * 8, [V, MVCC, V, V, MVCC]], [(0, 0, 0), (5, 2, 0)]


def held(i, block=0):
    return KVRead(f"k{i:02d}", Version(block, i))


def case_range_phantoms(ids):
    """Phantoms made and unmade by the block's own writes, under both
    `itr_exhausted`: the block is the envelope source's ("range"), and
    the next one, which reads what the phantoms' writes would have left,
    is the lane source's again."""
    full = RangeQueryInfo("k05", "k08", True, (held(5), held(6), held(7)))
    b1 = [tx(ids, rw(ranges=[full], writes=[KVWrite("z0", b"1")])),
          tx(ids, rw(writes=[KVWrite("k06", b"new")])),       # inside
          tx(ids, rw(ranges=[full], writes=[KVWrite("z1", b"1")])),
          tx(ids, rw(writes=[KVWrite("k09", b"x")])),         # outside
          tx(ids, rw(ranges=[RangeQueryInfo("k10", "k12", True,
                                            (held(10), held(11)))],
                     writes=[KVWrite("z2", b"1")])),
          tx(ids, rw(ranges=[RangeQueryInfo("k05", "k08", False,
                                            (held(5), held(6)))])),
          tx(ids, rw(writes=[KVWrite("k05", b"", True)])),    # the start key
          tx(ids, rw(ranges=[RangeQueryInfo("k10", "k12", False,
                                            (held(10),))],
                     writes=[KVWrite("z3", b"1")]))]          # a prefix: ok
    b2 = [tx(ids, rw(reads=[KVRead("z1", None),               # never landed
                            KVRead("z0", Version(1, 0)),
                            KVRead("k05", None)]))]
    stream = [(seed(ids, 13), None), (b1, None, "range"), (b2, None)]
    want = [[V] * 13, [V, V, PHANTOM, V, V, PHANTOM, V, V], [V]]
    return stream, want, [(0, 0, 0), (0, 0, 0), (3, 0, 0)]


def case_all_conflict_then_none(ids):
    """Every tx of a block reads a version nobody wrote: an empty batch,
    no history row.  Then every tx of a block holds, on keys of its own."""
    b1 = [tx(ids, rw(reads=[KVRead(f"k{i:02d}", Version(9, 9))],
                     writes=[KVWrite(f"k{i:02d}", b"x")])) for i in range(8)]
    b2 = [tx(ids, rw(reads=[held(i)], writes=[KVWrite(f"n{i}", b"y")]))
          for i in range(8)]
    stream = [(seed(ids), None), (b1, None), (b2, None)]
    return (stream, [[V] * 8, [MVCC] * 8, [V] * 8],
            [(0, 0, 0), (8, 0, 8), (8, 0, 0)])


def case_gate_losers_write_nothing(ids):
    """Txs the signature gate or the policy failed, with rw-sets that
    would have won MVCC: their writes never land, so the tx after them
    conflicts with the winner only, and a read of what the loser wrote
    still holds."""
    b1 = [tx(ids, rw(reads=[held(0)], writes=[KVWrite("k00", b"a")])),
          tx(ids, rw(reads=[held(0)], writes=[KVWrite("k00", b"b")])),
          tx(ids, rw(writes=[KVWrite("k01", b"c")])),
          tx(ids, rw(reads=[held(0)], writes=[KVWrite("k00", b"d")])),
          tx(ids, rw(reads=[held(1)]))]
    stream = [(seed(ids), None), (b1, [V, POLICY, BADSIG, V, V])]
    return (stream, [[V] * 8, [V, POLICY, BADSIG, MVCC, V]],
            [(0, 0, 0), (3, 1, 0)])


def case_adjacent_block_chains(ids):
    """Chains across adjacent blocks: a block reads and rewrites what the
    one before it wrote (write-read), overwrites it blind (write-write),
    and the block after reads both the stale and the fresh version
    (read-write), the fresh one once more after a tx of its own block
    took it."""
    b1 = [tx(ids, rw(writes=[KVWrite(f"k{i:02d}", b"v1")])) for i in range(4)]
    b2 = [tx(ids, rw(reads=[held(0, 1)], writes=[KVWrite("k00", b"w1")])),
          tx(ids, rw(writes=[KVWrite("k01", b"blind")])),
          tx(ids, rw(writes=[KVWrite("z0", b"z")]))]
    b3 = [tx(ids, rw(reads=[held(0, 1)],                      # stale
                     writes=[KVWrite("lost0", b"never")])),
          tx(ids, rw(reads=[KVRead("k00", Version(2, 0))],
                     writes=[KVWrite("k00", b"w2")])),        # fresh
          tx(ids, rw(reads=[KVRead("k00", Version(2, 0))])),  # taken: block
          tx(ids, rw(reads=[held(1, 1)],                      # overwritten
                     writes=[KVWrite("lost3", b"never")]))]
    stream = [(seed(ids), None), (b1, None), (b2, None), (b3, None)]
    want = [[V] * 8, [V] * 4, [V] * 3, [MVCC, V, MVCC, MVCC]]
    return stream, want, [(0, 0, 0), (0, 0, 0), (1, 0, 0), (4, 1, 2)]


def case_cross_block_range_phantom(ids):
    """A key written into an interval by one block is a phantom to the
    next block's scan of it, and part of the result for the scan after."""
    b1 = [tx(ids, rw(writes=[KVWrite("k025", b"phantom")]))]
    b2 = [tx(ids, rw(ranges=[RangeQueryInfo("k02", "k05", True,
                                            (held(2), held(3), held(4)))],
                     writes=[KVWrite("z1", b"s")])),
          tx(ids, rw(writes=[KVWrite("z2", b"i")]))]
    b3 = [tx(ids, rw(ranges=[RangeQueryInfo(
              "k02", "k05", True,
              (held(2), KVRead("k025", Version(1, 0)), held(3), held(4)))],
              writes=[KVWrite("z3", b"s")])),
          tx(ids, rw(reads=[KVRead("z1", None)]))]            # never landed
    stream = [(seed(ids), None), (b1, None), (b2, None, "range"),
              (b3, None, "range")]
    want = [[V] * 8, [V], [PHANTOM, V], [V, V]]
    return stream, want, [(0, 0, 0)] * 3 + [(1, 0, 0)]


def case_doomed_then_rewritten(ids):
    """A tx that loses MVCC leaves none of its writes: the next block
    reads its key as absent, and the version it would have had as
    stale; the key the winner rewrote is read at the winner's."""
    b1 = [tx(ids, rw(reads=[KVRead("k00", Version(9, 9))],
                     writes=[KVWrite("k50", b"never")])),
          tx(ids, rw(reads=[held(1)], writes=[KVWrite("k01", b"won")]))]
    b2 = [tx(ids, rw(reads=[KVRead("k50", None)],
                     writes=[KVWrite("z3", b"ok")])),
          tx(ids, rw(reads=[KVRead("k01", Version(1, 1))])),
          tx(ids, rw(reads=[KVRead("k50", Version(1, 0))]))]
    stream = [(seed(ids), None), (b1, None), (b2, None)]
    return (stream, [[V] * 8, [MVCC, V], [V, V, MVCC]],
            [(0, 0, 0), (2, 0, 1), (3, 0, 1)])


@pytest.mark.parametrize("case", [
    case_bump_repeats, case_deletes, case_absent_keys,
    case_garbage_bad_and_config, case_smallbank_chains,
    case_thrice_written, case_nil_after_a_staged_delete,
    case_bad_between_valid, case_valid_txs_write_nothing, case_one_tx,
    case_parameters_dropped, case_chain_reads_the_blocks_own_puts,
    case_range_phantoms, case_all_conflict_then_none,
    case_gate_losers_write_nothing, case_adjacent_block_chains,
    case_cross_block_range_phantom, case_doomed_then_rewritten],
    ids=lambda c: c.__name__[5:])
@pytest.mark.parametrize("prepared", [False, True],
                         ids=["opened_in_commit", "opened_ahead"])
def test_the_three_walks_give_the_same_answers(ids, case, prepared):
    stream, want_codes, want_tallies = case(ids)
    codes, tallies = through_three_walks(stream, prepared=prepared)
    assert codes == want_codes
    if want_tallies is not None:
        assert tallies == want_tallies


# -- the seeded generators ----------------------------------------------------
#
# Written against the wave scheduler (PR 8), the commit window (PR 11) and
# the fused device validator (PR 17); generator and seeds as they were,
# one case a seed, through the walk's three forms.


def fuzz_stream(ids, opening, blocks, gate_ranges):
    """`blocks` — the rw-sets of each — after the `opening` blocks, as a
    stream for `through_three_walks`.  A block in which a range query is
    still VALID is the envelope source's ("range"); with `gate_ranges`
    every tx that carries one failed its policy, and the same reads,
    writes and deletes are the lane source's."""
    stream = [([tx(ids, r) for r in rwsets], None) for rwsets in opening]
    for rwsets in blocks:
        ranged = [any(n.range_queries for n in r.ns_rwsets) for r in rwsets]
        gate = [POLICY if gate_ranges and r else V for r in ranged]
        reason = "range" if any(ranged) and not gate_ranges else None
        stream.append(([tx(ids, r) for r in rwsets], gate, reason))
    return stream


def through_three_walks_with_and_without_ranges(ids, opening, blocks):
    ranged = any(n.range_queries for rwsets in blocks for r in rwsets
                 for n in r.ns_rwsets)
    for gate_ranges in (False, True) if ranged else (False,):
        through_three_walks(fuzz_stream(ids, opening, blocks, gate_ranges))


def twenty_keys_at_block_one():
    """Blocks 0 and 1: k00..k19 = b"v<i>" at Version(1, i)."""
    return [[rw(writes=[KVWrite("opened", b"")])],
            [rw(writes=[KVWrite(f"k{i:02d}", b"v%d" % i)])
             for i in range(20)]]


def random_rwsets(rng, keys, n_txs, version_of, range_stop):
    """One block of the PR 8 / PR 11 generator: stale, fresh and nil
    reads, puts, deletes, and a range query in three txs of ten."""
    rwsets = []
    for _t in range(n_txs):
        reads, writes, ranges = [], [], []
        for _ in range(rng.randrange(0, 3)):
            k = rng.choice(keys)
            reads.append(KVRead(k, rng.choice(
                [version_of(int(k[1:])), Version(7, 7), None])))
        for _ in range(rng.randrange(0, 3)):
            k = rng.choice(keys)
            if rng.random() < 0.25:
                writes.append(KVWrite(k, b"", True))
            else:
                writes.append(KVWrite(k, rng.randbytes(4)))
        if rng.random() < 0.3:
            lo, hi = sorted(rng.sample(range(12), 2))
            recs = tuple(KVRead(f"k{i:02d}", version_of(i))
                         for i in range(lo, min(hi, range_stop)))
            ranges.append(RangeQueryInfo(f"k{lo:02d}", f"k{hi:02d}",
                                         rng.random() < 0.5, recs))
        rwsets.append(rw(reads=reads, writes=writes, ranges=ranges))
    return rwsets


@pytest.mark.parametrize("rng_seed", range(25))
def test_a_seeded_random_block_through_the_three_walks(ids, rng_seed):
    """`test_differential_fuzz_random_blocks` of PR 8: one block of 1-9
    txs over twelve of twenty committed keys."""
    rng = random.Random(rng_seed)
    keys = [f"k{i:02d}" for i in range(12)]
    block = random_rwsets(rng, keys, rng.randrange(1, 10),
                          lambda i: Version(1, i), 12)
    through_three_walks_with_and_without_ranges(
        ids, twenty_keys_at_block_one(), [block])


@pytest.mark.parametrize("rng_seed", range(1000, 1025))
def test_a_seeded_random_stream_through_the_three_walks(ids, rng_seed):
    """`test_window_differential_fuzz_25_seeds` of PR 11: eight of twelve
    keys opened in block 0, then 2-4 blocks of 1-5 txs, each over the
    state the ones before it left."""
    rng = random.Random(rng_seed)
    keys = [f"k{i:02d}" for i in range(12)]
    opening = [[rw(writes=[KVWrite(k, b"s%d" % i)])
                for i, k in enumerate(keys[:8])]]
    blocks = [random_rwsets(rng, keys, rng.randrange(1, 6),
                            lambda i: Version(0, i), 8)
              for _b in range(rng.randrange(2, 5))]
    through_three_walks_with_and_without_ranges(ids, opening, blocks)


@pytest.mark.parametrize("rng_seed", range(7000, 7010))
def test_a_seeded_stream_of_blind_rewrites_keeps_its_batch_order(ids,
                                                                 rng_seed):
    """`test_window_level_batch_insertion_order_fuzz` of PR 11: three
    blocks of 1-4 txs, up to two writes a tx on ten keys that repeat, so
    a key's first position and last value are what the batch must keep
    (`walked` compares the batches item by item, in order)."""
    rng = random.Random(rng_seed)
    keys = [f"k{i:02d}" for i in range(10)]
    blocks = []
    for _b in range(3):
        rwsets = []
        for _t in range(rng.randrange(1, 5)):
            reads = [KVRead(rng.choice(keys),
                            rng.choice([Version(1, 3), None]))
                     for _ in range(rng.randrange(0, 2))]
            writes = [KVWrite(rng.choice(keys), rng.randbytes(3))
                      for _ in range(rng.randrange(0, 3))]
            rwsets.append(rw(reads=reads, writes=writes))
        blocks.append(rwsets)
    through_three_walks(
        fuzz_stream(ids, twenty_keys_at_block_one(), blocks, False))


@pytest.mark.parametrize("rng_seed", [0xFAB11])
def test_seeded_blocks_of_eight_through_the_three_walks(ids, rng_seed):
    """`test_seeded_random_blocks` of PR 17: 3 blocks x 8 txs, up to
    three reads (the version block 0 left, a random one, none) and two
    writes or deletes a tx over eight keys.  The reads stay those of
    block 0 as the state drifts under them."""
    rng = random.Random(rng_seed)
    keys = [f"k{i:02d}" for i in range(8)]
    committed = {k: Version(0, i) for i, k in enumerate(keys)}
    blocks = []
    for blk in (1, 2, 3):
        rwsets = []
        for _tx in range(8):
            reads, writes = [], []
            for k in rng.sample(keys, rng.randint(0, 3)):
                choice = rng.random()
                if choice < 0.5:
                    ver = committed.get(k)
                elif choice < 0.75:
                    ver = Version(rng.randint(0, 3), rng.randint(0, 7))
                else:
                    ver = None
                reads.append(KVRead(k, ver))
            for k in rng.sample(keys, rng.randint(0, 2)):
                if rng.random() < 0.25:
                    writes.append(KVWrite(k, b"", True))
                else:
                    writes.append(KVWrite(k, bytes([blk, rng.randint(0, 9)])))
            rwsets.append(rw(reads=reads, writes=writes))
        blocks.append(rwsets)
    opening = [[rw(writes=[KVWrite(k, b"v0")]) for k in keys]]
    codes, tallies = through_three_walks(
        fuzz_stream(ids, opening, blocks, False))
    flat = [c for block in codes[1:] for c in block]
    assert flat.count(MVCC) > 5 and flat.count(V) > 5
    assert sum(t[1] for t in tallies) > 0 < sum(t[2] for t in tallies)


def test_the_batch_keeps_a_keys_first_position_and_last_value(ids):
    stream, _, _ = case_thrice_written(ids)
    ledger = KVLedger("ch", LedgerConfig())
    raw0, prev = raw_block(0, GENESIS, stream[0][0])
    ledger.commit(view_of(raw0, [V] * 8))
    raw1, _ = raw_block(1, prev, stream[1][0])
    view = view_of(raw1, [V] * 4)
    table, _ = mvcc.lane_source_of(view, TxFlags.from_bytes(bytes(4)))
    got = walked(ledger.statedb, 1, table, bytes(4))
    assert got["walk"] == ("arrays", None)
    assert [(k, vv.value, vv.version.tx_num) for k, vv in got["batch"]] == [
        (("cc", "k03"), b"four", 3), (("cc", "new"), b"n", 1),
        (("cc", "k01"), b"m", 2)]
    assert [(t, key, value) for t, _, _, key, value, _ in got["history"]] == [
        (0, "k03", b"one"), (1, "new", b"n"), (1, "k03", b"two"),
        (2, "k01", b"m"), (3, "k03", b"three"), (3, "k03", b"four")]


def test_two_hundred_smallbank_blocks_end_alike_on_all_three(ids):
    """A seeded random stream: 5 opening blocks and 195 of the mix at
    s = 1.0 over 60 accounts, one envelope in 9 tampered."""
    creator, endorsers = ids
    plan = model.plan_chain(2**31 + 43, 60, 195, 12, 6, 9)
    assert len(plan) == 200
    stream, want = [], []
    for block in plan:
        raw, _ = model.build_block(block, GENESIS, "ch", "smallbank",
                                   endorsers, [creator] * 6)
        stream.append(([Envelope.deserialize(b)
                        for b in Block.deserialize(raw).data],
                       [V if c == MVCC else c for c in block["codes"]]))
        want.append(list(block["codes"]))
    codes, tallies = through_three_walks(stream)
    assert codes == want
    # every tx of a block is simulated on the state before the block:
    # what fails, fails against the block
    assert sum(t[1] for t in tallies) == sum(
        c == MVCC for block in codes for c in block) > 300


@pytest.mark.parametrize("shards", [1, 13])
def test_the_three_walks_agree_at_other_stripe_widths(ids, shards):
    """One shard (no split at all) and a width that is no power of two:
    the array pass's warm split is the hashed one (`walked` compares)."""
    for case in (case_bump_repeats, case_parameters_dropped):
        stream, want_codes, _ = case(ids)
        codes, _ = through_three_walks(
            stream, lambda: LedgerConfig(state_shards=shards))
        assert codes == want_codes


@pytest.mark.parametrize("case", [
    case_bump_repeats, case_parameters_dropped, case_range_phantoms,
    case_gate_losers_write_nothing, case_adjacent_block_chains,
    case_cross_block_range_phantom, case_doomed_then_rewritten],
    ids=lambda c: c.__name__[5:])
def test_three_ledgers_one_stream_end_at_the_same_bytes(ids, tmp_path, case):
    """The durable three: ledgers on disk, fed views walked as arrays,
    views walked in Python and plain blocks, write the same state and
    history WALs byte for byte and, reopened, agree with each other and
    with themselves."""
    stream, want, _ = case(ids)
    roots = {walk: str(tmp_path / walk) for walk in WALKS}
    ledgers = {walk: KVLedger("ch", LedgerConfig(root=roots[walk]))
               for walk in WALKS}
    prev = GENESIS
    for number, (envelopes, gate, *reason) in enumerate(stream):
        raw, prev = raw_block(number, prev, envelopes)
        gate = gate or [V] * len(envelopes)
        ledgers["arrays"].commit(view_of(raw, gate))
        with the_python_walk():
            ledgers["python"].commit(view_of(raw, gate))
        ledgers["envelopes"].commit(plain_of(raw, gate))
        assert {w: mvcc_span(ledgers[w]) for w in WALKS} == spans_of(*reason)
    for db, wal in (("statedb", "state.wal"), ("historydb", "history.wal")):
        written = {}
        for w in WALKS:
            with open(os.path.join(getattr(ledgers[w], db).root, wal),
                      "rb") as f:
                written[w] = f.read()
        assert written["arrays"] == written["python"] == written["envelopes"]
        assert len(written["arrays"]) > 100
    reopened = {w: KVLedger("ch", LedgerConfig(root=roots[w])) for w in WALKS}
    for w in WALKS:
        for a, b in ((ledgers[w], ledgers["envelopes"]),
                     (reopened[w], reopened["envelopes"]),
                     (ledgers[w], reopened[w])):
            assert a.commit_hash == b.commit_hash
            assert state_of(a) == state_of(b)
            assert history_of(a) == history_of(b)
    assert [list(reopened["arrays"].blockstore.get_by_number(n)
                 .metadata.items[META_TXFLAGS])
            for n in range(len(stream))] == want


@pytest.mark.parametrize("torn", [1, 2, 3])
def test_a_crash_between_state_and_history_replays_the_block_once(
        ids, tmp_path, torn):
    """The ledger fed views, killed after block `torn`'s state commit
    and before its history commit, over the adjacent-block chains: the
    reopened ledger replays that block's history from the flags the
    array walk stored — the rows of its VALID txs, none of the ones that
    lost MVCC, none twice — and the chain goes on over it."""
    stream, want, _ = case_adjacent_block_chains(ids)
    root = str(tmp_path / "torn")
    ledger, whole = KVLedger("ch", LedgerConfig(root=root)), KVLedger("ch")
    raws, prev = [], GENESIS
    for number, (envelopes, _gate) in enumerate(stream):
        raw, prev = raw_block(number, prev, envelopes)
        raws.append(raw)

    def feed(ledger, numbers):
        for number in numbers:
            ledger.commit(view_of(raws[number], [V] * len(stream[number][0])))
            assert mvcc_span(ledger) == WALKS["arrays"]

    feed(whole, range(len(raws)))
    feed(ledger, range(torn))

    def die(number, rows):
        raise RuntimeError("kill -9 (injected before the history commit)")

    ledger.historydb.commit = die
    with pytest.raises(RuntimeError, match="before the history commit"):
        feed(ledger, [torn])
    assert ledger.statedb.savepoint == torn
    assert ledger.historydb.savepoint == torn - 1

    reopened = KVLedger("ch", LedgerConfig(root=root))
    assert reopened.last_recovery == {"replayed_blocks": 1, "start": torn,
                                      "height": torn + 1}
    assert reopened.historydb.savepoint == torn
    feed(reopened, range(torn + 1, len(raws)))
    assert reopened.commit_hash == whole.commit_hash
    assert state_of(reopened) == state_of(whole)
    assert history_of(reopened) == history_of(whole)
    assert not {("cc", "lost0"), ("cc", "lost3")} & set(history_of(reopened))
    assert [list(reopened.blockstore.get_by_number(n)
                 .metadata.items[META_TXFLAGS])
            for n in range(len(raws))] == want
    # and a second reopening finds nothing left to replay
    assert KVLedger("ch", LedgerConfig(root=root)).last_recovery[
        "replayed_blocks"] == 0


# -- the rule and its demotions -----------------------------------------------


def without_nonce(ids):
    """An endorser tx whose signature header lacks its nonce: the rw-set
    decodes (`parse_endorser_tx` reads no signature header), the header
    does not (`Envelope.header()` raises).  Lane status UNKNOWN."""
    good = tx(ids, rw(writes=[KVWrite("k06", b"u")]))
    payload = serde.decode(good.payload)
    del payload["header"]["signature_header"]["nonce"]
    return Envelope(serde.encode(payload), good.signature)


def demotion_plain_block(ids, monkeypatch):
    block = [tx(ids, rw(writes=[KVWrite("k05", b"x")]))]
    return block, None, plain_of, "no_view"


def demotion_no_native(ids, monkeypatch):
    """What FABRIC_TPU_NO_NATIVE=1 leaves behind: `wire._fastparse` is
    None, so the extractor would be the Python mirror."""
    block = [tx(ids, rw(writes=[KVWrite("k05", b"x")]))]

    def parse(raw, gate):
        view = view_of(raw, gate)
        monkeypatch.setattr(wire, "_fastparse", None)
        return view
    return block, None, parse, "no_native"


def demotion_collision(ids, monkeypatch):
    """djb2-64("ab") == djb2-64("bA"): the table's flag is 1."""
    block = [tx(ids, rw(writes=[KVWrite("ab", b"1")])),
             tx(ids, rw(writes=[KVWrite("bA", b"2")]))]
    return block, None, view_of, "collision"


def demotion_valid_range(ids, monkeypatch):
    held = tuple(KVRead(f"k{i:02d}", Version(0, i)) for i in range(3))
    block = [tx(ids, rw(ranges=[RangeQueryInfo("k00", "k03", True, held)])),
             tx(ids, rw(ranges=[RangeQueryInfo("k00", "k03", True,
                                               held[:2])]))]
    return block, None, view_of, "range"


def demotion_unknown(ids, monkeypatch):
    block = [tx(ids, rw(writes=[KVWrite("k05", b"x")])), without_nonce(ids)]
    return block, None, view_of, "unknown"


def demotion_count(ids, monkeypatch):
    """One flag more than the block has txs."""
    block = [tx(ids, rw(writes=[KVWrite("k05", b"x")]))]
    return block, [V, V], view_of, "count"


@pytest.mark.parametrize("demotion", [
    demotion_plain_block, demotion_no_native, demotion_collision,
    demotion_valid_range, demotion_unknown, demotion_count],
    ids=lambda d: d.__name__[9:])
def test_a_demoted_block_commits_through_the_envelope_source(
        ids, monkeypatch, demotion):
    envelopes, gate, parse, reason = demotion(ids, monkeypatch)
    channel = "ch-" + reason
    ledger, oracle = (KVLedger(channel, LedgerConfig()) for _ in range(2))
    raw0, prev = raw_block(0, GENESIS, seed(ids))
    ledger.commit(view_of(raw0, [V] * 8))
    oracle.commit(plain_of(raw0, [V] * 8))
    before = source_counts(channel)
    raw, _ = raw_block(1, prev, envelopes)
    gate = gate or [V] * len(envelopes)
    ledger.commit(parse(raw, gate))
    assert mvcc_span(ledger) == {"source": "envelopes", "reason": reason,
                                 "walk": "python"}
    oracle.commit(plain_of(raw, gate))
    assert ledger.commit_hash == oracle.commit_hash
    assert state_of(ledger) == state_of(oracle)
    assert history_of(ledger) == history_of(oracle)
    after = source_counts(channel)
    # the oracle shares the channel's series: two blocks, both by envelopes
    assert after["lanes"] == before["lanes"]
    assert after["envelopes"] - before["envelopes"] == 2 * len(gate)
    # ... and both by the Python walk, each for its source's reason
    want = collections.Counter({("arrays", "none"): 8,
                                ("python", "no_view"): 8 + len(gate)})
    want[("python", reason)] += len(gate)
    assert walk_counts(channel, reason, "no_view") == want


# -- the table opened ahead: the validator's wait for the device --------------


def validated_committer(org, prepare=True):
    """A committer over channel "ch" whose validator knows the one org
    that signs everything here.  `prepare=False`: the validator's step in
    its wait taken out — the program as it was before that step existed,
    whose answers a block must keep."""
    from fabric_tpu.committer.committer import Committer
    from fabric_tpu.committer.txvalidator import PolicyRegistry, TxValidator
    from fabric_tpu.msp import CachedMSP
    from fabric_tpu.policy import parse_policy
    validator = TxValidator(
        "ch", {"Org1": CachedMSP(org.msp())},
        init_factories(FactoryOpts(default="SW")),
        PolicyRegistry(parse_policy("OR('Org1.member')")))
    if not prepare:
        validator._prepare_lanes = lambda block, wait: None
    return Committer(KVLedger("ch", LedgerConfig()), validator)


@pytest.mark.parametrize("demotion,source,opened", [
    (demotion_plain_block, "envelopes", None),
    (demotion_no_native, "envelopes", None),
    (demotion_collision, "envelopes", None),
    # the table is open, and the rule refuses it afterwards as before
    (demotion_valid_range, "envelopes", "validator_wait"),
    # the gate refuses a tx whose header does not decode: not VALID, so
    # its status demotes nothing and the lanes supply the block
    (demotion_unknown, "lanes", "validator_wait")],
    ids=lambda d: d.__name__[9:] if callable(d) else None)
def test_a_validated_block_keeps_its_answers_where_the_wait_prepares_nothing(
        org, ids, monkeypatch, lanes_opened, demotion, source, opened):
    """The blocks `wire.prepare_lanes` returns without work for (no view,
    no native extractor — whose Python mirror must not run — a collision)
    and the ones whose table opens and is refused later (a VALID range)
    or speaks for all but one tx (UNKNOWN): validated and committed with
    the flags, commit hash, state, history rows and `ledger.mvcc` source
    and reason of a program whose validator prepares nothing."""
    envelopes, _gate, _parse, reason = demotion(ids, monkeypatch)
    monkeypatch.undo()
    subject, oracle = (validated_committer(org, prepare)
                       for prepare in (True, False))
    raw0, prev = raw_block(0, GENESIS, seed(ids))
    raw, _ = raw_block(1, prev, envelopes)
    mirror = []
    results = {}
    for name, committer in (("subject", subject), ("oracle", oracle)):
        committer.store_block(view_of(raw0))
        before = lanes_opened()
        with monkeypatch.context() as patch:
            patch.setattr(wire, "rwset_lanes_py",
                          lambda *a: mirror.append(a) or None)
            parse = demotion(ids, patch)[2]     # its patches: this commit's
            results[name] = committer.store_block(parse(raw, None))
        at = opened if name == "subject" else opened and "commit"
        assert lanes_opened(before) == ({at: len(envelopes)} if at else {})
    assert not mirror
    span = {"source": source, "walk": "python" if reason != "unknown"
            else "arrays"}
    if source == "envelopes":
        span["reason"] = reason
    for committer in (subject, oracle):
        assert mvcc_span(committer.ledger) == span
    assert (results["subject"].validation.flags.codes()
            == results["oracle"].validation.flags.codes())
    assert (results["subject"].final_flags.codes()
            == results["oracle"].final_flags.codes())
    assert V in results["subject"].final_flags.codes()
    assert subject.ledger.commit_hash == oracle.ledger.commit_hash
    assert state_of(subject.ledger) == state_of(oracle.ledger)
    assert history_of(subject.ledger) == history_of(oracle.ledger)


@pytest.mark.parametrize("reason", ["no_native"])
def test_a_lane_table_the_array_pass_cannot_take_is_walked_in_python(
        ids, reason):
    """One case a reason of `mvcc.walk_of`.  It has one: the pass folds
    the parameter a delete takes along into its decision, so a block that
    deletes on a channel that holds parameters is walked as arrays too
    (`case_parameters_dropped`), and what is left is a process whose
    `native/fastmvcc.c` did not build."""
    channel = "ch-walk-" + reason
    ledger, oracle = (KVLedger(channel, LedgerConfig()) for _ in range(2))
    stream, want, _ = case_parameters_dropped(ids)
    prev = GENESIS
    for number, (envelopes, _gate) in enumerate(stream):
        raw, prev = raw_block(number, prev, envelopes)
        gate = [V] * len(envelopes)
        before = walk_counts(channel, reason)
        oracle.commit(view_of(raw, gate))
        assert mvcc_span(oracle) == WALKS["arrays"]
        assert mvcc.walk_of() == ("arrays", None)
        with the_python_walk():
            assert mvcc.walk_of() == ("python", reason)
            view = view_of(raw, gate)
            ledger.commit(view)
        assert mvcc_span(ledger) == {"source": "lanes", "walk": "python",
                                     "reason": reason}
        after = walk_counts(channel, reason)
        assert {k: after[k] - before[k] for k in after} == {
            ("arrays", "none"): len(gate), ("python", reason): len(gate)}
        assert ledger.commit_hash == oracle.commit_hash
        assert list(view.metadata.items[META_TXFLAGS]) == want[number]
    assert state_of(ledger) == state_of(oracle)
    assert history_of(ledger) == history_of(oracle)
    assert ledger.statedb.meta_keys() == oracle.statedb.meta_keys() == (3, 1)


def test_a_gate_invalid_range_or_unknown_tx_does_not_demote(ids):
    held = tuple(KVRead(f"k{i:02d}", Version(0, i)) for i in range(3))
    envelopes = [tx(ids, rw(ranges=[RangeQueryInfo("k00", "k03", True,
                                                   held[:2])])),
                 without_nonce(ids),
                 tx(ids, rw(reads=[KVRead("k00", Version(0, 0))],
                            writes=[KVWrite("k00", b"y")]))]
    codes, tallies = through_three_walks(
        [(seed(ids), None), (envelopes, [POLICY, POLICY, V])])
    assert codes[1] == [POLICY, POLICY, V]
    assert tallies[1] == (1, 0, 0)


def test_the_mvcc_span_carries_its_source(ids):
    from fabric_tpu.committer.committer import Committer
    from fabric_tpu.committer.txvalidator import PolicyRegistry, TxValidator
    from fabric_tpu.msp import CachedMSP
    from fabric_tpu.policy import parse_policy
    org = DevOrg("Org1")
    creator, endorser = org.new_identity("c"), org.new_identity("e")
    validator = TxValidator(
        "ch-span", {"Org1": CachedMSP(org.msp())},
        init_factories(FactoryOpts(default="SW")),
        PolicyRegistry(parse_policy("OR('Org1.member')")))
    committer = Committer(KVLedger("ch-span", LedgerConfig()), validator)
    raw, prev = raw_block(0, GENESIS, [build.endorser_tx(
        "ch-span", "cc", "1.0", rw(writes=[KVWrite("a", b"1")]), creator,
        [endorser])])
    raw1, _ = raw_block(1, prev, [build.endorser_tx(
        "ch-span", "cc", "1.0", rw(writes=[KVWrite("b", b"1")]), creator,
        [endorser])])
    t = tracing.tracer
    was = t.enabled
    t.configure({"enabled": True})
    try:
        committer.store_block(view_of(raw))
        committer.store_block(plain_of(raw1))
        got = {}
        for rec in t.recorder.list()["recent"]:
            for s in t.recorder.get(rec["trace_id"])["spans"]:
                if (s["name"] == "ledger.mvcc"
                        and rec["root"] == "committer.store_block"):
                    got[s["attributes"]["source"]] = s["attributes"]
    finally:
        t.enabled = was
    assert got["lanes"] == WALKS["arrays"]
    assert got["envelopes"] == WALKS["envelopes"]


def walk_counts(channel, *reasons):
    c = registry.counter("ledger_mvcc_walk_total")
    return {(w, r): c.value(channel=channel, walk=w, reason=r)
            for w, r in [("arrays", "none")]
            + [("python", r) for r in dict.fromkeys(reasons)]}


SERIAL_SERIES = [("ledger_commit_source_total", {"source": "lanes"}),
                 ("ledger_commit_source_total", {"source": "envelopes"}),
                 ("ledger_mvcc_reads_total", {"path": "serial"}),
                 ("ledger_mvcc_conflicts_total",
                  {"path": "serial", "against": "block"}),
                 ("ledger_mvcc_conflicts_total",
                  {"path": "serial", "against": "state"}),
                 ("ledger_state_writes_total", {}),
                 ("ledger_state_write_bytes_total", {})]


def serial_counts(channel):
    return [registry.counter(name).value(channel=channel, **labels)
            for name, labels in SERIAL_SERIES]


def test_the_walk_counter_adds_up_and_moves_no_other_series(ids):
    """Per block, arrays + python = the block's tx count; and what the
    serial walk counts — its source, the reads it validated, the
    conflicts it found, the writes it staged — reads the same under the
    array pass as under the Python walk on the same stream."""
    channels = {"arrays": "ch-series-arrays", "python": "ch-series-python"}
    ledgers = {w: KVLedger(ch, LedgerConfig()) for w, ch in channels.items()}
    streams = [case(ids)[0] for case in (
        case_smallbank_chains, case_absent_keys, case_parameters_dropped)]
    moved_reads = 0
    for stream in streams:
        prev = GENESIS
        for walk, ledger in ledgers.items():     # a fresh chain a stream
            ledgers[walk] = KVLedger(channels[walk], LedgerConfig())
        for number, (envelopes, gate) in enumerate(stream):
            raw, prev = raw_block(number, prev, envelopes)
            gate = gate if gate is not None else [V] * len(envelopes)
            moved = {}
            for walk, channel in channels.items():
                before = (walk_counts(channel, "no_native"),
                          serial_counts(channel))
                with (the_python_walk() if walk == "python"
                      else contextlib.nullcontext()):
                    ledgers[walk].commit(view_of(raw, gate))
                after = (walk_counts(channel, "no_native"),
                         serial_counts(channel))
                walks = {k: after[0][k] - before[0][k] for k in after[0]}
                assert sum(walks.values()) == len(envelopes)
                assert walks[("arrays", "none")] == (
                    len(envelopes) if walk == "arrays" else 0)
                moved[walk] = [a - b for a, b in zip(after[1], before[1])]
            assert moved["arrays"] == moved["python"]
            assert moved["arrays"][:2] == [len(envelopes), 0]
            moved_reads += moved["arrays"][2]
    assert moved_reads > 100


def source_counts(channel):
    c = registry.counter("ledger_commit_source_total")
    return {s: c.value(channel=channel, source=s)
            for s in ("lanes", "envelopes")}


def test_the_source_counter_adds_up_to_the_blocks_txs(ids):
    """Per block, lanes + envelopes = the block's tx count."""
    ledger = KVLedger("ch-count", LedgerConfig())
    prev = GENESIS
    blocks = [(seed(ids, 5), view_of), (seed(ids, 3), plain_of),
              ([tx(ids, rw(writes=[KVWrite("ab", b"1")])),
                tx(ids, rw(writes=[KVWrite("bA", b"2")]))], view_of),
              (seed(ids, 7), view_of)]
    want = [("lanes", 5), ("envelopes", 3), ("envelopes", 2), ("lanes", 7)]
    for number, ((envelopes, parse), (source, n)) in enumerate(
            zip(blocks, want)):
        raw, prev = raw_block(number, prev, envelopes)
        before = source_counts("ch-count")
        ledger.commit(parse(raw, [V] * len(envelopes)))
        after = source_counts("ch-count")
        moved = {s: after[s] - before[s] for s in after}
        assert moved == {"lanes": 0, "envelopes": 0, source: n}
        assert sum(moved.values()) == len(envelopes)


# -- the extractor: OK promises a header that decodes -------------------------


def test_a_header_that_does_not_decode_is_unknown_in_c_and_in_the_mirror(ids):
    good = tx(ids, rw(writes=[KVWrite("k", b"v")]))

    def cut(path):
        payload = serde.decode(good.payload)
        node = payload["header"]
        for key in path[:-1]:
            node = node[key]
        del node[path[-1]]
        return Envelope(serde.encode(payload), good.signature)

    broken = [cut(("signature_header", "nonce")),
              cut(("signature_header", "creator")),
              cut(("signature_header",)),
              cut(("channel_header", "channel_id"))]
    payload = serde.decode(good.payload)
    payload["header"]["signature_header"] = [1, 2]
    broken.append(Envelope(serde.encode(payload), good.signature))
    raw, _ = raw_block(0, GENESIS, [good] + broken)
    view = view_of(raw)
    native = wire._fastparse.rwset_lanes(*view.data_spans)
    mirror = wire.rwset_lanes_py(*view.data_spans)
    assert bytes(native[5]) == bytes(mirror[5]) and native[:5] == mirror[:5]
    table, _ = wire.lane_table(view)
    assert table.status.tolist() == [wire.LANE_OK] + [wire.LANE_UNKNOWN] * 5
    assert table.txids == [good.header().channel_header.txid] + [None] * 5
    for env in broken:
        with pytest.raises(Exception):
            env.header()
        assert mvcc.parse_endorser_tx(env) is not None


# -- the three txid readers ---------------------------------------------------


def reader_block(ids, n, window_dupes=True):
    """`n` endorser txs with a config tx, a tx whose header does not
    decode and an envelope that is no envelope among them, and txids that
    repeat: inside the block, next to each other and far apart."""
    creator, _ = ids
    envelopes = [tx(ids, rw(writes=[KVWrite(f"w{i:04d}", b"v")]))
                 for i in range(n)]
    envelopes[3] = build.signed_envelope(TX_CONFIG, "ch", {"config": 1},
                                         creator)
    envelopes[5] = without_nonce(ids)
    envelopes[8] = envelopes[7]
    envelopes[n - 2] = envelopes[1]
    envelopes[n // 2] = envelopes[n // 2 - 9]
    data = [e.serialize() for e in envelopes]
    data[11] = b"not an envelope"
    codes = [(V, MVCC, POLICY)[i % 3] for i in range(n)]
    return data, codes


def block_of(number, prev, data):
    from fabric_tpu.protocol.types import (BlockHeader, BlockMetadata,
                                           block_data_hash)
    block = Block(BlockHeader(number, prev, block_data_hash(data)), data,
                  BlockMetadata())
    return block.serialize(), block.hash()


def outcomes_of(watched, blocks):
    """What `CommitNotifier` should hold after `blocks` for the txids
    someone waits for: every envelope decoded in order, the outcome of a
    watched txid kept where its block brings it (the last appearance
    inside that block), and a txid already told ignored afterwards."""
    history, told = {}, set()
    for block, codes in blocks:
        here = {}
        for i, env_bytes in enumerate(block.data):
            try:
                txid = Envelope.deserialize(
                    env_bytes).header().channel_header.txid
            except Exception:
                continue
            if txid and txid in watched and txid not in told:
                here[txid] = (codes[i], int(block.header.number))
        history.update(here)
        told.update(here)
    return history


@pytest.mark.parametrize("window,sizes", [(16, (48, 48)), (16, (5, 30, 7)),
                                          (64, (48, 20, 48)), (4096, (40,))],
                         ids=["3x-window", "small-large-small",
                              "under-window", "default-window"])
def test_the_notifier_keeps_what_someone_waits_for(ids, window, sizes):
    sizes = [max(s, 24) for s in sizes]
    notifier = CommitNotifier("ch", window=window)
    prev, fed = GENESIS, []
    datas = [reader_block(ids, n) for n in sizes]
    # a txid of the last block that an earlier block already holds: told
    # once, where it first came
    datas[-1][0][2] = datas[0][0][sizes[0] - 1]

    def txid_at(b, i):
        return Envelope.deserialize(datas[b][0][i]).header() \
            .channel_header.txid
    # watched: one plain tx a block, the repeated txids of block 0 (next
    # to each other, far apart), the txid two blocks hold, and one no
    # block brings; tx 5's header does not decode and is nobody's
    watched = {txid_at(b, 13) for b in range(len(sizes))}
    watched |= {txid_at(0, 7), txid_at(0, 1), txid_at(0, sizes[0] - 1),
                "never-ordered"}
    for txid in sorted(watched):
        notifier.watch(txid)
    waited = txid_at(0, 20)
    got = []
    waiter = threading.Thread(
        target=lambda: got.append(notifier.wait(waited, 30.0)))
    waiter.start()
    while waited not in notifier._waiters:
        pass
    for number, (data, codes) in enumerate(datas):
        raw, prev = block_of(number, prev, data)
        view = view_of(raw)
        notifier.on_block(view, TxFlags.from_codes(codes))
        assert view._data is not None       # tx 11 and tx 5: decoded
        fed.append((plain_of(raw), codes))
        want = outcomes_of(watched | {waited}, fed)
        held = {txid: (c.code, c.block)
                for txid, c in notifier._history.items()}
        assert held == want
        assert len(notifier._history) <= window
        # what came is no longer watched; what did not still is
        assert set(notifier._watched) == watched - set(want)
        if number == 0:
            # the waiter registered before the block is woken by it
            waiter.join(30.0)
            assert not waiter.is_alive() and not notifier._waiters
            assert got == [notifier.peek(waited)]
            assert got[0][:3] == (codes[20], 0, None)
        # a plain block takes the per-envelope path to the same outcomes
        again = CommitNotifier("ch", window=window)
        for txid in sorted(watched | {waited}):
            again.watch(txid)
        for block, block_codes in fed:
            again.on_block(block, TxFlags.from_codes(block_codes))
        assert {txid: (c.code, c.block)
                for txid, c in again._history.items()} == want
    assert "never-ordered" in notifier._watched


def test_the_notifier_decodes_nothing_of_a_block_the_table_speaks_for(ids):
    raw, _ = raw_block(0, GENESIS, seed(ids, 40))
    view = view_of(raw)
    txids = [Envelope.deserialize(b).header().channel_header.txid
             for b in plain_of(raw).data]
    notifier = CommitNotifier("ch", window=16)
    for txid in txids[-20:]:
        notifier.watch(txid)                # the window drops the first 4
    notifier.on_block(view, TxFlags.from_codes([V] * 40))
    assert view._data is None
    assert list(notifier._history) == txids[-16:]
    assert notifier.peek(txids[-1])[:3] == (V, 0, None)
    assert notifier.peek(txids[0]) is None      # nobody waited for it
    # the block's stamps come with the outcome: none on a block no
    # committer took, three in order on one that a committer did
    assert notifier.peek(txids[-1]).stamps is None
    view2 = view_of(raw)
    view2.intake = (view2.parsed[0], view2.parsed[1])
    notifier.watch(txids[0])
    notifier.on_block(view2, TxFlags.from_codes([V] * 40))
    received, taken, held = notifier.peek(txids[0]).stamps
    assert received <= taken <= held


def test_the_block_store_finds_every_txid_where_it_found_it(ids, tmp_path):
    stores = {"views": BlockStore(None), "plain": BlockStore(None),
              "disk": BlockStore(str(tmp_path / "blocks"))}
    prev = GENESIS
    datas = [reader_block(ids, 30), reader_block(ids, 26)]
    datas[1][0][4] = datas[0][0][0]         # first writer wins: block 0
    for number, (data, _codes) in enumerate(datas):
        raw, prev = block_of(number, prev, data)
        stores["views"].add_block(view_of(raw))
        stores["disk"].add_block(view_of(raw))
        stores["plain"].add_block(plain_of(raw))
    want = stores["plain"]._by_txid
    assert len(want) > 40
    assert stores["views"]._by_txid == want
    assert stores["disk"]._by_txid == want
    first = Envelope.deserialize(datas[0][0][0]).header().channel_header.txid
    assert want[first] == (0, 0)
    twice = Envelope.deserialize(datas[0][0][7]).header().channel_header.txid
    assert want[twice] == (0, 7)
    for txid in want:
        assert stores["views"].has_txid(txid)
    # what the recovery scan rebuilds from the file is the same index
    assert BlockStore(str(tmp_path / "blocks"))._by_txid == want


# -- the private-data coordinator ---------------------------------------------


def private_peers(provider, tmp_path, org):
    """Two coordinators over their own ledgers: one is fed views, the
    other plain blocks."""
    from test_privdata import make_peer
    from fabric_tpu.privdata import CollectionConfig
    peers = []
    for name in ("views", "plain"):
        coord, transient, pvt, ledger = make_peer(
            org, provider, tmp=str(tmp_path / name))
        coord.registry.define("cc", CollectionConfig(
            "others", member_orgs=("Org9",), block_to_live=0))
        peers.append((coord, transient, pvt, ledger))
    return peers


def private_tx(org, i, collection, transients=(), public=True):
    from fabric_tpu.chaincode.stub import ChaincodeStub
    from fabric_tpu.ledger.statedb import StateDB
    stub = ChaincodeStub(StateDB(), "cc", channel_id="ch", txid="")
    if public:
        stub.put_state(f"pub{i}", b"open")
    if collection:
        stub.put_private_data(collection, f"sec{i}", b"classified%d" % i)
    env = build.endorser_tx("ch", "cc", "1.0", stub.rwset(),
                            org.new_identity("client"),
                            [org.new_identity("e")])
    for transient in transients:
        transient.persist(env.header().channel_header.txid, 0,
                          stub.private_sets())
    return env


def test_the_coordinator_decodes_only_what_writes_to_a_collection(
        sw_provider, tmp_path, monkeypatch):
    org = DevOrg("Org1")
    (c_v, tr_v, pvt_v, lg_v), (c_p, tr_p, pvt_p, lg_p) = private_peers(
        sw_provider, tmp_path, org)
    both = (tr_v, tr_p)
    decoded = []
    real = coordinator_mod._tx_rwset
    monkeypatch.setattr(coordinator_mod, "_tx_rwset",
                        lambda env: decoded.append(env) or real(env))

    def store(envelopes):
        raw, _ = raw_block(lg_v.height, lg_v.blockstore.chain_info()
                           .current_hash if lg_v.height else GENESIS,
                           envelopes)
        del decoded[:]
        c_v.store_block(view_of(raw))
        by_view = len(decoded)
        c_p.store_block(plain_of(raw))
        return by_view, len(decoded) - by_view

    # block 0: the transient stores hold nothing, no tx is private
    assert store([private_tx(org, i, None) for i in range(6)]) == (0, 6)
    # block 1: public txs, two writing to the member collection (the
    # cleartext of one was never staged), one to a collection of others,
    # one that writes to the collection and nothing public, and an
    # invalid tx (its creator signature is cut) that writes to it too
    stale = private_tx(org, 90, "secrets", both)       # never committed
    cut = private_tx(org, 7, "secrets", both)
    cut = Envelope(cut.payload, cut.signature[:-2] + b"\x00\x01")
    envelopes = [private_tx(org, 0, None), private_tx(org, 1, "secrets", both),
                 private_tx(org, 2, None), private_tx(org, 3, "secrets"),
                 private_tx(org, 4, "others", both), private_tx(org, 5, None),
                 private_tx(org, 6, "secrets", both, public=False), cut]
    assert len(tr_v) == 5
    assert store(envelopes) == (4, 7)
    for pvt in (pvt_v, pvt_p):
        assert pvt.get("cc", "secrets", "sec1") == b"classified1"
        assert pvt.get("cc", "secrets", "sec6") == b"classified6"
        assert pvt.get("cc", "secrets", "sec3") is None
        assert pvt.get("cc", "secrets", "sec7") is None
        assert pvt.get("cc", "others", "sec4") is None
    assert pvt_v._state == pvt_p._state and pvt_v._by_txid == pvt_p._by_txid
    assert [(m.block_num, m.txid, m.namespace, m.collection, m.expected)
            for m in c_v.missing] == [
        (m.block_num, m.txid, m.namespace, m.collection, m.expected)
        for m in c_p.missing]
    assert [m.collection for m in c_v.missing] == ["secrets"]
    # purged by txid: every VALID tx of the block; the invalid one's and
    # the never-committed one's cleartext stays
    kept = {stale.header().channel_header.txid,
            cut.header().channel_header.txid}
    assert set(tr_v._by_txid) == set(tr_p._by_txid) == kept
    assert lg_v.commit_hash == lg_p.commit_hash
    assert state_of(lg_v) == state_of(lg_p)


def test_the_coordinators_tail_has_a_span_in_the_blocks_trace(
        sw_provider, tmp_path):
    org = DevOrg("Org1")
    (coord, _tr, _pvt, _lg), _ = private_peers(sw_provider, tmp_path, org)
    raw, _ = raw_block(0, GENESIS, [private_tx(org, 0, None)])
    t = tracing.tracer
    was = t.enabled
    t.configure({"enabled": True})
    try:
        coord.store_block(view_of(raw))
        rec = t.recorder.get(next(
            r["trace_id"] for r in t.recorder.list()["recent"]
            if r["root"] == "committer.store_block"))
    finally:
        t.enabled = was
    root = next(s for s in rec["spans"] if s["parent_id"] is None)
    tail = next(s for s in rec["spans"]
                if s["name"] == "privdata.store_block")
    assert tail["parent_id"] == root["span_id"]
    assert tail["start"] >= root["start"] + root["duration_s"]
