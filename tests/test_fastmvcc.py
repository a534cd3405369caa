"""`native/fastmvcc.c` against plain Python: the state store's key hash
(`statedb.shard_of`) against its mirror, bit for bit — placement is
persistent, so a ledger the mirror wrote must recover unchanged under
the native hash — and the array pass's C steps (`slot_shards`,
`fetch_versions`, `walk`) against small models over seeded random
arrays, well-formed and not.

The corpus doubles as the ASan/UBSan smoke driver: run
`python tests/test_fastmvcc.py --asan-corpus` against a sanitizer build
of _fastmvcc (tests/smoke.sh does this).
"""
import os
import random
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")

import numpy as np
import pytest

from fabric_tpu.ledger import (KVLedger, LedgerConfig, historydb, mvcc,
                               statedb)
from fabric_tpu.ledger.statedb import VersionedValue, _shard_of_py, shard_of
from fabric_tpu.protocol import (KVRead, KVWrite, NsRwSet, TxRwSet,
                                 ValidationCode, Version, build, wire)
from fabric_tpu.protocol.types import META_TXFLAGS

native = statedb._fastmvcc
pytestmark = pytest.mark.skipif(native is None,
                                reason="native _fastmvcc unavailable")

VALID, CONFLICT, BAD = (int(ValidationCode.VALID),
                        int(ValidationCode.MVCC_READ_CONFLICT),
                        int(ValidationCode.BAD_RWSET))
CODES = (VALID, CONFLICT, BAD)

# -- the hash ----------------------------------------------------------------

ASCII = "abcdefghijklmnopqrstuvwxyzABCXYZ0123456789_-.:/ "
WIDE = "äöüßéñçøλπжщ中文字符日本語한국어😀🎉\x7f߿ࠀ￿\U00010000"


def text(rng, alphabet, n_bytes):
    out = ""
    while len(out.encode()) < n_bytes:
        out += rng.choice(alphabet)
    return out


def draw_pair(rng, kind):
    size = rng.choice([1, 2, 7, 8, 9, 63, 64, 65, 255, 1000])
    if kind == "ascii":
        return text(rng, ASCII, rng.randint(1, 12)), text(rng, ASCII, size)
    if kind == "utf8":
        return text(rng, WIDE, rng.randint(1, 12)), text(rng, WIDE, size)
    if kind == "empty_key":
        return text(rng, ASCII + WIDE, size), ""
    if kind == "meta_ns":
        return (text(rng, ASCII, rng.randint(1, 12)) + statedb.META_SUFFIX,
                text(rng, ASCII + WIDE, size))
    if kind == "nul_inside":
        return (text(rng, ASCII, 3) + "\x00" + text(rng, ASCII, 2),
                "\x00" + text(rng, ASCII, size) + "\x00")
    raise AssertionError(kind)


@pytest.mark.parametrize("n_shards", [1, 8, 13])
@pytest.mark.parametrize("kind", ["ascii", "utf8", "empty_key", "meta_ns",
                                  "nul_inside"])
def test_the_native_hash_is_the_mirror(kind, n_shards):
    assert shard_of is native.shard_of
    rng = random.Random(f"{kind}/{n_shards}")
    seen = set()
    for _ in range(400):
        ns, key = draw_pair(rng, kind)
        got = shard_of(ns, key, n_shards)
        assert got == _shard_of_py(ns, key, n_shards), (ns, key)
        assert type(got) is int and 0 <= got < n_shards
        seen.add(got)
    assert len(seen) == n_shards       # and it spreads: every shard is met


def test_the_native_hash_at_the_edges():
    for n in (0, -3, 1):
        assert shard_of("cc", "anything", n) == 0
    for n in (2, 1 << 30, (1 << 63) - 1):
        assert shard_of("cc", "k", n) == _shard_of_py("cc", "k", n)
    assert shard_of("", "", 8) == _shard_of_py("", "", 8)
    assert shard_of("ab", "c", 1 << 30) != shard_of("a", "bc", 1 << 30)
    # what does not encode raises the same error in both
    for fn in (shard_of, _shard_of_py):
        with pytest.raises(UnicodeEncodeError):
            fn("cc", "lone \ud800 surrogate", 8)
    with pytest.raises(TypeError):
        shard_of(b"cc", "k", 8)
    with pytest.raises(TypeError):
        shard_of("cc", "k")


@pytest.fixture(scope="module")
def ids():
    from fabric_tpu.bccsp.factory import FactoryOpts, init_factories
    from fabric_tpu.msp.ca import DevOrg
    init_factories(FactoryOpts(default="SW"))
    org = DevOrg("Org1")
    return org.new_identity("client"), [org.new_identity("e1")]


def block_of(ids, number, prev, rwsets):
    creator, endorsers = ids
    block = build.new_block(number, prev, [
        build.endorser_tx("ch", "cc", "1.0", r, creator, endorsers)
        for r in rwsets])
    return block.serialize(), block.hash()


@pytest.mark.parametrize("n_shards", [1, 8, 13])
def test_a_slots_shard_is_its_keys_shard(ids, n_shards):
    rng = random.Random(n_shards)
    pairs = [draw_pair(rng, kind) for kind in
             ("ascii", "utf8", "empty_key", "meta_ns") for _ in range(12)]
    rwsets = [TxRwSet(tuple(
        NsRwSet(ns, reads=(KVRead(key, None),), writes=(KVWrite(key, b"v"),))
        for ns, key in pairs[i:i + 3])) for i in range(0, len(pairs), 3)]
    raw, _ = block_of(ids, 0, b"\x00" * 32, rwsets)
    table, reason = wire.lane_table(wire.parse_block(raw))
    assert reason is None and set(table.key_strs) == set(pairs)
    got = np.frombuffer(native.slot_shards(table.base, table.keys, n_shards),
                        dtype=np.int32)
    assert got.tolist() == [_shard_of_py(ns, key, n_shards)
                            for ns, key in table.key_strs]


def test_a_ledger_the_mirror_wrote_recovers_unchanged_under_the_native_hash(
        ids, tmp_path, monkeypatch):
    """The parent's code hashed in Python: its checkpoints, WALs and the
    split of its batches were placed by `_shard_of_py`.  Written so —
    checkpoints every 4 blocks, a WAL tail after the last one — and
    reopened under the native hash, the ledger holds every key in the
    shard it was written to."""
    rng = random.Random(43)
    root = str(tmp_path / "ledger")
    config = dict(root=root, snapshot_every=4, state_shards=8)
    with monkeypatch.context() as parent:
        parent.setattr(statedb, "shard_of", _shard_of_py)
        parent.setattr(historydb, "shard_of", _shard_of_py)
        parent.setattr(mvcc, "shard_of", _shard_of_py)
        parent.setattr(mvcc, "_fastmvcc", None)     # its walk, too
        written = KVLedger("ch", LedgerConfig(**config))
        prev, pairs = b"\x00" * 32, []
        for number in range(7):
            fresh = [draw_pair(rng, kind) for kind in
                     ("ascii", "utf8", "empty_key", "meta_ns")
                     for _ in range(4)]
            pairs += fresh
            gone = rng.sample(pairs, 3) if number else []
            raw, prev = block_of(ids, number, prev, [TxRwSet(tuple(
                [NsRwSet(ns, writes=(KVWrite(key, b"v%d" % number),))
                 for ns, key in fresh[i:i + 4]]
                + [NsRwSet(ns, writes=(KVWrite(key, b"", True),))
                   for ns, key in gone[i // 4:i // 4 + 1]]))
                for i in range(0, len(fresh), 4)])
            block = wire.parse_block(raw)
            block.metadata.items[META_TXFLAGS] = bytes(block.n_data)
            written.commit(block)
        before = written.state_status()
        assert before["state"]["checkpoint_gen"] >= 1
        assert before["state"]["batches_since_checkpoint"] == 3
        held = {k: (vv.value, vv.version) for k, vv
                in written.statedb._data.items()}
        history = {k: list(m) for k, m in written.historydb._index.items()}
    assert statedb.shard_of is native.shard_of
    reopened = KVLedger("ch", LedgerConfig(**config))
    after = reopened.state_status()
    assert after["state"]["last_recovery"]["wal_blocks"] == 3
    assert after["state"]["shard_keys"] == before["state"]["shard_keys"]
    assert after["commit_hash"] == before["commit_hash"]
    assert after["state"]["keys"] == len(held) > 80
    for (ns, key), (value, version) in held.items():
        assert reopened.statedb.get(ns, key) == VersionedValue(value, version)
        shard = reopened.statedb._shards[_shard_of_py(ns, key, 8)]
        assert (ns, key) in shard.data
    for (ns, key), mods in history.items():
        assert reopened.historydb.get_history(ns, key) == mods[::-1]
        assert (ns, key) in reopened.historydb._shards[
            _shard_of_py(ns, key, 8)]


# -- the walk against a model --------------------------------------------------


def model_walk(tx, reads, writes, flags, has, blk, txn, block_num, companion):
    """`_fastmvcc.walk` in plain Python, as `mvcc.validate_and_prepare_batch`
    and `_stage_writes` state it: a dict of what was staged, by ident."""
    flags, staged, out = list(flags), {}, []
    n_reads = against_block = against_state = 0
    for t, (status, _off, _len) in enumerate(tx):
        if status == wire.LANE_SKIP or flags[t] != VALID:
            continue
        if status == wire.LANE_BAD:
            flags[t] = BAD
            continue
        ok = True
        for _t, slot, has_v, b, n in (r for r in reads if r[0] == t):
            n_reads += 1
            version = (b, n) if has_v else None
            if slot in staged:
                if staged[slot] != version:
                    against_block += 1
                    ok = False
            else:
                held = (blk[slot], txn[slot]) if has[slot] else None
                if held != version:
                    against_state += 1
                    ok = False
            if not ok:
                flags[t] = CONFLICT
                break
        if not ok:
            continue
        mine = [(i, w) for i, w in enumerate(writes) if w[0] == t]
        for i, (_t, slot, is_delete, _o, _n) in mine:
            staged[slot] = None if is_delete else (block_num, t)
            out.append((i, -1))
        for i, (_t, _slot, is_delete, _o, _n) in mine:
            c = -1 if companion is None else companion[i]
            if is_delete and c >= 0 and (
                    staged[c] is not None if c in staged else has[c]):
                staged[c] = None
                out.append((i, c))
    return bytes(flags), (n_reads, against_block, against_state), out


def draw_table(rng):
    """Random lanes over few idents (so chains form): -> walk's arguments."""
    n_tx, n_slots = rng.randint(0, 24), rng.randint(1, 9)
    n_past = rng.randint(0, 3)
    n_ids = n_slots + n_past
    block_num = rng.randint(1, 5)
    tx = [[rng.choice([wire.LANE_OK] * 8 + [wire.LANE_SKIP, wire.LANE_BAD]),
           0, 0] for _ in range(n_tx)]
    flags = [rng.choice([VALID] * 6 + [10, 4]) for _ in range(n_tx)]
    has = [rng.random() < 0.6 for _ in range(n_ids)]
    blk = [rng.randint(0, block_num - 1) if h else 0 for h in has]
    txn = [rng.randint(0, 3) if h else 0 for h in has]
    reads, writes = [], []
    for t in range(n_tx):
        if tx[t][0] != wire.LANE_OK:
            continue
        for _ in range(rng.choice([0, 1, 1, 2, 3])):
            slot = rng.randrange(n_slots)
            kind = rng.random()
            if kind < 0.5:          # what the state holds
                version = (1, blk[slot], txn[slot]) if has[slot] else (0, 0, 0)
            elif kind < 0.7:        # what an earlier tx of the block staged
                version = (1, block_num, rng.randrange(max(t, 1)))
            elif kind < 0.85:
                version = (0, 0, 0)
            else:
                version = (1, rng.randint(-2, 5), rng.randint(-2, 5))
            reads.append([t, slot, *version])
        for _ in range(rng.choice([0, 1, 1, 2, 3])):
            writes.append([t, rng.randrange(n_slots),
                           int(rng.random() < 0.35), 0, 0])
    companion = None
    if rng.random() < 0.7:
        companion = [rng.randrange(-1, n_ids) if w[2] else -1 for w in writes]
    return tx, reads, writes, flags, has, blk, txn, block_num, companion


def i64(rows, width):
    return np.asarray(rows, dtype=np.int64).reshape(len(rows), width)


def native_walk(tx, reads, writes, flags, has, blk, txn, block_num,
                companion, mod=native):
    codes = bytearray(flags)
    n_reads, a_block, a_state, staged = mod.walk(
        i64(tx, 3), i64(reads, 5), i64(writes, 5), codes,
        np.asarray(has, dtype=np.uint8), np.asarray(blk, dtype=np.int64),
        np.asarray(txn, dtype=np.int64), block_num,
        None if companion is None else np.asarray(companion, dtype=np.int64),
        CODES)
    pairs = np.frombuffer(staged, dtype=np.int64).reshape(-1, 2)
    return bytes(codes), (n_reads, a_block, a_state), [
        tuple(p) for p in pairs.tolist()]


@pytest.mark.parametrize("seed", range(12))
def test_the_walk_is_the_model_on_random_lanes(seed):
    rng = random.Random(4300 + seed)
    met = 0
    for _ in range(150):
        table = draw_table(rng)
        want = model_walk(*table)
        assert native_walk(*table) == want
        met += want[1][1] > 0 and any(c >= 0 for _, c in want[2])
    assert met > 3      # conflicts against the block and dropped parameters


def malformed(rng, table):
    """A well-formed table whose lanes are put out of order, or name a tx
    past the block's end: -> (arguments, what breaks), None where the
    draw has too few lanes to break."""
    tx, reads, writes, *rest = table
    reads, writes = [list(r) for r in reads], [list(w) for w in writes]
    lanes = rng.choice([reads, writes])
    how = rng.choice(["unsorted", "tx_past_end"])
    if how == "unsorted":
        if len(lanes) < 2 or lanes[0][0] == lanes[-1][0]:
            return None
        lanes[0], lanes[-1] = lanes[-1], lanes[0]
    else:
        lanes.append([len(tx) + rng.randrange(2), 0, 0, 0, 0])
    return (tx, reads, writes, *rest), how


def test_the_walk_refuses_lanes_that_do_not_ascend_or_overrun():
    rng = random.Random(43)
    refused = 0
    while refused < 40:
        made = malformed(rng, draw_table(rng))
        if made is None:
            continue
        with pytest.raises(ValueError):
            native_walk(*made[0])
        refused += 1


def test_the_walk_refuses_an_ident_outside_the_arrays():
    ok = [[wire.LANE_OK, 0, 0]]
    args = dict(tx=ok, flags=[VALID], has=[1, 0], blk=[0, 0], txn=[0, 0],
                block_num=1)
    for reads, writes, companion in [
            ([[0, 2, 1, 0, 0]], [], None), ([[0, -1, 1, 0, 0]], [], None),
            ([], [[0, 2, 0, 0, 0]], None), ([], [[0, -1, 0, 0, 0]], None),
            ([], [[0, 1, 1, 0, 0]], [2])]:
        with pytest.raises(ValueError):
            native_walk(reads=reads, writes=writes, companion=companion,
                        **args)
    # shapes that disagree
    with pytest.raises(ValueError):
        native.walk(i64(ok, 3), i64([], 5), i64([], 5), bytearray(2),
                    np.zeros(1, np.uint8), np.zeros(1, np.int64),
                    np.zeros(1, np.int64), 1, None, CODES)
    with pytest.raises(ValueError):
        native.walk(i64(ok, 3), i64([], 5), i64([[0, 0, 1, 0, 0]], 5),
                    bytearray(1), np.zeros(1, np.uint8), np.zeros(1, np.int64),
                    np.zeros(1, np.int64), 1, np.zeros(3, np.int64), CODES)


# -- the fetch ------------------------------------------------------------------


def run_fetch(mod, rng):
    n = rng.randint(0, 40)
    key_strs = [(rng.choice(["cc", "cc#meta", "ü"]), f"k{rng.randrange(30)}")
                for _ in range(n)]
    shards = np.asarray([rng.randrange(4) for _ in range(n)], dtype=np.int32)
    data = {k: VersionedValue(b"v", Version(rng.randrange(9),
                                            rng.randrange(9)))
            for k in rng.sample(key_strs, n // 2)}
    has = np.full(n, 7, dtype=np.uint8)
    blk, txn = (np.full(n, -7, dtype=np.int64) for _ in range(2))
    shard = rng.randrange(4)
    mod.fetch_versions(data, key_strs, shards, shard, has, blk, txn)
    for i, k in enumerate(key_strs):
        if shards[i] != shard:
            assert (has[i], blk[i], txn[i]) == (7, -7, -7)
        elif k in data:
            assert (has[i], blk[i], txn[i]) == (
                1, data[k].version.block_num, data[k].version.tx_num)
        else:
            assert (has[i], blk[i], txn[i]) == (0, 0, 0)


@pytest.mark.parametrize("seed", range(3))
def test_the_fetch_fills_its_shards_slots_and_no_others(seed):
    rng = random.Random(seed)
    for _ in range(100):
        run_fetch(native, rng)
    with pytest.raises(ValueError):
        native.fetch_versions({}, [("a", "b")], np.zeros(2, np.int32), 0,
                              np.zeros(1, np.uint8), np.zeros(1, np.int64),
                              np.zeros(1, np.int64))
    with pytest.raises(AttributeError):     # what the store does not hold
        native.fetch_versions({("a", "b"): object()}, [("a", "b")],
                              np.zeros(1, np.int32), 0, np.zeros(1, np.uint8),
                              np.zeros(1, np.int64), np.zeros(1, np.int64))


def test_slot_shards_refuses_spans_outside_the_block():
    keys = np.asarray([[0, 0, 2, 2, 3]], dtype=np.int64)
    assert len(native.slot_shards(b"ccabc", keys, 8)) == 4
    for bad in ([0, 0, 2, 2, 4], [0, 6, 0, 0, 0], [0, 0, -1, 0, 0]):
        with pytest.raises(ValueError):
            native.slot_shards(b"ccabc", np.asarray([bad], dtype=np.int64), 8)
    with pytest.raises(ValueError):
        native.slot_shards(b"ccabc", b"\x00" * 39, 8)


# -- the sanitizer corpus ---------------------------------------------------------


def run_sanitizer_corpus(mod):
    """Everything above that needs no ledger, through `mod`."""
    rng = random.Random(43)
    n_hash = n_walk = n_refused = 0
    for kind in ("ascii", "utf8", "empty_key", "meta_ns", "nul_inside"):
        for _ in range(300):
            ns, key = draw_pair(rng, kind)
            for n in (1, 8, 13):
                assert mod.shard_of(ns, key, n) == _shard_of_py(ns, key, n)
            raw = ns.encode() + key.encode()
            keys = np.asarray([[0, 0, len(ns.encode()), len(ns.encode()),
                                len(key.encode())]], dtype=np.int64)
            assert np.frombuffer(mod.slot_shards(raw, keys, 13),
                                 dtype=np.int32)[0] == _shard_of_py(ns, key, 13)
            n_hash += 1
    for _ in range(2000):
        table = draw_table(rng)
        assert native_walk(*table, mod=mod) == model_walk(*table)
        n_walk += 1
        made = malformed(rng, table)
        if made is not None:
            try:
                native_walk(*made[0], mod=mod)
            except ValueError:
                n_refused += 1
            else:
                raise AssertionError(made[1])
    for _ in range(300):
        run_fetch(mod, rng)
    return n_hash, n_walk, n_refused


if __name__ == "__main__":
    if "--asan-corpus" in sys.argv:
        import importlib
        counts = run_sanitizer_corpus(importlib.import_module("_fastmvcc"))
        print("sanitizer corpus clean: %d keys hashed, %d tables walked, "
              "%d malformed refused" % counts)
