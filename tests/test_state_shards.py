"""Sharded state plane: placement determinism, flat-vs-sharded
differential bit-identity, checkpoint/reopen recovery, and the snapshot
export -> chunk -> install state-transfer roundtrip.

The sharded StateDB claims EXACT observable identity with the flat
(n_shards=1) store — same merged key map, same range-scan order, same
rich-query results, same commit-hash chain when driven through the
ledger.  Every corpus here runs at N ∈ {1, 4, 7} and the outputs are
compared literally; 7 is deliberately coprime with the default 8 so the
re-stripe recovery path gets a shard count that divides nothing.
"""

import hashlib
import os
import random

import pytest

from fabric_tpu.ledger import KVLedger, LedgerConfig, StateDB, UpdateBatch
from fabric_tpu.ledger import checkpoint as ckpt
from fabric_tpu.ledger import snapshot
from fabric_tpu.ledger.historydb import HistoryDB
from fabric_tpu.ledger.statedb import shard_of
from fabric_tpu.protocol import (KVWrite, NsRwSet, TxFlags, TxRwSet,
                                 ValidationCode, Version, build)
from fabric_tpu.protocol.types import META_TXFLAGS

SHARD_COUNTS = (1, 4, 7)


@pytest.fixture(scope="module", autouse=True)
def provider():
    from fabric_tpu.bccsp.factory import FactoryOpts, init_factories
    return init_factories(FactoryOpts(default="SW"))


@pytest.fixture(scope="module")
def org():
    from fabric_tpu.msp.ca import DevOrg
    return DevOrg("Org1")


# ---------------------------------------------------------------------------
# placement
# ---------------------------------------------------------------------------

def test_shard_of_deterministic_and_bounded():
    for n in (1, 2, 7, 8, 64):
        for i in range(200):
            ns, key = f"ns{i % 3}", f"key-{i:04d}"
            s = shard_of(ns, key, n)
            assert 0 <= s < max(1, n)
            assert s == shard_of(ns, key, n)     # stable
    # n_shards <= 1 is always shard 0 (the flat store)
    assert shard_of("cc", "anything", 1) == 0
    assert shard_of("cc", "anything", 0) == 0


def test_shard_of_separates_namespace_from_key():
    # ("ab", "c") and ("a", "bc") must not collapse to one hash input
    vals = {(shard_of("ab", "c", 1 << 30), shard_of("a", "bc", 1 << 30))}
    assert len({v for pair in vals for v in pair}) == 2


def test_shard_of_spreads_keys():
    n = 8
    counts = [0] * n
    for i in range(4000):
        counts[shard_of("cc", f"k{i:05d}", n)] += 1
    assert min(counts) > 0
    # FNV over short keys is not perfect, but no shard should hog
    assert max(counts) < 3 * (4000 // n)


def test_update_batch_preshard_cache_invalidation():
    b = UpdateBatch()
    b.put("cc", "k1", b"v", Version(1, 0))
    first = b.items_by_shard(4)
    assert b.items_by_shard(4) is first          # cached
    b.put("cc", "k2", b"v", Version(1, 1))       # invalidates
    second = b.items_by_shard(4)
    assert second is not first
    assert sum(len(x) for x in second) == 2
    # a different width recomputes rather than serving the stale split
    assert sum(len(x) for x in b.items_by_shard(7)) == 2


# ---------------------------------------------------------------------------
# flat vs sharded StateDB differential
# ---------------------------------------------------------------------------

def _random_batches(seed=7, blocks=6, keys=120):
    rnd = random.Random(seed)
    names = [f"k{i:04d}" for i in range(keys)]
    batches = []
    for blk in range(1, blocks + 1):
        b = UpdateBatch()
        for t, key in enumerate(rnd.sample(names, 40)):
            if rnd.random() < 0.2:
                b.delete("cc", key, Version(blk, t))
            else:
                b.put("cc", key, b"v-%d-%s" % (blk, key.encode()),
                      Version(blk, t))
        # a few JSON docs for the rich-query comparison
        for t, i in enumerate(rnd.sample(range(keys), 10)):
            b.put("docs", f"d{i:04d}",
                  b'{"size": %d, "owner": "o%d"}' % (i, i % 3),
                  Version(blk, 100 + t))
        batches.append(b)
    return batches


def _dump(db):
    return {k: (vv.value, vv.version.block_num, vv.version.tx_num)
            for k, vv in db._data.items()}


def test_sharded_statedb_matches_flat():
    dbs = {n: StateDB(n_shards=n) for n in SHARD_COUNTS}
    for n, db in dbs.items():
        db.create_index("docs", "size")
        for blk, batch in enumerate(_random_batches(), start=1):
            db.apply_updates(batch, blk)
    flat = dbs[1]
    ref_dump = _dump(flat)
    ref_scan = list(flat.range_scan("cc", "", ""))
    ref_page = list(flat.range_scan("cc", "k0010", "k0050", limit=7))
    ref_query = list(flat.execute_query(
        "docs", {"size": {"$gte": 10, "$lt": 90}}))
    for n in SHARD_COUNTS[1:]:
        db = dbs[n]
        assert _dump(db) == ref_dump, f"n_shards={n} state diverged"
        assert list(db.range_scan("cc", "", "")) == ref_scan
        assert list(db.range_scan("cc", "k0010", "k0050",
                                  limit=7)) == ref_page
        assert list(db.execute_query(
            "docs", {"size": {"$gte": 10, "$lt": 90}})) == ref_query
        assert sum(db.shard_sizes()) == len(ref_dump)
        assert sum(1 for s in db.shard_sizes() if s) > 1  # actually striped


# ---------------------------------------------------------------------------
# checkpoint + reopen (incl. the re-stripe path)
# ---------------------------------------------------------------------------

def test_statedb_checkpoint_reopen_and_restripe(tmp_path):
    root = str(tmp_path / "state")
    db = StateDB(root, snapshot_every=2, n_shards=4)
    for blk, batch in enumerate(_random_batches(blocks=5), start=1):
        db.apply_updates(batch, blk)
    ref = _dump(db)
    assert db.status()["checkpoint_gen"] >= 1    # auto-checkpoint fired

    re4 = StateDB(root, snapshot_every=2, n_shards=4)
    assert re4.last_recovery["source"] == "manifest"
    assert re4.savepoint == 5
    assert _dump(re4) == ref

    # shard-count change re-stripes the checkpoint payloads on load
    re7 = StateDB(root, snapshot_every=2, n_shards=7)
    assert _dump(re7) == ref
    assert list(re7.range_scan("cc", "", "")) == list(
        re4.range_scan("cc", "", ""))


def test_statedb_checkpoint_reuse_when_clean(tmp_path):
    root = str(tmp_path / "state")
    db = StateDB(root, snapshot_every=100, n_shards=2)
    b = UpdateBatch()
    b.put("cc", "k", b"v", Version(1, 0))
    db.apply_updates(b, 1)
    m1 = db.checkpoint()
    m2 = db.checkpoint()                 # nothing applied in between
    assert m1["gen"] == m2["gen"] == 1
    assert m1["savepoint"] == 1


def test_historydb_sharded_checkpoint_reopen(tmp_path):
    root = str(tmp_path / "history")
    h = HistoryDB(root, n_shards=4, checkpoint_every=2)
    for blk in range(1, 6):
        h.commit(blk, [(0, f"tx{blk}", "cc", f"k{blk % 3}",
                        b"v%d" % blk, False)])
    mods = h.get_history("cc", "k1")
    re4 = HistoryDB(root, n_shards=4, checkpoint_every=2)
    assert re4.last_recovery["source"] in ("manifest", "manifest_prev")
    assert re4.savepoint == 5
    assert re4.get_history("cc", "k1") == mods
    # re-stripe
    re3 = HistoryDB(root, n_shards=3, checkpoint_every=2)
    assert re3.get_history("cc", "k1") == mods


# ---------------------------------------------------------------------------
# ledger-level differential: commit hash + state across shard widths
# ---------------------------------------------------------------------------

def _endorser_envs(org, n_blocks=4, txs_per_block=6):
    """Deterministic envelope matrix, built ONCE and committed to every
    ledger — byte-identical blocks in, bit-identical chains out."""
    rnd = random.Random(11)
    blocks = []
    for blk in range(n_blocks):
        envs = []
        for t in range(txs_per_block):
            key = f"k{rnd.randrange(18):03d}"
            writes = [KVWrite(key, b"b%d-t%d" % (blk, t))]
            if rnd.random() < 0.25:
                writes.append(KVWrite(f"gone{t}", b"", True))
            rwset = TxRwSet((NsRwSet("cc", writes=tuple(writes)),))
            envs.append(build.endorser_tx("ch", "cc", "1.0", rwset,
                                          org.admin, [org.admin]))
        blocks.append(envs)
    return blocks


def _commit_all(ledger, env_blocks):
    for envs in env_blocks:
        prev = (ledger.blockstore.chain_info().current_hash
                if ledger.height else b"\x00" * 32)
        blk = build.new_block(ledger.height, prev, envs)
        blk.metadata.items[META_TXFLAGS] = TxFlags(
            len(envs), ValidationCode.VALID).to_bytes()
        ledger.commit(blk)


def test_ledger_commit_chain_identical_across_shard_widths(tmp_path, org):
    env_blocks = _endorser_envs(org)
    ledgers = {}
    for n in SHARD_COUNTS:
        cfg = LedgerConfig(root=str(tmp_path / f"n{n}"), snapshot_every=3,
                           state_shards=n)
        ledgers[n] = KVLedger("ch", cfg)
        _commit_all(ledgers[n], env_blocks)
    ref = ledgers[1]
    for n in SHARD_COUNTS[1:]:
        lg = ledgers[n]
        assert lg.commit_hash == ref.commit_hash, f"n={n} chain diverged"
        assert _dump(lg.statedb) == _dump(ref.statedb)
        assert list(lg.range_query("cc", "", "")) == list(
            ref.range_query("cc", "", ""))
        assert lg.get_history("cc", "k000") == ref.get_history("cc", "k000")

    # reopen each from disk: checkpoint + WAL/chain-tail recovery lands
    # on the same chain state
    for n in SHARD_COUNTS:
        cfg = LedgerConfig(root=str(tmp_path / f"n{n}"), snapshot_every=3,
                           state_shards=n)
        re = KVLedger("ch", cfg)
        assert re.commit_hash == ref.commit_hash
        assert _dump(re.statedb) == _dump(ref.statedb)


# ---------------------------------------------------------------------------
# snapshot state transfer: export -> chunks -> install -> reopen
# ---------------------------------------------------------------------------

def _fetch_via_chunks(ledger, meta):
    """Assemble every snapshot file through serve_chunk (the wire path
    minus the wire), verifying the manifest hashes like the client."""
    payloads = {"state": [], "history": []}
    for ent in meta["files"]:
        buf = bytearray()
        while True:
            resp = snapshot.serve_chunk(ledger, ent["db"], ent["gen"],
                                        ent["file"], len(buf))
            buf += resp["data"]
            if resp["eof"]:
                break
        assert hashlib.sha256(bytes(buf)).hexdigest() == ent["sha256"]
        payloads[ent["db"]].append(bytes(buf))
    return payloads


def test_snapshot_roundtrip_installs_and_reopens(tmp_path, org):
    src_root = str(tmp_path / "src")
    cfg = LedgerConfig(root=src_root, snapshot_every=100, state_shards=4)
    src = KVLedger("ch", cfg)
    _commit_all(src, _endorser_envs(org, n_blocks=5))

    meta = snapshot.export_meta(src)
    assert meta["height"] == src.height
    assert meta["commit_hash"] == src.commit_hash
    assert any(e["db"] == "state" for e in meta["files"])
    payloads = _fetch_via_chunks(src, meta)

    dst_root = str(tmp_path / "dst")
    assert snapshot.needs_bootstrap(dst_root, "ch")
    snapshot.install(dst_root, "ch", meta, payloads)
    assert not snapshot.needs_bootstrap(dst_root, "ch")

    dst = KVLedger("ch", LedgerConfig(root=dst_root, state_shards=4))
    assert dst.height == src.height
    assert dst.commit_hash == src.commit_hash
    assert dst.blockstore.base == meta["height"]
    assert _dump(dst.statedb) == _dump(src.statedb)
    assert dst.get_history("cc", "k000") == src.get_history("cc", "k000")
    assert dst.last_recovery["replayed_blocks"] == 0   # nothing to replay
    # pre-snapshot blocks read as pruned, not silently wrong
    from fabric_tpu.ledger.blkstorage import BlockStoreError
    with pytest.raises(BlockStoreError, match="pruned"):
        dst.blockstore.get_by_number(0)

    # the installed peer keeps committing on the restored chain: feed it
    # the SAME next block the source commits, chains must stay in step
    tail = _endorser_envs(org, n_blocks=1, txs_per_block=3)
    _commit_all(src, tail)
    _commit_all(dst, tail)
    assert dst.height == src.height
    assert dst.commit_hash == src.commit_hash


def test_snapshot_install_tail_replay_bounded(tmp_path, org):
    """A peer that installed a snapshot then crashed mid-tail only
    replays the post-snapshot tail, never from genesis."""
    src_root = str(tmp_path / "src")
    src = KVLedger("ch", LedgerConfig(root=src_root, snapshot_every=100,
                                      state_shards=4))
    _commit_all(src, _endorser_envs(org, n_blocks=3))
    meta = snapshot.export_meta(src)
    payloads = _fetch_via_chunks(src, meta)

    dst_root = str(tmp_path / "dst")
    snapshot.install(dst_root, "ch", meta, payloads)
    dst = KVLedger("ch", LedgerConfig(root=dst_root, state_shards=4))
    tail = _endorser_envs(org, n_blocks=2, txs_per_block=3)
    _commit_all(src, tail)
    _commit_all(dst, tail)

    # lose the state WAL (the tail's only state-side record): recovery
    # falls back to the installed checkpoint (savepoint = base-1) and
    # replays ONLY the post-snapshot tail from the block store — never
    # from genesis, whose blocks are pruned here
    os.remove(os.path.join(dst_root, "ch", "state", "state.wal"))
    re = KVLedger("ch", LedgerConfig(root=dst_root, state_shards=4))
    assert re.commit_hash == src.commit_hash
    assert _dump(re.statedb) == _dump(src.statedb)
    assert re.last_recovery["start"] >= meta["height"]
    assert re.last_recovery["replayed_blocks"] == 2


def test_serve_chunk_rejects_traversal_and_unknown_db(tmp_path, org):
    src = KVLedger("ch", LedgerConfig(root=str(tmp_path / "src"),
                                      state_shards=2))
    _commit_all(src, _endorser_envs(org, n_blocks=1, txs_per_block=2))
    meta = snapshot.export_meta(src)
    ent = meta["files"][0]
    with pytest.raises(snapshot.SnapshotError):
        snapshot.serve_chunk(src, "wat", ent["gen"], ent["file"], 0)
    for bad in ("../MANIFEST", "shard_0000.bin/../../MANIFEST",
                "MANIFEST", "shard_.evil"):
        with pytest.raises(snapshot.SnapshotError):
            snapshot.serve_chunk(src, "state", ent["gen"], bad, 0)
    with pytest.raises(snapshot.SnapshotError, match="gone"):
        snapshot.serve_chunk(src, "state", 99999, ent["file"], 0)


def test_snapshot_fetch_survives_concurrent_checkpoints(tmp_path, org):
    """A bootstrap fetch keeps serving while the source checkpoints
    concurrently: export_meta reuses the on-disk generation instead of
    minting one per request, and the served generation is lease-pinned
    so checkpoint GC (which otherwise retains only {gen, gen-1}) cannot
    delete it mid-fetch."""
    src_root = str(tmp_path / "src")
    src = KVLedger("ch", LedgerConfig(root=src_root, snapshot_every=100,
                                      state_shards=4))
    _commit_all(src, _endorser_envs(org, n_blocks=4))
    meta = snapshot.export_meta(src)
    assert len(meta["files"]) >= 2

    # a second meta request while nothing changed serves the SAME
    # generation — N concurrent bootstrappers share one snapshot
    meta2 = snapshot.export_meta(src)
    assert meta2["state_manifest"]["gen"] == meta["state_manifest"]["gen"]

    # fetch with TWO forced checkpoints landing mid-flight (two fresh
    # generations: without the pin, {gen, gen-1} retention would have
    # deleted the generation being fetched after the second one)
    forced_gen = None
    payloads = {"state": [], "history": []}
    for i, ent in enumerate(meta["files"]):
        if i == 1:
            for _ in range(2):
                _commit_all(src, _endorser_envs(org, n_blocks=1,
                                                txs_per_block=3))
                forced_gen = int(src.snapshot_export()[0]["gen"])
            assert forced_gen > int(meta["state_manifest"]["gen"])
        buf = bytearray()
        while True:
            resp = snapshot.serve_chunk(src, ent["db"], ent["gen"],
                                        ent["file"], len(buf))
            buf += resp["data"]
            if resp["eof"]:
                break
        assert hashlib.sha256(bytes(buf)).hexdigest() == ent["sha256"]
        payloads[ent["db"]].append(bytes(buf))

    # a NEW meta request after the checkpoints serves the new tip
    meta3 = snapshot.export_meta(src)
    assert int(meta3["state_manifest"]["gen"]) == forced_gen

    # the stale-but-consistent snapshot still installs; the joiner just
    # joins lower and tail-replays the post-snapshot blocks to tip
    dst_root = str(tmp_path / "dst")
    snapshot.install(dst_root, "ch", meta, payloads)
    dst = KVLedger("ch", LedgerConfig(root=dst_root, state_shards=4))
    assert dst.height == meta["height"]
    assert dst.commit_hash == meta["commit_hash"]


def test_needs_bootstrap_only_on_virgin_dirs(tmp_path, org):
    root = str(tmp_path / "lg")
    assert snapshot.needs_bootstrap(root, "ch")
    lg = KVLedger("ch", LedgerConfig(root=root, state_shards=2))
    assert snapshot.needs_bootstrap(root, "ch")     # no blocks yet
    _commit_all(lg, _endorser_envs(org, n_blocks=1, txs_per_block=2))
    assert not snapshot.needs_bootstrap(root, "ch")  # has a chain: never clobber


# ---------------------------------------------------------------------------
# shard-parallel checkpoint serialization: bit-identity with the serial path
# ---------------------------------------------------------------------------

def _filled_statedb(root, n_keys=800, n_shards=8):
    db = StateDB(root=root, n_shards=n_shards)
    b = UpdateBatch()
    for i in range(n_keys):
        b.put("cc", f"k{i:05d}", b"v%d" % i, Version(1, i))
    db.apply_updates(b, 1)
    return db


def test_statedb_checkpoint_parallel_serial_bit_identity(tmp_path):
    """The thread fan-out over shards must produce byte-identical
    checkpoint payloads (the manifest records per-shard sha256)."""
    par = _filled_statedb(str(tmp_path / "par"))
    ser = _filled_statedb(str(tmp_path / "ser"))
    par._HOST_CORES = 8        # force the pool path even on 1-core CI
    ser._HOST_CORES = 1        # force the serial path
    mp, ms = par.checkpoint(), ser.checkpoint()
    assert [s["sha256"] for s in mp["shards"]] \
        == [s["sha256"] for s in ms["shards"]]
    assert [s["bytes"] for s in mp["shards"]] \
        == [s["bytes"] for s in ms["shards"]]
    # both recover to the same merged key map
    ra = StateDB(root=str(tmp_path / "par"), n_shards=8)
    rb = StateDB(root=str(tmp_path / "ser"), n_shards=8)
    assert ra._data == rb._data
    assert len(ra) == 800


def test_historydb_checkpoint_parallel_serial_bit_identity(tmp_path):
    def _filled(root):
        db = HistoryDB(root=root, n_shards=8)
        db.commit(1, [(i, f"tx{i}", "cc", f"k{i:05d}", b"v", False)
                      for i in range(800)])
        return db
    par, ser = _filled(str(tmp_path / "par")), _filled(str(tmp_path / "ser"))
    par._HOST_CORES = 8
    ser._HOST_CORES = 1
    mp, ms = par.checkpoint(), ser.checkpoint()
    assert [s["sha256"] for s in mp["shards"]] \
        == [s["sha256"] for s in ms["shards"]]
    re = HistoryDB(root=str(tmp_path / "par"), n_shards=8)
    assert re.last_recovery["source"] != "fresh"
    assert [m.txid for m in re.get_history("cc", "k00007")] == ["tx7"]


# ---------------------------------------------------------------------------
# the count of key-level validation parameters (`StateDB.meta_keys`)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("n_shards,seed", [(1, 5), (4, 6), (7, 7)])
def test_meta_keys_count_equals_a_scan(tmp_path, n_shards, seed):
    """What the validator's per-block rule asks the state: after any
    sequence of puts, deletes (of live and of absent keys), checkpoints
    and recoveries — from the WAL, from a checkpoint, re-striped — the
    count of live keys in `#meta` namespaces is a brute-force scan's, and
    a batch notes a `#meta` namespace only when it stages one."""
    rng = random.Random(seed)
    root = str(tmp_path / "state")
    namespaces = ("cc", "cc#meta", "bank#meta", "#meta", "meta", "cc#metal")

    def scan(db):
        return sum(1 for ns, _k in db._data if ns.endswith("#meta"))

    def random_batch(block, choose_from):
        batch = UpdateBatch()
        for _ in range(rng.randrange(1, 12)):
            ns, key = rng.choice(choose_from), f"k{rng.randrange(8)}"
            if rng.random() < 0.45:
                batch.delete(ns, key, Version(block, 0))
            else:
                batch.put(ns, key, b"v", Version(block, 0))
        assert batch.touches_meta == any(
            ns.endswith("#meta") for (ns, _k), _ in batch.items())
        return batch

    db = StateDB(root, snapshot_every=5, n_shards=n_shards)
    seen_nonzero = False
    for block in range(1, 21):
        plain = rng.random() < 0.3
        db.apply_updates(
            random_batch(block, ("cc", "meta") if plain else namespaces),
            block)
        assert db.meta_keys() == (block, scan(db))
        seen_nonzero |= scan(db) > 0
    assert seen_nonzero
    # every parameter deleted: the count is back at zero, and stays there
    # through a recovery
    wipe = UpdateBatch()
    for ns, key in db._data:
        if ns.endswith("#meta"):
            wipe.delete(ns, key, Version(21, 0))
    db.apply_updates(wipe, 21)
    assert db.meta_keys() == (21, 0)
    db = StateDB(root, snapshot_every=5, n_shards=n_shards)
    assert db.meta_keys() == (21, 0)
    for block in range(22, 60):
        db.apply_updates(random_batch(block, namespaces), block)
        if rng.random() < 0.2:
            db.checkpoint()
        if rng.random() < 0.3:
            # WAL alone, checkpoint + WAL, or a re-striped checkpoint
            restripe = rng.random() < 0.3
            db = StateDB(root, snapshot_every=5,
                         n_shards=(n_shards % 7) + 2 if restripe
                         else n_shards)
        assert db.meta_keys() == (block, scan(db))
    assert scan(db) > 0


def test_meta_keys_count_moves_before_the_savepoint():
    """`meta_keys` takes no lock, so that a validator running ahead never
    waits for a commit; it reads the savepoint, then the count.  That is
    sound only while an apply moves the count BEFORE the savepoint: at
    the moment a savepoint becomes visible, the count already holds that
    block's batch.  Watched here at that very moment, a parameter added
    every block."""
    class Watched(StateDB):
        at_savepoint = []

        @property
        def _savepoint(self):
            return self.__dict__.get("savepoint_")

        @_savepoint.setter
        def _savepoint(self, block):
            self.__dict__["savepoint_"] = block
            self.at_savepoint.append((block, getattr(self, "_meta_keys", 0)))

    db = Watched()
    for block in range(1, 9):
        batch = UpdateBatch()
        batch.put("cc", f"k{block}", b"v", Version(block, 0))
        batch.put("cc#meta", f"k{block}", b"POL", Version(block, 0))
        db.apply_updates(batch, block)
        assert db.meta_keys() == (block, block)
    assert db.at_savepoint == [(None, 0)] + [(b, b) for b in range(1, 9)]


# ---------------------------------------------------------------------------
# a shard's ordered key index follows the keys a batch adds and removes
# ---------------------------------------------------------------------------

_HUGE = 1 << 60


def _index_scenario(name, rng):
    """(n_shards, batches) of one case; a batch is a list of
    (ns, key, value-or-None) with one entry a key."""
    keys = [f"k{i:05d}" for i in range(900)]

    def puts(ns, ks, tag):
        return [(ns, k, b"%s-%s" % (tag, k.encode())) for k in ks]

    def dels(ns, ks):
        return [(ns, k, None) for k in ks]

    seed = puts("cc", keys[:400], b"s")
    if name == "updates_only":
        return 4, [seed] + [puts("cc", rng.sample(keys[:400], 200), b"u%d" % i)
                            for i in range(5)]
    if name == "few_changes":
        batches, live, spare = [seed], set(keys[:400]), list(keys[400:])
        for i in range(8):
            gone = rng.sample(sorted(live), rng.randrange(0, 4))
            new = [spare.pop() for _ in range(rng.randrange(0, 4))]
            live = (live - set(gone)) | set(new)
            kept = rng.sample(sorted(live - set(new)), 200)
            batches.append(dels("cc", gone) + puts("cc", new + kept, b"f%d" % i))
        return 4, batches
    if name == "hundreds":
        batches, live, spare = [seed], set(keys[:400]), set(keys[400:])
        for i in range(5):
            gone = rng.sample(sorted(live), 300)
            new = rng.sample(sorted(spare), 300)
            live, spare = (live - set(gone)) | set(new), \
                (spare - set(new)) | set(gone)
            batches.append(dels("cc", gone) + puts("cc", new, b"h%d" % i))
        return 2, batches
    if name == "delete_absent":
        return 4, [seed,
                   dels("cc", keys[500:560]),
                   dels("cc", keys[560:600]) + puts("cc", keys[:50], b"d"),
                   dels("cc", keys[600:700] + keys[10:11]),
                   dels("nowhere", keys[:5])]
    if name == "delete_then_recreate":
        pick = rng.sample(keys[:400], 120)
        return 4, [seed, dels("cc", pick), puts("cc", pick, b"again"),
                   dels("cc", pick[:60]) + puts("cc", pick[60:], b"upd"),
                   puts("cc", pick[:60], b"third"), dels("cc", pick)]
    if name == "meta_twin":
        batches, live = [puts("cc", keys[:200], b"v")
                         + puts("cc#meta", keys[:200], b"POL")], set(keys[:200])
        for i in range(6):
            gone = rng.sample(sorted(live), 25)
            new = rng.sample(sorted(set(keys[:400]) - live), 25)
            live = (live - set(gone)) | set(new)
            batches.append(dels("cc", gone) + dels("cc#meta", gone)
                           + puts("cc", new, b"v%d" % i)
                           + puts("cc#meta", new, b"POL%d" % i)
                           + puts("cc", rng.sample(sorted(live), 40), b"w"))
        return 4, batches
    if name == "empty_shard":
        # 16 shards and a handful of keys: most shards stay empty, and
        # the ones that fill do so from nothing
        return 16, [puts("cc", keys[:3], b"a"), puts("cc", keys[3:6], b"b"),
                    dels("cc", keys[:2]), puts("cc", keys[:40], b"c")]
    if name == "empties_a_shard":
        in_zero = [k for k in keys[:400] if shard_of("cc", k, 4) == 0]
        return 4, [seed, dels("cc", in_zero), puts("cc", in_zero[:7], b"z"),
                   dels("cc", keys[:400]), puts("cc", keys[100:140], b"y")]
    raise AssertionError(name)


def _batch_of(ops, block):
    batch = UpdateBatch()
    for t, (ns, key, value) in enumerate(ops):
        if value is None:
            batch.delete(ns, key, Version(block, t))
        else:
            batch.put(ns, key, value, Version(block, t))
    return batch


@pytest.mark.parametrize("name,seed", [
    ("updates_only", 1), ("few_changes", 2), ("few_changes", 3),
    ("hundreds", 4), ("delete_absent", 5), ("delete_then_recreate", 6),
    ("meta_twin", 7), ("meta_twin", 8), ("empty_shard", 9),
    ("empties_a_shard", 10)])
def test_sorted_keys_follow_a_batch_the_same_both_ways(tmp_path, name, seed):
    """The bisects and the one sort are two ways to the same list: with
    the threshold forced to 0 (every structural change takes the sort)
    and to a huge number (every one takes the bisects), after every batch
    each shard's sorted_keys is sorted(shard.data), a range over random
    bounds is the flat reference's, and the two stores' checkpoints are
    bit for bit the same."""
    rng = random.Random(seed)
    n_shards, batches = _index_scenario(name, rng)
    stores = {}
    for way, bisect_max in (("merge", 0), ("incremental", _HUGE)):
        db = StateDB(str(tmp_path / way), snapshot_every=1000,
                     n_shards=n_shards)
        db._INDEX_BISECT_MAX = bisect_max
        stores[way] = db
    flat = {}
    for block, ops in enumerate(batches, start=1):
        for ns, key, value in ops:
            if value is None:
                flat.pop((ns, key), None)
            else:
                flat[(ns, key)] = value
        digests = []
        for way, db in stores.items():
            db.apply_updates(_batch_of(ops, block), block)
            for sh in db._shards:
                assert sh.sorted_keys == sorted(sh.data), (way, block)
            assert {k: vv.value for k, vv in db._data.items()} == flat
            for ns in ("cc", "cc#meta"):
                lo, hi = sorted(f"k{rng.randrange(950):05d}" for _ in "ab")
                for start, end in ((lo, hi), ("", hi), (lo, ""), ("", "")):
                    want = [(k, v) for (n, k), v in sorted(flat.items())
                            if n == ns and k >= start and (not end or k < end)]
                    got = [(k, vv.value)
                           for k, vv in db.range_scan(ns, start, end)]
                    assert got == want, (way, block, ns, start, end)
            manifest = db.checkpoint()
            digests.append([(s["sha256"], s["bytes"])
                            for s in manifest["shards"]])
        assert digests[0] == digests[1], block
    for way, db in stores.items():
        again = StateDB(str(tmp_path / way), n_shards=n_shards)
        assert {k: vv.value for k, vv in again._data.items()} == flat


def _index_counts(channel):
    from fabric_tpu.ops_plane.metrics import registry
    modes = registry.counter("state_index_update_total")
    keys = registry.counter("state_index_changed_keys_total")
    return ({m: modes.value(channel=channel, mode=m)
             for m in ("none", "incremental", "merge")},
            keys.value(channel=channel))


@pytest.mark.parametrize("case,want", [
    ("one_add_among_200_updates", {"incremental": 1}),
    ("more_adds_than_the_threshold", {"merge": 1}),
    ("more_removes_than_the_threshold", {"merge": 1}),
    ("updates_only", {"none": 1}),
    ("a_delete_of_an_absent_key", {"none": 1})])
def test_the_way_is_chosen_by_the_keys_a_batch_adds_and_removes(case, want):
    """Not by the batch's size: 200 updates and one new key take the
    bisects; more new (or leaving) keys than the threshold take the sort;
    no key's existence changed, nothing is done — as the counter says."""
    channel = "idx-way-" + case
    db = StateDB(n_shards=1, channel=channel)
    many = db._INDEX_BISECT_MAX + 1
    held = [f"k{i:05d}" for i in range(max(400, 2 * many))]
    db.apply_updates(_batch_of([("cc", k, b"v") for k in held], 1), 1)
    ops = {
        "one_add_among_200_updates":
            [("cc", k, b"w") for k in held[:200]] + [("cc", "new", b"v")],
        "more_adds_than_the_threshold":
            [("cc", f"n{i:05d}", b"v") for i in range(many)],
        "more_removes_than_the_threshold":
            [("cc", k, None) for k in held[:many]],
        "updates_only": [("cc", k, b"w") for k in held[:200]],
        "a_delete_of_an_absent_key":
            [("cc", "absent", None)] + [("cc", k, b"w") for k in held[:99]],
    }[case]
    modes0, keys0 = _index_counts(channel)
    db.apply_updates(_batch_of(ops, 2), 2)
    modes1, keys1 = _index_counts(channel)
    moved = {m: modes1[m] - modes0[m] for m in modes1 if modes1[m] != modes0[m]}
    assert moved == want
    assert keys1 - keys0 == {"incremental": 1, "merge": many, "none": 0}[
        next(iter(want))]
    assert db._shards[0].sorted_keys == sorted(db._shards[0].data)


@pytest.mark.parametrize("bisect_max", [0, StateDB._INDEX_BISECT_MAX, _HUGE])
def test_changed_keys_counter_is_adds_plus_removes(bisect_max):
    """`state_index_changed_keys_total` counts every key whose existence
    a batch changed — re-puts, and deletes of absent keys, are none —
    and `state_index_update_total` one apply a shard a batch touched."""
    channel = f"idx-keys-{bisect_max}"
    rng = random.Random(46)
    db = StateDB(n_shards=4, channel=channel)
    db._INDEX_BISECT_MAX = bisect_max
    live, want_keys, want_applies = set(), 0, 0
    for block in range(1, 13):
        batch = UpdateBatch()
        picked = rng.sample(range(600), rng.choice((5, 80, 400)))
        for t, i in enumerate(picked):
            ns, key = ("cc", "cc#meta")[i % 2], f"k{i:04d}"
            if rng.random() < 0.4:
                batch.delete(ns, key, Version(block, t))
                want_keys += (ns, key) in live
                live.discard((ns, key))
            else:
                batch.put(ns, key, b"v", Version(block, t))
                want_keys += (ns, key) not in live
                live.add((ns, key))
        want_applies += len({shard_of(ns, k, 4) for (ns, k), _ in batch.items()})
        db.apply_updates(batch, block)
    modes, keys = _index_counts(channel)
    assert keys == want_keys > 0
    assert sum(modes.values()) == want_applies
    assert set(db._data) == live
    if bisect_max == 0:
        assert modes["incremental"] == 0 < modes["merge"]
    if bisect_max == _HUGE:
        assert modes["merge"] == 0 < modes["incremental"]
