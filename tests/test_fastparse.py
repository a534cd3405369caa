"""Zero-copy ingest: native wire parser vs its pure-Python mirror.

Hard gates (ISSUE r09):
  - seeded adversarial corpora (truncations, junk, bitflips, duplicated
    dict fields, unsorted keys, oversized length claims) run through the
    native parser with NO crashes and accept/reject decisions + every
    extracted field byte-identical to the wire.py mirrors;
  - end-to-end: a block validated through the BlockView path produces
    the same final tx flags and commit hash as the materialized
    Block + pure-Python walk;
  - the gateway's derive_items produces identical VerifyItem streams
    through the native extractor and the collect_py fallback;
  - the parse stage allocates O(1) Python objects regardless of block
    tx count (the per-tx object elimination this PR claims).

The corpus builder doubles as the ASan/UBSan smoke driver: run
`python tests/test_fastparse.py --asan-corpus` against a sanitizer
build of _fastparse (tests/smoke.sh does this).
"""

import gc
import random
import struct
import sys
import time

import pytest

from fabric_tpu.bccsp.factory import FactoryOpts, init_factories
from fabric_tpu.committer import PolicyRegistry, TxValidator
from fabric_tpu.msp import CachedMSP
from fabric_tpu.msp.ca import DevOrg
from fabric_tpu.policy import parse_policy
from fabric_tpu.protocol import build, wire
from fabric_tpu.protocol.types import (Block, BlockHeader, BlockMetadata,
                                       KVRead, KVWrite, NsRwSet,
                                       RangeQueryInfo, TxRwSet, Version,
                                       block_data_hash)
from fabric_tpu.utils import serde

pytestmark = pytest.mark.skipif(
    wire._fastparse is None, reason="native _fastparse unavailable")


# -- corpus ------------------------------------------------------------------

def _u32(n):
    return struct.pack(">I", n)


def _s(v):
    return b"S" + _u32(len(v)) + v.encode()


def _b(v):
    return b"B" + _u32(len(v)) + v


def _d(entries):
    return b"D" + _u32(len(entries)) + b"".join(k + v for k, v in entries)


def _handcrafted():
    """Structural adversaries serde.encode cannot produce: duplicated
    fields, unsorted keys, miscounted containers, oversized claims."""
    hdr = _d([(_s("data_hash"), _b(b"\x00" * 32)),
              (_s("number"), b"I" + struct.pack(">q", 1)),
              (_s("previous_hash"), _b(b"\x00" * 32))])
    data = b"L" + _u32(0)
    meta = _d([(_s("items"), _d([]))])
    good = _d([(_s("data"), data), (_s("header"), hdr),
               (_s("metadata"), meta)])
    return [
        good,                                              # baseline accept
        # duplicated field: "data" appears twice (count raised to 4)
        _d([(_s("data"), data), (_s("data"), data),
            (_s("header"), hdr), (_s("metadata"), meta)]),
        # unsorted keys
        _d([(_s("header"), hdr), (_s("data"), data),
            (_s("metadata"), meta)]),
        # count says 4, only 3 entries present
        b"D" + _u32(4) + good[5:],
        # extra top-level key (native demands exactly 3)
        _d([(_s("data"), data), (_s("header"), hdr),
            (_s("metadata"), meta), (_s("zzz"), _b(b""))]),
        # oversized list-count claim with no payload behind it
        _d([(_s("data"), b"L" + _u32(0x00FFFFFF)), (_s("header"), hdr),
            (_s("metadata"), meta)]),
        # oversized bytes-length claim
        _d([(_s("data"), data), (_s("header"), hdr),
            (_s("metadata"), _d([(_s("x"), b"B" + _u32(0x7FFFFFFF))]))]),
        # trailing garbage after a valid block
        good + b"\x00",
        # truncated mid-length
        good[:7],
        # header with a duplicated inner field
        _d([(_s("data"), data),
            (_s("header"), _d([(_s("data_hash"), _b(b"")),
                               (_s("data_hash"), _b(b"")),
                               (_s("number"), b"I" + struct.pack(">q", 1)),
                               (_s("previous_hash"), _b(b""))])),
            (_s("metadata"), meta)]),
        b"", b"D", b"L" + _u32(1),
    ]


def _org_world():
    org1, org2 = DevOrg("Org1"), DevOrg("Org2")
    return org1, org2


def _tx(org1, org2, chan="ch", nonce=None):
    rwset = TxRwSet((NsRwSet("cc", writes=(KVWrite("k", b"v"),)),))
    return build.endorser_tx(
        chan, "cc", "1.0", rwset, org1.new_identity("client"),
        [org1.new_identity("e1"), org2.new_identity("e2")],
        **({"nonce": nonce} if nonce else {}))


def fuzz_corpus(seed, org1=None, org2=None, n=60):
    """Seeded adversarial corpus of BLOCK byte strings.  Mix of valid
    blocks, mutations of valid blocks, handcrafted structural attacks,
    and junk — deterministic per seed."""
    rng = random.Random(seed)
    if org1 is None:
        org1, org2 = _org_world()
    envs = [_tx(org1, org2).serialize() for _ in range(4)]
    out = list(_handcrafted())
    for _ in range(n):
        kind = rng.randrange(8)
        data = [rng.choice(envs) for _ in range(rng.randrange(0, 4))]
        blk = Block(BlockHeader(rng.randrange(0, 1 << 40),
                                rng.randbytes(32), block_data_hash(data)),
                    data, BlockMetadata())
        raw = blk.serialize()
        if kind == 0:
            pass                                           # valid
        elif kind == 1 and len(raw) > 4:
            raw = raw[:rng.randrange(1, len(raw))]         # truncated
        elif kind == 2:
            raw = rng.randbytes(rng.randrange(0, 64))      # junk
        elif kind == 3:
            mut = bytearray(raw)
            mut[rng.randrange(len(mut))] ^= 1 << rng.randrange(8)
            raw = bytes(mut)                               # bitflip
        elif kind == 4:
            raw = raw + rng.randbytes(rng.randrange(1, 8))  # trailing
        elif kind == 5:
            # number outside i64 (encodes as 'V'): mirror + native reject
            blk2 = {"data": data,
                    "header": {"data_hash": b"\x00" * 32,
                               "number": 2 ** 63 + rng.randrange(9),
                               "previous_hash": b"\x00" * 32},
                    "metadata": {}}
            raw = serde.encode(blk2)
        elif kind == 6:
            # envelope list holding a non-bytes item
            raw = serde.encode({"data": ["oops"],
                                "header": {"data_hash": b"", "number": 1,
                                           "previous_hash": b""},
                                "metadata": {}})
        out.append(raw)
    return out


def env_fuzz_corpus(seed, org1=None, org2=None, n=60):
    """Seeded adversarial corpus of ENVELOPE byte strings."""
    rng = random.Random(seed)
    if org1 is None:
        org1, org2 = _org_world()
    out = []
    for _ in range(n):
        kind = rng.randrange(8)
        raw = _tx(org1, org2,
                  chan=rng.choice(["ch", "other"])).serialize()
        if kind == 1 and len(raw) > 4:
            raw = raw[:rng.randrange(1, len(raw))]
        elif kind == 2:
            raw = rng.randbytes(rng.randrange(0, 64))
        elif kind == 3:
            mut = bytearray(raw)
            mut[rng.randrange(len(mut))] ^= 1 << rng.randrange(8)
            raw = bytes(mut)
        elif kind == 4:
            raw = serde.encode({"payload": b"junk", "signature": b"s"})
        elif kind == 5:
            raw = serde.encode({"payload": serde.encode(
                {"header": {"channel_header": {"type": "x"},
                            "signature_header": {}}}),
                "signature": b"s"})
        elif kind == 6:
            raw = serde.encode({"signature": b"s"})        # no payload
        out.append(raw)
    # the structural block attacks double as envelope attacks
    out.extend(_handcrafted())
    return out


# -- differential: native vs mirror ------------------------------------------

def test_parse_block_differential_fuzz():
    org1, org2 = _org_world()
    for seed in (11, 22, 33):
        for raw in fuzz_corpus(seed, org1, org2):
            nat = wire._fastparse.parse_block(raw)
            mir = wire.parse_block_py(raw)
            assert (nat is None) == (mir is None), raw.hex()[:120]
            if nat is None:
                continue
            number, prev, dhash, data_off, data_end, ndata, spans, moff = nat
            m_number, m_prev, m_dhash, m_data, m_meta, m_moff = mir
            assert (number, prev, dhash) == (m_number, m_prev, m_dhash)
            assert ndata == len(m_data) and moff == m_moff
            view = wire.parse_block(raw)
            assert isinstance(view, wire.BlockView)
            assert view.data == m_data                    # byte-identical
            assert serde.decode(bytes(raw[moff:])) == m_meta
            # layout facts the zero-copy paths rely on
            assert view.computed_data_hash == block_data_hash(m_data)
            assert bytes(view.serialize()) == bytes(raw)  # identity
            blk = Block.deserialize(raw)                  # never raises here
            assert blk.header.number == number
            assert blk.data == m_data


def test_envelope_summary_differential_fuzz():
    org1, org2 = _org_world()
    for seed in (11, 22, 33):
        for raw in env_fuzz_corpus(seed, org1, org2):
            nat = wire._fastparse.envelope_summary(raw)
            mir = wire.envelope_summary_py(raw)
            assert nat == mir, raw.hex()[:120]


def test_metadata_splice_reserialize_identity():
    """Mutating metadata then serializing must equal the full re-encode
    (the splice the gossip/commit paths rely on)."""
    org1, org2 = _org_world()
    data = [_tx(org1, org2).serialize()]
    blk = Block(BlockHeader(3, b"p" * 32, block_data_hash(data)), data,
                BlockMetadata())
    raw = blk.serialize()
    view = wire.parse_block(raw)
    assert isinstance(view, wire.BlockView)
    assert bytes(view.serialize()) == raw        # untouched: raw identity
    view.metadata.items["flags"] = b"\x00"
    blk.metadata.items["flags"] = b"\x00"
    assert bytes(view.serialize()) == blk.serialize()


# -- end-to-end: committer flags through BlockView vs Python -----------------

def test_committer_flags_parity_blockview_vs_python(tmp_path):
    provider = init_factories(FactoryOpts(default="SW"))
    org1, org2 = _org_world()
    msps = {o.mspid: CachedMSP(o.msp()) for o in (org1, org2)}
    policies = PolicyRegistry()
    policies.set_policy(
        "cc", parse_policy("AND('Org1.member', 'Org2.member')"))

    good = [_tx(org1, org2).serialize() for _ in range(3)]
    bad = good[0][:40]                    # truncated envelope in-block
    wrong = _tx(org1, org2, chan="other").serialize()
    data = good + [bad, wrong]
    raw = Block(BlockHeader(0, b"\x00" * 32, block_data_hash(data)), data,
                BlockMetadata()).serialize()

    def run(native):
        block = wire.parse_block(raw) if native else Block.deserialize(raw)
        if native:
            assert isinstance(block, wire.BlockView)
        v = TxValidator("ch", msps, provider, policies)
        v.force_python_collect = not native
        res = v.validate(block)
        return res.flags.codes(), block.metadata.items.copy()

    codes_nat, meta_nat = run(True)
    codes_py, meta_py = run(False)
    assert codes_nat == codes_py
    assert meta_nat == meta_py


# -- gateway: derive_items native vs fallback --------------------------------

def test_derive_items_native_matches_fallback(monkeypatch):
    from fabric_tpu.verify_plane import speculative
    from fabric_tpu.verify_plane.cache import item_digest
    if speculative._fastcollect is None:
        pytest.skip("native _fastcollect unavailable")
    org1, org2 = _org_world()
    msps = {o.mspid: CachedMSP(o.msp()) for o in (org1, org2)}
    raws = [_tx(org1, org2).serialize() for _ in range(3)]
    raws.append(raws[0][:25])                       # structurally invalid
    raws.append(b"")

    def items(native):
        if not native:
            monkeypatch.setattr(speculative, "_fastcollect", None)
        out = []
        for raw in raws:
            c, e = speculative.derive_items(raw, "ch", msps)
            out.append(([item_digest(i) for i in c],
                        [item_digest(i) for i in e]))
        monkeypatch.undo()
        return out

    nat, py = items(True), items(False)
    assert nat == py                                # same items, same order
    assert nat[0][0] and nat[0][1]                  # creator + endorsements
    assert nat[3] == ([], []) and nat[4] == ([], [])


# -- allocation regression: O(1) parse stage ---------------------------------

def test_parse_stage_allocations_independent_of_tx_count():
    """The whole point of the arena/span design: parsing a block into a
    BlockView allocates a CONSTANT number of Python objects however many
    txs ride in it, while the materializing path scales linearly."""
    org1, org2 = _org_world()
    env = _tx(org1, org2).serialize()

    def block_raw(n):
        data = [env] * n
        return Block(BlockHeader(0, b"\x00" * 32, block_data_hash(data)),
                     data, BlockMetadata()).serialize()

    raw_s, raw_l = block_raw(256), block_raw(512)
    wire.parse_block(raw_s)                          # warm caches/arena

    def allocs(fn):
        gc.collect()
        gc.disable()
        try:
            before = sys.getallocatedblocks()
            keep = fn()
            after = sys.getallocatedblocks()
        finally:
            gc.enable()
        assert keep is not None
        return after - before

    a_s = allocs(lambda: wire.parse_block(raw_s))
    a_l = allocs(lambda: wire.parse_block(raw_l))
    # native path: span table lives in the C arena, no per-tx objects
    assert abs(a_l - a_s) <= 16, (a_s, a_l)
    # the displaced path really did scale (sanity of the measurement)
    p_s = allocs(lambda: Block.deserialize(raw_s))
    p_l = allocs(lambda: Block.deserialize(raw_l))
    assert p_l - p_s >= 200, (p_s, p_l)


def test_arena_ring_reuse():
    """Dropping a BlockView returns its span arena to the ring pool; the
    next parse reuses it instead of mallocing."""
    org1, org2 = _org_world()
    env = _tx(org1, org2).serialize()
    data = [env] * 8
    raw = Block(BlockHeader(0, b"\x00" * 32, block_data_hash(data)), data,
                BlockMetadata()).serialize()
    wire.parse_block(raw)                            # prime the pool
    before = wire._fastparse.stats()
    for _ in range(4):
        v = wire.parse_block(raw)
        assert isinstance(v, wire.BlockView)
        del v
    after = wire._fastparse.stats()
    assert after["pool_hit"] - before["pool_hit"] >= 4
    assert after["block_accept"] > before["block_accept"]


# -- rwset lane extraction: native vs mirror ---------------------------------

def _lane_envs(org1, org2):
    """Serialized envelopes with adversarial rw-set shapes (lane corpus
    building blocks; built once per call — signing is the slow part)."""
    def env(rwset):
        return build.endorser_tx(
            "ch", "cc", "1.0", rwset, org1.new_identity("c"),
            [org1.new_identity("e1")]).serialize()

    V = Version
    envs = [
        env(TxRwSet(())),                              # empty rwset
        env(TxRwSet((NsRwSet("cc", reads=(
            KVRead("a", None), KVRead("b", V(0, 1)),
            KVRead("a", V(3, 4)))),))),                # dup key interning
        env(TxRwSet((NsRwSet("cc", writes=(
            KVWrite("a", b""), KVWrite("del", b"", True),
            KVWrite("big", bytes(range(256)) * 7))),))),
        env(TxRwSet((NsRwSet("cc", range_queries=(
            RangeQueryInfo("a", "z", True, ()),)),))),  # status RANGE
        env(TxRwSet((NsRwSet("ns-β", reads=(
            KVRead("κ-key", V(1, 2)),),
            writes=(KVWrite("κ-key", "vé".encode()),)),))),
        env(TxRwSet((NsRwSet("cc", writes=(
            KVWrite("ab", b"1"), KVWrite("bA", b"2"))),))),  # djb2 collision
        env(TxRwSet((NsRwSet("cc", reads=(
            KVRead("k", V(1 << 40, (1 << 40) + 3)),)),))),   # > i32 versions
        env(TxRwSet((NsRwSet("x", writes=(KVWrite("k", b"1"),)),
                     NsRwSet("y", writes=(KVWrite("k", b"2"),))))),
    ]
    return envs


def _span_table(parts):
    spans, off = bytearray(), 0
    for p in parts:
        spans += struct.pack("QQ", off, len(p))
        off += len(p)
    return b"".join(parts), bytes(spans)


def lane_fuzz_corpus(seed, org1=None, org2=None, envs=None):
    """(base, spans) pairs for rwset_lanes: well-formed blocks over the
    adversarial rw-set envelopes, plus mutated bases and bogus/ragged
    span tables — deterministic per seed."""
    rng = random.Random(seed)
    if envs is None:
        if org1 is None:
            org1, org2 = _org_world()
        envs = _lane_envs(org1, org2)
    pool = envs + [b"", b"junk", envs[1][:30]]         # junk -> status BAD
    out = []
    groups = [[rng.choice(pool) for _ in range(rng.randrange(0, 5))]
              for _ in range(10)]
    groups.append(list(envs))                           # incl. collision
    groups.append(envs[:5])                             # collision-free mix
    for parts in groups:
        base, spans = _span_table(parts)
        out.append((base, spans))
        if spans:
            mut = bytearray(spans)
            mut[rng.randrange(len(mut))] ^= 1 << rng.randrange(8)
            out.append((base, bytes(mut)))              # bogus offset/len
            out.append((base, spans[:rng.randrange(len(spans))]))  # ragged
        if base:
            mb = bytearray(base)
            mb[rng.randrange(len(mb))] ^= 1 << rng.randrange(8)
            out.append((bytes(mb), spans))              # bitflipped envelope
    out.append((b"", b""))
    out.append((b"x", struct.pack("QQ", 1 << 63, 1 << 63)))  # huge offsets
    out.append((b"x" * 64, struct.pack("QQ", 60, 10)))       # end past base
    return out


def test_rwset_lanes_native_matches_mirror():
    """Full-tuple bit identity: accept/reject/collision decision, lane
    counts, and every arena byte (the commit path's walk consumes these
    lanes verbatim — tests/test_commit_lanes.py gates end-to-end)."""
    org1, org2 = _org_world()
    envs = _lane_envs(org1, org2)
    n_accept = n_collide = 0
    for seed in (11, 22, 33):
        for base, spans in lane_fuzz_corpus(seed, envs=envs):
            nat = wire._fastparse.rwset_lanes(base, spans)
            mir = wire.rwset_lanes_py(base, spans)
            assert (nat is None) == (mir is None), (spans.hex()[:64],)
            if nat is None:
                continue
            nf, nt, nk, nr, nw, narena = nat
            mf, mt, mk, mr, mw, marena = mir
            assert (nf, nt, nk, nr, nw) == (mf, mt, mk, mr, mw)
            if nf:
                n_collide += 1
                assert narena is None and marena is None
                continue
            n_accept += 1
            assert bytes(memoryview(narena)) == bytes(marena)
    assert n_accept > 10 and n_collide > 0  # corpus exercised both paths


def _lanes_plain(lanes):
    return lanes if lanes is None or lanes[5] is None else (
        lanes[:5] + (bytes(memoryview(lanes[5])),))


def test_rwset_lanes_yields_the_interpreter_and_keeps_its_scratch_whole():
    """The extractor's walk runs without the interpreter lock (the
    validator opens a block's lanes while another thread waits for the
    device): a thread that only counts gets to run while one call walks a
    large block, and calls from four threads at once — every kind of
    ending: lanes, a collision, a rejected span table — give what the
    same calls give alone (the scratch is one caller's at a time)."""
    import threading
    org1, org2 = _org_world()
    envs = _lane_envs(org1, org2)
    corpus = [c for seed in (11, 22) for c in lane_fuzz_corpus(seed,
                                                               envs=envs)]
    alone = [_lanes_plain(wire._fastparse.rwset_lanes(b, sp))
             for b, sp in corpus]
    assert {None, 0, 1} == {a if a is None else a[0] for a in alone}
    got = {}

    def worker(k):
        order = list(range(len(corpus)))
        random.Random(k).shuffle(order)
        got[k] = {i: _lanes_plain(wire._fastparse.rwset_lanes(*corpus[i]))
                  for _ in range(5) for i in order}
    threads = [threading.Thread(target=worker, args=(k,)) for k in range(4)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=120)
        assert not t.is_alive()
    for k in range(4):
        assert [got[k][i] for i in range(len(corpus))] == alone

    # one long walk (collision-free envelopes, many times over) beside a
    # thread that needs the interpreter to count: it counts through the
    # walk about as fast as through a sleep of the same length.  Under a
    # walk that kept the interpreter it would get one hand-over at most,
    # a switch interval (5 ms) of the walk's ~100
    base, spans = _span_table(envs[:5] * 8000)
    ticks, done = [0], threading.Event()

    def count():
        while not done.is_set():
            ticks[0] += 1
    counter = threading.Thread(target=count)
    counter.start()
    try:
        time.sleep(0.05)
        t0, before = time.perf_counter(), ticks[0]
        lanes = wire._fastparse.rwset_lanes(base, spans)
        walk_s, during = time.perf_counter() - t0, ticks[0] - before
        before = ticks[0]
        time.sleep(walk_s)
        asleep = ticks[0] - before
    finally:
        done.set()
        counter.join(timeout=60)
    assert not counter.is_alive()
    assert lanes[0] == 0 and lanes[1] == 40000
    assert walk_s > 0.03 and during > 0.4 * asleep, (walk_s, during, asleep)


def _arena_strings(mod, base, lanes):
    """(key_strs, txids) of an extracted arena through `mod`'s decoders."""
    _f, n_tx, n_keys, n_reads, n_writes, arena = lanes
    cells = memoryview(arena).cast("B")
    keys_at = 8 * (3 * n_tx + 5 * (n_reads + n_writes))
    return (mod.arena_keys(base, cells[keys_at:keys_at + 40 * n_keys]),
            mod.arena_txids(base, cells[:24 * n_tx]))


def test_arena_strings_native_match_the_python_decode(monkeypatch):
    """`LaneTable.key_strs` / `.txids` through `arena_keys` / `arena_txids`
    against the comprehension they replace, over the lane corpus; a
    namespace that repeats is one str object."""
    n_tables = n_keys = 0
    for seed in (11, 22):
        for base, spans in lane_fuzz_corpus(seed):
            lanes = wire._fastparse.rwset_lanes(base, spans)
            if lanes is None or lanes[0]:
                continue
            native = wire.LaneTable(base, lanes)
            with monkeypatch.context() as m:
                m.setattr(wire, "_fastparse", None)
                mirror = wire.LaneTable(base, lanes)
                want = (mirror.key_strs, mirror.txids)
            assert (native.key_strs, native.txids) == want
            assert _arena_strings(wire._fastparse, base, lanes) == want
            for (a, _), (b, _) in zip(native.key_strs, native.key_strs[1:]):
                assert (a is b) == (a == b)
            n_tables += 1
            n_keys += len(want[0])
    assert n_tables > 10 and n_keys > 50
    with pytest.raises(ValueError):
        wire._fastparse.arena_keys(b"abc", struct.pack("=5Q", 0, 0, 2, 2, 2))
    with pytest.raises(ValueError):
        wire._fastparse.arena_txids(b"abc", struct.pack("=3Q", 0, 2, 2))
    with pytest.raises(ValueError):
        wire._fastparse.arena_keys(b"abc", b"\x00" * 39)
    with pytest.raises(UnicodeDecodeError):
        wire._fastparse.arena_keys(b"\xff\xfe", struct.pack("=5Q", 0, 0, 1, 1, 1))
    assert wire._fastparse.arena_txids(
        b"abc", struct.pack("=6Q", 1, 9, 9, 0, 1, 2)) == [None, "bc"]


# -- ASan/UBSan smoke driver (tests/smoke.sh) --------------------------------

def run_sanitizer_corpus(mod, seeds=(11, 22, 33)):
    """Drive a (sanitizer-built) _fastparse module over the full corpus;
    any memory error aborts the process — that IS the gate."""
    org1, org2 = _org_world()
    lane_envs = _lane_envs(org1, org2)
    n_blk = n_env = n_lane = 0
    for seed in seeds:
        for raw in fuzz_corpus(seed, org1, org2):
            r = mod.parse_block(raw)
            if r is not None:
                n_blk += 1
                memoryview(r[6])[:]                  # touch the arena
                # key-hash lane extraction over the parsed span table
                # (bounds-stress: spans index the full block buffer)
                lanes = mod.rwset_lanes(raw, bytes(memoryview(r[6])))
                if lanes is not None and lanes[5] is not None:
                    memoryview(lanes[5])[:]          # touch the lane arena
        for raw in env_fuzz_corpus(seed, org1, org2):
            if mod.envelope_summary(raw) is not None:
                n_env += 1
        for base, spans in lane_fuzz_corpus(seed, envs=lane_envs):
            lanes = mod.rwset_lanes(base, spans)
            if lanes is not None:
                if lanes[5] is not None:
                    memoryview(lanes[5])[:]
                    _arena_strings(mod, base, lanes)
                n_lane += 1
    return n_blk, n_env, n_lane


if __name__ == "__main__":
    if "--asan-corpus" in sys.argv:
        import importlib
        mod = importlib.import_module("_fastparse")
        n_blk, n_env, n_lane = run_sanitizer_corpus(mod)
        print(f"sanitizer corpus clean: {n_blk} blocks, "
              f"{n_env} envelopes, {n_lane} lane tables accepted; "
              f"stats={mod.stats()}")
