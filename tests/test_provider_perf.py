"""Provider dispatch-economics regression tests.

Round 4 shipped a fast lane that re-uploaded ~124 MB of key tables per
dispatch; a timed run on the device caught it, CI did not.  These tests pin the
economics the bank redesign (ops/device_bank.py) guarantees:

  * tables cross host->device ONCE per key (h2d_bytes accounting);
  * steady-state dispatches ship only signature words + slot indices;
  * lane choice at 3 / 8 / 64 / 100 distinct keys;
  * the key-cache capacity cliff (eviction) stays correct and bounded.

All on the CPU backend (conftest), same code paths as TPU minus jit.
"""

import hashlib
import random
import threading
import time

import numpy as np
import pytest

from fabric_tpu.crypto import hashes
from fabric_tpu.crypto import ec as cec
from fabric_tpu.crypto import (
    decode_dss_signature, encode_dss_signature)
from fabric_tpu.crypto import (
    Encoding, PublicFormat)

from fabric_tpu.bccsp import SCHEME_P256, VerifyItem
from fabric_tpu.bccsp.jaxtpu import JaxTpuProvider
from fabric_tpu.ops import p256

# the kernel compiles below take minutes on the CPU backend
_slow = pytest.mark.slow

# one P-256 comb table in bytes (f32 (COMB_WINDOWS*COMB_ENTRIES, 2L))
from fabric_tpu.ops import p256_tables as _pt
TABLE_BYTES = _pt.COMB_WINDOWS * _pt.COMB_ENTRIES * 2 * _pt.L * 4


def _sigs(keys, per_key, seed=7):
    rng = random.Random(seed)
    pubs = [k.public_key().public_bytes(
        Encoding.X962, PublicFormat.UncompressedPoint) for k in keys]
    items = []
    for ki, k in enumerate(keys):
        for _ in range(per_key):
            msg = rng.randbytes(24)
            d = hashlib.sha256(msg).digest()
            r, s = decode_dss_signature(k.sign(msg, cec.ECDSA(hashes.SHA256())))
            if s > p256.HALF_N:
                s = p256.N - s
            items.append(VerifyItem(SCHEME_P256, pubs[ki],
                                    encode_dss_signature(r, s), d))
    rng.shuffle(items)
    return items


@pytest.fixture(scope="module")
def keypool():
    return [cec.generate_private_key(cec.SECP256R1()) for _ in range(100)]


def _fresh(monkeypatch, **env):
    for k, v in env.items():
        monkeypatch.setenv(k, str(v))
    prov = JaxTpuProvider()
    prov.fast_key_threshold = 4
    return prov


@_slow
def test_steady_state_ships_no_tables(monkeypatch, keypool):
    """After the first batch builds tables, later batches must ship only
    signature words: h2d per call stays ~100 B/sig, nowhere near the
    ~0.5 MB/key a table re-upload would cost (the round-4 regression)."""
    prov = _fresh(monkeypatch)
    items = _sigs(keypool[:3], 40)
    prov.batch_verify(items)
    assert prov.key_tables.stats["builds"] == 3
    base = prov.stats["h2d_bytes"]
    for _ in range(3):
        out = prov.batch_verify(items)
    per_call = (prov.stats["h2d_bytes"] - base) / 3
    # 120 sigs pad to 1 row-bucket of work: words are 8*4*3 B/sig + pad;
    # one table re-upload alone would be > TABLE_BYTES
    assert per_call < TABLE_BYTES / 4, per_call
    assert prov.key_tables.stats["builds"] == 3          # no rebuilds
    assert bool(np.asarray(out).all())


@_slow
def test_table_upload_once_per_key(monkeypatch, keypool):
    prov = _fresh(monkeypatch)
    items = _sigs(keypool[:8], 10)
    prov.batch_verify(items)
    b0 = prov.key_tables.stats["h2d_bytes"]
    assert b0 == 8 * TABLE_BYTES
    prov.batch_verify(items)
    assert prov.key_tables.stats["h2d_bytes"] == b0      # resident


@pytest.mark.parametrize("n_keys", [3, 8, 64])
@_slow
def test_lane_choice_hot_keys_ride_rows(monkeypatch, keypool, n_keys):
    """>= threshold sigs per key in one batch -> every sig on the comb
    lane regardless of how many distinct keys there are (the round-3
    NK<=4 cap must never come back)."""
    prov = _fresh(monkeypatch, FABRIC_TPU_KEY_CACHE=100)
    items = _sigs(keypool[:n_keys], 5)
    out = prov.batch_verify(items)
    assert bool(np.asarray(out).all())
    assert prov.stats["fast_key_sigs"] == len(items)
    assert prov.key_tables.stats["builds"] == n_keys


@_slow
def test_lane_choice_cold_keys_ride_generic(monkeypatch, keypool):
    """Below-threshold groups must NOT earn a table build (one-off
    creators ride the generic ladder)."""
    prov = _fresh(monkeypatch)
    items = _sigs(keypool[:100], 2)          # 2 < threshold 4
    out = prov.batch_verify(items)
    assert bool(np.asarray(out).all())
    assert prov.stats["fast_key_sigs"] == 0
    assert prov.key_tables.stats["builds"] == 0
    # a resident key rides the fast lane even for a single signature
    warm = _sigs(keypool[:1], 4, seed=9)
    prov.batch_verify(warm)
    one = _sigs(keypool[:1], 1, seed=11)
    prov.batch_verify(one)
    assert prov.stats["fast_key_sigs"] == len(warm) + len(one)


@_slow
def test_capacity_cliff_overflow_spills_to_generic(monkeypatch, keypool):
    """More hot keys than slots in ONE batch: the first max_keys groups
    win slots (pinned for the batch), the overflow rides the generic
    ladder, and verdicts stay correct — a mid-batch eviction of a
    claimed slot would verify rows against the WRONG table."""
    monkeypatch.setenv("FABRIC_TPU_KEY_CACHE", "4")
    prov = JaxTpuProvider()
    prov.fast_key_threshold = 4
    assert prov.key_tables.max_keys == 4
    for rep in range(2):
        items = _sigs(keypool[:6], 5, seed=20 + rep)     # 6 keys, 4 slots
        out = prov.batch_verify(items)
        assert bool(np.asarray(out).all())
    st = prov.key_tables.stats
    # exactly 4 winners per batch (one per slot); the 2 losers spill to
    # the generic lane or evict an unclaimed slot — churn stays bounded
    # by capacity per batch
    assert st["builds"] <= 2 * 4
    assert st["pinned_spills"] + st["evictions"] >= 2
    assert prov.stats["fast_key_sigs"] == 2 * 4 * 5


@_slow
def test_capacity_cliff_rotation_evicts_correctly(monkeypatch, keypool):
    """Alternating hot-key populations churn the LRU across batches;
    verdicts stay correct and rebuild cost is bounded by the rotation."""
    monkeypatch.setenv("FABRIC_TPU_KEY_CACHE", "4")
    prov = JaxTpuProvider()
    prov.fast_key_threshold = 4
    for rep in range(3):
        a = _sigs(keypool[:4], 5, seed=50 + rep)
        b = _sigs(keypool[4:8], 5, seed=60 + rep)
        assert bool(np.asarray(prov.batch_verify(a)).all())
        assert bool(np.asarray(prov.batch_verify(b)).all())
    st = prov.key_tables.stats
    assert st["evictions"] > 0
    assert st["builds"] <= 4 * 6              # bounded by full rotation
    # capacity >= population -> warm after one pass, zero further builds
    monkeypatch.setenv("FABRIC_TPU_KEY_CACHE", "8")
    prov2 = JaxTpuProvider()
    prov2.fast_key_threshold = 4
    prov2.batch_verify(_sigs(keypool[:6], 5, seed=33))
    builds = prov2.key_tables.stats["builds"]
    for rep in range(2):
        prov2.batch_verify(_sigs(keypool[:6], 5, seed=40 + rep))
    assert prov2.key_tables.stats["builds"] == builds == 6


@_slow
def test_dispatch_count_single_rows_dispatch(monkeypatch, keypool):
    """A mixed hot-key batch that fits one row chunk = exactly one
    device dispatch (merged rows lane), no generic-lane dispatch."""
    prov = _fresh(monkeypatch)
    items = _sigs(keypool[:4], 8)
    prov.batch_verify(items)
    d0 = prov.stats["dispatches"]
    prov.batch_verify(items)
    assert prov.stats["dispatches"] - d0 == 1


def test_rows_chunk_splits_large_grids(keypool):
    """Grids beyond rows_chunk rows split into several dispatches (the
    pack/compute overlap), with verdicts identical.  Geometry comes in
    through the PUBLIC constructor knobs — no class monkeypatching."""
    prov = JaxTpuProvider(fast_row_c=4, rows_chunk=2,
                          fast_key_threshold=4)
    items = _sigs(keypool[:3], 9)            # 3 rows/key of C=4
    d0 = prov.stats["dispatches"]
    out = prov.batch_verify(items)
    assert bool(np.asarray(out).all())
    assert prov.stats["dispatches"] - d0 >= 3
    sw = prov.fallback.batch_verify(items)
    assert (np.asarray(out) == np.asarray(sw)).all()


def test_packed_verb_is_the_item_verb_verdict_for_verdict(keypool):
    """`batch_verify_packed_async` over a signature table == the item
    verb over the table's items: the verdicts and every stat (`rows`
    for three keys above `fast_key_threshold`, the ladder lane for two
    below, a `rows_chunk` that splits the grid), over a batch that holds
    one of each thing the host must refuse or the device must — and an
    exact duplicate, which the table holds once."""
    from fabric_tpu.native import load
    fc = load("_fastcollect")
    if fc is None:
        pytest.skip("no native extension")
    good = _sigs(keypool[:3], 6) + _sigs(keypool[3:5], 2, seed=8)
    it = good[0]
    r, s_ = decode_dss_signature(it.signature)
    odd = [
        it._replace(signature=it.signature[:-1]),               # cut DER
        it._replace(signature=it.signature + b"\x00"),          # trailing
        it._replace(signature=encode_dss_signature(1 << 256, s_)),  # r
        it._replace(signature=b"\x30\x06\x02\x01\x01\x02\x01\x00"),   # s = 0
        it._replace(signature=encode_dss_signature(r, p256.N - s_)),  # high S
        it._replace(pubkey=it.pubkey[1:]),                      # 64-byte key
        it._replace(payload=it.payload[:31]),                   # 31-byte digest
        it._replace(payload=hashlib.sha256(b"other").digest()),  # unsound
        VerifyItem(*good[1]),                                   # duplicate
    ]
    batch = good + odd
    random.Random(3).shuffle(batch)
    table = fc.pack_items(batch, VerifyItem, SCHEME_P256)
    items = list(table)
    assert items == list(dict.fromkeys(batch)) and len(items) == len(batch) - 1
    assert table.n_rows == len(items) - 1 and len(table.rest) == 1   # 31 bytes

    stats = ("host_rejects", "dispatches", "device_sigs", "fast_key_sigs",
             "h2d_bytes", "fallbacks")

    def run(verb, arg):
        prov = JaxTpuProvider(fast_row_c=4, rows_chunk=2,
                              fast_key_threshold=4)
        out = getattr(prov, verb)(arg)()
        return np.asarray(out).tolist(), {k: prov.stats[k] for k in stats}

    packed, packed_stats = run("batch_verify_packed_async", table)
    by_item, item_stats = run("batch_verify_async", items)
    assert packed == by_item
    assert packed_stats == item_stats
    # cut, trailing, r, s = 0, the short key, the short digest
    assert item_stats["host_rejects"] == 6
    assert item_stats["fast_key_sigs"] == 18 + 2         # high S, unsound
    assert item_stats["device_sigs"] == 18 + 4 + 2
    assert item_stats["dispatches"] >= 3 + 1             # the grid is split
    want = JaxTpuProvider().fallback.batch_verify(items)
    assert packed == np.asarray(want).tolist() and sum(packed) == 22


class _SlowAsyncProvider:
    """Fake device with an injected verify latency.  batch_verify_async
    enqueues instantly and returns a resolve() that blocks until the
    background 'device' finishes — the same contract as
    JaxTpuProvider.batch_verify_async.  Records the device-busy windows
    so the test can measure collect-under-verify overlap without real
    kernels (no XLA compile, quick-gate safe)."""

    name = "slow-async-fake"

    def __init__(self, delay: float = 0.25):
        self.delay = delay
        self.busy = []                    # (enqueue_t, done_t) per dispatch

    def batch_verify_async(self, items):
        t_enq = time.perf_counter()
        done = threading.Event()
        out = np.ones(len(items), dtype=bool)

        def work():
            time.sleep(self.delay)
            self.busy.append((t_enq, time.perf_counter()))
            done.set()

        threading.Thread(target=work, daemon=True).start()

        def resolve():
            done.wait()
            return out

        return resolve

    def batch_verify(self, items):
        return self.batch_verify_async(items)()


def _overlap(win, busy):
    """Seconds of `win` covered by the union of `busy` intervals."""
    a, b = win
    total = 0.0
    for s, e in busy:
        lo, hi = max(a, s), min(b, e)
        if hi > lo:
            total += hi - lo
    return total


def test_window_collect_under_verify():
    """Streamed-window economics regression (the config-5 pipeline):

    * validate_begin must NEVER synchronize with the device; any hidden
      resolve() on the begin path would cost >= one injected 0.25 s
      device delay per block;
    * the measured collect-under-verify fraction for steady-state blocks
      (every begin after the pipeline fills) must clear a floor — the
      depth-2 window drives collect of block N+1 entirely under the
      device's verify of block N when the host tail is fast enough.
    """
    from fabric_tpu.committer import PolicyRegistry, TxValidator
    from fabric_tpu.msp import CachedMSP
    from fabric_tpu.msp.ca import DevOrg
    from fabric_tpu.policy import parse_policy
    from fabric_tpu.protocol import KVWrite, NsRwSet, TxRwSet, build

    org = DevOrg("Org1")
    msps = {org.mspid: CachedMSP(org.msp())}
    policies = PolicyRegistry(parse_policy("OR('Org1.member')"))
    endorser = org.new_identity("e")
    client = org.new_identity("c")
    blocks = []
    for b in range(4):
        envs = []
        for i in range(40):
            rws = TxRwSet((NsRwSet(
                "cc", writes=(KVWrite(f"b{b}k{i}", b"v"),)),))
            envs.append(build.endorser_tx("ch", "cc", "1.0", rws,
                                          client, (endorser,)))
        blocks.append(build.new_block(b, b"\x00" * 32, envs))

    prov = _SlowAsyncProvider(delay=0.25)
    validator = TxValidator("ch", msps, prov, policies)
    begins = []                           # (start_t, end_t) per block
    pending = []
    for blk in blocks:
        t0 = time.perf_counter()
        state = validator.validate_begin(blk)
        begins.append((t0, time.perf_counter()))
        pending.append(state)
        if len(pending) >= 2:             # depth-2 pipeline
            res = validator.validate_finish(pending.pop(0))
            assert res.flags.valid_count() == 40
    while pending:
        res = validator.validate_finish(pending.pop(0))
        assert res.flags.valid_count() == 40

    # 1: begin never blocked on the device
    slowest = max(e - s for s, e in begins)
    assert slowest < prov.delay * 0.5, (slowest, begins)
    # 2: steady-state collects ran under an in-flight device verify
    steady = begins[1:]
    collect_s = sum(e - s for s, e in steady)
    under = sum(_overlap(w, prov.busy) for w in steady)
    frac = under / max(1e-9, collect_s)
    assert frac >= 0.9, (frac, steady, prov.busy)


def test_stats_snapshot_public_surface(keypool):
    """stats_snapshot() exposes counters + table-bank builds + the
    effective tuning as a frozen dataclass, decoupled from the live
    mutable dicts."""
    import dataclasses

    prov = JaxTpuProvider(fast_row_c=8, rows_chunk=16,
                          fast_key_threshold=4, max_cached_keys=12)
    items = _sigs(keypool[:2], 6)
    prov.batch_verify(items)
    snap = prov.stats_snapshot()
    assert snap.dispatches >= 1
    assert snap.p256_table_builds == 2
    assert snap.tuning == {"fast_row_c": 8, "rows_chunk": 16,
                           "fast_key_threshold": 4,
                           "max_cached_keys": 12}
    # a snapshot is immutable: observers can't poke the provider
    with pytest.raises(dataclasses.FrozenInstanceError):
        snap.dispatches = -1
