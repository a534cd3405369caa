"""Differential fuzz: fused device validation vs the host serial oracle.

Every test drives the SAME envelope bytes through full Committer stacks
built with device_validate off (host gate + serial MVCC — the round-8
oracle) and on (one fused XLA dispatch per block), and asserts bit
identity on: final flag bytes, block-metadata flags, state rows,
history rows, and the running commit hash.  Adversarial corpora cover
same-key ww chains, delete-then-read, phantoms (range queries — demote),
engineered uint64 key-hash collisions (demote without error), 0%/100%
conflict, policy/signature failures, and seeded random blocks.

Counters are process-global, so every assertion is a delta against a
snapshot taken before the run.
"""
import os

os.environ.setdefault("JAX_PLATFORMS", "cpu")

import random

import pytest

from fabric_tpu.bccsp.factory import init_factories, FactoryOpts
from fabric_tpu.committer import Committer, PolicyRegistry, TxValidator
from fabric_tpu.committer.device_validate import DeviceValidator
from fabric_tpu.ledger import KVLedger, LedgerConfig
from fabric_tpu.msp import CachedMSP
from fabric_tpu.msp.ca import DevOrg
from fabric_tpu.ops_plane import registry
from fabric_tpu.policy import parse_policy
from fabric_tpu.protocol import (Envelope, KVRead, KVWrite, NsRwSet,
                                 RangeQueryInfo, TxRwSet, ValidationCode,
                                 Version)
from fabric_tpu.protocol import build
from fabric_tpu.protocol.types import META_TXFLAGS


@pytest.fixture(scope="module", autouse=True)
def sw_provider():
    return init_factories(FactoryOpts(default="SW"))


@pytest.fixture(scope="module")
def orgs():
    return DevOrg("Org1"), DevOrg("Org2")


def rw(reads=(), writes=(), ranges=(), ns="cc"):
    return TxRwSet((NsRwSet(ns, reads=tuple(reads), writes=tuple(writes),
                            range_queries=tuple(ranges)),))


def make_stack(sw_provider, orgs, device, parallel=False):
    org1, org2 = orgs
    msps = {o.mspid: CachedMSP(o.msp()) for o in (org1, org2)}
    policies = PolicyRegistry()
    policies.set_policy("cc", parse_policy("AND('Org1.member', 'Org2.member')"))
    ledger = KVLedger("ch", LedgerConfig(device_validate=device,
                                         parallel_commit=parallel))
    dv = None
    if device:
        dv = DeviceValidator(ledger.statedb, "ch")
        ledger.set_prepared_source(dv.take_prepared)
    validator = TxValidator("ch", msps, sw_provider, policies,
                            device_validate=dv)
    return Committer(ledger, validator)


def run_blocks(sw_provider, orgs, env_blocks, device, parallel=False):
    """-> (per-block (final codes, metadata flag bytes), ledger)."""
    committer = make_stack(sw_provider, orgs, device, parallel)
    out = []
    for envs in env_blocks:
        lg = committer.ledger
        prev = (lg.blockstore.chain_info().current_hash
                if lg.height else b"\x00" * 32)
        block = build.new_block(lg.height, prev, envs)
        res = committer.store_block(block)
        out.append((res.final_flags.codes(),
                    bytes(block.metadata.items[META_TXFLAGS])))
    return out, committer.ledger


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


def _cval(name, **labels):
    try:
        return registry.counter(name).value(**labels)
    except Exception:
        return 0.0


def _snap():
    reasons = ("savepoint", "block_num", "window", "extract",
               "hash_collision", "range_query", "inexpressible",
               "policy_width", "policy_error", "version_range", "error")
    return {
        "dispatches": _cval("validator_device_dispatches_total",
                            channel="ch"),
        "blocks": _cval("validator_device_blocks_total", channel="ch"),
        "stash_misses": _cval("validator_device_stash_misses_total",
                              channel="ch"),
        "demotions": {r: _cval("validator_device_demotions_total",
                               channel="ch", reason=r) for r in reasons},
    }


def assert_identical(sw_provider, orgs, env_blocks, *,
                     device_blocks=None, demotions=None, parallel=False):
    """Run host + device stacks over shared envelopes; assert bit
    identity and (optionally) exact counter deltas.  Returns the
    per-block final codes for expectation checks."""
    before = _snap()
    host, host_lg = run_blocks(sw_provider, orgs, env_blocks, device=False,
                               parallel=parallel)
    mid = _snap()
    # the host stack must never touch the device counters
    assert mid == before
    dev, dev_lg = run_blocks(sw_provider, orgs, env_blocks, device=True)
    after = _snap()

    assert host == dev
    assert host_lg.commit_hash == dev_lg.commit_hash
    assert state_of(host_lg) == state_of(dev_lg)
    assert history_of(host_lg) == history_of(dev_lg)

    n_dispatch = after["dispatches"] - before["dispatches"]
    n_blocks = after["blocks"] - before["blocks"]
    # exactly-one-dispatch contract: every device-validated block is one
    # dispatch, demoted blocks are zero
    assert n_dispatch == n_blocks
    assert after["stash_misses"] == before["stash_misses"]
    if device_blocks is not None:
        assert n_blocks == device_blocks
    got_dem = {r: after["demotions"][r] - before["demotions"][r]
               for r in after["demotions"]}
    if demotions is not None:
        want = dict.fromkeys(got_dem, 0.0)
        want.update(demotions)
        assert got_dem == want
    return [codes for codes, _meta in host]


def make_tx(orgs, rwset, endorsers=None, creator=None):
    org1, org2 = orgs
    endorsers = endorsers or [org1.new_identity("e1"),
                              org2.new_identity("e2")]
    return build.endorser_tx("ch", "cc", "1.0", rwset,
                             creator or org1.new_identity("client"),
                             endorsers)


def seed_block(orgs, n=8):
    """Block 0: put k00..k{n-1} = b"v0"."""
    return [make_tx(orgs, rw(writes=[KVWrite(f"k{i:02d}", b"v0")]))
            for i in range(n)]


V = int(ValidationCode.VALID)
MVCC = int(ValidationCode.MVCC_READ_CONFLICT)
PHANTOM = int(ValidationCode.PHANTOM_READ_CONFLICT)
POLICY = int(ValidationCode.ENDORSEMENT_POLICY_FAILURE)
BADSIG = int(ValidationCode.BAD_CREATOR_SIGNATURE)
BADRW = int(ValidationCode.BAD_RWSET)


def test_ww_chain_same_key(sw_provider, orgs):
    """Five txs all read k00@(0,0) and write it: only the first wins;
    later readers observe the in-block writer."""
    envs1 = [make_tx(orgs, rw(reads=[KVRead("k00", Version(0, 0))],
                              writes=[KVWrite("k00", bytes([i]))]))
             for i in range(5)]
    codes = assert_identical(sw_provider, orgs, [seed_block(orgs), envs1],
                             device_blocks=2, demotions={})
    assert codes[1] == [V, MVCC, MVCC, MVCC, MVCC]


def test_delete_then_read(sw_provider, orgs):
    """Delete in one block, stale/None reads after; plus an in-block
    delete-then-read chain."""
    envs1 = [make_tx(orgs, rw(writes=[KVWrite("k01", b"", True)]))]
    envs2 = [
        # stale: k01 was deleted at (1, 0)
        make_tx(orgs, rw(reads=[KVRead("k01", Version(0, 1))])),
        # correct: key gone -> version None
        make_tx(orgs, rw(reads=[KVRead("k01", None)],
                         writes=[KVWrite("k01", b"back")])),
        # in-block: deletes k02 ...
        make_tx(orgs, rw(reads=[KVRead("k02", Version(0, 2))],
                         writes=[KVWrite("k02", b"", True)])),
        # ... so this committed-version read now conflicts
        make_tx(orgs, rw(reads=[KVRead("k02", Version(0, 2))])),
    ]
    codes = assert_identical(sw_provider, orgs,
                             [seed_block(orgs), envs1, envs2],
                             device_blocks=3, demotions={})
    assert codes[2] == [MVCC, V, V, MVCC]


def test_phantom_range_query_demotes(sw_provider, orgs):
    """Range queries are inexpressible on-device: the block demotes to
    the host path (reason range_query) and stays bit-identical —
    including a phantom conflict verdict."""
    seed = seed_block(orgs, 4)
    ok_set = tuple(KVRead(f"k{i:02d}", Version(0, i)) for i in range(3))
    bad_set = ok_set[:2]  # claims k02 absent -> phantom
    envs1 = [
        make_tx(orgs, rw(ranges=[RangeQueryInfo("k00", "k03", True,
                                                ok_set)])),
        make_tx(orgs, rw(ranges=[RangeQueryInfo("k00", "k03", True,
                                                bad_set)])),
    ]
    envs2 = [make_tx(orgs, rw(writes=[KVWrite("k09", b"x")]))]
    codes = assert_identical(
        sw_provider, orgs, [seed, envs1, envs2],
        device_blocks=2,  # seed + envs2; envs1 demotes
        demotions={"range_query": 1})
    assert codes[1] == [V, PHANTOM]


def test_engineered_hash_collision_demotes(sw_provider, orgs):
    """djb2-64("ab") == djb2-64("bA"): interning detects the collision
    byte-wise and demotes — never a wrong verdict, never an error."""
    envs0 = [make_tx(orgs, rw(writes=[KVWrite("ab", b"1")])),
             make_tx(orgs, rw(writes=[KVWrite("bA", b"2")]))]
    envs1 = [make_tx(orgs, rw(reads=[KVRead("ab", Version(0, 0)),
                                     KVRead("bA", Version(0, 1))],
                              writes=[KVWrite("k05", b"x")]))]
    codes = assert_identical(
        sw_provider, orgs, [envs0, envs1],
        device_blocks=0, demotions={"hash_collision": 2})
    assert codes == [[V, V], [V]]


def test_zero_and_full_conflict(sw_provider, orgs):
    envs_ok = [make_tx(orgs, rw(reads=[KVRead(f"k{i:02d}", Version(0, i))],
                                writes=[KVWrite(f"k{i:02d}", b"v1")]))
               for i in range(6)]
    envs_bad = [make_tx(orgs, rw(reads=[KVRead(f"k{i:02d}", Version(9, 9))]))
                for i in range(6)]
    codes = assert_identical(sw_provider, orgs,
                             [seed_block(orgs), envs_ok, envs_bad],
                             device_blocks=3, demotions={})
    assert codes[1] == [V] * 6
    assert codes[2] == [MVCC] * 6


def test_policy_and_signature_failures(sw_provider, orgs):
    """Gate failures fold on-device via per-entry truth tables; MVCC
    must skip the gate-invalid txs exactly like the oracle."""
    org1, _org2 = orgs
    good = make_tx(orgs, rw(reads=[KVRead("k00", Version(0, 0))],
                            writes=[KVWrite("k00", b"a")]))
    # AND(Org1, Org2) with only Org1 endorsing -> 10
    only1 = make_tx(orgs, rw(reads=[KVRead("k00", Version(0, 0))],
                             writes=[KVWrite("k00", b"b")]),
                    endorsers=[org1.new_identity("e")])
    # corrupted creator signature -> 4
    bad = make_tx(orgs, rw(writes=[KVWrite("k01", b"c")]))
    bad = Envelope(bad.payload, bad.signature[:-2] + b"\x00\x01")
    # would conflict with `good` — and does, because the gate-failed
    # writers in between never land
    chaser = make_tx(orgs, rw(reads=[KVRead("k00", Version(0, 0))],
                              writes=[KVWrite("k00", b"d")]))
    codes = assert_identical(
        sw_provider, orgs, [seed_block(orgs), [good, only1, bad, chaser]],
        device_blocks=2, demotions={})
    assert codes[1] == [V, POLICY, BADSIG, MVCC]


def test_garbage_endorser_payload(sw_provider, orgs):
    """An envelope whose data is not a Transaction dict: lane status BAD,
    oracle stamps BAD_RWSET during MVCC on the gate-valid tx."""
    org1, _ = orgs
    junk = build.signed_envelope("endorser_transaction", "ch",
                                 {"not": "a tx"}, org1.new_identity("j"))
    good = make_tx(orgs, rw(writes=[KVWrite("k07", b"g")]))
    codes = assert_identical(sw_provider, orgs, [[good, junk]],
                             demotions={})
    assert codes[0][0] == V
    assert codes[0][1] != V


def test_seeded_random_blocks(sw_provider, orgs):
    """Seeded random reads/writes/deletes with correct, stale, and None
    versions over a small keyspace; 3 blocks x 8 txs."""
    rng = random.Random(0xFAB11)
    keys = [f"k{i:02d}" for i in range(8)]
    env_blocks = [seed_block(orgs, 8)]
    # committed versions after block 0: k_i @ (0, i)
    committed = {k: Version(0, i) for i, k in enumerate(keys)}
    for blk in (1, 2, 3):
        envs = []
        for _tx in range(8):
            reads, writes = [], []
            for k in rng.sample(keys, rng.randint(0, 3)):
                choice = rng.random()
                if choice < 0.5:
                    ver = committed.get(k)  # may be None (deleted)
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
            envs.append(make_tx(orgs, rw(reads=reads, writes=writes)))
        env_blocks.append(envs)
        # `committed` stays the block-0 map on purpose: reads generated
        # from it mix correct, stale, and phantom versions as the real
        # state drifts — exactly the adversarial spread we want
    assert_identical(sw_provider, orgs, env_blocks, device_blocks=4,
                     demotions={})


def test_serial_parallel_device_three_way(sw_provider, orgs):
    """{serial oracle, wavefront parallel commit, fused device} all land
    the same bytes."""
    envs1 = [make_tx(orgs, rw(reads=[KVRead("k00", Version(0, 0))],
                              writes=[KVWrite("k00", b"a")])),
             make_tx(orgs, rw(reads=[KVRead("k00", Version(0, 0))])),
             make_tx(orgs, rw(reads=[KVRead("k03", Version(0, 3))],
                              writes=[KVWrite("k03", b"", True)])),
             make_tx(orgs, rw(reads=[KVRead("k03", Version(0, 3))]))]
    blocks = [seed_block(orgs), envs1]
    serial, serial_lg = run_blocks(sw_provider, orgs, blocks, device=False)
    wave, wave_lg = run_blocks(sw_provider, orgs, blocks, device=False,
                               parallel=True)
    dev, dev_lg = run_blocks(sw_provider, orgs, blocks, device=True)
    assert serial == wave == dev
    assert (serial_lg.commit_hash == wave_lg.commit_hash
            == dev_lg.commit_hash)
    assert state_of(serial_lg) == state_of(wave_lg) == state_of(dev_lg)
    assert history_of(serial_lg) == history_of(dev_lg)


def test_stash_miss_falls_back(sw_provider, orgs):
    """If block metadata flags change between validate and commit, the
    prepared batch must be discarded and host MVCC re-run."""
    committer = make_stack(sw_provider, orgs, device=True)
    envs = seed_block(orgs, 3)
    block = build.new_block(0, b"\x00" * 32, envs)
    before = _snap()
    res = committer.validator.validate(block)
    block.metadata.items[META_TXFLAGS] = bytes([255] * 3)  # tamper
    committer.ledger.commit(block)
    after = _snap()
    assert after["stash_misses"] - before["stash_misses"] == 1
    # host fallback ran with the tampered (all-invalid) flags
    assert committer.ledger.get_state("cc", "k00") is None
    assert res is not None


def test_programming_error_raises_instead_of_demoting(sw_provider, orgs,
                                                      monkeypatch):
    """A TypeError inside the device path (an API this installation does
    not have) is not a block shape: it must surface, not turn into a
    quiet reason="error" demotion that leaves the path dead."""
    from fabric_tpu.committer.device_validate import DeviceValidator

    def broken(self, *a, **kw):
        raise TypeError("shard_map() got an unexpected keyword argument")

    committer = make_stack(sw_provider, orgs, device=True)
    block = build.new_block(0, b"\x00" * 32, seed_block(orgs, 3))
    monkeypatch.setattr(DeviceValidator, "_dispatch", broken)
    before = _snap()
    with pytest.raises(TypeError):
        committer.validator.validate(block)
    assert _snap()["demotions"] == before["demotions"]

    # anything else a block can provoke still demotes to the host path
    def choke(self, *a, **kw):
        raise ValueError("hostile lane bytes")

    monkeypatch.setattr(DeviceValidator, "_dispatch", choke)
    res = committer.store_block(block)
    assert res.final_flags.valid_count() == 3
    after = _snap()
    assert after["demotions"]["error"] - before["demotions"]["error"] == 1
