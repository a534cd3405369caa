"""Verify-once plane: signed verdict cache + speculative verification.

Safety gates (the ISSUE's hard requirements):
  - a poisoned or stale cache entry can NEVER turn into a skipped
    verification (MAC tamper / sig substitution / revoked identity /
    eviction all force full re-verification);
  - cache-on and cache-off validation produce bit-identical TxFlags
    over adversarial corpora, on every collect path (deep C tail,
    classic C walker, pure Python);
  - verify-count telemetry shows at most ONE device verification per
    unique (identity, signature) pair per node.
"""
import random

import numpy as np
import pytest

from fabric_tpu.bccsp.factory import init_factories, FactoryOpts
from fabric_tpu.committer import Committer, PolicyRegistry, TxValidator
from fabric_tpu.committer.txvalidator import PROBE, probe_positions
from fabric_tpu.ledger import KVLedger, LedgerConfig
from fabric_tpu.msp import CachedMSP
from fabric_tpu.msp.ca import DevOrg
from fabric_tpu.policy import parse_policy
from fabric_tpu.protocol import (Envelope, KVRead, KVWrite, NsRwSet,
                                 ValidationCode, TxRwSet, Version, build)
from fabric_tpu.protocol.types import Block, BlockHeader, BlockMetadata
from fabric_tpu.verify_plane import (CachingProvider, SpeculativeVerifier,
                                     VerdictCache, derive_items, item_digest)
from fabric_tpu.verify_plane.cache import _m


@pytest.fixture(scope="module", autouse=True)
def sw_provider():
    return init_factories(FactoryOpts(default="SW"))


@pytest.fixture()
def orgs():
    return DevOrg("Org1"), DevOrg("Org2")


def _msps(*orgs):
    return {o.mspid: CachedMSP(o.msp()) for o in orgs}


def rw(reads=(), writes=(), ns="cc"):
    return TxRwSet((NsRwSet(ns, reads=tuple(reads), writes=tuple(writes)),))


def make_tx(org1, org2, rwset=None, endorsers=None, creator=None,
            nonce=None):
    endorsers = endorsers or [org1.new_identity("e1"),
                              org2.new_identity("e2")]
    return build.endorser_tx(
        "ch", "cc", "1.0", rwset or rw(writes=[KVWrite("k", b"v")]),
        creator or org1.new_identity("client"), endorsers, nonce=nonce)


def make_block(envs, number=0):
    data = [e if isinstance(e, (bytes, bytearray)) else e.serialize()
            for e in envs]
    return Block(BlockHeader(number, b"p", b"d"), data, BlockMetadata())


def creator_item(env, msps):
    creators, _ = derive_items(env.serialize(), "ch", msps)
    assert len(creators) == 1
    return creators[0]


def counts():
    m = _m()
    return {"hits": m["hits"].total(), "misses": m["misses"].total(),
            "rejects": m["rejects"].total(),
            "mac": m["rejects"].value(reason="mac"),
            "stale": m["rejects"].value(reason="stale"),
            "evictions": m["evictions"].total(),
            "bypassed": m["bypassed"].total(),
            "device": m["device"].total(), "dupes": m["dupes"].total(),
            "attested": m["attested"].total()}


def delta(before, after):
    return {k: after[k] - before[k] for k in before}


class CountingProvider:
    """Delegating provider that records every device dispatch."""

    def __init__(self, inner):
        self.inner = inner
        self.batches = []
        self.name = inner.name

    def batch_verify(self, items):
        items = list(items)
        self.batches.append(items)
        return self.inner.batch_verify(items)

    def batch_verify_async(self, items):
        items = list(items)
        self.batches.append(items)
        resolve = self.inner.batch_verify_async(items)
        return resolve

    def batch_verify_packed_async(self, batch):
        # a wrapper that forwards what it does not know owns the packed
        # verb, or a signature table would go round its count
        return self.batch_verify_async(batch)

    def __getattr__(self, name):
        return getattr(self.inner, name)

    @property
    def dispatched(self):
        return sum(len(b) for b in self.batches)


# -- cache semantics ---------------------------------------------------------


def test_cache_roundtrip_and_sign_of_verdict(orgs, sw_provider):
    org1, org2 = orgs
    msps = _msps(org1, org2)
    cache = VerdictCache(capacity=16)
    it = creator_item(make_tx(org1, org2), msps)
    assert cache.get(it) is None                    # cold miss
    cache.put(it, True)
    assert cache.get(it) is True
    cache.put(it, False)                            # overwrite
    assert cache.get(it) is False
    assert len(cache) == 1


def test_mac_tamper_never_silently_accepted(orgs, sw_provider):
    """THE hard gate: flipping a cached verdict bit (the stored MAC no
    longer matches) must read as a miss — the poisoned verdict can
    never be served — and the entry is dropped so the next fill
    re-verifies on the device."""
    org1, org2 = orgs
    msps = _msps(org1, org2)
    cache = VerdictCache(capacity=16)

    # a tx whose creator signature is BROKEN: honest verdict is False
    env = make_tx(org1, org2)
    env = Envelope(env.payload, env.signature[:-2] + b"\x00\x01")
    it = creator_item(env, msps)
    cache.put(it, False)

    # attacker flips the verdict bit in place; without the per-node
    # secret they cannot recompute the MAC
    d = item_digest(it)
    mac, verdict, scope, epoch, trace = cache._data[d]
    cache._data[d] = (mac, True, scope, epoch, trace)

    before = counts()
    assert cache.get(it) is None                    # NOT True — rejected
    assert d not in cache._data                     # hard-dropped
    moved = delta(before, counts())
    assert moved["mac"] == 1 and moved["hits"] == 0

    # end to end: the commit gate re-verifies and still flags the tx
    validator = TxValidator("ch", msps, sw_provider,
                            _policies(), verify_cache=cache)
    res = validator.validate(make_block([env]))
    assert res.flags.codes() == [int(ValidationCode.BAD_CREATOR_SIGNATURE)]


def test_entry_from_another_node_rejected(orgs, sw_provider):
    """Entries MAC'd under a different node's secret (a copied/injected
    cache state) fail verification here."""
    org1, org2 = orgs
    msps = _msps(org1, org2)
    theirs, ours = VerdictCache(capacity=4), VerdictCache(capacity=4)
    it = creator_item(make_tx(org1, org2), msps)
    theirs.put(it, True)
    d = item_digest(it)
    ours._data[d] = theirs._data[d]
    assert ours.get(it) is None
    assert d not in ours._data


def test_sig_substitution_changes_cache_key(orgs, sw_provider):
    """A signature swapped after a verdict was cached produces a
    different cache key: the stale verdict is unreachable, the new
    signature gets its own device verification."""
    org1, org2 = orgs
    msps = _msps(org1, org2)
    cache = VerdictCache(capacity=16)
    env = make_tx(org1, org2)
    cache.put(creator_item(env, msps), True)

    swapped = Envelope(env.payload, env.signature[:-2] + b"\x00\x01")
    it2 = creator_item(swapped, msps)
    assert cache.get(it2) is None

    inner = CountingProvider(init_factories(FactoryOpts(default="SW")))
    validator = TxValidator("ch", msps, inner, _policies(),
                            verify_cache=cache)
    res = validator.validate(make_block([swapped]))
    assert res.flags.codes() == [int(ValidationCode.BAD_CREATOR_SIGNATURE)]
    assert inner.dispatched > 0                     # really re-verified


def test_epoch_bump_invalidates_cached_verdicts(orgs, sw_provider):
    org1, org2 = orgs
    msps = _msps(org1, org2)
    cache = VerdictCache(capacity=16)
    it = creator_item(make_tx(org1, org2), msps)
    cache.put(it, True)
    cache.set_epoch(1)                   # config update: CRL / CA rotation
    before = counts()
    assert cache.get(it) is None
    assert delta(before, counts())["stale"] == 1
    assert len(cache) == 0
    cache.put(it, True)                  # re-verified under the new epoch
    assert cache.get(it) is True


def test_epoch_is_scoped_per_channel(orgs, sw_provider):
    """One node-wide cache, many channels: a config bump on one channel
    must stale only ITS entries — the other channels' verdicts stay
    live (no epoch flapping), and two channels sitting at the SAME
    sequence number never alias (bumping one cannot be masked by the
    other's equal sequence)."""
    org1, org2 = orgs
    msps = _msps(org1, org2)
    cache = VerdictCache(capacity=16)
    it_a = creator_item(make_tx(org1, org2), msps)
    it_b = creator_item(make_tx(org1, org2), msps)
    cache.set_epoch(3, scope="chA")
    cache.set_epoch(3, scope="chB")      # same sequence number: no alias
    cache.put(it_a, True, scope="chA")
    cache.put(it_b, True, scope="chB")

    # chA's config rotates; chB keeps validating between chA's blocks
    cache.set_epoch(4, scope="chA")
    before = counts()
    assert cache.get(it_a) is None       # chA entry stale
    assert cache.get(it_b) is True       # chB entry untouched
    moved = delta(before, counts())
    assert moved["stale"] == 1 and moved["hits"] == 1

    # re-pinning chB to its own (unchanged) sequence must not
    # invalidate anything — the old global-epoch flap
    cache.set_epoch(3, scope="chB")
    assert cache.get(it_b) is True


def test_lru_bound_and_eviction_counter(orgs, sw_provider):
    org1, org2 = orgs
    msps = _msps(org1, org2)
    cache = VerdictCache(capacity=4)
    items = [creator_item(make_tx(org1, org2), msps) for _ in range(7)]
    before = counts()
    for it in items:
        cache.put(it, True)
    assert len(cache) == 4
    assert delta(before, counts())["evictions"] == 3
    assert cache.get(items[0]) is None              # evicted: plain miss
    assert cache.get(items[-1]) is True


def test_peek_skips_counters_and_lru(orgs, sw_provider):
    org1, org2 = orgs
    msps = _msps(org1, org2)
    cache = VerdictCache(capacity=16)
    it = creator_item(make_tx(org1, org2), msps)
    other = creator_item(make_tx(org1, org2), msps)
    cache.put(it, True)
    # the counters are process-global and nodes of earlier modules may
    # still be winding down (an orderer authorizing a last deliver seek):
    # keep nothing but the two peeks between the snapshots
    before = counts()
    assert cache.peek(it) is True
    assert cache.peek(other) is None
    assert delta(before, counts()) == {k: 0 for k in before}


# -- caching provider --------------------------------------------------------


def test_caching_provider_dispatches_each_item_once(orgs, sw_provider):
    org1, org2 = orgs
    msps = _msps(org1, org2)
    envs = [make_tx(org1, org2) for _ in range(4)]
    items = [creator_item(e, msps) for e in envs]
    inner = CountingProvider(init_factories(FactoryOpts(default="SW")))
    p = CachingProvider(inner, VerdictCache(capacity=16), site="orderer")

    out1 = p.batch_verify(items)
    assert out1.all() and inner.dispatched == 4
    out2 = p.batch_verify(items)                    # all cached
    np.testing.assert_array_equal(out1, out2)
    assert inner.dispatched == 4                    # no new device work
    # partial overlap: only the new item hits the device
    extra = creator_item(make_tx(org1, org2), msps)
    out3 = p.batch_verify(items[:2] + [extra])
    assert out3.all() and inner.dispatched == 5


def test_caching_provider_async_all_hit_path(orgs, sw_provider):
    org1, org2 = orgs
    msps = _msps(org1, org2)
    items = [creator_item(make_tx(org1, org2), msps) for _ in range(3)]
    inner = CountingProvider(init_factories(FactoryOpts(default="SW")))
    p = CachingProvider(inner, VerdictCache(capacity=16), site="commit")
    assert p.batch_verify_async(items)().all()
    resolve = p.batch_verify_async(items)
    assert inner.dispatched == 3
    assert resolve().all()


# -- the one primitive: partition / settle ------------------------------------


def _reference_filter_store(cache, items, verify, site, scope, trace_id=""):
    """The partition as its callers wrote it out by hand before it had one
    home (`filter` + `store`): the plain reference, on the cache's public
    `lookup` and `put` only."""
    out = [None] * len(items)
    missed = []
    for i, it in enumerate(items):
        v, _ = cache.lookup(it)
        if v is None:
            missed.append(i)
        else:
            out[i] = v
    if missed:
        dupes = 0
        for i, v in zip(missed, verify([items[i] for i in missed])):
            dupes += cache.put(items[i], bool(v), trace_id=trace_id,
                               scope=scope)
            out[i] = bool(v)
        _m()["device"].add(len(missed), site=site)
        if dupes:
            _m()["dupes"].add(dupes, site=site)
    return out, len(missed)


def _broken(env):
    return Envelope(env.payload, env.signature[:-2] + b"\x00\x01")


def _prepare(case, cache, items, truth):
    """Bring `cache` into the state `case` names; returns how many of
    `items` must be dispatched."""
    n = len(items)
    cached = {"all_miss": 0, "all_hit": n}.get(case, n - 2)
    for it, v in zip(items[:cached], truth[:cached]):
        cache.put(it, v, scope="ch", trace_id="spec-1")
    if case == "mac_tampered":
        # items[1] is the broken signature: flip its cached False to True
        d = item_digest(items[1])
        mac, verdict, scope, epoch, trace = cache._data[d]
        assert verdict is False
        cache._data[d] = (mac, True, scope, epoch, trace)
        return n - cached + 1
    if case == "stale_epoch":
        cache.put(items[0], truth[0], scope="other")
        cache.set_epoch(3, scope="other")
        return n - cached + 1
    return n - cached


@pytest.mark.parametrize("case", ["all_hit", "all_miss", "mixed",
                                  "mac_tampered", "stale_epoch"])
def test_partition_settle_equals_filter_then_store(orgs, sw_provider, case):
    """One pair of calls, five cache states: the answer is aligned with
    the input, and verdicts, counters and the cache's contents are what
    the hand-written filter + store gave."""
    org1, org2 = orgs
    msps = _msps(org1, org2)
    envs = [make_tx(org1, org2) for _ in range(6)]
    envs[1] = _broken(envs[1])
    items = [creator_item(e, msps) for e in envs]
    truth = [bool(v) for v in sw_provider.batch_verify(items)]
    assert truth == [True, False, True, True, True, True]

    secret = b"k" * 32
    ref, new = (VerdictCache(capacity=64, secret=secret) for _ in range(2))
    want_missed = _prepare(case, ref, items, truth)
    assert _prepare(case, new, items, truth) == want_missed

    before = counts()
    ref_out, ref_missed = _reference_filter_store(
        ref, items, sw_provider.batch_verify, "commit", "ch", "t-9")
    ref_moved = delta(before, counts())
    assert ref_missed == want_missed and ref_out == truth

    before = counts()
    part = new.partition(items)
    assert (part.n_hits, part.n_misses) == (len(items) - want_missed,
                                            want_missed)
    missed = set(part.misses)
    assert part.misses == [it for it in items if it in missed]  # in order
    assert part.links == ({"spec-1"} if part.n_hits else set())
    out = part.settle(
        sw_provider.batch_verify(part.misses) if part.misses else None,
        site="commit", scope="ch", trace_id="t-9")
    moved = delta(before, counts())

    assert out.dtype == bool and out.tolist() == truth
    assert moved == ref_moved
    assert moved["mac"] == (case == "mac_tampered")
    assert moved["stale"] == (case == "stale_epoch")
    assert moved["device"] == want_missed and moved["dupes"] == 0
    assert dict(new._data) == dict(ref._data)            # MACs included
    assert list(new._data) == list(ref._data)            # and LRU order
    for it, v in zip(items, truth):                      # re-stored too
        assert new.peek(it) is v


def test_settle_refuses_a_misaligned_answer(orgs, sw_provider):
    org1, org2 = orgs
    msps = _msps(org1, org2)
    items = [creator_item(make_tx(org1, org2), msps) for _ in range(3)]
    cache = VerdictCache(capacity=8)
    part = cache.partition(items)
    with pytest.raises(ValueError):
        part.settle([True, True], site="commit")
    assert len(cache) == 0                               # nothing stored


def _block_items(envs, msps):
    items = {}
    for e in envs:
        creators, endorsements = derive_items(e.serialize(), "ch", msps)
        items.update(dict.fromkeys(creators + endorsements))
    return list(items)


@pytest.mark.parametrize("caller", ["provider_sync", "provider_async",
                                    "speculative", "validator_classic",
                                    "validator_deep"])
def test_five_callers_one_partition(orgs, sw_provider, caller):
    """Every site that consults the cache goes through the same pair of
    calls: same verdicts in the cache, one device verification per
    unique item, booked under the caller's own site and scope; a second
    pass dispatches nothing."""
    org1, org2 = orgs
    msps = _msps(org1, org2)
    shared = [org1.new_identity("e1"), org2.new_identity("e2")]
    envs = [make_tx(org1, org2, endorsers=shared) for _ in range(5)]
    envs[2] = _broken(envs[2])
    items = _block_items(envs, msps)
    truth = [bool(v) for v in sw_provider.batch_verify(items)]
    assert truth.count(False) == 1 and len(items) == 5 * 3

    inner = CountingProvider(sw_provider)
    cache = VerdictCache(capacity=256)
    site, scope = "commit", "ch"
    if caller.startswith("provider"):
        site, scope = "orderer", "sys"
        p = CachingProvider(inner, cache, site=site, scope=scope)
        if caller == "provider_sync":
            run = lambda: p.batch_verify(items)
        else:
            run = lambda: p.batch_verify_async(items)()
    elif caller == "speculative":
        site = "speculative"
        spec = SpeculativeVerifier(cache, lambda: inner, lambda cid: msps)
        run = lambda: spec._verify_batch(items, stage="overlap", scope="ch")
    else:
        codes = [int(ValidationCode.VALID)] * 5
        codes[2] = int(ValidationCode.BAD_CREATOR_SIGNATURE)

        def run():
            # a validator per pass: the second is a peer's replay, not a
            # block of duplicate txids
            v = TxValidator("ch", msps, inner, _policies(),
                            verify_cache=cache)
            if caller == "validator_classic":
                v.sbe_lookup = lambda ns, key: None      # keeps the classic tail
            state = v.validate_begin(make_block(envs))
            assert bool(state.get("deep")) == (caller == "validator_deep")
            assert v.validate_finish(state).flags.codes() == codes

    before = counts()
    site_before = _m()["device"].value(site=site)
    first = run()
    assert inner.dispatched == len(items)
    assert sorted(inner.batches[0]) == sorted(items)     # one dispatch
    second = run()
    assert inner.dispatched == len(items) and len(inner.batches) == 1
    moved = delta(before, counts())
    assert moved["device"] == len(items) and moved["dupes"] == 0
    assert _m()["device"].value(site=site) - site_before == len(items)
    assert moved["misses"] == len(items) and moved["hits"] == len(items)
    for it, want in zip(items, truth):
        assert cache.peek(it) is want
        assert cache._data[item_digest(it)][2] == scope
    if caller.startswith("provider"):
        assert first.tolist() == truth == second.tolist()


# -- the validator's probe: a block the cache is silent on goes round it -------

TAILS = ["validator_classic", "validator_deep"]


def _sized_envs(org1, org2, n_items):
    """Envelopes of one block with exactly `n_items` unique verify items:
    every tx its own creator and its own endorsers, three items a tx, two
    for a tx that Org1 alone endorsed."""
    full, rest = divmod(n_items, 3)
    if rest == 1:
        full, rest = full - 1, 4
    solo = rest // 2
    envs = [make_tx(org1, org2, creator=org1.new_identity(f"c{i}"))
            for i in range(full)]
    envs += [make_tx(org1, org2, creator=org1.new_identity(f"s{i}"),
                     endorsers=[org1.new_identity("e")])
             for i in range(solo)]
    return envs


def _broken_endorsement(env, signer):
    """`env`'s transaction with its second endorsement's signature
    altered, signed anew by `signer`: only the endorsement is unsound."""
    tx = env.payload_dict()["data"]
    e2 = tx["actions"][0]["endorsements"][1]
    e2["signature"] = e2["signature"][:-2] + b"\x00\x01"
    return build.signed_envelope("endorser_transaction", "ch", tx, signer)


def _validate(tail, msps, provider, cache, envs):
    """One block through a fresh validator on the named tail: (the
    block's unique items in dispatch order, its flags)."""
    v = TxValidator("ch", msps, provider, _policies(), verify_cache=cache)
    if tail == "validator_classic":
        v.sbe_lookup = lambda ns, key: None          # keeps the classic tail
    state = v.validate_begin(make_block(envs))
    assert bool(state.get("deep")) == (tail == "validator_deep")
    order = list(state["items"])
    return order, v.validate_finish(state).flags.codes()


def _record_spans(monkeypatch):
    from fabric_tpu.ops_plane import tracing
    spans = []
    monkeypatch.setattr(
        tracing.tracer, "record_span",
        lambda name, start, end, attributes=None, parent=None,
        context=None:
        spans.append((name, attributes or {})))
    return spans


def _commit_site():
    return (_m()["device"].value(site="commit"),
            _m()["bypassed"].value(site="commit"))


def _off_probe_tx(order, envs, msps, width=3):
    """Index of a full tx none of whose items sits at a probe position."""
    probed = set(probe_positions(len(order)))
    for t, env in enumerate(envs):
        at = order.index(creator_item(env, msps))
        if not probed & set(range(at, at + width)):
            return t
    raise AssertionError("every tx is probed")


def test_probe_positions_are_spread_and_unaliased():
    for n in (PROBE + 1, PROBE + 2, 300, 1023, 39_999, 40_000):
        pos = probe_positions(n)
        assert len(pos) == PROBE == len(set(pos)) and pos == sorted(pos)
        assert pos[0] == 0 and pos[-1] == n - 1
        assert pos == probe_positions(n)                 # of n only
        runs = [pos[i:i + 4] for i in range(0, PROBE, 4)]
        assert all(r == list(range(r[0], r[0] + 4)) for r in runs)
        starts = [r[0] for r in runs]
        gaps = {b - a for a, b in zip(starts, starts[1:])}
        assert max(gaps) - min(gaps) <= 1                # evenly spaced
    # the runs do not start on one phase of a four-item transaction
    assert len({p % 4 for p in probe_positions(40_000)[::4]}) == 4


@pytest.mark.parametrize("tail", TAILS)
def test_probe_empty_cache_bypasses_a_big_block(orgs, sw_provider, tail,
                                                monkeypatch):
    """(a) Nothing cached: one dispatch of all n items, PROBE lookups,
    nothing stored, the device's work still booked under `commit`."""
    org1, org2 = orgs
    msps = _msps(org1, org2)
    envs = _sized_envs(org1, org2, 300)
    envs[7] = _broken(envs[7])
    order, off = _validate(tail, msps, sw_provider, None, envs)
    n = len(order)
    assert n == 300 > PROBE
    assert off.count(int(ValidationCode.BAD_CREATOR_SIGNATURE)) == 1

    inner = CountingProvider(sw_provider)
    cache = VerdictCache(capacity=4096)
    spans = _record_spans(monkeypatch)
    before, site_before = counts(), _commit_site()
    got, on = _validate(tail, msps, inner, cache, envs)
    moved = delta(before, counts())
    device, bypassed = (a - b for a, b in zip(_commit_site(), site_before))

    assert on == off and got == order
    assert inner.batches == [order]                      # one dispatch
    assert (moved["hits"], moved["misses"], moved["bypassed"]) == (
        0, PROBE, n - PROBE)
    assert (device, bypassed) == (n, n - PROBE)
    assert moved["device"] == n and moved["dupes"] == 0
    assert moved["rejects"] == 0 and moved["evictions"] == 0
    assert len(cache) == 0                               # nothing stored
    names = [name for name, _ in spans]
    assert "validator.cache_store" not in names
    assert names.count("validator.cache_filter") == 1
    (collect,) = [a for name, a in spans if name == "validator.collect"]
    assert (collect["unique_items"], collect["cache_hits"],
            collect["cache_misses"], collect["cache_bypassed"]) == (
        n, 0, PROBE, n - PROBE)
    assert cache.coverage.frac() == 0.0 and len(cache.coverage._blocks) == 1


@pytest.mark.parametrize("tail", TAILS)
def test_probe_hit_keeps_a_big_block_on_the_cache_path(orgs, sw_provider,
                                                       tail, monkeypatch):
    """(b) One cached item at a probe position (and a few elsewhere):
    verdicts, counters, contents and LRU order are the hand-written
    lookup + put reference's, and no item is looked up twice."""
    org1, org2 = orgs
    msps = _msps(org1, org2)
    envs = _sized_envs(org1, org2, 300)
    envs[11] = _broken(envs[11])
    order, off = _validate(tail, msps, sw_provider, None, envs)
    n = len(order)
    truth = [bool(v) for v in sw_provider.batch_verify(order)]
    pos = probe_positions(n)
    probed = set(pos)
    off_probe = [i for i in range(n) if i not in probed]
    # one probed item alone decides; the rest scrambles the LRU order
    for cached in ([pos[41]],
                   [pos[200], off_probe[30], pos[3], off_probe[2], pos[90]]):
        secret = b"k" * 32
        ref, new = (VerdictCache(capacity=4096, secret=secret)
                    for _ in range(2))
        for c in (ref, new):
            for i in cached:
                c.put(order[i], truth[i], scope="ch", trace_id="spec-1")

        before = counts()
        ref_out, ref_missed = _reference_filter_store(
            ref, order, sw_provider.batch_verify, "commit", "ch")
        ref_moved = delta(before, counts())
        assert ref_out == truth and ref_missed == n - len(cached)

        asked = []
        lookup = new.lookup
        new.lookup = lambda it: (asked.append(it), lookup(it))[1]
        inner = CountingProvider(sw_provider)
        spans = _record_spans(monkeypatch)
        before = counts()
        got, on = _validate(tail, msps, inner, new, envs)
        moved = delta(before, counts())

        assert on == off and got == order
        assert moved == ref_moved and moved["bypassed"] == 0
        assert moved["hits"] == len(cached) and moved["dupes"] == 0
        assert sorted(asked) == sorted(order)            # each item once
        assert inner.batches == [[it for i, it in enumerate(order)
                                  if i not in cached]]
        assert dict(new._data) == dict(ref._data)        # MACs included
        assert list(new._data) == list(ref._data)        # and LRU order
        (collect,) = [a for name, a in spans if name == "validator.collect"]
        assert "cache_bypassed" not in collect
        assert collect["cache_hits"] == len(cached)
        assert collect["links"] == ["spec-1"]
        assert [a["items"] for name, a in spans
                if name == "validator.cache_store"] == [n - len(cached)]


@pytest.mark.parametrize("tail", TAILS)
def test_probe_miss_never_trusts_what_it_did_not_ask(orgs, sw_provider,
                                                     tail):
    """(c) Cached items only off the probe positions, the two unsound
    signatures' among them: the block is bypassed, the device verifies
    every item itself, and the flags are the cache-off validator's."""
    org1, org2 = orgs
    msps = _msps(org1, org2)
    envs = _sized_envs(org1, org2, 900)      # room between the probe's runs
    order, _ = _validate(tail, msps, sw_provider, None, envs)
    t = _off_probe_tx(order, envs, msps)
    envs[t] = _broken(envs[t])
    u = _off_probe_tx(order, envs[t + 1:], msps) + t + 1
    envs[u] = _broken_endorsement(envs[u], org1.new_identity("fresh"))
    order, off = _validate(tail, msps, sw_provider, None, envs)
    n = len(order)
    assert off[t] == int(ValidationCode.BAD_CREATOR_SIGNATURE)
    assert off[u] == int(ValidationCode.ENDORSEMENT_POLICY_FAILURE)
    truth = [bool(v) for v in sw_provider.batch_verify(order)]
    assert truth.count(False) == 2

    probed = set(probe_positions(n))
    cached = [i for i in range(n)
              if i not in probed and (i % 5 == 0 or not truth[i])]
    cache = VerdictCache(capacity=4096)
    for i in cached:
        # were the cache asked, these would read as sound
        cache.put(order[i], True, scope="ch")
    held = list(cache._data.items())

    inner = CountingProvider(sw_provider)
    before = counts()
    got, on = _validate(tail, msps, inner, cache, envs)
    moved = delta(before, counts())
    assert on == off and got == order
    assert inner.batches == [order]                      # the unsound two too
    assert (moved["hits"], moved["misses"], moved["bypassed"]) == (
        0, PROBE, n - PROBE)
    assert moved["device"] == n and moved["dupes"] == 0
    assert list(cache._data.items()) == held             # neither read nor fed


@pytest.mark.parametrize("tail", TAILS)
def test_probe_rejects_are_misses_not_hits(orgs, sw_provider, tail):
    """(d) A MAC-tampered and a stale-epoch entry at probe positions are
    dropped and counted as rejects + misses: neither keeps the block on
    the cache path, neither skips a verification."""
    org1, org2 = orgs
    msps = _msps(org1, org2)
    envs = _sized_envs(org1, org2, 300)
    order, _ = _validate(tail, msps, sw_provider, None, envs)
    pos = probe_positions(len(order))
    # the tx whose creator signature sits at a probe position, broken
    t = next(t for t, e in enumerate(envs)
             if order.index(creator_item(e, msps)) in pos[40:])
    envs[t] = _broken(envs[t])
    order, off = _validate(tail, msps, sw_provider, None, envs)
    n = len(order)
    bad = order.index(creator_item(envs[t], msps))
    assert bad in pos and off[t] == int(ValidationCode.BAD_CREATOR_SIGNATURE)
    stale = next(p for p in pos if p != bad)

    cache = VerdictCache(capacity=4096)
    cache.put(order[bad], False, scope="ch")
    d = item_digest(order[bad])
    mac, verdict, scope, epoch, trace = cache._data[d]
    cache._data[d] = (mac, True, scope, epoch, trace)    # flipped, MAC kept
    cache.put(order[stale], True, scope="other")
    cache.set_epoch(3, scope="other")

    inner = CountingProvider(sw_provider)
    before = counts()
    got, on = _validate(tail, msps, inner, cache, envs)
    moved = delta(before, counts())
    assert on == off and got == order
    assert inner.batches == [order]
    assert (moved["mac"], moved["stale"], moved["rejects"]) == (1, 1, 2)
    assert (moved["hits"], moved["misses"], moved["bypassed"]) == (
        0, PROBE, n - PROBE)
    assert moved["device"] == n and len(cache) == 0      # both dropped


@pytest.mark.parametrize("tail", TAILS)
def test_probe_leaves_a_block_of_probe_items_alone(orgs, sw_provider, tail,
                                                   monkeypatch):
    """(e) Exactly PROBE items: the unprobed path — every item looked up
    and stored; one item more and the empty cache is gone round."""
    org1, org2 = orgs
    msps = _msps(org1, org2)
    probes = []
    probed = VerdictCache.partition_probed
    monkeypatch.setattr(
        VerdictCache, "partition_probed",
        lambda self, items, positions, *, site:
        (probes.append(len(items)),
         probed(self, items, positions, site=site))[1])
    for n, bypassed in ((PROBE, 0), (PROBE + 1, 1)):
        envs = _sized_envs(org1, org2, n)
        cache = VerdictCache(capacity=4096)
        inner = CountingProvider(sw_provider)
        spans = _record_spans(monkeypatch)
        before = counts()
        order, on = _validate(tail, msps, inner, cache, envs)
        moved = delta(before, counts())
        assert len(order) == n and inner.batches == [order]
        assert on.count(int(ValidationCode.VALID)) >= n // 3 - 1
        assert probes == [PROBE + 1] * bypassed
        assert (moved["hits"], moved["misses"], moved["bypassed"]) == (
            0, PROBE, bypassed)
        assert moved["device"] == n
        assert len(cache) == (0 if bypassed else PROBE)
        stored = [a["items"] for name, a in spans
                  if name == "validator.cache_store"]
        assert stored == ([] if bypassed else [PROBE])


# -- the deep tail's hand-over: arrays where the block is dispatched unasked ----

def test_an_answered_probe_hands_the_block_over_as_items(orgs, sw_provider,
                                                         monkeypatch,
                                                         handed_over):
    """A deep-tail block above PROBE whose probe the cache answers — one
    probed item known, then the whole block on its replay — builds its
    items and goes the items' way: flags the cache-less validator's, the
    cache fed, `form="items", reason="cache_answered"` moved by every
    unique item of the block and `arrays` by none."""
    org1, org2 = orgs
    msps = _msps(org1, org2)
    envs = _sized_envs(org1, org2, 300)
    envs[5] = _broken(envs[5])
    before = handed_over()
    order, off = _validate("validator_deep", msps, sw_provider, None, envs)
    n = len(order)
    assert handed_over(before) == {("arrays", "bypassed"): n}   # no cache
    truth = [bool(v) for v in sw_provider.batch_verify(order)]

    cache = VerdictCache(capacity=4096)
    at = probe_positions(n)[17]
    cache.put(order[at], truth[at], scope="ch")
    inner = CountingProvider(sw_provider)
    spans = _record_spans(monkeypatch)
    for replay, dispatched in enumerate(([it for i, it in enumerate(order)
                                          if i != at], None)):
        before = handed_over()
        got, on = _validate("validator_deep", msps, inner, cache, envs)
        assert on == off and got == order
        assert handed_over(before) == {("items", "cache_answered"): n}
        if dispatched is not None:
            assert inner.batches == [dispatched]         # as items, once
            assert all(type(it) is type(order[0]) for it in inner.batches[0])
        else:
            assert len(inner.batches) == 1               # all answered
    collects = [a for name, a in spans if name == "validator.collect"]
    assert [(c["handoff_arrays"], c["handoff_items"]) for c in collects] == [
        (0, n), (0, n)]
    assert len(cache) == n


def test_a_small_deep_block_and_a_classic_block_go_as_items(orgs,
                                                            sw_provider,
                                                            handed_over):
    org1, org2 = orgs
    msps = _msps(org1, org2)
    envs = _sized_envs(org1, org2, PROBE)
    before = handed_over()
    _validate("validator_deep", msps, sw_provider, VerdictCache(), envs)
    assert handed_over(before) == {("items", "small_block"): PROBE}
    before = handed_over()
    _validate("validator_classic", msps, sw_provider, None,
              _sized_envs(org1, org2, 300))
    assert handed_over(before) == {("items", "classic_tail"): 300}


def test_a_provider_without_the_packed_verb_gets_items(orgs, sw_provider,
                                                       handed_over):
    """`no_verb`: a provider that knows items only — it has no packed
    verb, or its item verb was replaced on the instance, as the
    benchmark's yes-verifier control does — is handed the items, through
    the verb it has."""
    org1, org2 = orgs
    msps = _msps(org1, org2)
    envs = _sized_envs(org1, org2, 300)
    envs[9] = _broken(envs[9])
    order, off = _validate("validator_deep", msps, sw_provider, None, envs)

    class ItemsOnly:
        name = "items-only"

        def __init__(self):
            self.batches = []

        def batch_verify_async(self, items):
            self.batches.append(items)
            return sw_provider.batch_verify_async(items)

    bare = ItemsOnly()
    before = handed_over()
    got, on = _validate("validator_deep", msps, bare, None, envs)
    assert on == off and bare.batches == [order]
    assert handed_over(before) == {("items", "no_verb"): 300}

    from fabric_tpu.bccsp.sw import SoftwareProvider
    yes = SoftwareProvider()
    real = yes.batch_verify_async
    yes.batch_verify_async = lambda items: (
        lambda resolve=real(items): np.ones_like(resolve()))
    before = handed_over()
    _, flags = _validate("validator_deep", msps, yes, None, envs)
    assert flags.count(int(ValidationCode.VALID)) == len(envs)   # all "sound"
    assert handed_over(before) == {("items", "no_verb"): 300}


def test_a_bypassed_deep_block_builds_no_item_a_signature(orgs, sw_provider,
                                                          monkeypatch,
                                                          handed_over):
    """The mechanical guard.  On a silent cache the deep tail of a block
    far above PROBE constructs the probe's PROBE VerifyItems and no
    other — `VerifyItem.__new__` is counted — and, as `assemble` then
    calls nothing in Python per signature, the cyclic collector (which
    CPython runs only between bytecodes) begins no generation-1 or -2
    pass inside it.  The parent's `assemble` constructed an item a
    signature, and each construction let the collector in while the
    whole block's containers were young."""
    import gc
    from fabric_tpu.committer import txvalidator as tv
    org1, org2 = orgs
    msps = _msps(org1, org2)
    creator = org1.new_identity("client")
    endorsers = [org1.new_identity("e1"), org2.new_identity("e2")]
    n_txs = 1500
    envs = [make_tx(org1, org2, creator=creator, endorsers=endorsers,
                    rwset=rw(writes=[KVWrite(f"k{i}", b"v")]))
            for i in range(n_txs)]
    block = make_block(envs)

    made = []

    class Counted(tv.VerifyItem):
        __slots__ = ()

        def __new__(cls, *fields):
            made.append(1)
            return super().__new__(cls, *fields)

    inside, began = [], []

    class Watched:
        def __getattr__(self, name):
            fn = getattr(real, name)
            if name != "assemble":
                return fn

            def assemble(*args):
                inside.append(1)
                try:
                    return fn(*args)
                finally:
                    inside.pop()
            return assemble

    def on_gc(phase, info):
        if phase == "start" and inside:
            began.append(info["generation"])

    class TakesArrays:
        """A provider that reads the table's buffers and builds nothing
        (the device provider's part; its verdicts are not the point)."""
        name = "takes-arrays"

        def batch_verify_packed_async(self, batch):
            assert len(batch.digest) == 32 * batch.n_rows == 32 * len(batch)
            return lambda: np.ones(len(batch), dtype=bool)

    real = tv._fastcollect
    monkeypatch.setattr(tv, "VerifyItem", Counted)
    monkeypatch.setattr(tv, "_fastcollect", Watched())
    v = TxValidator("ch", msps, TakesArrays(), _policies(),
                    verify_cache=VerdictCache(capacity=4096))
    before = handed_over()
    gc.collect()
    gc.freeze()                  # as utils/heap.block_boundary leaves it
    gc.callbacks.append(on_gc)
    try:
        state = v.validate_begin(block)
    finally:
        gc.callbacks.remove(on_gc)
        gc.unfreeze()
    assert state.get("deep") and len(state["items"]) == 3 * n_txs
    assert len(made) == PROBE                 # the probe's, and no other
    assert not [g for g in began if g >= 1], began
    assert handed_over(before) == {("arrays", "bypassed"): 3 * n_txs}
    assert v.validate_finish(state).flags.valid_count() == n_txs


# -- differential fuzz: cache-on == cache-off --------------------------------


def _policies():
    p = PolicyRegistry()
    p.set_policy("cc", parse_policy("AND('Org1.member', 'Org2.member')"))
    return p


def _adversarial_corpus(org1, org2, rng, n=24):
    """Serialized envelopes mixing valid txs, broken creator sigs,
    broken endorsements, intra-corpus duplicates, truncations and junk
    — every class the verify plane could get wrong."""
    raws = []
    for i in range(n):
        kind = rng.randrange(8)
        if kind == 0 and raws:
            raws.append(rng.choice(raws))           # duplicate txid
            continue
        env = make_tx(org1, org2,
                      rw(reads=[KVRead("r", Version(0, 1))],
                         writes=[KVWrite(f"k{rng.random()}", b"v")]))
        raw = env.serialize()
        if kind == 1:
            raw = Envelope(env.payload,
                           env.signature[:-2] + b"\x00\x01").serialize()
        elif kind == 2:                             # Org1-only endorsement
            raw = make_tx(org1, org2,
                          endorsers=[org1.new_identity("e")]).serialize()
        elif kind == 3 and len(raw) > 8:
            raw = raw[:rng.randrange(4, len(raw))]  # truncated
        elif kind == 4:
            raw = rng.randbytes(rng.randrange(0, 40))   # junk
        raws.append(raw)
    return raws


def _run_blocks(validator, blocks):
    flags = []
    for i, raws in enumerate(blocks):
        res = validator.validate(make_block(raws, number=i))
        flags.append(res.flags.codes())
    return flags


def _mode(validator, mode):
    from fabric_tpu.committer import txvalidator as tv
    if mode == "python":
        validator.force_python_collect = True
    return validator


@pytest.mark.parametrize("mode", ["native", "python"])
def test_differential_fuzz_cache_on_equals_cache_off(orgs, sw_provider,
                                                     mode):
    """Same corpora, same blocks, four runs: cache-off, cache-on,
    cache-on with a 3-entry cache (evictions mid-block), and cache-on
    warmed with every other item's verdict (the last block, above
    PROBE items, goes round the first two caches and through the
    fourth).  All must produce bit-identical TxFlags, on the native and
    pure-Python collect paths."""
    org1, org2 = orgs
    msps = _msps(org1, org2)
    for seed in (7, 19, 40):
        rng = random.Random(seed)
        blocks = [_adversarial_corpus(org1, org2, rng) for _ in range(3)]
        # the same envelope appears in two different blocks too
        blocks[2] = blocks[2] + [blocks[0][0]]
        blocks.append(_adversarial_corpus(org1, org2, rng, n=200))

        def run(cache, provider=sw_provider):
            v = _mode(TxValidator("ch", msps, provider, _policies(),
                                  verify_cache=cache), mode)
            return _run_blocks(v, blocks)

        counting = CountingProvider(sw_provider)
        off = run(None, counting)
        assert len(counting.batches[-1]) > PROBE
        on = run(VerdictCache(capacity=4096))
        tiny = run(VerdictCache(capacity=3))
        warm = VerdictCache(capacity=4096)
        seen = [it for b in counting.batches for it in b][::2]
        for it, v in zip(seen, sw_provider.batch_verify(seen)):
            warm.put(it, bool(v), scope="ch")
        before = counts()
        warmed = run(warm)
        moved = delta(before, counts())
        assert moved["hits"] >= len(set(seen)) and moved["bypassed"] == 0
        assert off == on == tiny == warmed, \
            f"verdict fork at seed {seed} ({mode})"


def test_cached_verdict_cannot_vouch_for_revoked_identity(orgs,
                                                          sw_provider):
    """Identity validity is judged live at the gate: a True signature
    verdict cached while an org was trusted must not keep its txs valid
    after the org is dropped (CRL / config revocation between ingress
    and commit)."""
    org1, org2 = orgs
    both = _msps(org1, org2)
    env = make_tx(org1, org2)
    cache = VerdictCache(capacity=64)

    v1 = TxValidator("ch", both, sw_provider, _policies(),
                     verify_cache=cache)
    assert v1.validate(make_block([env])).flags.codes() == [
        int(ValidationCode.VALID)]

    # org2 revoked; same shared cache, fresh validator state
    only1 = _msps(org1)
    for with_cache in (cache, None):
        v2 = TxValidator("ch", only1, sw_provider, _policies(),
                         verify_cache=with_cache)
        assert v2.validate(make_block([env])).flags.codes() == [
            int(ValidationCode.ENDORSEMENT_POLICY_FAILURE)]


def test_verify_once_telemetry_one_device_verify_per_item(orgs,
                                                          sw_provider):
    """≤ 1 device verification per unique (identity, signature) pair:
    re-validating the same envelopes dispatches nothing new, and the
    duplicate-device-verification counter stays flat."""
    org1, org2 = orgs
    msps = _msps(org1, org2)
    envs = [make_tx(org1, org2) for _ in range(6)]
    inner = CountingProvider(init_factories(FactoryOpts(default="SW")))
    validator = TxValidator("ch", msps, inner, _policies(),
                            verify_cache=VerdictCache(capacity=4096))
    before = counts()
    validator.validate(make_block(envs, number=0))
    first = inner.dispatched
    assert first > 0
    validator.validate(make_block(envs, number=1))
    assert inner.dispatched == first                # zero new device work
    assert delta(before, counts())["dupes"] == 0


# -- speculative verification ------------------------------------------------


def test_derive_items_match_commit_time_keys(orgs, sw_provider):
    """The speculative path's item derivation must be bit-identical to
    the committer's — otherwise cache keys never match at commit.
    Proven transitively: stamping an envelope at ingress makes the
    commit-time validation of that envelope fully cache-served."""
    org1, org2 = orgs
    msps = _msps(org1, org2)
    envs = [make_tx(org1, org2) for _ in range(5)]
    cache = VerdictCache(capacity=4096)
    spec = SpeculativeVerifier(cache, lambda: sw_provider,
                               lambda cid: msps)
    attests = spec.stamp(envs, ["ch"] * len(envs))
    assert all(a for a in attests)                  # creator verdicts in
    # drain the endorsement queue synchronously (worker not started)
    while spec._queue:
        cid, items = spec._queue.popleft()
        spec._verify_batch(items, stage="overlap", scope=cid)

    inner = CountingProvider(init_factories(FactoryOpts(default="SW")))
    validator = TxValidator("ch", msps, inner, _policies(),
                            verify_cache=cache)
    res = validator.validate(make_block(envs))
    assert res.flags.codes() == [int(ValidationCode.VALID)] * 5
    assert inner.dispatched == 0        # commit degraded to cache lookups
    assert cache.coverage.frac() == 1.0


def test_speculative_worker_fills_cache_in_background(orgs, sw_provider):
    import time
    org1, org2 = orgs
    msps = _msps(org1, org2)
    envs = [make_tx(org1, org2) for _ in range(3)]
    cache = VerdictCache(capacity=4096)
    spec = SpeculativeVerifier(cache, lambda: sw_provider,
                               lambda cid: msps).start()
    try:
        spec.stamp(envs, ["ch"] * 3)
        deadline = time.time() + 5.0
        want = 3 * 3                    # creator + 2 endorsements each
        while len(cache) < want and time.time() < deadline:
            time.sleep(0.02)
        assert len(cache) == want
        assert spec.dispatched >= 6     # endorsements went via the worker
    finally:
        spec.stop()


def test_structurally_invalid_envelope_stamps_nothing(orgs, sw_provider):
    org1, org2 = orgs
    msps = _msps(org1, org2)
    cache = VerdictCache(capacity=64)
    spec = SpeculativeVerifier(cache, lambda: sw_provider,
                               lambda cid: msps)

    class FakeEnv:
        def serialize(self):
            return b"\xde\xad"

    attests = spec.stamp([FakeEnv()], ["ch"])
    assert attests == [""] and len(cache) == 0


# -- orderer attestation trust ----------------------------------------------


def _attestor_binding(ident):
    from fabric_tpu.orderer.cluster import cert_fingerprint
    return {"mspid": ident.mspid, "cert_fp": cert_fingerprint(ident.cert)}


def _processor(org, provider, cache, trust, attestors=None):
    from fabric_tpu.orderer.msgprocessor import StandardChannelProcessor
    return StandardChannelProcessor(
        "ch", {"Org1": CachedMSP(org.msp())}, provider,
        parse_policy("OR('Org1.member')"),
        verify_cache=cache, trust_attestations=trust,
        attestors=attestors)


def _order_env(org, creator=None):
    rwset = TxRwSet((NsRwSet("cc", writes=(KVWrite("k", b"v"),)),))
    return build.endorser_tx("ch", "cc", "1.0", rwset,
                             creator or org.new_identity("client"),
                             [org.new_identity("e")])


def test_attestation_skips_orderer_device_verify(sw_provider):
    org = DevOrg("Org1")
    gw = org.new_identity("gateway")
    env = _order_env(org)
    msps = {"Org1": CachedMSP(org.msp())}
    it = creator_item(env, msps)
    inner = CountingProvider(init_factories(FactoryOpts(default="SW")))
    proc = _processor(org, inner, VerdictCache(capacity=64), trust=True,
                      attestors=[_attestor_binding(gw)])
    before = counts()
    proc.process(env, attest=item_digest(it).hex(), attestor=gw)
    assert inner.dispatched == 0        # admission served from the cache
    assert delta(before, counts())["attested"] == 1


def test_self_attested_invalid_signature_rejected(sw_provider):
    """THE forgery scenario: the attestation digest is a public hash, so
    a submitter can always compute a CORRECT digest over its own
    envelope — including one whose signature is garbage.  Because the
    submitter is not an authorized attestor, the self-vouch seeds
    nothing: the SigFilter device-verifies and rejects."""
    from fabric_tpu.orderer.msgprocessor import MsgProcessorError
    org = DevOrg("Org1")
    gw = org.new_identity("gateway")
    attacker = org.new_identity("attacker")
    env = _order_env(org)
    broken = Envelope(env.payload, env.signature[:-2] + b"\x00\x01")
    msps = {"Org1": CachedMSP(org.msp())}
    # the attacker computes the digest of the item the orderer itself
    # will derive — bit-identical, so the digest check alone passes
    self_attest = item_digest(creator_item(broken, msps)).hex()
    inner = CountingProvider(init_factories(FactoryOpts(default="SW")))
    proc = _processor(org, inner, VerdictCache(capacity=64), trust=True,
                      attestors=[_attestor_binding(gw)])
    before = counts()
    with pytest.raises(MsgProcessorError):
        proc.process(broken, attest=self_attest, attestor=attacker)
    assert inner.dispatched == 1        # really verified, not vouched
    assert delta(before, counts())["attested"] == 0


def test_attestation_requires_configured_attestor_set(sw_provider):
    """No attestor set configured -> NOBODY may vouch, even with
    trust_attestations on and a transport-authenticated sender; and an
    unauthenticated frame (attestor=None) never vouches either."""
    from fabric_tpu.orderer.msgprocessor import MsgProcessorError
    org = DevOrg("Org1")
    gw = org.new_identity("gateway")
    env = _order_env(org)
    broken = Envelope(env.payload, env.signature[:-2] + b"\x00\x01")
    msps = {"Org1": CachedMSP(org.msp())}
    self_attest = item_digest(creator_item(broken, msps)).hex()
    for attestor, attestors in ((gw, None), (None, [_attestor_binding(gw)])):
        inner = CountingProvider(init_factories(FactoryOpts(default="SW")))
        proc = _processor(org, inner, VerdictCache(capacity=64),
                          trust=True, attestors=attestors)
        with pytest.raises(MsgProcessorError):
            proc.process(broken, attest=self_attest, attestor=attestor)
        assert inner.dispatched == 1


def test_forged_attestation_is_ignored(sw_provider):
    """An attestation whose digest does not match the item the orderer
    derives ITSELF from the wire bytes seeds nothing — the device
    verify runs as if no attestation came."""
    org = DevOrg("Org1")
    gw = org.new_identity("gateway")
    env = _order_env(org)
    inner = CountingProvider(init_factories(FactoryOpts(default="SW")))
    proc = _processor(org, inner, VerdictCache(capacity=64), trust=True,
                      attestors=[_attestor_binding(gw)])
    before = counts()
    proc.process(env, attest="ab" * 32, attestor=gw)
    assert inner.dispatched == 1
    assert delta(before, counts())["attested"] == 0


def test_attestation_cannot_vouch_for_tampered_envelope(sw_provider):
    """Replaying a VALID attestation digest next to an envelope with a
    swapped signature: the orderer derives the item from the bytes it
    holds, digests differ, the tampered envelope is fully verified and
    rejected — even when the vouching identity IS authorized."""
    from fabric_tpu.orderer.msgprocessor import MsgProcessorError
    org = DevOrg("Org1")
    gw = org.new_identity("gateway")
    env = _order_env(org)
    msps = {"Org1": CachedMSP(org.msp())}
    good_digest = item_digest(creator_item(env, msps)).hex()
    tampered = Envelope(env.payload, env.signature[:-2] + b"\x00\x01")
    inner = CountingProvider(init_factories(FactoryOpts(default="SW")))
    proc = _processor(org, inner, VerdictCache(capacity=64), trust=True,
                      attestors=[_attestor_binding(gw)])
    with pytest.raises(MsgProcessorError):
        proc.process(tampered, attest=good_digest, attestor=gw)
    assert inner.dispatched == 1


def test_attestation_ignored_when_trust_disabled(sw_provider):
    org = DevOrg("Org1")
    gw = org.new_identity("gateway")
    env = _order_env(org)
    msps = {"Org1": CachedMSP(org.msp())}
    it = creator_item(env, msps)
    inner = CountingProvider(init_factories(FactoryOpts(default="SW")))
    proc = _processor(org, inner, VerdictCache(capacity=64), trust=False,
                      attestors=[_attestor_binding(gw)])
    proc.process(env, attest=item_digest(it).hex(), attestor=gw)
    assert inner.dispatched == 1


def test_trust_attestations_defaults_off(sw_provider):
    """The trust toggle is a security decision: both the processor and
    the orderer node's config parser must default it OFF (and the
    attestor allowlist to empty — nobody may vouch)."""
    import inspect
    from fabric_tpu.node.orderer import attestation_trust
    from fabric_tpu.orderer.msgprocessor import StandardChannelProcessor
    sig = inspect.signature(StandardChannelProcessor.__init__)
    assert sig.parameters["trust_attestations"].default is False
    assert attestation_trust({}) == (False, [])
    trust, attestors = attestation_trust(
        {"trust_attestations": True,
         "attestors": [{"mspid": "Org1", "cert_fp": "ab" * 32}]})
    assert trust is True and len(attestors) == 1


def test_orderer_resubmission_served_from_cache(sw_provider):
    """Even without attestations, a client retry (same envelope twice
    through broadcast) verifies on the device exactly once."""
    org = DevOrg("Org1")
    env = _order_env(org)
    inner = CountingProvider(init_factories(FactoryOpts(default="SW")))
    proc = _processor(org, inner, VerdictCache(capacity=64), trust=False)
    proc.process(env)
    proc.process(env)
    assert inner.dispatched == 1


# -- ops surface -------------------------------------------------------------


def test_verify_plane_ops_route(orgs, sw_provider):
    from fabric_tpu import verify_plane

    routes = {}

    class FakeOps:
        def register_route(self, method, path, fn):
            routes[(method, path)] = fn

    cache = VerdictCache(capacity=8, owner="Org1")
    spec = SpeculativeVerifier(cache, lambda: sw_provider, lambda cid: {})
    verify_plane.register_ops(FakeOps(), cache, spec=spec,
                              extra=lambda: {"trust_attestations": True})
    code, out = routes[("GET", "/verify_plane")]("/verify_plane", None)
    assert code == 200
    assert out["owner"] == "Org1" and out["capacity"] == 8
    assert out["speculative"] is True
    assert out["trust_attestations"] is True
    assert out["speculative_dispatched"] == 0


# -- deliver-time attestations (orderer -> peer) -----------------------------


def test_attest_block_emits_digests_only_for_cached_true(sw_provider):
    from fabric_tpu.verify_plane import attest_block
    org = DevOrg("Org1")
    msps = {"Org1": CachedMSP(org.msp())}
    envs = [_order_env(org), _order_env(org), _order_env(org)]
    cache = VerdictCache(capacity=64)
    block = make_block(envs, number=3)
    assert attest_block(cache, block, "ch", msps) is None  # nothing cached
    cache.put(creator_item(envs[0], msps), True, scope="ch")
    cache.put(creator_item(envs[2], msps), False, scope="ch")  # never attested
    attests = attest_block(cache, block, "ch", msps)
    assert attests is not None and len(attests) == 3
    assert attests[0] == item_digest(creator_item(envs[0], msps)).hex()
    assert attests[1] is None and attests[2] is None


def test_accept_block_attestations_rederives_before_seeding(sw_provider):
    from fabric_tpu.verify_plane import accept_block_attestations
    org = DevOrg("Org1")
    msps = {"Org1": CachedMSP(org.msp())}
    env = _order_env(org)
    good = item_digest(creator_item(env, msps)).hex()
    # a forged digest next to the envelope seeds nothing; the correct
    # digest next to TAMPERED bytes seeds nothing either (the peer
    # derives from its own bytes, digests diverge)
    tampered = Envelope(env.payload, env.signature[:-2] + b"\x00\x01")
    cache = VerdictCache(capacity=64)
    before = counts()
    assert accept_block_attestations(
        cache, make_block([env]), ["ab" * 32], "ch", msps) == 0
    assert accept_block_attestations(
        cache, make_block([tampered]), [good], "ch", msps) == 0
    assert cache.peek(creator_item(env, msps)) is None
    assert accept_block_attestations(
        cache, make_block([env]), [good], "ch", msps) == 1
    assert cache.peek(creator_item(env, msps)) is True
    assert delta(before, counts())["attested"] == 1


def test_attest_roundtrip_skips_peer_device_verify(sw_provider):
    """Orderer caches an admission verdict -> attests it on deliver ->
    peer seeds its cache -> the peer-side CachingProvider answers the
    commit-gate dispatch without touching the device."""
    from fabric_tpu.verify_plane import accept_block_attestations, attest_block
    org = DevOrg("Org1")
    msps = {"Org1": CachedMSP(org.msp())}
    env = _order_env(org)
    block = make_block([env], number=7)
    orderer_cache = VerdictCache(capacity=64, owner="orderer")
    orderer_cache.put(creator_item(env, msps), True, scope="ch")
    attests = attest_block(orderer_cache, block, "ch", msps)

    peer_cache = VerdictCache(capacity=64, owner="peer")
    assert accept_block_attestations(peer_cache, block, attests,
                                     "ch", msps) == 1
    inner = CountingProvider(init_factories(FactoryOpts(default="SW")))
    cp = CachingProvider(inner, peer_cache, site="committer", scope="ch")
    verdicts = cp.batch_verify([creator_item(env, msps)])
    assert bool(verdicts.all()) and inner.dispatched == 0


# -- per-identity attestor standing (verify_plane/trust.py) ------------------


def test_attestor_revoked_on_digest_mismatch_and_persisted(
        sw_provider, tmp_path):
    """A forged attestation no longer just gets ignored: the vouching
    identity is revoked — its NEXT attestation is not honoured even
    when bit-correct — and the revocation survives a restart via the
    JSON state file."""
    from fabric_tpu.verify_plane import AttestorTrust
    org = DevOrg("Org1")
    gw = org.new_identity("gateway")
    msps = {"Org1": CachedMSP(org.msp())}
    path = str(tmp_path / "attestor_trust.json")
    trust = AttestorTrust(path)
    inner = CountingProvider(init_factories(FactoryOpts(default="SW")))
    proc = _processor(org, inner, VerdictCache(capacity=64), trust=True,
                      attestors=[_attestor_binding(gw)])
    proc.attestor_trust = trust

    env1, env2 = _order_env(org), _order_env(org)
    proc.process(env1, attest="ab" * 32, attestor=gw)   # mismatch: revoke
    assert inner.dispatched == 1
    assert trust.revoked_count() == 1
    # a correct attestation from the now-revoked identity seeds nothing
    before = counts()
    proc.process(env2, attest=item_digest(creator_item(env2, msps)).hex(),
                 attestor=gw)
    assert inner.dispatched == 2                        # device-verified
    assert delta(before, counts())["attested"] == 0

    reloaded = AttestorTrust(path)                      # restart
    assert reloaded.revoked_count() == 1
    binding = _attestor_binding(gw)
    assert not reloaded.allowed((binding["mspid"], binding["cert_fp"]))


def test_attestor_standing_accumulates_accepts(sw_provider, tmp_path):
    from fabric_tpu.verify_plane import AttestorTrust
    org = DevOrg("Org1")
    gw = org.new_identity("gateway")
    msps = {"Org1": CachedMSP(org.msp())}
    trust = AttestorTrust(str(tmp_path / "t.json"))
    inner = CountingProvider(init_factories(FactoryOpts(default="SW")))
    proc = _processor(org, inner, VerdictCache(capacity=64), trust=True,
                      attestors=[_attestor_binding(gw)])
    proc.attestor_trust = trust
    for _ in range(3):
        env = _order_env(org)
        proc.process(env, attest=item_digest(creator_item(env, msps)).hex(),
                     attestor=gw)
    assert inner.dispatched == 0                        # all vouched
    (ent,) = trust.snapshot().values()
    assert ent["accepted"] == 3 and ent["mismatched"] == 0
    assert not ent["revoked"] and trust.revoked_count() == 0


def test_deliver_attestation_mismatch_revokes_sender(orgs, sw_provider):
    """The orderer->peer direction: accept_block_attestations feeds the
    sender's standing — one bad digest in a delivered block revokes."""
    from fabric_tpu.verify_plane import (AttestorTrust,
                                         accept_block_attestations)
    org1, org2 = orgs
    msps = _msps(org1, org2)
    envs = [make_tx(org1, org2) for _ in range(2)]
    block = make_block(envs)
    good = item_digest(creator_item(envs[0], msps)).hex()
    cache = VerdictCache(capacity=64)
    trust = AttestorTrust()
    binding = ("OrdererOrg", "ab" * 32)
    n = accept_block_attestations(cache, block, [good, "cd" * 32], "ch",
                                  msps, trust=trust,
                                  attestor_binding=binding)
    assert n == 1                       # the good digest still seeded
    assert not trust.allowed(binding)   # ...but the forgery revoked
