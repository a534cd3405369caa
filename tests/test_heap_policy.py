"""The collector policy at the block boundary (`fabric_tpu/utils/heap.py`):
freeze what a committed block left alive, thaw by doubling, and keep an
account of the full passes that touches no lock."""
import gc
import sys
import threading
import weakref

import pytest

from fabric_tpu.bccsp.factory import FactoryOpts, init_factories
from fabric_tpu.committer import Committer, PolicyRegistry, TxValidator
from fabric_tpu.committer import committer as committer_module
from fabric_tpu.ledger import KVLedger, LedgerConfig
from fabric_tpu.msp import CachedMSP
from fabric_tpu.msp.ca import DevOrg
from fabric_tpu.ops_plane import registry
from fabric_tpu.ops_plane.tracing import Tracer
from fabric_tpu.policy import parse_policy
from fabric_tpu.protocol import (KVRead, KVWrite, NsRwSet, TxRwSet,
                                 ValidationCode, Version, build)
from fabric_tpu.protocol.types import META_TXFLAGS
from fabric_tpu.utils import heap

SERIES = ("runtime_gc_full_seconds_sum", "runtime_gc_full_seconds_count",
          "runtime_gc_frozen_objects", "runtime_gc_thaws_total")


@pytest.fixture(scope="module")
def provider():
    return init_factories(FactoryOpts(default="SW"))


@pytest.fixture(scope="module")
def orgs():
    return DevOrg("Org1"), DevOrg("Org2")


@pytest.fixture()
def at_start_up(monkeypatch):
    """The policy as a process finds it before its first block; the
    worker's own standing comes back afterwards.  Collections that are
    asked for by name run all the same."""
    monkeypatch.setattr(heap, "_thaws", 0)
    monkeypatch.setattr(heap, "_blocks_at_thaw", 0)
    monkeypatch.setattr(heap, "_frozen_at_thaw", 0)
    heap.install()
    gc.disable()            # only the passes the test itself asks for
    yield
    gc.enable()


def new_committer(provider, orgs) -> Committer:
    msps = {o.mspid: CachedMSP(o.msp()) for o in orgs}
    policies = PolicyRegistry()
    policies.set_policy(
        "cc", parse_policy("AND('Org1.member', 'Org2.member')"))
    validator = TxValidator("ch", msps, provider, policies)
    return Committer(KVLedger("ch", LedgerConfig()), validator)


def seeded_envelopes(orgs, n_blocks: int, txs: int = 4) -> list:
    """Per block: fresh keys written, one key read at a version the
    block before it wrote (block 2 reads a stale one: an MVCC abort), and
    one envelope with only Org1's endorsement (a policy failure)."""
    org1, org2 = orgs
    creator = org1.new_identity("client")
    both = [org1.new_identity("e1"), org2.new_identity("e2")]

    def tx(reads, writes, endorsers=both):
        rwset = TxRwSet((NsRwSet("cc", reads=tuple(reads),
                                 writes=tuple(writes)),))
        return build.endorser_tx("ch", "cc", "1.0", rwset, creator,
                                 endorsers)

    blocks = []
    for b in range(n_blocks):
        envs = [tx([], [KVWrite(f"k{b}_{i}", b"v%d" % b)])
                for i in range(txs)]
        if b:
            seen = Version(0 if b == 2 else b - 1, 0)
            envs.append(tx([KVRead(f"k{b - 1}_0", seen)],
                           [KVWrite(f"k{b - 1}_0", b"again")]))
        envs.append(tx([], [KVWrite(f"lonely{b}", b"x")], both[:1]))
        blocks.append(envs)
    return blocks


def commit(committer: Committer, envs) -> bytes:
    lg = committer.ledger
    prev = (lg.blockstore.chain_info().current_hash
            if lg.height else b"\x00" * 32)
    block = build.new_block(lg.height, prev, envs)
    committer.store_block(block)
    return bytes(lg.blockstore.get_by_number(lg.height - 1)
                 .metadata.items[META_TXFLAGS])


def frozen(obj) -> bool:
    """In the permanent generation: tracked, and in none of the
    generations `gc.get_objects()` lists."""
    return gc.is_tracked(obj) and not any(
        o is obj for o in gc.get_objects())


def ballast(n: int) -> list:
    """n objects the collector tracks, one allocated block each, alive
    while the list is."""
    return [[] for _ in range(n)]


def room() -> int:
    """Blocks the heap may still grow by before the rule thaws, read
    after a collection: garbage on its way out is no part of the heap."""
    gc.collect()
    return 2 * heap._blocks_at_thaw - sys.getallocatedblocks()


def slack() -> int:
    """What a commit may give back or take between the rule's reading of
    the heap and the test's, as a share of the heap the worker has: the
    block's own temporaries, and whatever an earlier test file of this
    worker left filling up — a flight recorder at capacity drops a whole
    trace for each one a block adds.  A twentieth of the bar."""
    return heap._blocks_at_thaw // 20


def thaws() -> int:
    registry.expose_text()
    return int(registry.get("runtime_gc_thaws_total").value())


def test_freezes_at_each_boundary_and_thaws_by_doubling(
        provider, orgs, at_start_up):
    committer = new_committer(provider, orgs)
    blocks = seeded_envelopes(orgs, 6)
    keep = []

    commit(committer, blocks[0])
    assert thaws() == 1                  # the first boundary thaws
    at_thaw = heap._blocks_at_thaw       # the heap's size the rule doubles
    assert at_thaw > 0
    assert abs(at_thaw - sys.getallocatedblocks()) < slack()
    assert frozen(committer.ledger)

    counts = [gc.get_freeze_count()]
    for envs in blocks[1:3]:
        keep.append(ballast(1000))       # what a block leaves alive
        commit(committer, envs)
        counts.append(gc.get_freeze_count())
        assert frozen(keep[-1])
    assert thaws() == 1
    assert counts[2] > counts[1] > counts[0], counts

    # half-way to twice the size: still a plain freeze
    keep.append(ballast(room() // 2))
    commit(committer, blocks[3])
    assert at_thaw < sys.getallocatedblocks() < 2 * at_thaw
    assert thaws() == 1 and heap._blocks_at_thaw == at_thaw

    # past it: one whole-heap pass, and the bar moves to the new size
    keep.append(ballast(room() + slack()))
    commit(committer, blocks[4])
    assert thaws() == 2
    # (the pass took what cyclic garbage the blocks had left frozen)
    assert heap._blocks_at_thaw > 2 * at_thaw - at_thaw // 20
    commit(committer, blocks[5])
    assert thaws() == 2


def test_same_flags_hash_and_state_as_without_the_policy(
        provider, orgs, at_start_up, monkeypatch):
    blocks = seeded_envelopes(orgs, 5)
    with_policy = new_committer(provider, orgs)
    flags = [commit(with_policy, envs) for envs in blocks]
    assert thaws() >= 1

    calls = []
    monkeypatch.setattr(committer_module.heap, "block_boundary",
                        lambda: calls.append(1))
    without = new_committer(provider, orgs)
    assert [commit(without, envs) for envs in blocks] == flags
    assert len(calls) == len(blocks)
    # the seeded blocks really exercise the three outcomes
    assert {code for f in flags for code in f} == {
        ValidationCode.VALID, ValidationCode.ENDORSEMENT_POLICY_FAILURE,
        ValidationCode.MVCC_READ_CONFLICT}, flags
    assert with_policy.ledger.commit_hash == without.ledger.commit_hash
    assert with_policy.ledger.height == without.ledger.height == 5
    keys = [f"k{b}_{i}" for b in range(5) for i in range(4)] + [
        f"lonely{b}" for b in range(5)]
    state = [with_policy.ledger.get_state("cc", k) for k in keys]
    assert state == [without.ledger.get_state("cc", k) for k in keys]
    assert b"again" in state and None in state


class Knot:
    pass


def test_a_cycle_dropped_among_frozen_objects_goes_at_the_next_thaw(
        at_start_up):
    heap.block_boundary()                # start-up: thaw
    assert thaws() == 1
    knot = Knot()
    knot.itself = knot
    gone = weakref.ref(knot)
    heap.block_boundary()                # plain freeze, the knot with it
    assert thaws() == 1 and frozen(knot)
    del knot
    gc.collect()
    assert gone() is not None            # what a thaw is for
    keep = ballast(room())
    heap.block_boundary()
    assert thaws() == 2
    assert gone() is None
    assert frozen(keep)


def test_a_full_pass_under_the_tracers_and_a_histograms_lock_returns(
        at_start_up):
    """The account's hook runs on whichever thread allocates — one that
    holds these non-re-entrant locks included."""
    tracer = Tracer().configure({"enabled": True})
    histogram = registry.histogram("heap_policy_test_seconds")
    histogram.observe(0.1)
    seconds, count = heap._full_seconds, heap._full_count
    started = heap._full_started
    done = threading.Event()

    def collect_under_the_locks():
        with tracer.start_span("test.gc"):
            with tracer._lock, histogram._lock:
                gc.collect()
        done.set()

    threading.Thread(target=collect_under_the_locks, daemon=True).start()
    assert done.wait(10.0)
    assert heap._full_count == count + 1
    assert heap._full_seconds > seconds
    assert heap._full_started > started


def test_boundaries_of_many_channels_lose_no_thaw(at_start_up, monkeypatch):
    """Several committers share the one policy: with a heap that reads as
    doubled at every look, each boundary is a thaw and each is counted."""
    class Doubling:
        calls = 0

        def getallocatedblocks(self):
            self.calls += 1
            return 4 ** self.calls

    monkeypatch.setattr(heap, "sys", Doubling())
    threads, rounds = 12, 3
    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        workers = [threading.Thread(
            target=lambda: [heap.block_boundary() for _ in range(rounds)],
            daemon=True) for _ in range(threads)]
        for w in workers:
            w.start()
        for w in workers:
            w.join(60.0)
    finally:
        sys.setswitchinterval(interval)
    assert not any(w.is_alive() for w in workers)
    assert heap._thaws == threads * rounds


def test_the_four_series_are_stamped_at_each_exposition(at_start_up):
    def stamped():
        return {name: registry.get(name).value() for name in SERIES}

    text = registry.expose_text()
    for name in SERIES:
        assert f"\n{name} " in text, name
    first = stamped()
    gc.collect()                         # the account moves ...
    heap.block_boundary()
    assert heap._full_count > first["runtime_gc_full_seconds_count"]
    assert stamped() == first            # ... the series do not, until
    text = registry.expose_text()        # the next exposition
    second = stamped()
    assert second["runtime_gc_full_seconds_count"] == heap._full_count
    assert second["runtime_gc_full_seconds_sum"] == heap._full_seconds \
        > first["runtime_gc_full_seconds_sum"]
    assert second["runtime_gc_thaws_total"] == 1
    # the frozen count is the last thaw's: counting is a walk of the heap
    assert 0 < second["runtime_gc_frozen_objects"] == heap._frozen_at_thaw
    assert second["runtime_gc_frozen_objects"] <= gc.get_freeze_count() + 1000
    assert f"\nruntime_gc_thaws_total {second['runtime_gc_thaws_total']}" \
        in text


def test_a_registry_of_its_own_carries_no_account(at_start_up):
    from fabric_tpu.ops_plane import MetricsRegistry
    assert "runtime_gc_" not in MetricsRegistry().expose_text()
