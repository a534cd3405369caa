"""The provider's account of its dispatches (bccsp/dispatch_account.py):
its arithmetic under a scripted clock, the two identities against the
provider's own stats, who-asked through the wrapping providers, and the
table-build span under the batch's span.  No device: the lanes' programs
are stand-ins whose outputs become ready when the script says."""

import hashlib

import numpy as np
import pytest

from fabric_tpu.bccsp.degrade import DegradingProvider
from fabric_tpu.bccsp.dispatch_account import DispatchAccount
from fabric_tpu.bccsp.jaxtpu import JaxTpuProvider
from fabric_tpu.bccsp.provider import (DISPATCH_SITES, SCHEME_P256,
                                       VerifyItem, current_site,
                                       dispatch_site)
from fabric_tpu.bccsp.sw import SoftwareProvider
from fabric_tpu.ops_plane import tracing
from fabric_tpu.ops_plane.metrics import MetricsRegistry
from fabric_tpu.verify_plane.cache import CachingProvider, VerdictCache

SW = SoftwareProvider()


class Clock:
    """perf_counter, scripted."""

    def __init__(self):
        self.now = 100.0

    def __call__(self) -> float:
        return self.now


class Output:
    """A program's output: ready once the clock has passed `done_at`;
    blocking on it moves the clock there."""

    def __init__(self, clock, done_at, values):
        self.clock, self.done_at, self.values = clock, done_at, values

    def is_ready(self) -> bool:
        return self.clock.now >= self.done_at

    def block_until_ready(self):
        self.clock.now = max(self.clock.now, self.done_at)
        return self

    def __array__(self, dtype=None, copy=None):
        return self.values


class Chip:
    """One device running stand-in programs in order: a call costs
    `enqueue_s` on the host and `run_s` on the chip."""

    def __init__(self, clock, enqueue_s=0.001, run_s=0.022):
        self.clock, self.enqueue_s, self.run_s = clock, enqueue_s, run_s
        self.free_at = 0.0

    def generic(self, *words):
        self.clock.now += self.enqueue_s
        start = max(self.clock.now, self.free_at)
        self.free_at = start + self.run_s
        return Output(self.clock, self.free_at,
                      np.ones(words[0].shape[-1], dtype=bool))

    def rows(self, bank, row_key, *words):
        self.clock.now += self.enqueue_s
        start = max(self.clock.now, self.free_at)
        self.free_at = start + self.run_s
        return Output(self.clock, self.free_at,
                      np.ones(words[0].shape[1:], dtype=bool))


def signed(n_keys: int, per_key: int = 1) -> list:
    items = []
    for i in range(n_keys):
        key = SW.key_gen(SCHEME_P256)
        for j in range(per_key):
            digest = hashlib.sha256(b"item %d %d" % (i, j)).digest()
            items.append(VerifyItem(SCHEME_P256, key.public_bytes(),
                                    SW.sign(key, digest), digest))
    return items


@pytest.fixture
def rig():
    """A provider whose lanes run on the scripted chip, with an account
    and a registry of its own."""
    clock = Clock()
    chip = Chip(clock)
    p = JaxTpuProvider(max_cached_keys=2)
    p._clock = clock
    p._fns[SCHEME_P256] = chip.generic
    p._fns["p256-rows"] = chip.rows
    reg = MetricsRegistry()
    p.account = DispatchAccount(p.device_labels, registry=reg)
    return p, clock, chip, reg


def series(reg, name) -> dict:
    """{labels as a sorted tuple: value} of a counter."""
    return dict(reg.get(name)._values)


def test_in_order_start_queue_wait_and_held():
    reg = MetricsRegistry()
    acct = DispatchAccount(("tpu:0",), registry=reg)
    # A: called at 0, packed 2 ms, enqueued by 3 ms, seen ready at 27 ms
    a = acct.enqueued("generic", "generic@128", "endorser", 1,
                      0.000, 0.002, 0.003)
    # B: enqueued at 5 ms, behind A
    b = acct.enqueued("generic", "generic@128", "handshake", 1,
                      0.001, 0.004, 0.005)
    acct.ready(a, 0.027, observed=True)
    acct.ready(b, 0.051, observed=True)
    assert a.pack_s == pytest.approx(0.002)
    assert a.queue_wait_s == 0.0 and a.held_s == pytest.approx(0.024)
    # B started when A ended, not when it was enqueued
    assert b.queue_wait_s == pytest.approx(0.022)
    assert b.held_s == pytest.approx(0.024)
    # C: the chip was idle when it came: no wait, held from its enqueue
    c = acct.enqueued("rows", "rows@384", "validator", 39000,
                      1.000, 1.300, 1.310)
    acct.ready(c, 1.400, observed=True)
    assert c.queue_wait_s == 0.0 and c.held_s == pytest.approx(0.090)
    assert c.pack_s == pytest.approx(0.300)
    held = reg.get("provider_device_held_seconds_total")
    assert held.value(device="tpu:0") == pytest.approx(0.024 + 0.024 + 0.090)
    h = reg.get("provider_dispatch_held_seconds")
    assert h._n[(("lane", "generic"), ("program", "generic@128"))] == 2
    assert h._sum[(("lane", "rows"), ("program", "rows@384"))] == \
        pytest.approx(0.090)
    q = reg.get("provider_dispatch_queue_wait_seconds")
    assert q._sum[(("lane", "generic"), ("site", "handshake"))] == \
        pytest.approx(0.022)


def test_an_unobserved_dispatch_adds_no_held_time():
    reg = MetricsRegistry()
    acct = DispatchAccount(("tpu:0",), registry=reg)
    a = acct.enqueued("generic", "generic@128", "speculative", 1,
                      0.0, 0.001, 0.002)
    # nobody waited: first looked at long after, already ready
    acct.ready(a, 0.500, observed=False)
    assert a.held_s == 0.0 and not a.observed
    assert reg.get("provider_dispatch_unobserved_total").value(
        lane="generic") == 1
    assert reg.get("provider_device_held_seconds_total") is None
    assert reg.get("provider_dispatch_held_seconds") is None
    # and its look is not an end: the next dispatch starts at its own
    # enqueue, not at 0.5
    b = acct.enqueued("generic", "generic@128", "endorser", 1,
                      0.400, 0.401, 0.402)
    acct.ready(b, 0.424, observed=True)
    assert b.queue_wait_s == 0.0 and b.held_s == pytest.approx(0.022)
    # a waiter that woke late cannot make the next one's time negative
    c = acct.enqueued("generic", "generic@128", "endorser", 1,
                      0.410, 0.411, 0.412)
    acct.ready(c, 0.420, observed=True)
    assert c.held_s == 0.0 and c.queue_wait_s == pytest.approx(0.008)


def test_the_account_equals_the_providers_stats(rig):
    p, clock, chip, reg = rig
    with dispatch_site("endorser"):
        assert p.batch_verify(signed(1)).all()
    with dispatch_site("validator"):
        # 3 keys x 70 signatures earn rows-lane slots (only 2 to be
        # had: the third spills), 5 lone keys ride the generic lane
        resolve = p.batch_verify_async(signed(3, 70) + signed(5))
        assert resolve().all()
    assert p.batch_verify(signed(2)).all()          # nobody said who
    dispatches = series(reg, "provider_dispatch_total")
    sigs = series(reg, "provider_dispatch_sigs_total")
    assert sum(dispatches.values()) == p.stats["dispatches"] == 4
    assert sum(sigs.values()) == p.stats["device_sigs"] == 1 + 215 + 2
    by_site = {}
    for labels, n in sigs.items():
        site = dict(labels)["site"]
        by_site[site] = by_site.get(site, 0) + n
    assert by_site == {"endorser": 1, "validator": 215, "other": 2}
    programs = {dict(k)["program"] for k in dispatches}
    assert programs == {"generic@128", "rows@4"}
    # every wait saw its output become ready: all observed, and the
    # device's held time is the chip's run time, dispatch by dispatch
    assert reg.get("provider_dispatch_unobserved_total") is None
    assert reg.get("provider_device_held_seconds_total").value(
        device=p.device_labels[0]) == pytest.approx(4 * chip.run_s)
    # the validator's batch: rows first, then the generic lane's
    # dispatch queued behind it for one run
    q = reg.get("provider_dispatch_queue_wait_seconds")
    assert q._sum[(("lane", "generic"), ("site", "validator"))] == \
        pytest.approx(chip.run_s - chip.enqueue_s)


def test_an_output_already_ready_is_counted_unobserved(rig):
    p, clock, chip, reg = rig
    resolve = p.batch_verify_async(signed(1))
    clock.now += 1.0                  # the caller did something else
    assert resolve().all()
    assert reg.get("provider_dispatch_unobserved_total").value(
        lane="generic") == 1
    assert reg.get("provider_device_held_seconds_total") is None
    assert sum(series(reg, "provider_dispatch_total").values()) == \
        p.stats["dispatches"] == 1


@pytest.mark.parametrize("wrap", ["caching", "degrading", "both"])
def test_site_survives_the_wrapping_providers(rig, wrap):
    p, clock, chip, reg = rig
    outer = p
    if wrap in ("degrading", "both"):
        outer = DegradingProvider(outer, SW)
    if wrap in ("caching", "both"):
        outer = CachingProvider(outer, VerdictCache(), site="sigfilter")
    with dispatch_site("block_sig"):
        assert outer.batch_verify(signed(1)).all()
        assert outer.batch_verify_async(signed(1))().all()
    assert outer.verify(signed(1)[0])
    sites = {dict(k)["site"]: n for k, n in
             series(reg, "provider_dispatch_total").items()}
    assert sites == {"block_sig": 2, "other": 1}


def test_sites_are_a_fixed_list_and_nest():
    assert current_site() == "other"
    with dispatch_site("endorser"):
        with dispatch_site("handshake"):
            assert current_site() == "handshake"
        assert current_site() == "endorser"
    assert current_site() == "other"
    assert len(DISPATCH_SITES) == 8
    with pytest.raises(ValueError):
        with dispatch_site("somewhere"):
            pass


def test_table_build_span_under_the_batchs_span(rig):
    p, clock, chip, reg = rig
    t = tracing.tracer
    was = t.enabled
    t.enabled = True
    try:
        with t.start_span("test.block") as root:
            with dispatch_site("validator"):
                assert p.batch_verify(signed(1, 64)).all()
        rec = t.recorder.get(root.context.trace_id)
    finally:
        t.enabled = was
    spans = {s["name"]: s for s in rec["spans"]}
    batch, build = spans["bccsp.batch_verify"], spans["provider.table_build"]
    assert batch["parent_id"] == root.context.span_id
    assert build["parent_id"] == batch["span_id"]
    assert build["attributes"]["keys"] == 1
    assert p.stats_snapshot().p256_table_builds == 1
    # the batch's span carries its dispatches' records
    (record,) = batch["attributes"]["dispatch_records"]
    assert record["program"] == "rows@4" and record["site"] == "validator"
    assert record["sigs"] == 64 and record["observed"]
    assert record["held_ms"] == pytest.approx(chip.run_s * 1e3)
    # the build is not the dispatch's packing: the scripted clock does
    # not move while packing, so nothing is left
    assert record["pack_ms"] == 0.0
    from fabric_tpu.ops_plane import registry
    assert registry.get("provider_table_build_seconds") is not None


# -- two curves, four lanes (PR 32) ---------------------------------------------

def signed_ed25519(n_keys: int, per_key: int = 1) -> list:
    from fabric_tpu.bccsp.provider import SCHEME_ED25519
    items = []
    for i in range(n_keys):
        key = SW.key_gen(SCHEME_ED25519)
        for j in range(per_key):
            msg = b"message %d %d " % (i, j) * (1 + 40 * j)
            items.append(VerifyItem(SCHEME_ED25519, key.public_bytes(),
                                    SW.sign(key, msg), msg))
    return items


@pytest.fixture
def mixed_rig(rig):
    """The rig with stand-ins on the Ed25519 lanes too, each call noted
    in the order the chip got it."""
    from fabric_tpu.bccsp.provider import SCHEME_ED25519
    p, clock, chip, reg = rig
    calls = []

    def noting(name, fn):
        def call(*args):
            calls.append(name)
            return fn(*args)
        return call

    p._fns[SCHEME_P256] = noting("generic", chip.generic)
    p._fns["p256-rows"] = noting("rows", chip.rows)
    p._fns[SCHEME_ED25519] = noting("ed25519", chip.generic)
    p._fns["ed25519-rows"] = noting("ed25519-rows", chip.rows)
    return p, reg, calls


def lanes_of(reg, name) -> dict:
    out = {}
    for labels, n in series(reg, name).items():
        lane = dict(labels)["lane"]
        out[lane] = out.get(lane, 0) + n
    return out


def test_a_mixed_batch_moves_one_lane_a_kernel(mixed_rig):
    """A block of two curves is two programs, and the account tells them
    apart: `rows` and `ed25519-rows` move by one each, under their own
    program names; the sums are still the provider's own counts."""
    p, reg, calls = mixed_rig
    # the Ed25519 items first: the order is the provider's decision
    # (P-256 first), not the scheme of whichever item came first
    items = signed_ed25519(1, 70) + signed(1, 70)
    with dispatch_site("validator"):
        assert p.batch_verify(items).all()
    assert calls == ["rows", "ed25519-rows"]
    assert lanes_of(reg, "provider_dispatch_total") == {
        "rows": 1, "ed25519-rows": 1}
    assert lanes_of(reg, "provider_dispatch_sigs_total") == {
        "rows": 70, "ed25519-rows": 70}
    programs = {dict(k)["program"]
                for k in series(reg, "provider_dispatch_total")}
    assert programs == {"rows@4", "ed25519-rows@4"}
    assert sum(series(reg, "provider_dispatch_total").values()) \
        == p.stats["dispatches"] == 2
    assert sum(series(reg, "provider_dispatch_sigs_total").values()) \
        == p.stats["device_sigs"] == 140
    held = reg.get("provider_dispatch_held_seconds")
    assert {dict(k)["lane"] for k in held._sum} == {"rows", "ed25519-rows"}
    pack = reg.get("provider_dispatch_pack_seconds")
    assert {dict(k)["lane"] for k in pack._sum} == {"rows", "ed25519-rows"}


def test_the_ladder_lanes_are_told_apart_too(mixed_rig):
    p, reg, calls = mixed_rig
    assert p.batch_verify(signed_ed25519(2) + signed(3)).all()
    assert calls == ["generic", "ed25519"]
    assert lanes_of(reg, "provider_dispatch_sigs_total") == {
        "generic": 3, "ed25519": 2}
    programs = {dict(k)["program"]
                for k in series(reg, "provider_dispatch_total")}
    assert programs == {"generic@128", "ed25519@128"}


def test_a_p256_only_batch_leaves_no_ed25519_series(mixed_rig):
    """Nothing changes for a P-256-only window: its exposition carries
    no series of the Ed25519 lanes."""
    p, reg, calls = mixed_rig
    with dispatch_site("validator"):
        assert p.batch_verify(signed(1, 70) + signed(3)).all()
    assert calls == ["rows", "generic"]
    assert "ed25519" not in reg.expose_text()
    for name in ("provider_dispatch_total", "provider_dispatch_sigs_total"):
        assert set(lanes_of(reg, name)) == {"rows", "generic"}
