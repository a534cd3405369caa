"""Always-on sampling profiler (ops_plane/sampler.py).

Unit coverage under INJECTED stacks and clocks (no wall-clock sleeps,
no flakes): deterministic folded aggregation, fine-ring bounds, the
fine→coarse tier carry (evicted counts merge, never drop), trailing-
window profile selection, the folded-text interchange format, the
self/total top-N table, role collapsing for pool-numbered threads —
plus one real-thread walk (a named spinning function must appear in
the fold) and the zero-overhead guard: with no profiler constructed,
/profile/sampled does not exist and /metrics is byte-identical.
"""

import json
import threading
import time
import urllib.error
import urllib.request

import pytest

from fabric_tpu.ops_plane.metrics import MetricsRegistry
from fabric_tpu.ops_plane.sampler import (
    SamplingProfiler,
    register_routes,
    role_of,
)
from fabric_tpu.ops_plane.server import OperationsServer


def _prof(reg=None, **cfg):
    cfg.setdefault("hz", 10.0)
    cfg.setdefault("window_s", 10.0)
    cfg.setdefault("windows", 3)
    cfg.setdefault("coarse_window_s", 60.0)
    cfg.setdefault("coarse_windows", 2)
    return SamplingProfiler(cfg, registry=reg or MetricsRegistry())


def _inject(p, stacks):
    p._collect_stacks = lambda: list(stacks)


def _get(addr, path):
    return urllib.request.urlopen(f"http://{addr[0]}:{addr[1]}{path}",
                                  timeout=5)


# ---------------------------------------------------------------------------
# aggregation under injected stacks
# ---------------------------------------------------------------------------

def test_role_collapses_pool_numbered_names():
    assert role_of("workload-17") == "workload"
    assert role_of("Thread-3") == "Thread"
    assert role_of("slo-evaluator") == "slo-evaluator"
    assert role_of("raft_7") == "raft"
    assert role_of("123") == "123"      # never collapses to empty


def test_deterministic_folded_aggregation():
    p = _prof()
    _inject(p, ["main;a.f;a.g", "worker;b.h"])
    for i in range(7):
        p.sample_once(now=1000.0 + i)
    prof = p.profile(window_s=60.0, now=1006.0)
    assert prof["samples"] == 7
    assert prof["folded"] == {"main;a.f;a.g": 7, "worker;b.h": 7}


def test_fine_ring_bounds_and_tier_carry():
    """Evicted fine windows MERGE into coarse buckets: total sample
    counts are conserved across the tier boundary (the r15 carry)."""
    p = _prof(windows=3, coarse_window_s=60.0, coarse_windows=10)
    _inject(p, ["main;a.f"])
    # 8 sealed 10s windows + 1 open: fine holds 3, coarse absorbs 5
    for k in range(9):
        for _ in range(4):
            p.sample_once(now=1000.0 + k * 10.0)
    assert len(p._fine) == 3
    assert p._coarse, "evicted windows must land in the coarse tier"
    total = sum(w.samples for w in p._coarse) \
        + sum(w.samples for w in p._fine) + p._open.samples
    assert total == 9 * 4               # nothing dropped
    # coarse buckets align to coarse_window_s boundaries
    for w in p._coarse:
        assert w.start % 60.0 == 0.0


def test_coarse_ring_is_bounded():
    p = _prof(windows=1, coarse_window_s=60.0, coarse_windows=2)
    _inject(p, ["m;x.y"])
    for k in range(40):                 # 40 distinct 10s buckets
        p.sample_once(now=1000.0 + k * 10.0)
    assert len(p._coarse) <= 2


def test_profile_trailing_window_selection():
    """Only buckets overlapping (now - window_s, now] merge in."""
    p = _prof(windows=10)
    _inject(p, ["m;old.f"])
    p.sample_once(now=1000.0)
    _inject(p, ["m;new.f"])
    p.sample_once(now=1100.0)
    prof = p.profile(window_s=50.0, now=1110.0)
    assert "m;new.f" in prof["folded"]
    assert "m;old.f" not in prof["folded"]
    both = p.profile(window_s=200.0, now=1110.0)
    assert set(both["folded"]) == {"m;old.f", "m;new.f"}


def test_windows_overlapping():
    p = _prof()
    _inject(p, ["m;a.b"])
    p.sample_once(now=1000.0)
    p.sample_once(now=1010.0)
    assert len(p.windows_overlapping(1000.0, 1005.0)) == 1
    assert len(p.windows_overlapping(995.0, 1015.0)) == 2
    assert p.windows_overlapping(2000.0, 2010.0) == []


def test_folded_text_format():
    text = SamplingProfiler.folded_text(
        {"main;a.f;a.g": 31, "worker;b.h": 99})
    lines = text.splitlines()
    assert lines[0] == "worker;b.h 99"          # hottest first
    assert lines[1] == "main;a.f;a.g 31"


def test_top_table_self_vs_total():
    """`self` counts leaf appearances; `total` counts any appearance
    (once per stack, even if the frame recurses)."""
    folded = {"main;a.f;a.g": 10,       # a.g leaf, a.f interior
              "main;a.f": 5,            # a.f leaf
              "main;a.f;a.f;a.g": 2}    # recursion: a.f counted once
    rows = {r["frame"]: r for r in
            SamplingProfiler.top_table(folded, 10)}
    assert rows["a.g"]["self"] == 12
    assert rows["a.g"]["total"] == 12
    assert rows["a.f"]["self"] == 5
    assert rows["a.f"]["total"] == 17
    assert rows["a.g"]["self_frac"] == pytest.approx(12 / 17, abs=1e-3)


def test_max_depth_truncates_leaf_up():
    p = _prof(max_depth=2)

    def deep(n):
        if n:
            return deep(n - 1)
        time.sleep(0.5)

    th = threading.Thread(target=deep, args=(20,),
                          name="deep-worker", daemon=True)
    th.start()
    try:
        time.sleep(0.05)
        stacks = [s for s in p._collect_stacks()
                  if s.startswith("deep-worker;")]
        assert stacks
        # role + at most max_depth frames
        assert all(len(s.split(";")) <= 1 + 2 for s in stacks)
    finally:
        th.join(timeout=2.0)


# ---------------------------------------------------------------------------
# real threads + live route
# ---------------------------------------------------------------------------

def test_real_thread_walk_finds_named_function():
    stop = threading.Event()

    def spin_here_marker():
        while not stop.wait(0.001):
            pass

    th = threading.Thread(target=spin_here_marker,
                          name="spin-worker-1", daemon=True)
    th.start()
    p = _prof()
    try:
        time.sleep(0.02)
        found = False
        for _ in range(50):
            for s in p._collect_stacks():
                if s.startswith("spin-worker;") \
                        and "spin_here_marker" in s:
                    found = True
            if found:
                break
        assert found, "the spinning thread never appeared in the fold"
    finally:
        stop.set()
        th.join(timeout=2.0)


def test_sampler_thread_excludes_itself():
    reg = MetricsRegistry()
    p = _prof(reg)
    p.start()
    try:
        deadline = time.monotonic() + 5.0
        while time.monotonic() < deadline:
            prof = p.profile(window_s=120.0)
            if prof["samples"] >= 3:
                break
            time.sleep(0.05)
        assert prof["samples"] >= 3
        assert not any(s.startswith("profile-sampler;")
                       for s in prof["folded"])
    finally:
        p.stop()


def test_route_json_and_folded():
    reg = MetricsRegistry()
    p = _prof(reg)
    _inject(p, ["main;a.f;a.g"])
    p.sample_once(now=time.time())
    ops = OperationsServer(metrics=reg)
    register_routes(ops, p)
    ops.start()
    try:
        doc = json.load(_get(ops.addr, "/profile/sampled?window=3600"))
        assert doc["samples"] == 1
        assert isinstance(doc["folded"], str)
        assert "main;a.f;a.g 1" in doc["folded"]
        assert doc["top"][0]["frame"] == "a.g"
        resp = _get(ops.addr, "/profile/sampled?window=3600&fmt=folded")
        assert resp.read().decode() == "main;a.f;a.g 1"
        with pytest.raises(urllib.error.HTTPError) as ei:
            _get(ops.addr, "/profile/sampled?window=bogus")
        assert ei.value.code == 400
    finally:
        ops.stop()


# ---------------------------------------------------------------------------
# zero-overhead guard
# ---------------------------------------------------------------------------

def test_zero_overhead_when_disabled(same_exposition):
    """The acceptance guard: no profiler constructed -> no
    /profile/sampled route, no profiler_* series, /metrics
    byte-identical to a registry that never heard of this PR."""
    reg = MetricsRegistry()
    reg.counter("committed_txs_total").add(5)
    before = reg.expose_text()
    ops = OperationsServer(metrics=reg)
    ops.start()
    try:
        with pytest.raises(urllib.error.HTTPError) as ei:
            _get(ops.addr, "/profile/sampled")
        assert ei.value.code == 404
        text = _get(ops.addr, "/metrics").read().decode()
        same_exposition(before, text)
        assert "profiler_" not in text
    finally:
        ops.stop()
    # constructing (without sampling) registers counters at zero but
    # never invents samples; the live guard is the node never
    # constructing a disabled plane
    assert "profiler_samples_total" not in before
