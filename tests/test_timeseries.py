"""Metric time-series store + resource telemetry + leak gate.

Unit coverage, everything under INJECTED clocks (no wall-clock sleeps,
no flakes): ring retention and raw→1m→10m downsampling, registry-sweep
sampling of counters/gauges/histograms, the Theil–Sen slope detector
on the four canonical shapes (flat, linear leak, sawtooth, step), the
leak gate's per-series verdicts, the `/metrics/history` ops route, the
resource collector's gauges, and the zero-overhead guard: with nothing
enabled, /metrics carries no resource series and /metrics/history does
not exist.
"""

import json
import random
import urllib.error
import urllib.request

import pytest

from fabric_tpu.ops_plane.metrics import MetricsRegistry
from fabric_tpu.ops_plane.resources import ResourceCollector
from fabric_tpu.ops_plane.server import OperationsServer
from fabric_tpu.ops_plane import timeseries
from fabric_tpu.ops_plane.timeseries import (
    TimeSeriesStore,
    assess_leak,
    evaluate_leak_gate,
    theil_sen,
)


class FakeClock:
    def __init__(self, t: float = 0.0):
        self.t = t

    def __call__(self) -> float:
        return self.t

    def tick(self, dt: float = 1.0) -> float:
        self.t += dt
        return self.t


def make_store(clock, **cfg):
    base = {"interval_s": 1.0, "raw_window_s": 60.0,
            "m1_window_s": 600.0, "m10_window_s": 6000.0}
    base.update(cfg)
    return TimeSeriesStore(base, registry=MetricsRegistry(), clock=clock)


# ---------------------------------------------------------------------------
# ring store: retention + downsampling
# ---------------------------------------------------------------------------

def test_raw_ring_is_bounded_and_windowed():
    clk = FakeClock()
    st = make_store(clk)
    for i in range(500):
        st.record("s", float(i), now=float(i))
    h = st.history("s", window_s=30.0, now=499.0)
    assert h["tier"] == "raw"
    assert [p[0] for p in h["points"]] == [float(t) for t in
                                           range(469, 500)]
    # the ring itself never exceeds its configured span (60s @ 1s + 2)
    full = st.history("s", window_s=60.0, now=499.0)
    assert len(full["points"]) <= 62


def test_downsampling_tiers_carry_mean_min_max():
    clk = FakeClock()
    st = make_store(clk)
    # 0..599: value = minute index, with a +10 spike at each minute's
    # 30th second — the 1m bucket must keep mean strictly between
    # min and max and preserve the extremes
    for i in range(600):
        minute = i // 60
        v = float(minute) + (10.0 if i % 60 == 30 else 0.0)
        st.record("s", v, now=float(i))
    h = st.history("s", window_s=600.0, tier="1m", now=599.0)
    closed = h["points"][:-1]          # last entry is the open bucket
    assert len(closed) >= 9
    for t, mean, mn, mx in closed:
        assert t % 60 == 0
        assert mx == mn + 10.0
        assert mn < mean < mx
    # 10m tier: a single closed bucket only appears once 600s elapse
    st.record("s", 0.0, now=600.0)
    h10 = st.history("s", window_s=6000.0, tier="10m", now=600.0)
    closed10 = [p for p in h10["points"] if p[0] == 0.0]
    assert closed10 and closed10[0][3] == 19.0     # max spike preserved


def test_tier_autoselection_follows_window():
    clk = FakeClock()
    st = make_store(clk)
    st.record("s", 1.0, now=0.0)
    assert st.history("s", window_s=10.0)["tier"] == "raw"
    assert st.history("s", window_s=60.0)["tier"] == "raw"
    assert st.history("s", window_s=61.0)["tier"] == "1m"
    assert st.history("s", window_s=601.0)["tier"] == "10m"
    with pytest.raises(ValueError):
        st.history("s", tier="5m")


def test_sample_sweeps_every_registered_metric_kind():
    reg = MetricsRegistry()
    c = reg.counter("reqs_total")
    g = reg.gauge("depth")
    h = reg.histogram("lat_seconds")
    clk = FakeClock()
    st = TimeSeriesStore({"interval_s": 1.0}, registry=reg, clock=clk)
    for i in range(5):
        c.add(3, channel="ch")
        g.set(float(i), shard="0")
        g.set(float(i) + 2.0, shard="1")
        h.observe(0.01)
        st.sample(now=float(i))
    names = st.names()
    assert {"reqs_total", "depth", "lat_seconds_count",
            "lat_seconds_sum"} <= set(names)
    pts = st.history("reqs_total", now=4.0)["points"]
    assert [p[1] for p in pts] == [3.0, 6.0, 9.0, 12.0, 15.0]
    # gauges record the mean over label sets
    assert st.history("depth", now=4.0)["points"][-1][1] == 5.0
    assert st.history("lat_seconds_count", now=4.0)["points"][-1][1] == 5.0


# ---------------------------------------------------------------------------
# Theil–Sen detector: the four canonical shapes
# ---------------------------------------------------------------------------

def _shapes():
    rng = random.Random(7)
    flat = [(float(i), 100.0 + rng.uniform(-1, 1)) for i in range(60)]
    leak = [(float(i), 100.0 + 0.8 * i + rng.uniform(-0.5, 0.5))
            for i in range(60)]
    saw = [(float(i), 100.0 + (i % 10)) for i in range(60)]
    step = [(float(i), 100.0 + (5.0 if i >= 30 else 0.0))
            for i in range(60)]
    return flat, leak, saw, step


def test_theil_sen_estimates_slope_with_ci():
    _, leak, _, _ = _shapes()
    est = theil_sen(leak)
    assert est["ci_lo"] <= est["slope"] <= est["ci_hi"]
    assert abs(est["slope"] - 0.8) < 0.05
    assert est["ci_lo"] > 0.5
    assert theil_sen([(0.0, 1.0)]) is None
    assert theil_sen([]) is None


def test_leak_verdicts_flat_leak_sawtooth_step():
    flat, leak, saw, step = _shapes()
    assert assess_leak(flat)["leaking"] is False
    v = assess_leak(leak)
    assert v["leaking"] is True and v["verdict"] == "leaking"
    assert v["growth_frac"] > 0.05
    # a bounded oscillation is not a leak
    assert assess_leak(saw)["leaking"] is False
    # a one-time step is not a leak: the slope CI touches zero
    assert assess_leak(step)["leaking"] is False


def test_leak_gate_warmup_and_insufficient_data():
    # a startup ramp followed by flat: warmup excludes the ramp
    pts = [(float(i), 10.0 * min(i, 40)) for i in range(60)]
    assert assess_leak(pts)["leaking"] is True
    assert assess_leak(pts, warmup_s=40.0)["leaking"] is False
    v = assess_leak(pts[:3])
    assert v["verdict"] == "insufficient_data" and v["leaking"] is False


def test_evaluate_leak_gate_names_the_leaking_series():
    clk = FakeClock()
    st = make_store(clk)
    rng = random.Random(3)
    for i in range(60):
        st.record("flat_series", 50.0 + rng.uniform(-1, 1), now=float(i))
        st.record("leaky_series", 50.0 + 2.0 * i, now=float(i))
    clk.t = 59.0
    gate = evaluate_leak_gate(
        st, {"flat_series": {}, "leaky_series": {}}, window_s=60.0)
    assert gate["leaking"] == ["leaky_series"]
    assert gate["pass"] is False
    assert gate["series"]["leaky_series"]["slope_per_s"] > 1.5
    assert gate["series"]["flat_series"]["verdict"] == "flat"


# ---------------------------------------------------------------------------
# /metrics/history route + zero-overhead guard
# ---------------------------------------------------------------------------

def _get(addr, path):
    host, port = addr
    return urllib.request.urlopen(f"http://{host}:{port}{path}",
                                  timeout=5)


def test_history_route_serves_series_and_404s_unknown(same_exposition):
    reg = MetricsRegistry()
    clk = FakeClock()
    st = TimeSeriesStore({"interval_s": 1.0}, registry=reg, clock=clk)
    for i in range(10):
        st.record("queue_depth", float(i), now=float(i))
    ops = OperationsServer(metrics=reg)
    timeseries.register_routes(ops, st)
    ops.start()
    try:
        clk.t = 9.0
        idx = json.loads(_get(ops.addr, "/metrics/history").read())
        assert idx["series"] == ["queue_depth"]
        doc = json.loads(_get(
            ops.addr,
            "/metrics/history?name=queue_depth&window=5").read())
        assert doc["tier"] == "raw"
        assert [p[1] for p in doc["points"]] == [4.0, 5.0, 6.0, 7.0,
                                                 8.0, 9.0]
        with pytest.raises(urllib.error.HTTPError) as ei:
            _get(ops.addr, "/metrics/history?name=nope")
        assert ei.value.code == 404
        # the built-in exposition is untouched by the prefix route
        text = _get(ops.addr, "/metrics").read().decode()
        same_exposition(text, reg.expose_text())
    finally:
        ops.stop()


def test_zero_overhead_when_disabled(same_exposition):
    """The acceptance guard: a node that leaves timeseries/resources
    disabled serves a /metrics surface with NO resource series and NO
    /metrics/history route — byte-identical exposition to a registry
    that never heard of this PR."""
    reg = MetricsRegistry()
    reg.counter("committed_txs_total").add(5)
    before = reg.expose_text()
    ops = OperationsServer(metrics=reg)
    ops.start()
    try:
        with pytest.raises(urllib.error.HTTPError) as ei:
            _get(ops.addr, "/metrics/history")
        assert ei.value.code == 404
        text = _get(ops.addr, "/metrics").read().decode()
        same_exposition(before, text)
        for name in ("process_resident_memory_bytes", "process_open_fds",
                     "process_threads", "native_arena_pool_free"):
            assert name not in text
    finally:
        ops.stop()
    # constructing a store never mutates the registry either
    st = TimeSeriesStore(registry=reg, clock=FakeClock())
    st.sample()
    same_exposition(before, reg.expose_text())


# ---------------------------------------------------------------------------
# resource collector
# ---------------------------------------------------------------------------

def test_resource_collector_populates_gauges_and_sources():
    reg = MetricsRegistry()
    col = ResourceCollector({"interval_s": 60.0}, registry=reg)
    col.add_source("verdict_cache_occupancy", lambda: 42.0)
    snap = col.collect()
    # /proc is Linux; the suite runs there, so these must be live
    assert snap["process_resident_memory_bytes"] > 1e6
    assert snap["process_open_fds"] >= 3
    assert snap["process_threads"] >= 1
    assert snap["verdict_cache_occupancy"] == 42.0
    text = reg.expose_text()
    assert "process_resident_memory_bytes" in text
    assert "verdict_cache_occupancy 42.0" in text
    # a failing source skips the tick instead of killing the sweep
    col.add_source("broken", lambda: 1 / 0)
    snap2 = col.collect()
    assert "broken" not in snap2


def test_resource_series_flow_into_the_store():
    reg = MetricsRegistry()
    col = ResourceCollector({"interval_s": 60.0}, registry=reg)
    clk = FakeClock()
    st = TimeSeriesStore({"interval_s": 1.0}, registry=reg, clock=clk)
    for i in range(5):
        col.collect()
        st.sample(now=float(i))
    pts = st.history("process_open_fds", now=4.0)["points"]
    assert len(pts) == 5 and all(p[1] >= 3 for p in pts)
