"""SLO-triggered incident capture (ops_plane/incidents.py).

Unit coverage under injected clocks and `sync=True` capture (no
thread races): bundle layout + MANIFEST round-trip, tamper/truncation/
deletion detection by name, per-objective cooldown suppression,
bounded retention gc with sequence numbers surviving, cluster fan-out
with one live and one dead peer (bundle lands, marked partial, dead
peer recorded as an error entry), the live /incidents routes, the
SloEvaluator on_fire/on_clear integration, and the zero-overhead
guard: no recorder constructed -> no routes, no incidents_* series,
byte-identical /metrics.
"""

import json
import os
import urllib.error
import urllib.request

import pytest

from fabric_tpu.ops_plane import slo as slo_mod
from fabric_tpu.ops_plane.incidents import (
    IncidentRecorder,
    register_routes,
    verify_bundle,
)
from fabric_tpu.ops_plane.metrics import MetricsRegistry
from fabric_tpu.ops_plane.server import OperationsServer


class FakeClock:
    def __init__(self, t: float = 1000.0):
        self.t = t

    def __call__(self) -> float:
        return self.t

    def tick(self, dt: float = 1.0) -> float:
        self.t += dt
        return self.t


def _rec(tmp_path, reg=None, clock=None, **cfg):
    cfg.setdefault("dir", str(tmp_path / "incidents"))
    cfg.setdefault("sync", True)
    cfg.setdefault("cooldown_s", 30.0)
    return IncidentRecorder(cfg, registry=reg or MetricsRegistry(),
                            clock=clock or FakeClock(),
                            node_name="test-node")


def _alert(objective="shed_rate", **kw):
    a = {"objective": objective, "metric": "gateway_shed_total",
         "kind": "max", "threshold": 1.0, "value": 7.5,
         "burn_short": 7.5, "burn_long": 3.1, "state": "firing",
         "fired_at": 1000.0}
    a.update(kw)
    return a


def _get(addr, path):
    return urllib.request.urlopen(f"http://{addr[0]}:{addr[1]}{path}",
                                  timeout=5)


# ---------------------------------------------------------------------------
# bundle layout + MANIFEST
# ---------------------------------------------------------------------------

def test_bundle_layout_and_manifest_roundtrip(tmp_path):
    rec = _rec(tmp_path)
    rec.add_source("gateway", lambda: {"queue_depth": 12})
    try:
        bid = rec.on_alert_fired("shed_rate", _alert())
        assert bid == "incident_0001"
        bundle = os.path.join(rec.dir, bid)
        for f in ("incident.json", "snapshots.json", "jlog_tail.txt",
                  "traces.json", "MANIFEST.json"):
            assert os.path.exists(os.path.join(bundle, f)), f
        with open(os.path.join(bundle, "incident.json")) as f:
            inc = json.load(f)
        assert inc["objective"] == "shed_rate"
        assert inc["node"] == "test-node"
        assert inc["partial"] is False
        assert inc["alert"]["value"] == 7.5
        with open(os.path.join(bundle, "snapshots.json")) as f:
            snaps = json.load(f)
        assert snaps["gateway"] == {"queue_depth": 12}
        v = verify_bundle(bundle)
        assert v["ok"], v
        assert v["files"] >= 4
    finally:
        rec.stop()


def test_manifest_detects_tamper_missing_and_extra(tmp_path):
    rec = _rec(tmp_path)
    try:
        bundle = os.path.join(rec.dir,
                              rec.on_alert_fired("obj", _alert("obj")))
        # tamper
        with open(os.path.join(bundle, "snapshots.json"), "a") as f:
            f.write(" ")
        v = verify_bundle(bundle)
        assert not v["ok"] and v["mismatched"] == ["snapshots.json"]
        # deletion
        os.remove(os.path.join(bundle, "snapshots.json"))
        v = verify_bundle(bundle)
        assert not v["ok"] and v["missing"] == ["snapshots.json"]
        # planted file
        with open(os.path.join(bundle, "planted.txt"), "w") as f:
            f.write("x")
        assert "planted.txt" in verify_bundle(bundle)["extra"]
        # no MANIFEST at all
        os.remove(os.path.join(bundle, "MANIFEST.json"))
        assert not verify_bundle(bundle)["ok"]
    finally:
        rec.stop()


def test_failing_source_recorded_inline_not_fatal(tmp_path):
    rec = _rec(tmp_path)
    rec.add_source("boom", lambda: 1 / 0)
    rec.add_source("fine", lambda: {"ok": 1})
    try:
        bundle = os.path.join(rec.dir,
                              rec.on_alert_fired("o", _alert("o")))
        with open(os.path.join(bundle, "snapshots.json")) as f:
            snaps = json.load(f)
        assert "error" in snaps["boom"]
        assert snaps["fine"] == {"ok": 1}
        assert verify_bundle(bundle)["ok"]
    finally:
        rec.stop()


# ---------------------------------------------------------------------------
# cooldown + retention
# ---------------------------------------------------------------------------

def test_per_objective_cooldown(tmp_path):
    clock = FakeClock()
    reg = MetricsRegistry()
    rec = _rec(tmp_path, reg=reg, clock=clock, cooldown_s=60.0)
    try:
        assert rec.on_alert_fired("a", _alert("a")) is not None
        clock.tick(10.0)
        # same objective inside the window: suppressed
        assert rec.on_alert_fired("a", _alert("a")) is None
        # a DIFFERENT objective is not hostage to a's cooldown
        assert rec.on_alert_fired("b", _alert("b")) is not None
        clock.tick(60.0)
        assert rec.on_alert_fired("a", _alert("a")) is not None
        idx = rec.index()
        assert idx["count"] == 3
        assert len(idx["suppressed"]) == 1
        assert idx["suppressed"][0]["objective"] == "a"
        text = reg.expose_text()
        assert "incidents_captured_total 3" in text
        assert "incidents_suppressed_total 1" in text
    finally:
        rec.stop()


def test_retention_gc_keeps_newest_and_sequence_survives(tmp_path):
    clock = FakeClock()
    rec = _rec(tmp_path, clock=clock, keep=2, cooldown_s=0.0)
    try:
        for i in range(4):
            clock.tick(1.0)
            rec.on_alert_fired(f"obj{i}", _alert(f"obj{i}"))
        ids = [m["id"] for m in rec.list()]
        assert ids == ["incident_0003", "incident_0004"]
    finally:
        rec.stop()
    # a restarted recorder continues the sequence instead of reusing
    # gc'd ids (scan of surviving bundle dirs)
    rec2 = _rec(tmp_path, keep=10, cooldown_s=0.0)
    try:
        assert rec2.on_alert_fired("next", _alert("next")) \
            == "incident_0005"
    finally:
        rec2.stop()


def test_clear_transition_never_captures(tmp_path):
    rec = _rec(tmp_path)
    try:
        rec.on_alert_cleared("a", _alert("a", state="resolved"))
        assert rec.index()["count"] == 0
    finally:
        rec.stop()


# ---------------------------------------------------------------------------
# cluster fan-out
# ---------------------------------------------------------------------------

def test_fanout_one_live_one_dead_peer(tmp_path):
    peer_reg = MetricsRegistry()
    peer_rec = IncidentRecorder(
        {"dir": str(tmp_path / "peer_inc"), "sync": True},
        registry=peer_reg, node_name="peer-node")
    peer_rec.add_source("lifecycle", lambda: {"lifecycle": "serving"})
    peer_ops = OperationsServer(metrics=peer_reg)
    register_routes(peer_ops, peer_rec)
    peer_ops.start()
    live = "%s:%d" % peer_ops.addr
    dead = "127.0.0.1:1"
    rec = _rec(tmp_path, peers=[live, dead], peer_timeout_s=1.0)
    try:
        bid = rec.on_alert_fired("shed_rate", _alert())
        bundle = os.path.join(rec.dir, bid)
        with open(os.path.join(bundle, "incident.json")) as f:
            inc = json.load(f)
        assert inc["partial"] is True       # the dead peer marks it
        assert inc["peers"][live] == "ok"
        assert inc["peers"][dead] == "unreachable"
        live_file = os.path.join(
            bundle, "peers", live.replace(":", "_") + ".json")
        with open(live_file) as f:
            snap = json.load(f)
        assert snap["node"] == "peer-node"
        assert snap["snapshots"]["lifecycle"] == {"lifecycle": "serving"}
        dead_file = os.path.join(
            bundle, "peers", dead.replace(":", "_") + ".json")
        with open(dead_file) as f:
            assert json.load(f)["error"] == "unreachable"
        # partial bundles still verify: the MANIFEST covers what WAS
        # captured
        assert verify_bundle(bundle)["ok"]
    finally:
        rec.stop()
        peer_ops.stop()
        peer_rec.stop()


# ---------------------------------------------------------------------------
# live routes
# ---------------------------------------------------------------------------

def test_routes_index_get_snapshot(tmp_path):
    reg = MetricsRegistry()
    rec = _rec(tmp_path, reg=reg)
    ops = OperationsServer(metrics=reg)
    register_routes(ops, rec)
    ops.start()
    try:
        bid = rec.on_alert_fired("shed_rate", _alert())
        idx = json.load(_get(ops.addr, "/incidents"))
        assert idx["count"] == 1
        assert idx["incidents"][0]["id"] == bid
        assert idx["incidents"][0]["objective"] == "shed_rate"
        one = json.load(_get(ops.addr, f"/incidents/{bid}"))
        assert one["verify"]["ok"]
        assert one["incident"]["objective"] == "shed_rate"
        assert one["files"]
        with pytest.raises(urllib.error.HTTPError) as ei:
            _get(ops.addr, "/incidents/incident_9999")
        assert ei.value.code == 404
        snap = json.load(_get(ops.addr, "/incidents/snapshot"))
        assert snap["node"] == "test-node"
    finally:
        ops.stop()
        rec.stop()


# ---------------------------------------------------------------------------
# SLO evaluator integration
# ---------------------------------------------------------------------------

def test_slo_fire_captures_bundle_with_objective(tmp_path):
    """End-to-end through slo.py: a gauge objective crosses its
    threshold under an injected clock, the evaluator fires, the hook
    captures a bundle naming the objective; the clear transition
    captures nothing further."""
    reg = MetricsRegistry()
    g = reg.gauge("test_pressure", "test gauge")
    ev = slo_mod.SloEvaluator(
        {"sample_interval_s": 1.0, "short_window_s": 3.0,
         "long_window_s": 9.0,
         "objectives": {
             "pressure": {"kind": "max", "source": "gauge_mean",
                          "metric": "test_pressure", "threshold": 1.0},
             "commit_p99_s": {"enabled": False},
             "verify_throughput_floor": {"enabled": False},
             "breaker_open_frac": {"enabled": False},
             "overlap_floor": {"enabled": False},
         }},
        registry=reg)
    rec = _rec(tmp_path, reg=reg, cooldown_s=0.0)
    rec.attach_slo(ev)
    try:
        g.set(25.0)                     # 25x threshold: instant burn
        now = 1000.0
        for _ in range(12):
            ev.step(now)
            now += 1.0
        assert rec.index()["count"] == 1
        meta = rec.list()[0]
        assert meta["objective"] == "pressure"
        bundle = os.path.join(rec.dir, meta["id"])
        with open(os.path.join(bundle, "snapshots.json")) as f:
            snaps = json.load(f)
        assert "slo" in snaps           # evaluator status rode along
        # recovery clears the alert without another bundle
        g.set(0.0)
        for _ in range(30):
            ev.step(now)
            now += 1.0
        assert rec.index()["count"] == 1
    finally:
        rec.stop()
        ev.stop()


def test_detach_on_stop(tmp_path):
    ev = slo_mod.SloEvaluator({"sample_interval_s": 1.0},
                              registry=MetricsRegistry())
    rec = _rec(tmp_path)
    rec.attach_slo(ev)
    assert ev.on_fire is not None
    rec.stop()
    assert ev.on_fire is None and ev.on_clear is None
    ev.stop()


# ---------------------------------------------------------------------------
# zero-overhead guard
# ---------------------------------------------------------------------------

def test_zero_overhead_when_disabled(same_exposition):
    """No recorder constructed -> no /incidents routes and no
    incidents_* series; /metrics byte-identical."""
    reg = MetricsRegistry()
    reg.counter("committed_txs_total").add(5)
    before = reg.expose_text()
    ops = OperationsServer(metrics=reg)
    ops.start()
    try:
        for path in ("/incidents", "/incidents/snapshot"):
            with pytest.raises(urllib.error.HTTPError) as ei:
                _get(ops.addr, path)
            assert ei.value.code == 404
        text = _get(ops.addr, "/metrics").read().decode()
        same_exposition(before, text)
        assert "incidents_" not in text
    finally:
        ops.stop()
