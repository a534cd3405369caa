"""Ops plane: metrics registry, Prometheus exposition, /healthz, /logspec.

Reference parity targets: common/metrics provider semantics and
core/operations/system.go:75-267 endpoints (VERDICT.md missing #6 —
"curl-able /metrics and /healthz on a running node").
"""
import json
import logging
import urllib.request

import pytest

from fabric_tpu.ops_plane import MetricsRegistry, OperationsServer


def _get(addr, path):
    with urllib.request.urlopen(f"http://{addr[0]}:{addr[1]}{path}") as r:
        return r.status, r.read().decode()


def test_metrics_exposition():
    reg = MetricsRegistry()
    reg.counter("txs_total", "transactions").add(3, channel="ch")
    reg.counter("txs_total").add(2, channel="ch")
    reg.gauge("height").set(7, channel="ch")
    h = reg.histogram("latency_seconds", buckets=(0.1, 1.0, float("inf")))
    for v in (0.05, 0.5, 5.0):
        h.observe(v)
    text = reg.expose_text()
    assert 'txs_total{channel="ch"} 5.0' in text
    assert 'height{channel="ch"} 7' in text
    assert 'latency_seconds_bucket{le="0.1"} 1' in text
    assert 'latency_seconds_bucket{le="+Inf"} 3' in text
    assert "latency_seconds_count 3" in text
    assert "# TYPE txs_total counter" in text


def test_ops_http_endpoints():
    reg = MetricsRegistry()
    reg.counter("up").add(1)
    srv = OperationsServer(metrics=reg).start()
    try:
        code, body = _get(srv.addr, "/metrics")
        assert code == 200 and "up 1.0" in body

        code, body = _get(srv.addr, "/healthz")
        assert code == 200 and json.loads(body)["status"] == "OK"

        srv.register_checker("raft", lambda: (_ for _ in ()).throw(
            RuntimeError("no leader")))
        try:
            code, body = _get(srv.addr, "/healthz")
        except urllib.error.HTTPError as e:
            code, body = e.code, e.read().decode()
        assert code == 503
        assert json.loads(body)["failed_checks"][0]["component"] == "raft"

        code, body = _get(srv.addr, "/version")
        assert code == 200 and "fabric-tpu" in body

        # runtime log-level admin
        req = urllib.request.Request(
            f"http://{srv.addr[0]}:{srv.addr[1]}/logspec",
            data=json.dumps({"spec": "debug"}).encode(), method="PUT")
        with urllib.request.urlopen(req) as r:
            assert r.status == 204
        assert logging.getLogger().getEffectiveLevel() == logging.DEBUG
        logging.getLogger().setLevel(logging.WARNING)
    finally:
        srv.stop()


def test_commit_pipeline_metrics(tmp_path):
    from fabric_tpu.bccsp.factory import FactoryOpts, init_factories
    from fabric_tpu.committer.committer import Committer
    from fabric_tpu.committer.txvalidator import PolicyRegistry, TxValidator
    from fabric_tpu.ledger import KVLedger
    from fabric_tpu.msp import CachedMSP
    from fabric_tpu.msp.ca import DevOrg
    from fabric_tpu.ops_plane import registry
    from fabric_tpu.policy import parse_policy
    from fabric_tpu.protocol import KVWrite, NsRwSet, TxRwSet, build

    provider = init_factories(FactoryOpts(default="SW"))
    org = DevOrg("MetOrg")
    msps = {"MetOrg": CachedMSP(org.msp())}
    validator = TxValidator("met", msps, provider,
                            PolicyRegistry(parse_policy("OR('MetOrg.member')")))
    committer = Committer(KVLedger("met"), validator)
    rw = TxRwSet((NsRwSet("cc", writes=(KVWrite("k", b"v"),)),))
    env = build.endorser_tx("met", "cc", "1.0", rw,
                            org.new_identity("c"), [org.new_identity("e")])
    committer.store_block(build.new_block(0, b"\x00" * 32, [env]))
    text = registry.expose_text()
    assert 'committed_blocks_total{channel="met"} 1' in text
    assert 'ledger_height{channel="met"} 1' in text
    assert 'validation_duration_seconds_count{channel="met"} 1' in text
    # the per-phase twins went (PR 37): the stage family carries the
    # commit's seconds, the `ledger.*` spans its phases
    assert 'validator_stage_seconds_count{channel="met",stage="commit"} 1' \
        in text
    assert 'commit_phase_seconds' not in text
    assert 'validation_dispatch_seconds' not in text


def test_profiling_routes():
    """/debug/pprof returns pstats; /debug/profile captures a (CPU)
    jax.profiler trace directory — the pprof slot of
    internal/peer/node/start.go:813-825."""
    import json
    import urllib.request

    from fabric_tpu.ops_plane import OperationsServer
    from fabric_tpu.ops_plane.profiling import register_routes

    ops = OperationsServer("127.0.0.1", 0)
    register_routes(ops, enabled=True)
    ops.start()
    try:
        url = "http://%s:%d" % ops.addr
        req = urllib.request.Request(f"{url}/debug/pprof?seconds=0.2",
                                     method="POST")
        body = json.loads(urllib.request.urlopen(req, timeout=120).read())
        assert "pstats" in body and "cumulative" in body["pstats"]

        req = urllib.request.Request(f"{url}/debug/profile?seconds=0.2",
                                     method="POST")
        body = json.loads(urllib.request.urlopen(req, timeout=180).read())
        assert body.get("trace_dir"), body
        import os
        assert os.path.isdir(body["trace_dir"])
    finally:
        ops.stop()


# -- exposition correctness (escaping, name validation, le boundaries) ------


def test_label_value_escaping():
    reg = MetricsRegistry()
    reg.counter("esc_total").add(1, path='a\\b"c\nd')
    text = reg.expose_text()
    assert 'esc_total{path="a\\\\b\\"c\\nd"} 1.0' in text
    # stays one-line-per-sample despite the raw newline, and the
    # dashboard's exposition parser round-trips the original value
    assert sum("esc_total{" in line for line in text.splitlines()) == 1
    from fabric_tpu.node import top
    (labels, value), = top.parse_metrics(text)["esc_total"]
    assert labels == {"path": 'a\\b"c\nd'} and value == 1.0


def test_metric_and_label_name_validation():
    reg = MetricsRegistry()
    with pytest.raises(ValueError):
        reg.counter("bad-name")
    with pytest.raises(ValueError):
        reg.gauge("1starts_with_digit")
    with pytest.raises(ValueError):
        reg.histogram("bad metric")
    reg.counter("ns:ok_total").add(1)      # colons legal in metric names
    with pytest.raises(ValueError):
        reg.counter("ok_total").add(1, **{"bad:label": "x"})


def test_histogram_boundary_values_land_in_le_bucket():
    """le semantics are inclusive: a value EQUAL to an upper bound
    belongs in that bound's bucket."""
    reg = MetricsRegistry()
    h = reg.histogram("bound_seconds", buckets=(0.1, 1.0, float("inf")))
    h.observe(0.1)             # == first bound
    h.observe(1.0)             # == second bound
    h.observe(1.0000001)       # just over -> +Inf bucket only
    text = reg.expose_text()
    assert 'bound_seconds_bucket{le="0.1"} 1' in text
    assert 'bound_seconds_bucket{le="1.0"} 2' in text
    assert 'bound_seconds_bucket{le="+Inf"} 3' in text
    assert "bound_seconds_count 3" in text


def test_counter_gauge_locked_reads_and_aggregates():
    reg = MetricsRegistry()
    c = reg.counter("reads_total")
    c.add(2, x="1")
    c.add(3, x="2")
    assert c.value(x="1") == 2.0
    assert c.total() == 5.0
    g = reg.gauge("reads_gauge")
    g.set(4, x="1")
    g.add(-1, x="1")
    assert g.value(x="1") == 3.0
    assert g.values() == {(("x", "1"),): 3.0}
    counts, total, n = reg.histogram("reads_seconds").state()
    assert counts == [0] * len(reg.histogram("reads_seconds").buckets)
    assert total == 0.0 and n == 0


# -- SLO evaluator (multi-window burn rate, dedup/hysteresis, routes) -------


def _slo_eval(reg, **overrides):
    from fabric_tpu.ops_plane.slo import SloEvaluator
    cfg = {"sample_interval_s": 1.0, "short_window_s": 4.0,
           "long_window_s": 8.0}
    cfg.update(overrides)
    return SloEvaluator(cfg, registry=reg)


def test_slo_gauge_objective_fires_dedups_and_recovers():
    reg = MetricsRegistry()
    g = reg.gauge("gateway_orderer_breaker_open")
    g.set(0.0, orderer="a")
    g.set(0.0, orderer="b")
    ev = _slo_eval(reg)
    t = 0.0
    for _ in range(10):
        ev.sample(t)
        ev.evaluate(t)
        t += 1.0
    sts = {s["name"]: s for s in ev.evaluate(t)}
    assert sts["breaker_open_frac"]["state"] == "ok"
    assert not ev.alerts_snapshot()["active"]

    # blackout: every breaker opens -> frac 1.0 > 0.5 threshold
    g.set(1.0, orderer="a")
    g.set(1.0, orderer="b")
    for _ in range(10):
        ev.sample(t)
        ev.evaluate(t)
        t += 1.0
    sts = {s["name"]: s for s in ev.evaluate(t)}
    st = sts["breaker_open_frac"]
    assert st["state"] == "alerting"
    assert st["burn_short"] >= 1.0 and st["burn_long"] >= 1.0
    alerts = ev.alerts_snapshot()
    assert [a["objective"] for a in alerts["active"]] == \
        ["breaker_open_frac"]
    n_hist = len(alerts["history"])

    # dedup: sustained burn fires NO additional alert records
    for _ in range(5):
        ev.sample(t)
        ev.evaluate(t)
        t += 1.0
    assert len(ev.alerts_snapshot()["history"]) == n_hist

    # recovery with hysteresis: the first healthy sample leaves stale
    # burn in the short window -> still alerting; the window draining
    # below clear_ratio clears it
    g.set(0.0, orderer="a")
    g.set(0.0, orderer="b")
    ev.sample(t)
    ev.evaluate(t)
    assert ev.alerts_snapshot()["active"], "cleared too eagerly"
    cleared = None
    for i in range(10):
        t += 1.0
        ev.sample(t)
        ev.evaluate(t)
        if not ev.alerts_snapshot()["active"]:
            cleared = i
            break
    assert cleared is not None
    hist = ev.alerts_snapshot()["history"]
    assert hist[-1]["state"] == "resolved" and "cleared_at" in hist[-1]


def test_slo_throughput_floor_counter_rate():
    reg = MetricsRegistry()
    c = reg.counter("provider_device_sigs_total")
    ev = _slo_eval(reg, objectives={
        "verify_throughput_floor": {"threshold": 100.0}})
    t = 0.0
    for _ in range(10):
        c.add(500.0)             # 500 sigs/s, well above the floor
        ev.sample(t)
        ev.evaluate(t)
        t += 1.0
    sts = {s["name"]: s for s in ev.evaluate(t)}
    st = sts["verify_throughput_floor"]
    assert st["state"] == "ok"
    assert st["value_short"] == pytest.approx(500.0, rel=0.3)
    for _ in range(10):
        c.add(10.0)              # collapse below the floor
        ev.sample(t)
        ev.evaluate(t)
        t += 1.0
    sts = {s["name"]: s for s in ev.evaluate(t)}
    st = sts["verify_throughput_floor"]
    assert st["state"] == "alerting"
    assert st["burn_short"] > 1.0


def test_slo_histogram_quantile_windowed():
    reg = MetricsRegistry()
    h = reg.histogram("validation_duration_seconds",
                      buckets=(0.1, 1.0, 5.0, float("inf")))
    ev = _slo_eval(reg, objectives={
        "commit_p99_s": {"threshold": 1.0, "q": 0.99}})
    t = 0.0
    for _ in range(10):
        for _ in range(5):
            h.observe(0.05)
        ev.sample(t)
        ev.evaluate(t)
        t += 1.0
    sts = {s["name"]: s for s in ev.evaluate(t)}
    st = sts["commit_p99_s"]
    assert st["state"] == "ok"
    assert st["value_short"] == pytest.approx(0.1)   # bucket upper bound
    for _ in range(10):
        for _ in range(5):
            h.observe(3.0)       # p99 moves to the 5.0 bucket
        ev.sample(t)
        ev.evaluate(t)
        t += 1.0
    sts = {s["name"]: s for s in ev.evaluate(t)}
    st = sts["commit_p99_s"]
    assert st["state"] == "alerting"
    assert st["value_short"] == pytest.approx(5.0)


def test_slo_alert_lands_in_jlog_and_trace(caplog):
    from fabric_tpu.ops_plane import tracing
    reg = MetricsRegistry()
    g = reg.gauge("gateway_orderer_breaker_open")
    g.set(1.0, orderer="a")
    ev = _slo_eval(reg, short_window_s=2.0, long_window_s=4.0)
    prev_enabled = tracing.tracer.enabled
    tracing.tracer.enabled = True
    try:
        with caplog.at_level(logging.WARNING,
                             logger="fabric_tpu.ops_plane.slo"):
            t = 0.0
            for _ in range(8):
                ev.sample(t)
                ev.evaluate(t)
                t += 1.0
    finally:
        tracing.tracer.enabled = prev_enabled
    fired = [r for r in caplog.records if "slo.alert_fired" in r.message]
    assert fired, "alert must land as a jlog record"
    doc = json.loads(fired[0].message)
    assert doc["event"] == "slo.alert_fired"
    assert doc["objective"] == "breaker_open_frac"
    assert "slo.alert" in tracing.tracer.span_stats()


def test_slo_routes_shape():
    from fabric_tpu.ops_plane import slo as slomod
    reg = MetricsRegistry()
    reg.gauge("pipeline_collect_under_verify_frac").set(0.5, channel="ch")
    ev = slomod.SloEvaluator({}, registry=reg)
    ev.step()
    srv = OperationsServer(metrics=reg).start()
    try:
        slomod.register_routes(srv, ev)
        code, body = _get(srv.addr, "/slo")
        doc = json.loads(body)
        assert code == 200 and doc["enabled"] is True
        names = {o["name"] for o in doc["objectives"]}
        assert {"commit_p99_s", "verify_throughput_floor",
                "breaker_open_frac", "overlap_floor"} <= names
        for o in doc["objectives"]:
            assert {"state", "burn_short", "burn_long", "value_short",
                    "value_long", "threshold", "windows"} <= set(o)
            assert o["state"] in ("ok", "alerting", "no_data")
        code, body = _get(srv.addr, "/slo/alerts")
        doc = json.loads(body)
        assert code == 200
        assert doc["active"] == [] and doc["history"] == []
    finally:
        srv.stop()


# -- cluster top dashboard ---------------------------------------------------


def test_top_collect_and_render():
    from fabric_tpu.node import top
    reg = MetricsRegistry()
    reg.gauge("ledger_height").set(5, channel="ch")
    reg.counter("committed_txs_total").add(40, channel="ch")
    reg.counter("provider_pad_slots_total").add(25, lane="rows")
    reg.counter("provider_lane_slots_total").add(100, lane="rows")
    reg.gauge("pipeline_collect_under_verify_frac").set(0.42, channel="ch")
    srv = OperationsServer(metrics=reg).start()
    try:
        addr = "%s:%d" % srv.addr
        row = top.collect_node(addr)
        assert row["up"] and row["height"] == 5 and row["txs"] == 40
        assert row["occupancy"] == pytest.approx(0.75)
        assert row["overlap"] == pytest.approx(0.42)
        table = top.render([row])
        assert addr in table and "75%" in table and "42%" in table
    finally:
        srv.stop()
    down = top.collect_node("127.0.0.1:1")       # nothing listens there
    assert not down["up"] and "DOWN" in top.render([down])


def test_profiling_disabled_by_default():
    import urllib.error
    import urllib.request

    from fabric_tpu.ops_plane import OperationsServer
    from fabric_tpu.ops_plane.profiling import register_routes

    ops = OperationsServer("127.0.0.1", 0)
    register_routes(ops, enabled=False)
    ops.start()
    try:
        req = urllib.request.Request(
            "http://%s:%d/debug/pprof" % ops.addr, method="POST")
        try:
            urllib.request.urlopen(req, timeout=10)
            assert False, "profiling route should not exist"
        except urllib.error.HTTPError as e:
            assert e.code == 404
    finally:
        ops.stop()


def test_slo_per_channel_instance_fires_independently():
    """`per_channel: ["commit_p99_s"]` expands one alert instance per
    observed channel label; only the slow channel's instance fires, the
    quiet channel and the aggregated original are judged separately."""
    reg = MetricsRegistry()
    h = reg.histogram("validation_duration_seconds",
                      buckets=(0.1, 1.0, 5.0, float("inf")))
    ev = _slo_eval(reg, per_channel=["commit_p99_s"],
                   objectives={"commit_p99_s": {"threshold": 1.0}})
    t = 0.0
    for _ in range(12):
        for _ in range(5):
            h.observe(0.05, channel="fast")
            h.observe(3.0, channel="slow")    # p99 over threshold
        ev.sample(t)
        ev.evaluate(t)
        t += 1.0
    sts = {s["name"]: s for s in ev.evaluate(t)}
    slow = sts["commit_p99_s_by_channel[slow]"]
    fast = sts["commit_p99_s_by_channel[fast]"]
    assert slow["state"] == "alerting" and slow["group"] == "slow"
    assert slow["value_short"] == pytest.approx(5.0)
    assert fast["state"] == "ok"
    assert fast["value_short"] == pytest.approx(0.1)
    # the aggregated original keeps its own (blended) judgement
    assert "commit_p99_s" in sts
    active = {a["objective"] for a in ev.alerts_snapshot()["active"]}
    assert "commit_p99_s_by_channel[slow]" in active
    assert "commit_p99_s_by_channel[fast]" not in active


def test_slo_per_channel_no_observations_is_no_data():
    reg = MetricsRegistry()
    reg.histogram("validation_duration_seconds",
                  buckets=(0.1, 1.0, 5.0, float("inf")))
    ev = _slo_eval(reg, per_channel=["commit_p99_s"])
    t = 0.0
    for _ in range(6):
        ev.sample(t)
        ev.evaluate(t)
        t += 1.0
    sts = {s["name"]: s for s in ev.evaluate(t)}
    assert sts["commit_p99_s_by_channel"]["state"] == "no_data"


def test_slo_per_channel_unknown_template_rejected():
    from fabric_tpu.ops_plane.slo import SloEvaluator
    with pytest.raises(ValueError, match="unknown objective"):
        SloEvaluator({"per_channel": ["nope"]}, registry=MetricsRegistry())


def test_metrics_grouped_snapshots():
    reg = MetricsRegistry()
    c = reg.counter("x_total")
    c.add(2.0, channel="a")
    c.add(3.0, channel="a", phase="p")
    c.add(5.0, channel="b")
    c.add(7.0)                                   # unattributed: skipped
    assert c.total_by("channel") == {"a": 5.0, "b": 5.0}
    g = reg.gauge("x_gauge")
    g.set(1.0, channel="a", slot="1")
    g.set(3.0, channel="a", slot="2")
    g.set(9.0, channel="b")
    assert g.mean_by("channel") == {"a": 2.0, "b": 9.0}
    h = reg.histogram("x_seconds", buckets=(1.0, float("inf")))
    h.observe(0.5, channel="a", phase="p1")
    h.observe(2.0, channel="a", phase="p2")
    h.observe(0.5, channel="b")
    by = h.state_by("channel")
    assert by["a"][0] == [1, 1] and by["a"][2] == 2
    assert by["a"][1] == pytest.approx(2.5)
    assert by["b"][0] == [1, 0] and by["b"][2] == 1
