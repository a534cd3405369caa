"""Pure parts of the yardstick: schedules, the percentile rule, the
Prometheus parser, the trace reducer on plain data and on a recorded
trace, and the manifest's names and files."""

import json
import os
import re
import subprocess
import sys

import pytest

import harness
import readers
import run as launcher
import trace_reduce
from gen import arrivals

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
REPO = os.path.dirname(BENCH)
BIG_SEED = 2**31 + 12345


# -- schedules -----------------------------------------------------------------

@pytest.mark.parametrize("spec", [
    {"kind": "fixed_gaps", "rate": 9.0},
    {"kind": "constant", "rate": 9.0},
    {"kind": "ramp", "start_rate": 2.0, "end_rate": 20.0},
])
def test_schedule_is_a_pure_function_of_the_seed(spec):
    a = arrivals.schedule(spec, BIG_SEED, 40.0)
    assert a == arrivals.schedule(spec, BIG_SEED, 40.0)
    assert a != arrivals.schedule(spec, BIG_SEED + 1, 40.0)
    assert a == sorted(a) and 0.0 < a[0] and a[-1] < 40.0


def test_fixed_gaps_gives_every_seed_the_same_work():
    a = arrivals.fixed_gaps(9.0, 1, 40.0)
    b = arrivals.fixed_gaps(9.0, BIG_SEED, 40.0)
    assert len(a) == len(b) == 360

    def gaps(s):
        return sorted(round(y - x, 9) for x, y in zip([0.0] + s, s))
    assert gaps(a) == gaps(b)
    assert a[-1] == pytest.approx(b[-1])


# -- the percentile rule ------------------------------------------------------------

def test_percentile_needs_ten_samples_beyond_it():
    v = list(range(1, 201))
    assert harness.percentile(v, 0.5) == 100
    assert harness.percentile(v, 0.95) == 190
    assert harness.percentile(v[:199], 0.95) is None
    assert harness.percentile([], 0.5) is None
    assert harness.percentile([7], 0.5) == 7


# -- counters ---------------------------------------------------------------------

PROM = """# HELP x y
provider_lane_slots_total{lane="generic",device="tpu:0"} 1280.0
provider_lane_slots_total{lane="rows",device="tpu:0"} 512
provider_pad_slots_total{lane="generic",device="tpu:0"} 1270
provider_pad_slots_total{lane="rows",device="tpu:0"} 112
gateway_request_duration_seconds_sum{verb="endorse"} 2.5
gateway_request_duration_seconds_count{verb="endorse"} 10
"""


def test_prom_parse_and_lane_fill():
    after = harness.parse_prom(PROM)
    assert harness.prom_sum(after, "provider_lane_slots_total") == 1792
    assert harness.prom_sum(after, "provider_lane_slots_total",
                            lane="rows") == 512
    obs = {"prom_before": {}, "prom_after": after}
    assert readers.lane_fill_pct(obs) == pytest.approx(
        100 * (1 - 1382 / 1792))
    endorse = launcher.load_module("layer_metrics",
                                   "gateway.endorse_ms.steady")
    assert endorse.read(obs) == pytest.approx(250.0)
    assert endorse.read({}) is None


# -- the trace reducer ----------------------------------------------------------------

def planes():
    ms = 1_000_000
    return [
        {"name": "/device:TPU:0", "lines": [
            {"name": "XLA Modules", "events": [
                ("jit_whole(11)", 10 * ms, 20 * ms),
                ("jit_whole(11)", 50 * ms, 20 * ms),
                ("jit__lambda(12)", 100 * ms, 80 * ms)]},
            {"name": "XLA Ops", "events": [
                ("%while.1", 10 * ms, 20 * ms), ("%add.2", 12 * ms, 3 * ms),
                ("%while.1", 50 * ms, 20 * ms),
                ("%fusion.3", 100 * ms, 80 * ms)]}]},
        {"name": "/host:CPU", "lines": [
            {"name": "python", "events": [
                ("bench.mark", 0, 1), ("outer", 0, 200 * ms),
                ("bench.sleep", 71 * ms, 28 * ms)]}]},
        {"name": "Task Environment", "lines": []}]


def test_reduce_planes():
    out = trace_reduce.reduce_planes(planes(), {})
    assert out["window_s"] == pytest.approx(0.2)
    assert out["busy_s"] == pytest.approx(0.12)
    assert out["programs"] == {
        "jit_whole": {"device_s": pytest.approx(0.04), "executions": 2},
        "jit__lambda": {"device_s": pytest.approx(0.08), "executions": 1}}
    assert out["device_ops"][0] == ["%fusion.3", pytest.approx(0.08)]
    # the gap 70..100 ms lies under bench.sleep; 30..50 ms, the 10 ms
    # before the first op and the 20 ms after the last under nothing
    # narrower than the outer event
    assert out["idle_gaps"] == [["outer", pytest.approx(0.05)],
                                ["bench.sleep", pytest.approx(0.03)]]
    assert sum(s for _n, s in out["idle_gaps"]) == pytest.approx(
        out["window_s"] - out["busy_s"])


def test_program_spans_are_put_on_the_traces_clock():
    marks = {"mark_name": "bench.mark", "mark_perf": 100.0,
             "spans": [{"name": "ledger.state_commit", "start": 100.031,
                        "duration_s": 0.018}]}
    out = trace_reduce.reduce_planes(planes(), marks)
    # the span covers 31..49 ms of the 30..50 ms gap: the gap is cut at
    # its edges, and the two 1 ms rests stay with the outer event
    assert out["idle_gaps"] == [["outer", pytest.approx(0.032)],
                                ["bench.sleep", pytest.approx(0.03)],
                                ["ledger.state_commit", pytest.approx(0.018)]]


def test_a_trace_without_a_device_plane_is_refused():
    with pytest.raises(SystemExit):
        trace_reduce.reduce_planes(planes()[1:], {})


def test_kernel_reader_fails_rather_than_guess():
    trace = trace_reduce.reduce_planes(planes(), {})
    before = harness.parse_prom("")
    after = harness.parse_prom(
        'provider_lane_fill_count{lane="rows"} 1\n'
        'provider_lane_slots_total{lane="rows"} 49152\n'
        'provider_pad_slots_total{lane="rows"} 9152\n'
        'provider_lane_fill_count{lane="generic"} 5\n'
        'provider_lane_slots_total{lane="generic"} 640\n'
        'provider_pad_slots_total{lane="generic"} 600\n')
    obs = {"trace": trace, "traced_prom_before": before,
           "traced_prom_after": after}
    assert readers.kernel_sig_us(obs, "rows") == pytest.approx(2.0)
    with pytest.raises(harness.BenchFailure):
        readers.kernel_sig_us(obs, "generic")      # 2 executions, 5 dispatches
    assert readers.kernel_sig_us({}, "rows") is None


def test_recorded_trace():
    """A trace recorded on the v5e (two tiny programs under the lanes'
    names, a mark and a 50 ms sleep), reduced by the command the harness
    runs."""
    path = os.path.join(BENCH, "tests", "fixture.xplane.pb")
    proc = subprocess.run(
        [sys.executable, os.path.join(BENCH, "trace_reduce.py"), path, "{}"],
        capture_output=True, text=True, timeout=120,
        env=dict(os.environ, JAX_PLATFORMS="cpu"))
    assert proc.returncode == 0, proc.stderr[-2000:]
    out = json.loads(proc.stdout.strip().splitlines()[-1])
    assert out["programs"]["jit_whole"]["executions"] == 3
    assert out["programs"]["jit__lambda"]["executions"] == 2
    assert 0 < out["busy_s"] < out["window_s"]
    gaps = dict(map(tuple, out["idle_gaps"]))
    assert gaps["bench.sleep"] == pytest.approx(0.05, abs=0.01)


# -- the manifest ---------------------------------------------------------------------

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")


def test_manifest_names_units_and_files():
    m = launcher.load_json(REPO, "BENCHMARK.json")
    assert set(m) == {"command", "paths", "run_seconds", "configs",
                      "workloads", "end_to_end", "per_layer"}
    assert 1 <= m["run_seconds"] <= 51
    cells = [w["name"] for w in m["workloads"]]
    configs = [c["name"] for c in m["configs"]]
    metrics = [x["name"] for x in m["end_to_end"] + m["per_layer"]]
    for names in (cells, configs, metrics):
        assert len(set(names)) == len(names)
        assert all(NAME.match(n) for n in names)
    assert "setup_s" in metrics
    for c in m["configs"]:
        assert os.path.isfile(os.path.join(REPO, c["file"]))
        doc = launcher.load_json(REPO, c["file"])
        assert sorted(doc["reduced"]) == sorted(c["reduced"])
        assert all(NAME.match(k) for k in c["reduced"])
        assert any(w["config"] == c["name"] for w in m["workloads"])
    for w in m["workloads"]:
        assert w["config"] in configs and w["chips"] in (1, 4)
        assert NAME.match(w["traffic"]) and len(w["why"]) <= 200
        doc = launcher.load_json(BENCH, "workloads", w["name"] + ".json")
        assert doc["config"] == w["config"]
        assert os.path.isfile(os.path.join(BENCH, "drivers",
                                           doc["driver"] + ".py"))
        e2e = [x["name"] for x in launcher.metrics_of(m, "end_to_end",
                                                      w["name"])]
        assert "setup_s" in e2e and len(e2e) >= 2
        assert launcher.metrics_of(m, "per_layer", w["name"])
    e2e_names = {x["name"] for x in m["end_to_end"]}
    for x in m["end_to_end"]:
        assert UNIT.match(x["unit"]) and x["better"] in ("lower", "higher")
        assert 0.01 <= x["bound"] <= 0.25
        assert x["source"] in ("host_clock", "device_trace")
    for x in m["per_layer"]:
        assert UNIT.match(x["unit"]) and x["better"] in ("lower", "higher")
        assert x["moves"] in e2e_names and "bound" not in x
        assert x["source"] in ("device_trace", "program_span",
                               "program_counter", "host_clock")
        assert set(x.get("workloads", cells)) <= set(cells)
        reader = launcher.load_module("layer_metrics", x["name"])
        assert reader.read({}) is None      # nothing to read: no metric
