"""Arithmetic the per-layer readers share.  Each takes the run's
observations (`obs`, as a driver leaves them) and returns a number, or
None where there is nothing to read."""

from __future__ import annotations

import json
import os
import statistics

from harness import BenchFailure, prom_delta


def lane_fill_pct(obs: dict):
    """Real signatures over device slots dispatched, all lanes."""
    before, after = obs.get("prom_before"), obs.get("prom_after")
    if before is None or after is None:
        return None
    slots = prom_delta(before, after, "provider_lane_slots_total")
    if slots <= 0:
        return None
    pad = prom_delta(before, after, "provider_pad_slots_total")
    return 100.0 * (1.0 - pad / slots)


def idle_share_pct(obs: dict):
    trace = obs.get("trace")
    if not trace or trace["window_s"] <= 0:
        return None
    return 100.0 * (1.0 - trace["busy_s"] / trace["window_s"])


def block_ms(obs: dict, names: tuple):
    """Median over the window's blocks of the summed durations of the
    named spans of each block's trace."""
    per_block = {}
    for span in obs.get("spans", ()):
        if span["name"] in names:
            per_block[span["trace_id"]] = (
                per_block.get(span["trace_id"], 0.0) + span["duration_s"])
    if not per_block:
        return None
    return 1e3 * statistics.median(per_block.values())


def kernel_sig_us(obs: dict, lane: str):
    """Device time of the lane's program in the traced window over the
    real signatures that lane took there.  Fails, rather than guess,
    where the trace's executions are not the lane's dispatches."""
    trace = obs.get("trace")
    before, after = obs.get("traced_prom_before"), obs.get("traced_prom_after")
    if not trace or before is None or after is None:
        return None
    with open(os.path.join(os.path.dirname(os.path.abspath(__file__)),
                           "programs.json")) as f:
        name = json.load(f)[lane]
    prog = trace["programs"].get(name)
    dispatches = prom_delta(before, after, "provider_lane_fill_count",
                            lane=lane)
    sigs = (prom_delta(before, after, "provider_lane_slots_total", lane=lane)
            - prom_delta(before, after, "provider_pad_slots_total", lane=lane))
    if prog is None and dispatches == 0:
        return None
    executions = prog["executions"] if prog else 0
    # a dispatch enqueued just before the trace's edge runs inside it,
    # and the reverse: one execution of slack at each edge
    if abs(executions - dispatches) > obs.get("trace_edge_slack", 0):
        raise BenchFailure(
            f"cannot tell the {lane} lane's program in the trace: "
            f"{executions} executions named {name!r}, {dispatches:.0f} "
            f"dispatches on the lane in the traced window")
    if sigs <= 0:
        return None
    return 1e6 * prog["device_s"] / sigs
