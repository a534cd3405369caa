#!/usr/bin/env python3
"""From a profiler trace (`.xplane.pb`) to the numbers the per-layer
readers and the result line's `breakdown` use.

    python trace_reduce.py <file.xplane.pb> '<marks json>'

prints one JSON object:

    window_s     the traced window: first to last event over all planes
    busy_s       seconds in which an operation ran on the device: the
                 union of the device planes' op intervals, averaged
                 over the device planes
    programs     {program name: {"device_s", "executions"}} from the
                 device planes' "XLA Modules" line, the name cut at the
                 "(" that precedes the run's program id
    device_ops   [[op name, seconds]] the ten that took most device time
    idle_gaps    [[what the host was doing, idle seconds]], the ten
                 largest: the fifty longest gaps between device ops, cut
                 at the program's span boundaries, each piece named by
                 the shortest host event or program span that covers
                 most of it, or "unattributed"

`marks` may carry `mark_name` and `mark_perf` (the perf_counter reading
taken inside a TraceAnnotation of that name), which puts the program's
spans (`spans`: name, start, duration_s on the perf_counter clock) on
the trace's clock.

Reads the file with jax's own reader and nothing else; run it in a
process held to the CPU backend.
"""

from __future__ import annotations

import json
import sys

DEVICE_PREFIX = "/device:TPU:"
MODULES_LINE = "XLA Modules"
OPS_LINE = "XLA Ops"
HOST_PREFIX = "/host:"
OP_NAME_CHARS = 96               # an op's name is its whole HLO line


def union_seconds(starts, ends) -> tuple:
    """(covered seconds, [(gap start, gap end)]) of intervals in ns."""
    import numpy as np
    starts, ends = np.asarray(starts, float), np.asarray(ends, float)
    if starts.size == 0:
        return 0.0, []
    order = np.argsort(starts, kind="stable")
    starts, ends = starts[order], ends[order]
    reach = np.maximum.accumulate(ends)
    # a gap opens wherever the next start lies beyond all earlier ends
    opens = np.nonzero(starts[1:] > reach[:-1])[0]
    gaps = [(float(reach[k]), float(starts[k + 1])) for k in opens]
    covered = (reach[-1] - starts[0]) - sum(e - s for s, e in gaps)
    return covered / 1e9, gaps


def attribute_gaps(gaps: list, events: list, spans: list) -> list:
    """[[what the host was doing, idle seconds]], most first.  Each gap
    is cut where a program span begins or ends inside it, and each piece
    goes to the shortest event or span that covers most of it, else to
    "unattributed"."""
    by_name = {}
    for g0, g1 in gaps:
        cuts = sorted({g0, g1} | {t for _n, s, e in spans for t in (s, e)
                                  if g0 < t < g1})
        for p0, p1 in zip(cuts, cuts[1:]):
            best, best_len = "unattributed", float("inf")
            for name, s, e in events:
                if (min(e, p1) - max(s, p0) > 0.5 * (p1 - p0)
                        and e - s < best_len):
                    best, best_len = name, e - s
            by_name[best] = by_name.get(best, 0.0) + (p1 - p0) / 1e9
    return [[n, s] for n, s in sorted(by_name.items(), key=lambda kv: -kv[1])]


def program_name(event_name: str) -> str:
    return event_name.split("(", 1)[0]


def reduce_planes(planes, marks: dict) -> dict:
    """`planes`: [{"name", "lines": [{"name", "events": iterable of
    (name, start_ns, duration_ns)}]}] — the trace as plain data, so a
    test can build one.  Every line is read once, in one pass: a second
    of a busy chip is millions of op events."""
    from array import array
    t_min, t_max = float("inf"), 0.0
    programs, op_time = {}, {}
    busy, gaps, host, edges = [], [], [], []
    seen_device = False
    names = []
    for plane in planes:
        names.append(plane["name"])
        is_device = (plane["name"].startswith(DEVICE_PREFIX)
                     and plane["name"][len(DEVICE_PREFIX):].isdigit())
        is_host = plane["name"].startswith(HOST_PREFIX)
        found = set()
        for line in plane["lines"]:
            if is_device and line["name"] == MODULES_LINE:
                found.add(MODULES_LINE)
                for name, start, dur in line["events"]:
                    t_min, t_max = min(t_min, start), max(t_max, start + dur)
                    prog = programs.setdefault(
                        program_name(name), {"device_s": 0.0, "executions": 0})
                    prog["device_s"] += dur / 1e9
                    prog["executions"] += 1
            elif is_device and line["name"] == OPS_LINE:
                found.add(OPS_LINE)
                starts, ends = array("d"), array("d")
                for name, start, dur in line["events"]:
                    op_time[name] = op_time.get(name, 0.0) + dur / 1e9
                    starts.append(start)
                    ends.append(start + dur)
                if starts:
                    t_min, t_max = min(t_min, min(starts)), max(t_max,
                                                                max(ends))
                covered, plane_gaps = union_seconds(starts, ends)
                busy.append(covered)
                gaps.extend(plane_gaps)
                if starts:
                    edges.append((min(starts), max(ends)))
            elif is_host:
                for name, start, dur in line["events"]:
                    t_min, t_max = min(t_min, start), max(t_max, start + dur)
                    host.append((name, start, start + dur))
        if is_device:
            seen_device = True
            if found != {MODULES_LINE, OPS_LINE}:
                raise SystemExit(
                    f"plane {plane['name']} lacks the lines {OPS_LINE!r} / "
                    f"{MODULES_LINE!r}: has "
                    + str([ln["name"] for ln in plane["lines"]]))
    if not seen_device:
        raise SystemExit(f"no device plane in the trace: planes {names}")
    # the window's idle head and tail count as gaps too
    for first, last in edges:
        gaps.extend(g for g in ((t_min, first), (last, t_max)) if g[1] > g[0])
    # the program's spans, where a mark puts them on this clock
    spans = []
    if marks.get("mark_name"):
        at = [s for n, s, _e in host if n == marks["mark_name"]]
        if at:
            offset = at[0] - marks["mark_perf"] * 1e9
            spans = [(sp["name"], sp["start"] * 1e9 + offset,
                      (sp["start"] + sp["duration_s"]) * 1e9 + offset)
                     for sp in marks.get("spans", ())]
    idle = attribute_gaps(sorted(gaps, key=lambda g: g[0] - g[1])[:50],
                          host + spans, spans)
    return {"window_s": max(0.0, t_max - t_min) / 1e9,
            "busy_s": sum(busy) / len(busy),
            "programs": programs,
            "device_ops": [[n[:OP_NAME_CHARS], s] for n, s in sorted(
                op_time.items(), key=lambda kv: -kv[1])[:10]],
            "idle_gaps": idle[:10]}


def planes_of(data) -> list:
    """The lines of a `ProfileData` that `reduce_planes` reads, their
    events read lazily (the caller keeps `data` alive meanwhile).  A
    device plane's other lines (async copies, one event per op again)
    are left out."""
    planes = []
    for plane in data.planes:
        if plane.name.startswith(DEVICE_PREFIX):
            keep = [ln for ln in plane.lines
                    if ln.name in (MODULES_LINE, OPS_LINE)]
        elif plane.name.startswith(HOST_PREFIX):
            keep = list(plane.lines)
        else:
            continue
        planes.append({"name": plane.name, "lines": [
            {"name": ln.name,
             "events": ((ev.name, ev.start_ns, ev.duration_ns)
                        for ev in ln.events)} for ln in keep]})
    return planes


def main(argv) -> int:
    marks = json.loads(argv[1]) if len(argv) > 1 else {}
    from jax.profiler import ProfileData
    data = ProfileData.from_file(argv[0])
    print(json.dumps(reduce_planes(planes_of(data), marks)))
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
