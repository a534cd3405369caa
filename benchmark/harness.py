"""What every driver shares: the run's context, the list of numbers
compared beside their limits, the percentile rule, a Prometheus-text
parser, and the look for a chip.  Never imports jax."""

from __future__ import annotations

import ctypes
import glob
import json
import math
import os
import re
import signal
import subprocess
import sys
import time
from dataclasses import dataclass, field

BENCH = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(BENCH)
T0 = time.monotonic()            # the process's start, as near as Python gives it


class BenchFailure(Exception):
    """The run cannot give a result (no accelerator, a node died, a step
    timed out): exit non-zero, print no result line."""


def say(msg: str) -> None:
    print(f"[bench {time.monotonic() - T0:7.1f}s] {msg}", flush=True)


@dataclass
class Context:
    workload: dict               # workloads/<cell>.json
    config: dict                 # configs/<config>.json
    seed: int
    seconds: float
    trace: bool
    # tests only: skip the look for a chip, and break the timed path
    require_accelerator: bool = True
    faults: frozenset = frozenset()
    checks: list = field(default_factory=list)

    def check(self, name: str, value, op: str, limit) -> bool:
        """One number compared beside its limit; all of them decide
        `correct`, and every one is printed."""
        ok = {"<=": value <= limit, ">=": value >= limit,
              "==": value == limit}[op]
        self.checks.append({"name": name, "value": value, "op": op,
                            "limit": limit, "ok": ok})
        say(f"compare: {name} = {value} (limit {op} {limit}) "
            + ("ok" if ok else "NOT OK"))
        return ok


# -- statistics ----------------------------------------------------------------

MIN_BEYOND = 10                  # samples a reported percentile needs beyond it


def percentile(sorted_values: list, q: float):
    """Nearest-rank percentile of an ascending list, or None where fewer
    than MIN_BEYOND samples lie beyond it (the median needs only one
    sample)."""
    n = len(sorted_values)
    if n == 0:
        return None
    if q > 0.5 and n * (1.0 - q) < MIN_BEYOND:
        return None
    rank = max(1, math.ceil(q * n - 1e-9))
    return sorted_values[min(n, rank) - 1]


# -- Prometheus text -------------------------------------------------------------

_PROM_LINE = re.compile(r'^([a-zA-Z_:][a-zA-Z0-9_:]*)(\{[^}]*\})?\s+(\S+)$')
_PROM_LABEL = re.compile(r'([a-zA-Z_][a-zA-Z0-9_]*)="((?:[^"\\]|\\.)*)"')


def parse_prom(text: str) -> dict:
    """{metric name: [(labels dict, value)]} of an exposition."""
    out = {}
    for line in text.splitlines():
        if not line or line[0] == "#":
            continue
        m = _PROM_LINE.match(line.strip())
        if not m:
            continue
        labels = dict(_PROM_LABEL.findall(m.group(2) or ""))
        out.setdefault(m.group(1), []).append((labels, float(m.group(3))))
    return out


def prom_sum(snapshot: dict, name: str, **labels) -> float:
    return sum(v for lab, v in snapshot.get(name, ())
               if all(lab.get(k) == str(want) for k, want in labels.items()))


def prom_delta(before: dict, after: dict, name: str, **labels) -> float:
    return prom_sum(after, name, **labels) - prom_sum(before, name, **labels)


# -- the chip ---------------------------------------------------------------------

PROBE = ("import json, jax; d = jax.devices(); "
         "print(json.dumps({'platform': d[0].platform, "
         "'kind': d[0].device_kind, 'count': len(d)}))")


def start_probe() -> subprocess.Popen:
    """What JAX finds, asked in a child that exits (and so releases the
    chip) before the process that serves starts.  The launcher itself
    stays off jax."""
    return subprocess.Popen([sys.executable, "-c", PROBE],
                            stdout=subprocess.PIPE, stderr=subprocess.PIPE,
                            text=True)


def finish_probe(proc: subprocess.Popen, chips: int) -> dict:
    try:
        out, err = proc.communicate(timeout=240)
    except subprocess.TimeoutExpired:
        proc.kill()
        proc.communicate()
        raise BenchFailure("the look for a chip did not return")
    if proc.returncode != 0:
        raise BenchFailure("jax failed to start:\n" + err[-2000:])
    info = json.loads(out.strip().splitlines()[-1])
    require_chips(info["platform"], info["count"], chips)
    return info


def require_chips(platform: str, count: int, chips: int) -> None:
    if platform == "cpu":
        raise BenchFailure("JAX finds no accelerator (platform cpu)")
    if count < chips:
        raise BenchFailure(f"JAX finds {count} chips, the cell asks {chips}")


# -- processes -------------------------------------------------------------------

PR_SET_CHILD_SUBREAPER = 36


def adopt_orphans() -> None:
    """Make this process the parent of whatever a child of its leaves
    behind (a node's helper, a pool's tracker), so that `reap_descendants`
    finds it by its parent and can wait for it."""
    ctypes.CDLL(None, use_errno=True).prctl(PR_SET_CHILD_SUBREAPER, 1, 0, 0, 0)


def descendants() -> dict:
    """{pid: command name} of every live process below this one."""
    parent_of, name_of = {}, {}
    for entry in os.listdir("/proc"):
        if not entry.isdigit():
            continue
        try:
            with open(f"/proc/{entry}/stat") as f:
                stat = f.read()
        except OSError:
            continue                 # it ended while we looked
        # "pid (comm) state ppid ..."; comm may hold spaces and brackets
        comm = stat[stat.index("(") + 1:stat.rindex(")")]
        state, ppid = stat[stat.rindex(")") + 2:].split()[:2]
        if state != "Z":
            parent_of[int(entry)] = int(ppid)
            name_of[int(entry)] = comm
    found, frontier = {}, {os.getpid()}
    while frontier:
        frontier = {pid for pid, ppid in parent_of.items()
                    if ppid in frontier and pid not in found}
        found.update((pid, name_of[pid]) for pid in frontier)
    return found


def reap_descendants(deadline_s: float = 30.0) -> list:
    """Kill every process below this one and wait until each has ended.
    -> the names of those that were still running (none, on a path that
    stopped its own)."""
    killed = {}
    deadline = time.monotonic() + deadline_s
    while True:
        live = descendants()
        killed.update(live)
        for pid in live:
            try:
                os.kill(pid, signal.SIGKILL)
            except ProcessLookupError:
                pass
        try:                         # collect whatever has ended
            while os.waitpid(-1, os.WNOHANG)[0]:
                pass
        except ChildProcessError:
            pass
        if not live or time.monotonic() > deadline:
            return [f"{name}({pid})" for pid, name in killed.items()]
        time.sleep(0.05)


def build_native() -> None:
    """All three extensions from the committed .c sources.  A copied
    tree's .so says nothing by its mtime, so stale ones go first."""
    from fabric_tpu import native
    ndir = os.path.dirname(native.__file__)
    for so in glob.glob(os.path.join(ndir, "*.so")):
        os.remove(so)
    for name in ("_ftlv", "_fastcollect", "_fastparse"):
        if native.load(name) is None:
            raise BenchFailure(f"native extension {name} did not build")


def reduce_trace(trace_dir: str, marks: dict) -> dict:
    """trace_reduce.py over the `.xplane.pb` under `trace_dir`, in a
    child held to the CPU backend: reading the file needs jax's reader,
    and this process stays off jax."""
    found = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True))
    if not found:
        raise BenchFailure(f"no .xplane.pb under {trace_dir}")
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, os.path.join(BENCH, "trace_reduce.py"),
         found[-1], json.dumps(marks)],
        capture_output=True, text=True, env=env, timeout=300)
    if proc.returncode != 0:
        raise BenchFailure("trace reduction failed:\n" + proc.stderr[-3000:])
    return json.loads(proc.stdout.strip().splitlines()[-1])


def device_report(provider_status: dict) -> dict:
    """The result line's `device`, from a peer's provider status (its
    `/state`): the device as JAX reported it there, and the peak on the
    fullest chip.  A software provider has none (tests only)."""
    d = provider_status["device"]
    if d is None:
        return {"platform": "cpu", "kind": "none", "count": 0,
                "memory_peak_bytes": 0}
    return {"platform": d["platform"], "kind": d["device_kind"],
            "count": d["device_count"],
            "memory_peak_bytes": max(m["peak_bytes_in_use"] or 0
                                     for m in d["memory"])}


def peaks_of(device_kind: str) -> dict:
    """The published peaks of one chip (`peaks.json`, by `device_kind`).
    A device that is not in the table is an error, not a default."""
    with open(os.path.join(BENCH, "peaks.json")) as f:
        table = json.load(f)
    if device_kind == "source" or device_kind not in table:
        raise BenchFailure(f"no published peaks for device kind "
                           f"{device_kind!r} in peaks.json")
    return table[device_kind]
