"""Driver `catchup`: a peer's committer working off a backlog of blocks.

The backlog is made from the seed by `gen/backlog.py` in parallel
worker processes while the device peer — a child process that holds the
chip — warms its program.  One pilot block in set-up makes the keys'
tables resident.  The window hands the blocks, in order, to the device
peer's own committer.  The plain reference is a software-provider peer
(host-only process, same script, never imports jax) that replays the
pilot and the first blocks of the backlog during set-up, and the
generator's own serial simulation for every block.

Cell parameters (`workloads/<cell>.json`): `backlog_blocks`, `block_tx`,
`reference_blocks`, `warm_rows`, `generator_workers`, `trace_blocks`.
"""

from __future__ import annotations

import json
import multiprocessing
import os
import shutil
import subprocess
import sys
import tempfile
import time
from concurrent.futures import ProcessPoolExecutor, ThreadPoolExecutor
from multiprocessing import resource_tracker

import harness
from gen import backlog as gen_backlog
from gen.deployment import Deployment
from harness import BenchFailure, say

CHILD = os.path.join(harness.BENCH, "drivers", "catchup_child.py")


class Child:
    """catchup_child.py as a subprocess speaking JSON lines."""

    def __init__(self, name: str, dep: Deployment, org: str, trace: bool,
                 trace_dir: str, faults=()):
        self.name = name
        self.log_path = os.path.join(dep.base, name + ".log")
        with open(self.log_path, "wb") as log:
            self.proc = subprocess.Popen(
                [sys.executable, CHILD, dep.peer_cfg_path[org],
                 "1" if trace else "0", trace_dir, *faults],
                env=dep.env, stdin=subprocess.PIPE, stdout=subprocess.PIPE,
                stderr=log, text=True)

    def send(self, cmd: str, **fields) -> None:
        self.proc.stdin.write(json.dumps(dict(fields, cmd=cmd)) + "\n")
        self.proc.stdin.flush()

    def expect(self, event: str) -> dict:
        """The next JSON line, which must be `event`; anything else the
        child prints (a library's chatter) is skipped."""
        while True:
            line = self.proc.stdout.readline()
            if not line:
                self.proc.wait()
                with open(self.log_path, "rb") as f:
                    tail = f.read()[-3000:].decode("utf-8", "replace")
                raise BenchFailure(f"{self.name} exited "
                                   f"{self.proc.returncode} before "
                                   f"'{event}':\n{tail}")
            if not line.startswith("{"):
                continue
            msg = json.loads(line)
            if msg.get("event") == event:
                return msg

    def stop(self) -> None:
        if self.proc.poll() is None:
            self.proc.kill()
        self.proc.wait()
        self.proc.stdin.close()
        self.proc.stdout.close()


def run(ctx: harness.Context) -> dict:
    wl, cfg = ctx.workload, ctx.config
    n_backlog = int(wl["backlog_blocks"])
    n_ref = int(wl["reference_blocks"])          # pilot included
    block_tx = int(wl["block_tx"])
    sys.path.insert(0, harness.REPO)
    harness.build_native()
    base = tempfile.mkdtemp(prefix="bench_catchup_")
    trace_dir = os.path.join(base, "trace")
    children = []
    pool = ProcessPoolExecutor(
        max_workers=int(wl["generator_workers"]),
        mp_context=multiprocessing.get_context("spawn"))
    threads = ThreadPoolExecutor(2)
    try:
        tracing = {"enabled": bool(ctx.trace), "sample_rate": 1.0}
        dep = Deployment(base, cfg, harness.REPO, {"tracing": tracing})
        device = Child("device_peer", dep, dep.device_org, ctx.trace,
                       trace_dir, sorted(ctx.faults))
        children.append(device)
        say(f"device peer started (pid {device.proc.pid}); planning "
            f"{1 + n_backlog} blocks of {block_tx} tx from seed {ctx.seed}")
        plan = gen_backlog.plan_backlog(
            ctx.seed, 1 + n_backlog, block_tx, int(cfg["keyspace"]),
            int(cfg["client_identities"]), int(cfg["tamper_every"]))
        paths = [os.path.join(base, f"block_{b['number']}.bin") for b in plan]

        def reference_replay() -> dict:
            ref = Child("reference_peer", dep, wl["reference_org"], False,
                        trace_dir)
            children.append(ref)
            ref.expect("init")
            ref.send("replay", blocks=paths[:n_ref])
            report = ref.expect("replayed")
            ref.stop()
            return report

        def generate() -> dict:
            """Blocks built by the workers, chained and written in order;
            the software peer replays its share as soon as it exists.
            -> the software peer's report"""
            t = time.monotonic()
            workers = [pool.submit(gen_backlog.worker_build, dep.file,
                                   dep.channel, dep.chaincode, b)
                       for b in plan]
            prev = gen_backlog.GENESIS_PREVIOUS_HASH
            for i, worker in enumerate(workers):
                raw, prev = gen_backlog.chain_block(
                    worker.result(), plan[i]["number"], prev)
                with open(paths[i], "wb") as f:
                    f.write(raw)
                if i == n_ref - 1:
                    reference = threads.submit(reference_replay)
            say(f"{len(plan)} blocks written ({time.monotonic() - t:.1f} s)")
            report = reference.result()
            say(f"reference replayed ({time.monotonic() - t:.1f} s)")
            return report

        generated = threads.submit(generate)
        init = device.expect("init")
        prov = init["provider"]
        if ctx.require_accelerator:
            if prov["device"] is None:
                raise BenchFailure("the device peer runs no device provider")
            harness.require_chips(prov["device"]["platform"],
                                  prov["device"]["device_count"],
                                  int(wl["chips"]))
        say(f"device peer up in {init['seconds']:.1f} s: provider "
            f"{prov['name']}, device "
            f"{prov['device'] and prov['device']['devices']}")
        if prov["device"] is not None:
            device.send("warm", rows=wl["warm_rows"])
            warm = device.expect("warm")
            say(f"warm-up: {warm['timings']} ({warm['seconds']:.1f} s)")
        ref_report = generated.result()
        device.send("pilot", block=paths[0])
        pilot = device.expect("pilot")["block"]
        say(f"pilot block: {pilot['end'] - pilot['start']:.2f} s")
        device.send("load", blocks=paths[1:])
        loaded = device.expect("loaded")
        say(f"backlog of {loaded['blocks']} blocks loaded "
            f"({loaded['bytes']} bytes)")
        setup_s = time.monotonic() - harness.T0

        # ---- the window ----------------------------------------------------
        device.send("go", seconds=ctx.seconds,
                    trace_blocks=wl["trace_blocks"])
        rep = device.expect("done")
        device.stop()
        return judge(ctx, plan, pilot, rep, ref_report, setup_s, trace_dir)
    finally:
        for child in children:
            child.stop()
        pool.shutdown(wait=True, cancel_futures=True)
        threads.shutdown(wait=True, cancel_futures=True)
        for child in children:       # one a thread started meanwhile
            child.stop()
        resource_tracker._resource_tracker._stop()   # the pool's helper
        shutil.rmtree(base, ignore_errors=True)


def judge(ctx, plan, pilot, rep, ref_report, setup_s, trace_dir) -> dict:
    """Everything after the window: what counts, and whether it is right."""
    wl = ctx.workload
    t_stop = rep["t_go"] + rep["seconds"]
    done = [b for b in rep["blocks"] if b["end"] <= t_stop]
    say(f"window: {len(rep['blocks'])} blocks started, {len(done)} finished "
        f"inside {rep['seconds']:.0f} s"
        + ("; BACKLOG EXHAUSTED, rate over the time used"
           if rep["exhausted"] else ""))
    if not done:
        raise BenchFailure("no block finished inside the window")
    txs = sum(b["txs"] for b in done)
    used_s = done[-1]["end"] - done[0]["start"]
    by_number = {b["number"]: b for b in plan}
    want = {n: bytes(tx["code"] for tx in b["txs"])
            for n, b in by_number.items()}

    # the generator's serial simulation, for every block the device did
    wrong = tampered_missed = 0
    for b in [pilot] + done:
        got = bytes.fromhex(b["flags"])
        wrong += got != want[b["number"]]
        tampered_missed += sum(
            1 for tx, code in zip(by_number[b["number"]]["txs"], got)
            if tx["tampered"] and code != gen_backlog.POLICY_FAILURE)
    ctx.check("blocks whose flags differ from the generator's serial "
              "simulation (device peer)", wrong, "==", 0)
    ctx.check("tampered envelopes not ENDORSEMENT_POLICY_FAILURE "
              "(device peer)", tampered_missed, "==", 0)
    # the software peer, for every block both hold
    dev_by_number = {b["number"]: b for b in [pilot] + rep["blocks"]}
    both = [r for r in ref_report["blocks"] if r["number"] in dev_by_number]
    differ = sum(1 for r in both
                 if (r["flags"], r["commit_hash"])
                 != (dev_by_number[r["number"]]["flags"],
                     dev_by_number[r["number"]]["commit_hash"]))
    ctx.check("blocks the software peer also holds", len(both), ">=",
              min(int(wl["reference_blocks"]), 1 + len(done)))
    ctx.check("blocks whose flags or commit hash differ between the device "
              "peer and the software peer", differ, "==", 0)
    ref_wrong = sum(1 for r in ref_report["blocks"]
                    if bytes.fromhex(r["flags"]) != want[r["number"]])
    ctx.check("blocks whose flags differ from the generator's serial "
              "simulation (software peer)", ref_wrong, "==", 0)
    ctx.check("software peer's process imported jax",
              int(ref_report["jax_imported"]), "==", 0)

    before, after = rep["before"], rep["after"]
    device = harness.device_report(after)
    if after["device"] is not None:
        s0, s1 = before["stats"], after["stats"]
        c0, c1 = before["device"]["compile"], after["device"]["compile"]
        started_tx = sum(b["txs"] for b in rep["blocks"])
        ctx.check("provider fallbacks", s1["fallbacks"], "==", 0)
        ctx.check("provider degraded", int(after["degraded"]), "==", 0)
        ctx.check("compilations inside the window",
                  c1["compiles"] - c0["compiles"], "==", 0)
        ctx.check("signatures verified on the device per transaction",
                  (s1["device_sigs"] - s0["device_sigs"]) / started_tx, ">=",
                  float(ctx.config["signatures_per_tx"]))

    end_to_end = {"catchup_tps": txs / used_s, "setup_s": setup_s}
    say(f"catchup_tps {end_to_end['catchup_tps']:.2f} tx/s: {txs} tx in "
        f"{len(done)} blocks over {used_s:.3f} s; per block "
        f"{[round(b['end'] - b['start'], 3) for b in done]} s; "
        f"setup_s {setup_s:.1f}")

    traced = rep["traced"]
    # per-block spans: of the window's blocks, without those the profiler
    # watched (it slows them)
    t_profiled = traced.get("start", float("inf"))
    obs = {"prom_before": harness.parse_prom(rep["prom_before"]),
           "prom_after": harness.parse_prom(rep["prom_after"]),
           "spans": [s for s in rep["spans"]
                     if rep["t_go"] <= s["start"] < t_profiled]}
    if ctx.trace and "end" in traced:
        obs["traced_prom_before"] = harness.parse_prom(traced["prom_before"])
        obs["traced_prom_after"] = harness.parse_prom(traced["prom_after"])
        obs["trace"] = harness.reduce_trace(
            trace_dir,
            {"mark_name": "bench.mark", "mark_perf": traced["mark"],
             "spans": [s for s in rep["spans"]
                       if traced["start"] <= s["start"] <= traced["end"]]})
        device["busy_s"] = obs["trace"]["busy_s"]
        device["window_s"] = obs["trace"]["window_s"]
    failed = sum(a != b for blk in done
                 for a, b in zip(bytes.fromhex(blk["flags"]),
                                 want[blk["number"]]))
    return {"attempted": txs, "failed": failed, "end_to_end": end_to_end,
            "obs": obs, "device": device}
