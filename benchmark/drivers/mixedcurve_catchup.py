"""Driver `mixedcurve_catchup`: a peer's committer working off a backlog
of blocks whose signatures are on two curves.

`drivers/catchup.py` on a deployment in which some orgs have
re-enrolled on Ed25519 (`gen/mixedcurve.py`: its own deployment step
and a builder that breaks the endorsements of `tamper_orgs` in turn)
and with a device peer that is warmed for both kernel families
(`drivers/mixedcurve_child.py`).  What `catchup.judge` checks is checked
by it — flags against the generator's serial simulation, flags and
commit hash against the software peer, the provider, the rate; this
driver adds what only a mixed block can show: that the Ed25519
signatures ran on the device, on the fixed-comb lane alone, and that
the Ed25519 kernel said no to every endorsement broken on its curve.

Cell parameters (`workloads/<cell>.json`): `backlog_blocks`, `block_tx`,
`reference_blocks`, `warm_rows`, `warm_ed25519_rows`,
`generator_workers`, `trace_blocks`.
"""

from __future__ import annotations

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
from drivers import catchup
from gen import backlog as gen_backlog
from gen import mixedcurve as gen
from harness import BenchFailure, say

CHILD = os.path.join(harness.BENCH, "drivers", "mixedcurve_child.py")
ED25519_LANES = ("ed25519-rows", "ed25519")


class Child(catchup.Child):
    """mixedcurve_child.py as a subprocess speaking JSON lines."""

    def __init__(self, name: str, dep, org: str, trace: bool,
                 trace_dir: str, faults=()):
        self.name = name
        self.log_path = os.path.join(dep.base, name + ".log")
        with open(self.log_path, "wb") as log:
            self.proc = subprocess.Popen(
                [sys.executable, CHILD, dep.peer_cfg_path[org],
                 "1" if trace else "0", trace_dir, *faults],
                env=dep.env, stdin=subprocess.PIPE, stdout=subprocess.PIPE,
                stderr=log, text=True)


def run(ctx: harness.Context) -> dict:
    wl, cfg = ctx.workload, ctx.config
    n_backlog = int(wl["backlog_blocks"])
    n_ref = int(wl["reference_blocks"])          # pilot included
    block_tx = int(wl["block_tx"])
    tamper_every = int(cfg["tamper_every"])
    tamper_at = [cfg["peer_orgs"].index(o) for o in cfg["tamper_orgs"]]
    sys.path.insert(0, harness.REPO)
    gen.require_program_support()      # before anything is started
    harness.build_native()
    base = tempfile.mkdtemp(prefix="bench_mixedcurve_")
    trace_dir = os.path.join(base, "trace")
    children = []
    pool = ProcessPoolExecutor(
        max_workers=int(wl["generator_workers"]),
        mp_context=multiprocessing.get_context("spawn"))
    threads = ThreadPoolExecutor(2)
    try:
        tracing = {"enabled": bool(ctx.trace), "sample_rate": 1.0}
        dep = gen.Deployment(base, cfg, harness.REPO, {"tracing": tracing})
        device = Child("device_peer", dep, dep.device_org, ctx.trace,
                       trace_dir, sorted(ctx.faults))
        children.append(device)
        say(f"device peer started (pid {device.proc.pid}); planning "
            f"{1 + n_backlog} blocks of {block_tx} tx from seed {ctx.seed}; "
            f"schemes {cfg['org_schemes']}")
        plan = gen_backlog.plan_backlog(
            ctx.seed, 1 + n_backlog, block_tx, int(cfg["keyspace"]),
            int(cfg["client_identities"]), tamper_every)
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
            workers = [pool.submit(gen.worker_build, dep.file, dep.channel,
                                   dep.chaincode, b, tamper_every, tamper_at)
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
            device.send("warm", rows=wl["warm_rows"],
                        ed25519_rows=wl["warm_ed25519_rows"])
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
        out = catchup.judge(ctx, plan, pilot, rep, ref_report, setup_s,
                            trace_dir)
        judge_curves(ctx, plan, pilot, rep, out["obs"], tamper_at)
        return out
    finally:
        for child in children:
            child.stop()
        pool.shutdown(wait=True, cancel_futures=True)
        threads.shutdown(wait=True, cancel_futures=True)
        for child in children:       # one a thread started meanwhile
            child.stop()
        resource_tracker._resource_tracker._stop()   # the pool's helper
        shutil.rmtree(base, ignore_errors=True)


def judge_curves(ctx, plan, pilot, rep, obs, tamper_at) -> None:
    """What a mixed block adds to `catchup.judge`."""
    cfg = ctx.config
    tamper_every = int(cfg["tamper_every"])
    t_stop = rep["t_go"] + rep["seconds"]
    done = [b for b in rep["blocks"] if b["end"] <= t_stop]
    by_number = {b["number"]: b for b in plan}
    # the endorsements broken on the other curve: made, and flagged
    ed_at = {cfg["peer_orgs"].index(org)
             for org, scheme in cfg["org_schemes"].items()
             if scheme == "ed25519"}
    made = flagged = 0
    for b in [pilot] + done:
        got = bytes.fromhex(b["flags"])
        for t, tx in enumerate(by_number[b["number"]]["txs"]):
            if tx["tampered"] and tamper_at[gen.tampered_endorser(
                    t, tamper_every, len(tamper_at))] in ed_at:
                made += 1
                flagged += got[t] == gen_backlog.POLICY_FAILURE
    ctx.check("tampered Ed25519 endorsements made", made, ">=", 1)
    ctx.check("tampered Ed25519 endorsements flagged "
              "ENDORSEMENT_POLICY_FAILURE (of those made)", flagged, "==",
              made)
    if rep["after"]["device"] is None:
        return
    before, after = obs["prom_before"], obs["prom_after"]
    started_tx = sum(b["txs"] for b in rep["blocks"])
    by_lane = {lane: harness.prom_delta(
        before, after, "provider_dispatch_sigs_total", lane=lane)
        for lane in ED25519_LANES}
    say(f"Ed25519 signatures dispatched in the window, by lane: {by_lane}")
    ctx.check("Ed25519 signatures verified on the device per transaction",
              sum(by_lane.values()) / started_tx, ">=",
              float(cfg["ed25519_signatures_per_tx"]))
    ctx.check("Ed25519 signatures on the ladder lane (a key without a "
              "resident table) in the window", by_lane["ed25519"], "==", 0)
