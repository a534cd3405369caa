"""Driver `enrolled_catchup`: a peer's committer working off a backlog
of blocks whose creators it has not seen.

`drivers/catchup.py` on a deployment with a roll of enrolled clients
(`gen/enrolled.py`: its own deployment step, the plan simulated with the
roll in hand, a builder that takes a block's creators off the roll) and
a device peer that can be made blind to certificate chains
(`drivers/enrolled_child.py`).  What `catchup.judge` checks is checked
by it — flags against the generator's serial simulation, flags and
commit hash against the software peer, the provider, the rate; this
driver adds what only such a chain can show: that revoked and forged
creators were refused, that a block's creators were distinct by the
program's own count, and that every one of them cost the committing
peer a chain validation.

Cell parameters (`workloads/<cell>.json`): `backlog_blocks`, `block_tx`,
`reference_blocks`, `warm_rows`, `generator_workers`, `trace_blocks`.
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
from gen import enrolled as gen
from harness import BenchFailure, say

CHILD = os.path.join(harness.BENCH, "drivers", "enrolled_child.py")


class Child(catchup.Child):
    """enrolled_child.py as a subprocess speaking JSON lines."""

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
    sys.path.insert(0, harness.REPO)
    gen.require_program_support()      # before anything is started
    harness.build_native()
    base = tempfile.mkdtemp(prefix="bench_enrolled_")
    trace_dir = os.path.join(base, "trace")
    children = []
    pool = ProcessPoolExecutor(
        max_workers=int(wl["generator_workers"]),
        mp_context=multiprocessing.get_context("spawn"))
    threads = ThreadPoolExecutor(2)
    try:
        tracing = {"enabled": bool(ctx.trace), "sample_rate": 1.0}
        dep = gen.Deployment(base, cfg, harness.REPO, {"tracing": tracing},
                             ctx.seed)
        device = Child("device_peer", dep, dep.device_org, ctx.trace,
                       trace_dir, sorted(ctx.faults))
        children.append(device)
        say(f"device peer started (pid {device.proc.pid}); planning "
            f"{1 + n_backlog} blocks of {block_tx} tx from seed {ctx.seed}")
        plan = gen.plan_backlog(
            ctx.seed, 1 + n_backlog, block_tx, int(cfg["keyspace"]),
            dep.clients, int(cfg["tamper_every"]), int(cfg["forge_every"]),
            dep.revoked)
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
                                   dep.chaincode, b) for b in plan]
            prev = gen_backlog.GENESIS_PREVIOUS_HASH
            for i, worker in enumerate(workers):
                raw, prev = gen_backlog.chain_block(
                    worker.result(), plan[i]["number"], prev)
                with open(paths[i], "wb") as f:
                    f.write(raw)
                if i == n_ref - 1:
                    reference = threads.submit(reference_replay)
            say(f"generation: {len(plan)} blocks written "
                f"({time.monotonic() - t:.1f} s)")
            report = reference.result()
            say(f"reference replayed ({time.monotonic() - t:.1f} s)")
            return report

        generated = threads.submit(generate)
        init = device.expect("init")
        held = {"init": init["memory"]}      # the child's resident set
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
            held["warm"] = warm["memory"]
            say(f"warm-up: {warm['timings']} ({warm['seconds']:.1f} s)")
        ref_report = generated.result()
        device.send("pilot", block=paths[0])
        piloted = device.expect("pilot")
        pilot, held["pilot"] = piloted["block"], piloted["memory"]
        say(f"pilot block: {pilot['end'] - pilot['start']:.2f} s")
        device.send("load", blocks=paths[1:])
        loaded = device.expect("loaded")
        held["loaded"] = loaded["memory"]
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
        judge_identities(ctx, plan, pilot, rep, ref_report, out["obs"])
        held["done"] = rep["memory"]
        say(f"device peer's process, resident bytes now / at its peak, "
            f"stage by stage: "
            + "; ".join(f"{k} {v.get('rss')} / {v.get('peak')}"
                        for k, v in held.items())
            + f"; memory_peak_bytes {out['device']['memory_peak_bytes']}")
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


def judge_identities(ctx, plan, pilot, rep, ref_report, obs) -> None:
    """What a chain of unseen creators adds to `catchup.judge`."""
    by_number = {b["number"]: b for b in plan}

    def refused(blocks) -> dict:
        """{class: [made by the generator, BAD_CREATOR_SIGNATURE by the
        peer's flags]} over `blocks` (a peer's reports)."""
        out = {"revoked": [0, 0], "forged": [0, 0]}
        for b in blocks:
            got = bytes.fromhex(b["flags"])
            for tx, code in zip(by_number[b["number"]]["txs"], got):
                for cls, pair in out.items():
                    if tx[cls]:
                        pair[0] += 1
                        pair[1] += code == gen.BAD_CREATOR
        return out

    # every block the device peer stored, finished inside the window or not
    device = refused([pilot] + rep["blocks"])
    reference = refused(ref_report["blocks"])
    for cls, (made, flagged) in device.items():
        ctx.check(f"{cls}-creator transactions made (generator, the device "
                  "peer's blocks)", made, ">=", 1)
        ctx.check(f"{cls}-creator transactions BAD_CREATOR_SIGNATURE "
                  "(device peer, of those made)", flagged, "==", made)
        ctx.check(f"{cls}-creator transactions BAD_CREATOR_SIGNATURE "
                  "(software peer, of those made in its blocks)",
                  reference[cls][1], "==", reference[cls][0])
    before, after = obs["prom_before"], obs["prom_after"]
    started_tx = sum(b["txs"] for b in rep["blocks"])
    seen = {s: harness.prom_delta(before, after, "validator_creators_total",
                                  seen=s) for s in ("first", "again")}
    say(f"creators of the window's blocks by the validator's count: {seen}")
    ctx.check("creators resolved by the validator over the window's "
              "transactions", sum(seen.values()) / started_tx, "==", 1.0)
    ctx.check("distinct creators per transaction over the window's blocks "
              "(validator_creators_total, first / all)",
              seen["first"] / max(1.0, sum(seen.values())), ">=", 0.99)
    if rep["after"]["device"] is not None:
        # a refused creator's transaction brings none of its signatures
        # to the device; every other brings all of them, tampered or not
        per_tx = 1 + len(ctx.config["peer_orgs"])
        owed = per_tx * sum(not (tx["revoked"] or tx["forged"])
                            for b in rep["blocks"]
                            for tx in by_number[b["number"]]["txs"])
        ctx.check("signatures verified on the device over the window's "
                  "blocks (the generator's count: every signature of every "
                  "transaction whose creator is sound)",
                  rep["after"]["stats"]["device_sigs"]
                  - rep["before"]["stats"]["device_sigs"], ">=", owed)
    # by the generator: a block's distinct creators, block by block
    distinct = sum(len({(tx["creator"], tx["forged"])
                        for tx in by_number[b["number"]]["txs"]})
                   for b in rep["blocks"])
    validations = harness.prom_delta(before, after,
                                     "msp_validate_seconds_count")
    ctx.check("certificate chains validated by the device peer in the "
              "window (none of a block's distinct creators answered from "
              "anywhere but a validation)", validations, ">=", distinct)
