"""Driver `smallbank_catchup`: a peer's committer working off a backlog
of SmallBank blocks over hot accounts.

`drivers/catchup.py` with another generator and a state to compare: the
chain comes from `gen/smallbank.py` (a pure function of the seed) — the
opening blocks that create every account, replayed in set-up by the
device peer (the first is the pilot that makes the keys' tables
resident) and by the software peer, then the backlog the window works
off.  What `catchup.judge` checks is checked by it (flags against the
generator's serial block rule, flags and commit hash against the
software peer, the provider, the rate); this driver adds the opening
blocks, every account's balances on both peers against the model, and
the money account.  `setup_s` ends when the device peer holds the
backlog; the software peer's longer replay is waited for after that,
before the window.

Cell parameters (`workloads/<cell>.json`): `backlog_blocks`, `block_tx`,
`reference_blocks` (of the backlog, after the opening blocks),
`warm_rows`, `generator_workers`, `trace_blocks`.
"""

from __future__ import annotations

import multiprocessing
import os
import shutil
import statistics
import subprocess
import sys
import tempfile
import threading
import time
from concurrent.futures import ProcessPoolExecutor, ThreadPoolExecutor
from multiprocessing import resource_tracker

import harness
from drivers import catchup
from gen import backlog as gen_backlog
from gen import smallbank as gen
from gen.deployment import Deployment
from harness import BenchFailure, say

CHILD = os.path.join(harness.BENCH, "drivers", "smallbank_child.py")


class Child(catchup.Child):
    """smallbank_child.py as a subprocess speaking JSON lines."""

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


def run(ctx: harness.Context) -> dict:
    wl, cfg = ctx.workload, ctx.config
    sys.path.insert(0, harness.REPO)
    from fabric_tpu.node import peer as program_peer
    contract = cfg["chaincode"]["contract"]
    if contract not in program_peer.DEV_CONTRACTS:
        # a program from before the contract: nothing to measure, said
        # before anything is started
        raise BenchFailure(f"the program has no contract {contract!r}")
    accounts, block_tx = int(cfg["accounts"]), int(wl["block_tx"])
    n_open = -(-accounts // block_tx)
    n_backlog = int(wl["backlog_blocks"])
    n_ref = min(n_open + int(wl["reference_blocks"]), n_open + n_backlog)
    ask = {"namespace": cfg["chaincode"]["name"], "accounts": accounts}
    harness.build_native()
    base = tempfile.mkdtemp(prefix="bench_smallbank_")
    trace_dir = os.path.join(base, "trace")
    children = []
    pool = ProcessPoolExecutor(
        max_workers=int(wl["generator_workers"]),
        mp_context=multiprocessing.get_context("spawn"))
    threads = ThreadPoolExecutor(2)
    try:
        tracing = {"enabled": bool(ctx.trace), "sample_rate": 1.0}
        dep = Deployment(base, cfg, harness.REPO, {"tracing": tracing})
        # the control alters the device peer's report, not the verifier
        device = Child("device_peer", dep, dep.device_org, ctx.trace,
                       trace_dir, sorted(ctx.faults))
        children.append(device)
        say(f"device peer started (pid {device.proc.pid}); planning "
            f"{n_open} opening + {n_backlog} blocks of {block_tx} tx over "
            f"{accounts} accounts from seed {ctx.seed}")
        paths = [os.path.join(base, f"block_{n}.bin")
                 for n in range(n_open + n_backlog)]
        summaries = []
        # on disk: the opening blocks, the software peer's share
        opened, shared = threading.Event(), threading.Event()

        def reference_replay() -> dict:
            """The software peer replays its share as soon as it exists."""
            ref = Child("reference_peer", dep, wl["reference_org"], False,
                        trace_dir)
            children.append(ref)
            ref.expect("init")
            shared.wait()
            if not os.path.exists(paths[n_ref - 1]):
                raise BenchFailure("the generator stopped before the "
                                   "software peer's share was written")
            ref.send("replay", blocks=paths[:n_ref], **ask)
            report = ref.expect("replayed")
            ref.stop()
            return report

        def generate() -> None:
            """Blocks planned one after another (each needs the state the
            last left), built by the workers, chained and written in
            order."""
            t = time.monotonic()
            workers = []
            try:
                for block in gen.iter_chain(
                        ctx.seed, accounts, n_backlog, block_tx,
                        int(cfg["client_identities"]),
                        int(cfg["tamper_every"]), float(cfg["zipf_s"]),
                        float(cfg["p_write"])):
                    workers.append(pool.submit(
                        gen.worker_build, dep.file, dep.channel,
                        dep.chaincode, block))
                    summaries.append(gen.summary(block))
                say(f"{len(workers)} blocks planned "
                    f"({time.monotonic() - t:.1f} s)")
                prev = gen_backlog.GENESIS_PREVIOUS_HASH
                for i, worker in enumerate(workers):
                    raw, prev = gen_backlog.chain_block(worker.result(), i,
                                                        prev)
                    with open(paths[i], "wb") as f:
                        f.write(raw)
                    if i == n_open - 1:
                        opened.set()
                    if i == n_ref - 1:
                        shared.set()
            finally:
                opened.set()             # never leave a thread waiting
                shared.set()
            say(f"{len(paths)} blocks written ({time.monotonic() - t:.1f} s)")

        generated = threads.submit(generate)
        reference = threads.submit(reference_replay)
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
        opened.wait()
        if not os.path.exists(paths[n_open - 1]):
            generated.result()           # it failed: say why, now
        device.send("open", blocks=paths[:n_open])
        opening = device.expect("opened")["blocks"]
        say(f"opening blocks: "
            f"{[round(b['end'] - b['start'], 2) for b in opening]} s")
        generated.result()
        device.send("load", blocks=paths[n_open:])
        loaded = device.expect("loaded")
        say(f"backlog of {loaded['blocks']} blocks loaded "
            f"({loaded['bytes']} bytes)")
        # the device peer is ready: set-up ends here.  The software
        # peer's replay is the comparison's, so the seconds still spent
        # waiting for it (the window starts only once the cores are the
        # device peer's alone) are no part of `setup_s`
        setup_s = time.monotonic() - harness.T0
        ref_report = reference.result()
        say(f"reference replayed {len(ref_report['blocks'])} blocks "
            f"({time.monotonic() - harness.T0 - setup_s:.1f} s after "
            "set-up's end)")

        # ---- the window ----------------------------------------------------
        device.send("go", seconds=ctx.seconds,
                    trace_blocks=wl["trace_blocks"])
        rep = device.expect("done")
        device.send("balances", **ask)
        held = device.expect("balances")
        device.stop()
        # catchup.judge: the window's blocks and the pilot (here the first
        # opening block) against the plan and the software peer, the
        # provider's checks, the rate, the observations
        plan = []
        for s in summaries:
            # judge only reads: one dict for every tx of the same kind
            kinds = {(c, t): {"code": c, "tampered": t}
                     for c in set(s["codes"]) for t in (False, True)}
            tampered = set(s["tampered"])
            plan.append({"number": s["number"],
                         "txs": [kinds[c, n in tampered]
                                 for n, c in enumerate(s["codes"])]})
        out = catchup.judge(ctx, plan, opening[0], rep, ref_report, setup_s,
                            trace_dir)
        judge_state(ctx, summaries, opening, rep, ref_report, held, accounts)
        say_slow_blocks(rep["blocks"])
        out["obs"]["blocks"] = [
            b for b in rep["blocks"]
            if b["start"] < rep["traced"].get("start", float("inf"))]
        say_block_account(out["obs"])
        return out
    finally:
        for child in children:
            child.stop()
        pool.shutdown(wait=True, cancel_futures=True)
        threads.shutdown(wait=True, cancel_futures=True)
        for child in children:       # one a thread started meanwhile
            child.stop()
        # the pool's helper process, by a private name as
        # drivers/catchup.py ends it; where the name is gone, run.py
        # reaps the helper on its way out
        stop = getattr(resource_tracker._resource_tracker, "_stop", None)
        if stop is not None:
            stop()
        shutil.rmtree(base, ignore_errors=True)


def differing_accounts(reported: dict, balances: dict, accounts: int) -> int:
    """Accounts of 1..accounts whose savings or checking balance, as a
    peer reported them, is not the model's."""
    return sum(
        1 for i in range(1, accounts + 1)
        if (reported["savings"][i - 1], reported["checking"][i - 1])
        != (balances.get(gen.savings(i)), balances.get(gen.checking(i))))


def judge_state(ctx, summaries, opening, rep, ref_report, held,
                accounts) -> None:
    """What a deployment with balances adds to `correct`."""
    by_number = {s["number"]: s for s in summaries}

    def wrong_flags(blocks) -> int:
        return sum(1 for b in blocks
                   if bytes.fromhex(b["flags"]) != by_number[b["number"]]["codes"])
    # the opening blocks beyond the pilot, and a block the window started
    # and finished after its end: their writes are in the state compared
    ctx.check("opening blocks whose flags differ from the generator's "
              "(device peer)", wrong_flags(opening), "==", 0)
    ctx.check("transactions of the opening blocks not VALID (device peer)",
              sum(1 for b in opening for c in bytes.fromhex(b["flags"])
                  if c != gen.VALID), "==", 0)
    ctx.check("blocks stored in or after the window whose flags differ "
              "from the generator's (device peer)",
              wrong_flags(rep["blocks"]), "==", 0)
    ref_by_number = {r["number"]: r for r in ref_report["blocks"]}
    ctx.check("opening blocks whose flags or commit hash differ between "
              "the device peer and the software peer",
              sum(1 for b in opening
                  if (b["flags"], b["commit_hash"])
                  != (ref_by_number[b["number"]]["flags"],
                      ref_by_number[b["number"]]["commit_hash"])), "==", 0)

    last = (rep["blocks"] or opening)[-1]["number"]
    ctx.check("device peer's height against the last block it stored",
              held["height"], "==", last + 1)
    balances, money = gen.balances_after(summaries, last)
    ctx.check("accounts compared on the device peer",
              len(held["balances"]["savings"]), "==", accounts)
    ctx.check(f"accounts whose balances differ from the model's after "
              f"block {last} (device peer)",
              differing_accounts(held["balances"], balances, accounts),
              "==", 0)
    total = sum(v for table in held["balances"].values()
                for v in table if v is not None)
    ctx.check("sum of the device peer's balances against the money account "
              f"(opened {money['opened']} + deposited {money['deposited']} "
              f"- checks {money['checks']} - penalties {money['penalties']})",
              total, "==", money["opened"] + money["deposited"]
              - money["checks"] - money["penalties"])
    ref_last = ref_report["height"] - 1
    ref_balances, _ = gen.balances_after(summaries, ref_last)
    ctx.check(f"accounts whose balances differ from the model's after "
              f"block {ref_last} (software peer)",
              differing_accounts(ref_report["balances"], ref_balances,
                                 accounts), "==", 0)
    window = [by_number[b["number"]] for b in rep["blocks"]]
    txs = sum(len(s["codes"]) for s in window)
    say(f"window's blocks by the model: "
        f"{100.0 * sum(s['codes'].count(gen.VALID) for s in window) / txs:.2f}"
        f"% VALID, {sum(s['reads'] for s in window) / txs:.3f} reads and "
        f"{sum(len(s['writes']) for s in window) / txs:.3f} applied writes "
        f"a transaction, {sum(s['redrawn'] for s in window)} draws the "
        f"contract would refuse drawn again")


def say_slow_blocks(blocks: list) -> None:
    """A block far above the window's median, with the seconds the
    ledger counted in each of its phases (the rest is the validator's
    and the committer's): a stall of some seconds, as 2 of this cell's
    first 19 sound runs held, shows here and in `catchup_tps`, and
    nowhere else in a run that keeps no spans."""
    took = [b["end"] - b["start"] for b in blocks]
    for block, seconds in zip(blocks, took):
        if seconds > 1.5 * statistics.median(took):
            say(f"slow block {block['number']}: {seconds:.2f} s against a "
                f"median of {statistics.median(took):.2f}; the ledger's "
                f"phases {block['ledger_s']}")


def say_block_account(obs: dict) -> None:
    """A traced run's per-block account: every span's mean milliseconds
    a block, over the window's blocks the profiler did not watch."""
    blocks = obs["blocks"]
    if not blocks or not obs["spans"]:
        return
    total = {}
    for span in obs["spans"]:
        total[span["name"]] = total.get(span["name"], 0.0) + span["duration_s"]
    account = {name: round(1e3 * s / len(blocks), 1)
               for name, s in sorted(total.items(), key=lambda kv: -kv[1])}
    counts = {k: sum(b["counts"][k] for b in blocks) / len(blocks)
              for k in blocks[0]["counts"]}
    say(f"per block over {len(blocks)} blocks, ms: {account}; store_block "
        f"{1e3 * sum(b['end'] - b['start'] for b in blocks) / len(blocks):.1f}"
        f"; counters a block: {counts}")
