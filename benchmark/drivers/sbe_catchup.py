"""Driver `sbe_catchup`: a peer's committer working off a backlog of
blocks on a channel where every asset carries its owner organisation's
endorsement policy as a key-level validation parameter.

`drivers/catchup.py` with another generator and a state to compare, as
`smallbank_catchup.py` is: the chain comes from `gen/sbe.py` (a pure
function of the seed) — the load phase that creates every asset and sets
its parameter, replayed in set-up by the device peer (the first block is
the pilot that makes the keys' tables resident) and by the software
peer, then the backlog of the mix the window works off.  What
`catchup.judge` checks is checked by it (flags against the generator's
serial block rule, flags and commit hash against the software peer, the
provider, the rate); this driver adds the load phase, the exact count of
signatures the device owes, what the mix must make happen in every run,
every asset's record and parameter on both peers against the model, the
state's count of parameters, and the tail every block of the window
took.  `setup_s` ends when the device peer holds the backlog; the
software peer's replay is waited for after that, before the window.

Cell parameters (`workloads/<cell>.json`): `backlog_blocks`, `block_tx`,
`reference_blocks` (of the backlog, after the load phase), `warm_rows`,
`generator_workers`, `trace_blocks`.
"""

from __future__ import annotations

import multiprocessing
import os
import shutil
import subprocess
import sys
import tempfile
import threading
import time
from concurrent.futures import ProcessPoolExecutor, ThreadPoolExecutor
from multiprocessing import resource_tracker

import harness
from drivers import catchup
from drivers.smallbank_catchup import say_block_account, say_slow_blocks
from gen import backlog as gen_backlog
from gen import sbe as gen
from gen.deployment import Deployment
from harness import BenchFailure, prom_delta, say

CHILD = os.path.join(harness.BENCH, "drivers", "sbe_child.py")
# what the mix must make happen in every run, by the model's own codes
MUST_HAPPEN = ("wrong_org_failures", "overlay_failures", "mvcc_conflicts",
               "deletes", "recreates")


class Child(catchup.Child):
    """sbe_child.py as a subprocess speaking JSON lines."""

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
    assets, block_tx = int(cfg["assets"]), int(wl["block_tx"])
    n_load = -(-assets // block_tx)
    n_backlog = int(wl["backlog_blocks"])
    n_ref = min(n_load + int(wl["reference_blocks"]), n_load + n_backlog)
    namespace = cfg["chaincode"]["name"]
    harness.build_native()
    base = tempfile.mkdtemp(prefix="bench_sbe_")
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
            f"{n_load} load + {n_backlog} blocks of {block_tx} tx over "
            f"{assets} assets from seed {ctx.seed}")
        paths = [os.path.join(base, f"block_{n}.bin")
                 for n in range(n_load + n_backlog)]
        summaries = []
        # on disk: the load phase, the software peer's share
        loaded_phase, shared = threading.Event(), threading.Event()

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
            ref.send("replay", blocks=paths[:n_ref], namespace=namespace,
                     ids=highest_id(summaries[:n_ref]))
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
                        ctx.seed, assets, n_backlog, block_tx,
                        int(cfg["client_identities"]),
                        int(cfg["tamper_every"]), tuple(cfg["peer_orgs"])):
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
                    if i == n_load - 1:
                        loaded_phase.set()
                    if i == n_ref - 1:
                        shared.set()
            finally:
                loaded_phase.set()       # never leave a thread waiting
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
        loaded_phase.wait()
        if not os.path.exists(paths[n_load - 1]):
            generated.result()           # it failed: say why, now
        device.send("open", blocks=paths[:n_load])
        opening = device.expect("opened")["blocks"]
        say(f"load phase: "
            f"{[round(b['end'] - b['start'], 2) for b in opening]} s")
        generated.result()
        device.send("load", blocks=paths[n_load:])
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
        device.send("state", namespace=namespace,
                    ids=highest_id(summaries))
        held = device.expect("state")
        device.stop()
        # catchup.judge: the window's blocks and the pilot (here the first
        # load block) against the plan and the software peer, the
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
        judge_sbe(ctx, summaries, opening, rep, ref_report, held, n_ref,
                  out["obs"])
        say_slow_blocks(rep["blocks"])
        t_profiled = rep["traced"].get("start", float("inf"))
        out["obs"]["blocks"] = [b for b in rep["blocks"]
                                if b["start"] < t_profiled]
        out["obs"]["attributed_spans"] = [
            s for s in rep.get("attributed", ())
            if rep["t_go"] <= s["start"] < t_profiled]
        say_block_account(out["obs"])
        later = [b["start"] for b in rep["blocks"]
                 if b["start"] >= rep["traced"].get("end", float("inf"))]
        if later:
            say(f"the capture's end: {min(later) - rep['traced']['end']:.1f} "
                "s between the traced blocks and the next block's start")
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


def highest_id(summaries: list) -> int:
    """The highest asset number the given blocks create: asset1..asset<n>
    is every id they can have touched."""
    return max(s["highest_id"] for s in summaries)


def happened(summaries_by_number: dict, blocks) -> dict:
    """What the mix made happen in `blocks` (as a peer reported them), by
    the peer's OWN flags and the plan's kinds — to be set against the
    model's counts of the same blocks."""
    out = dict.fromkeys(MUST_HAPPEN, 0)
    for b in blocks:
        s = summaries_by_number[b["number"]]
        recreates = set(s["recreates"])
        for n, code in enumerate(bytes.fromhex(b["flags"])):
            kind = s["kinds"][n]
            out["mvcc_conflicts"] += code == gen.MVCC_CONFLICT
            if code == gen.POLICY_FAILURE:
                out["wrong_org_failures"] += s["causes"].get(n) == "wrong_org"
                out["overlay_failures"] += s["causes"].get(n) == "overlay"
            elif code == gen.VALID:
                out["deletes"] += kind == "delete"
                out["recreates"] += n in recreates
    return out


def differing(reported: list, want: list) -> int:
    return sum(a != b for a, b in zip(reported, want)) + abs(
        len(reported) - len(want))


def judge_sbe(ctx, summaries, opening, rep, ref_report, held, n_ref,
              obs) -> None:
    """What a deployment with key-level endorsement adds to `correct`."""
    by_number = {s["number"]: s for s in summaries}

    def wrong_flags(blocks) -> int:
        return sum(1 for b in blocks
                   if bytes.fromhex(b["flags"]) != by_number[b["number"]]["codes"])
    # the load blocks beyond the pilot, and a block the window started
    # and finished after its end: their writes are in the state compared
    ctx.check("load-phase blocks whose flags differ from the generator's "
              "(device peer)", wrong_flags(opening), "==", 0)
    ctx.check("transactions of the load phase not VALID (device peer)",
              sum(1 for b in opening for c in bytes.fromhex(b["flags"])
                  if c != gen.VALID), "==", 0)
    ctx.check("blocks stored in or after the window whose flags differ "
              "from the generator's (device peer)",
              wrong_flags(rep["blocks"]), "==", 0)
    ref_by_number = {r["number"]: r for r in ref_report["blocks"]}
    ctx.check("load-phase blocks whose flags or commit hash differ between "
              "the device peer and the software peer",
              sum(1 for b in opening
                  if (b["flags"], b["commit_hash"])
                  != (ref_by_number[b["number"]]["flags"],
                      ref_by_number[b["number"]]["commit_hash"])), "==", 0)

    # what the mix must make happen, in the blocks the window started:
    # the model's count > 0, and the peer's own flags say the same
    window = [by_number[b["number"]] for b in rep["blocks"]]
    got = happened(by_number, rep["blocks"])
    for what in MUST_HAPPEN:
        want = sum(s["counts"][what] for s in window)
        ctx.check(f"{what} in the window's blocks, by the model", want,
                  ">=", 1)
        ctx.check(f"{what} in the window's blocks, by the device peer's "
                  "flags", got[what], "==", want)

    # the device owes every unique signature of the blocks it started:
    # a creator's and each endorsement's, a transaction
    if rep["after"]["device"] is not None:
        owed = sum(s["counts"]["signatures"] for s in window)
        ctx.check("signatures verified on the device over the window's "
                  "blocks against the generator's count of them",
                  rep["after"]["stats"]["device_sigs"]
                  - rep["before"]["stats"]["device_sigs"], ">=", owed)

    # every block of the window on the classic tail, because the state
    # holds parameters
    txs = sum(b["txs"] for b in rep["blocks"])
    before, after = obs["prom_before"], obs["prom_after"]
    ctx.check("transactions of the window validated on the classic tail "
              "because the state holds validation parameters",
              prom_delta(before, after, "validator_tail_total",
                         tail="classic", reason="state_meta"), "==", txs)
    ctx.check("transactions of the window validated on the deep tail",
              prom_delta(before, after, "validator_tail_total",
                         tail="deep"), "==", 0)

    # every asset's record and parameter, and the absence of both for
    # every deleted id
    last = (rep["blocks"] or opening)[-1]["number"]
    ctx.check("device peer's height against the last block it stored",
              held["height"], "==", last + 1)
    ids = [gen.asset_key(i) for i in range(1, highest_id(summaries) + 1)]
    assets, params = gen.state_after(summaries, last)
    ctx.check("ids compared on the device peer", len(held["digests"]),
              "==", len(ids))
    ctx.check(f"assets whose record or validation parameter differs from "
              f"the model's after block {last} (device peer)",
              differing(held["digests"], gen.digests(assets, params, ids)),
              "==", 0)
    ctx.check("validation parameters the device peer's state counts "
              "(StateDB.meta_keys) against the model's",
              held["meta_keys"], "==", len(params))
    ref_last = ref_report["height"] - 1
    ref_assets, ref_params = gen.state_after(summaries, ref_last)
    ids = ids[:highest_id(summaries[:n_ref])]
    ctx.check(f"assets whose record or validation parameter differs from "
              f"the model's after block {ref_last} (software peer)",
              differing(ref_report["state"]["digests"],
                        gen.digests(ref_assets, ref_params, ids)), "==", 0)
    ctx.check("validation parameters the software peer's state counts "
              "against the model's", ref_report["state"]["meta_keys"], "==",
              len(ref_params))
    n = sum(len(s["codes"]) for s in window)
    tally = {k: sum(s["tally"][k] for s in window) for k in window[0]["tally"]}
    say(f"window's blocks by the model: "
        f"{100.0 * sum(s['codes'].count(gen.VALID) for s in window) / n:.2f}"
        f"% VALID; {sum(s['counts']['signatures'] for s in window) / len(window):.0f}"
        f" signatures a block; the policy check's tally {tally}; "
        f"{ {k: got[k] for k in MUST_HAPPEN} }; "
        f"{sum(s['counts']['upstream_differs'] for s in window)} transactions "
        "upstream's same-block rule could code differently; "
        f"{len(assets)} assets live and {len(params)} parameters after "
        f"block {last}")
