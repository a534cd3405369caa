"""Driver `queries_catchup`: a peer's committer working off the chain of
an asset registry whose blocks carry range queries.

`drivers/catchup.py` with another generator, blocks that the program's
own `BlockCutter` cuts, and a state to compare, as `ycsb_catchup.py` is:
the chain comes from `gen/queries.py` (a pure function of the seed and
of where the cutter ends each block) — the load phase that creates every
asset and its index entry, replayed in set-up by the device peer (its
first block is the pilot that makes the endorsers' tables resident and
runs the ladder lane once) and by the software peer, then the backlog of
the mix the window works off.  A block's transactions are simulated
against the state the block before it left, so the chain is formed
block by block: candidates simulated, built into envelopes by the worker
processes, cut by the cutter an orderer of this deployment runs, judged
by the model's serial block rule, and only then the next block's.  What
`catchup.judge` checks is checked by it (flags against the generator's
serial block rule, flags and commit hash against the software peer, the
provider, the rate); this driver adds the load phase, the cut itself,
what the mix must make happen in every run (phantoms, conflicts of a
by-colour hand-over with a transfer and with a delete), the ledger's
range counters and commit source against the model's counts, and every
asset's record and index entry on both peers against the model.
`setup_s` ends when the device peer holds the backlog; the software
peer's longer replay is waited for after that, before the window.

Cell parameters (`workloads/<cell>.json`): `run_tx` (the backlog, in
transactions), `reference_blocks` (of the backlog, after the load
phase), `warm_generic`, `warm_rows`, `generator_workers`,
`trace_blocks`.
"""

from __future__ import annotations

import collections
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
from gen import queries as gen
from gen.deployment import Deployment
from harness import BenchFailure, prom_delta, say

CHILD = os.path.join(harness.BENCH, "drivers", "queries_child.py")
MAX_TRACES = 4096                # a traced window's blocks, all kept
LARGEST_RANGE = 50               # above it upstream hashes a range's results
# what the mix must make happen in every run, by the model's own codes
MUST_HAPPEN = ("phantoms_by_create", "bycolor_mvcc_by_transfer",
               "bycolor_mvcc_by_delete", "ranges_held", "creates", "deletes")


class Child(catchup.Child):
    """queries_child.py as a subprocess speaking JSON lines."""

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
    # a program from before the contract or the shim's composite keys:
    # nothing to measure, said before anything is started
    gen.require_program_support(cfg["chaincode"]["contract"])
    assets, run_tx = int(cfg["assets"]), int(wl["run_tx"])
    n_ref_run = int(wl["reference_blocks"])
    workers = int(wl["generator_workers"])
    namespace = cfg["chaincode"]["name"]
    harness.build_native()
    base = tempfile.mkdtemp(prefix="bench_queries_")
    trace_dir = os.path.join(base, "trace")
    children = []
    pool = ProcessPoolExecutor(
        max_workers=workers,
        mp_context=multiprocessing.get_context("spawn"))
    threads = ThreadPoolExecutor(2)
    try:
        tracing = {"enabled": bool(ctx.trace), "sample_rate": 1.0,
                   "max_traces": MAX_TRACES}
        dep = Deployment(base, cfg, harness.REPO, {"tracing": tracing})
        # the range control alters how the device peer commits; the
        # software peer is left sound
        device = Child("device_peer", dep, dep.device_org, ctx.trace,
                       trace_dir, sorted(ctx.faults))
        children.append(device)
        say(f"device peer started (pid {device.proc.pid}); forming the "
            f"chain of {assets} creates + {run_tx} of the mix over "
            f"{cfg['colors']} colours from seed {ctx.seed}")
        summaries, paths = [], []
        # on disk: the load phase, the software peer's share
        opened, shared = threading.Event(), threading.Event()
        n_open = None            # how many blocks the load phase was cut into

        def n_ref() -> int:
            return n_open + n_ref_run

        def ids(upto_blocks: list) -> int:
            """asset0..asset<ids - 1> is every id these blocks touch."""
            return 1 + max(s["highest_id"] for s in upto_blocks)

        def reference_replay() -> dict:
            """The software peer replays its share as soon as it exists."""
            ref = Child("reference_peer", dep, wl["reference_org"], False,
                        trace_dir)
            children.append(ref)
            ref.expect("init")
            shared.wait()
            if n_open is None or len(paths) < n_ref():
                raise BenchFailure("the generator stopped before the "
                                   "software peer's share was written")
            ref.send("replay", blocks=paths[:n_ref()], namespace=namespace,
                     ids=ids(summaries[:n_ref()]))
            report = ref.expect("replayed")
            ref.stop()
            return report

        def build(txs: list) -> list:
            """A block's candidates as envelopes, in order: one chunk a
            worker."""
            size = -(-len(txs) // workers)
            chunks = [pool.submit(gen.worker_build, dep.file, dep.channel,
                                  dep.chaincode, txs[at:at + size])
                      for at in range(0, len(txs), size)]
            return [raw for chunk in chunks for raw in chunk.result()]

        def generate() -> None:
            """The chain formed block by block, chained and written in
            order."""
            nonlocal n_open
            t = time.monotonic()
            try:
                chain = gen.Chain(ctx.seed, assets, int(cfg["colors"]),
                                  run_tx, int(cfg["client_identities"]),
                                  int(cfg["tamper_every"]),
                                  tuple(cfg["peer_orgs"]))
                prev = gen_backlog.GENESIS_PREVIOUS_HASH
                for block in gen.form_chain(chain, build, cfg["batch"]):
                    raw, prev = gen_backlog.chain_block(
                        block.pop("data"), block["number"], prev)
                    path = os.path.join(base, f"block_{block['number']}.bin")
                    with open(path, "wb") as f:
                        f.write(raw)
                    if block["phase"] == "run" and n_open is None:
                        n_open = block["number"]
                        opened.set()
                        say(f"load phase: {n_open} blocks written "
                            f"({time.monotonic() - t:.1f} s)")
                    summaries.append(dict(gen.summary(block),
                                          bytes=len(raw)))
                    paths.append(path)
                    if n_open is not None and len(paths) == n_ref():
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
            device.send("warm", generic=wl["warm_generic"],
                        rows=wl["warm_rows"])
            warm = device.expect("warm")
            say(f"warm-up: {warm['timings']} ({warm['seconds']:.1f} s)")
        opened.wait()
        if n_open is None:
            generated.result()           # it failed: say why, now
            raise BenchFailure("the chain has no run phase")
        t = time.monotonic()
        device.send("open", blocks=paths[:n_open])
        opening = device.expect("opened")["blocks"]
        took = [b["end"] - b["start"] for b in opening]
        say(f"load phase replayed by the device peer: {len(opening)} blocks "
            f"in {time.monotonic() - t:.1f} s (pilot {took[0]:.2f} s, then "
            f"{1e3 * sum(took[1:]) / max(1, len(took) - 1):.1f} ms a block)")
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
        device.send("state", namespace=namespace, ids=ids(summaries))
        held = device.expect("state")
        device.stop()
        # catchup.judge: the window's blocks and the pilot (here the first
        # block of the load phase) against the plan and the software peer,
        # the provider's checks, the rate, the observations
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
        judge_queries(ctx, summaries, opening, rep, ref_report, held,
                      ids(summaries), ids(summaries[:n_ref()]), out["obs"],
                      cfg["batch"])
        say_window(summaries, rep, n_open)
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


def happened(by_number: dict, blocks) -> dict:
    """How the by-colour hand-overs of `blocks` (as a peer reported them)
    came out, by the peer's OWN flags — to be set against the model's
    counts of the same blocks."""
    out = dict.fromkeys(("phantoms", "bycolor_mvcc", "bycolor_valid"), 0)
    for b in blocks:
        flags = bytes.fromhex(b["flags"])
        for n in by_number[b["number"]]["ranged"]:
            out["phantoms"] += flags[n] == gen.PHANTOM_CONFLICT
            out["bycolor_mvcc"] += flags[n] == gen.MVCC_CONFLICT
            out["bycolor_valid"] += flags[n] == gen.VALID
    return out


def differing(reported: list, want: list) -> int:
    return sum(a != b for a, b in zip(reported, want)) + abs(
        len(reported) - len(want))


def judge_queries(ctx, summaries, opening, rep, ref_report, held, n_ids,
                  n_ref_ids, obs, batch) -> None:
    """What a deployment whose blocks carry range queries adds to
    `correct`."""
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

    # the cut: the program's BlockCutter under the configuration's batch
    run = [s for s in summaries if s["phase"] == "run"]
    ctx.check("load-phase transactions in the load phase's blocks",
              sum(s["txs"] for s in summaries if s["phase"] == "load"), "==",
              int(ctx.config["assets"]))
    ctx.check("backlog blocks but the last cut neither by bytes nor by count",
              sum(1 for s in run[:-1] if s["reason"] not in ("bytes", "count")),
              "==", 0)
    ctx.check("largest block of the chain, transactions, against "
              "max_message_count", max(s["txs"] for s in summaries), "<=",
              int(batch["max_message_count"]))
    ctx.check("largest block, bytes, against absolute_max_bytes",
              max(s["bytes"] for s in summaries), "<=",
              int(batch["absolute_max_bytes"]))
    ctx.check("largest range any transaction of the chain recorded, results "
              "(raw reads only: upstream hashes above its degree of 50)",
              max(s["counts"]["largest_range"] for s in summaries), "<=",
              LARGEST_RANGE)

    # what the mix must make happen, in the blocks the window started:
    # the model's count > 0, and the peer's own flags say the same
    window = [by_number[b["number"]] for b in rep["blocks"]]

    def model_count(what: str) -> int:
        return sum(s["counts"][what] for s in window)
    for what in MUST_HAPPEN:
        ctx.check(f"{what} in the window's blocks, by the model",
                  model_count(what), ">=", 1)
    got = happened(by_number, rep["blocks"])
    for what in got:
        ctx.check(f"{what} in the window's blocks, by the device peer's "
                  "flags against the model's", got[what], "==",
                  model_count(what))

    # the ledger's always-on account of the window against the model's
    before, after = obs["prom_before"], obs["prom_after"]

    def moved(name, **labels) -> float:
        return prom_delta(before, after, name, **labels)
    ctx.check("ledger_mvcc_range_queries_total{result=phantom} over the "
              "window against the model's phantoms",
              moved("ledger_mvcc_range_queries_total", result="phantom"),
              "==", model_count("phantoms"))
    ctx.check("ledger_mvcc_range_queries_total, both results, against the "
              "by-colour transactions valid at the gate whose reads held",
              moved("ledger_mvcc_range_queries_total"), "==",
              model_count("ranges_replayed"))
    ctx.check("ledger_mvcc_range_reads_total over the window against the "
              "results the model's replays re-read",
              moved("ledger_mvcc_range_reads_total"), "==",
              model_count("range_results_replayed"))
    ctx.check("ledger_commit_source_total{source=envelopes} over the window "
              "against the transactions of the blocks that hold a by-colour "
              "transaction still valid at the gate",
              moved("ledger_commit_source_total", source="envelopes"), "==",
              model_count("envelope_source_txs"))

    # every asset's record and index entry, and the absence of both for
    # every deleted id; the index counted by one scan of its prefix
    last = (rep["blocks"] or opening)[-1]["number"]
    ctx.check("device peer's height against the last block it stored",
              held["height"], "==", last + 1)
    ctx.check("ids compared on the device peer", len(held["digests"]),
              "==", n_ids)
    model = gen.digests_after(summaries, last, n_ids)
    ctx.check(f"assets whose record or index entry differs from the model's "
              f"after block {last} (device peer, one SHA-256 an id)",
              differing(held["digests"], model), "==", 0)
    ctx.check("keys under the index's prefix on the device peer (one scan) "
              "against the model's live assets", held["index_entries"], "==",
              sum(d is not None for d in model))
    ref_last = ref_report["height"] - 1
    ref_model = gen.digests_after(summaries, ref_last, n_ref_ids)
    ctx.check(f"assets whose record or index entry differs from the model's "
              f"after block {ref_last} (software peer, one SHA-256 an id)",
              differing(ref_report["state"]["digests"], ref_model), "==", 0)
    ctx.check("keys under the index's prefix on the software peer against "
              "the model's live assets",
              ref_report["state"]["index_entries"], "==",
              sum(d is not None for d in ref_model))
    n = sum(len(s["codes"]) for s in window)
    say(f"window's blocks by the model: "
        f"{100.0 * sum(s['codes'].count(gen.VALID) for s in window) / n:.2f}"
        f"% VALID; a block: "
        + ", ".join(f"{k} {model_count(k) / len(window):.2f}" for k in (
            "bycolor", "bycolor_at_gate", "bycolor_valid", "bycolor_mvcc",
            "bycolor_mvcc_by_transfer", "bycolor_mvcc_by_delete", "phantoms",
            "range_results_replayed", "creates", "deletes"))
        + f"; over the window {model_count('phantoms')} phantoms, "
        f"{model_count('bycolor_mvcc')} by-colour read conflicts; "
        f"{sum(d is not None for d in model)} assets live after block {last}")


def say_window(summaries, rep, n_open) -> None:
    """The cut as it came out, and whether the window or the backlog
    ended the run."""
    run = [s for s in summaries if s["phase"] == "run"]
    reasons = collections.Counter(s["reason"] for s in run)
    txs = sorted(s["txs"] for s in run[:-1]) or [0]
    say(f"the cut: {n_open} load-phase + {len(run)} backlog blocks, backlog "
        f"reasons {dict(reasons)}; backlog blocks but the last hold "
        f"{txs[0]}-{txs[-1]} tx (median {txs[len(txs) // 2]}), "
        f"{sum(s['bytes'] for s in run[:-1]) // max(1, len(run) - 1)} bytes "
        f"a block; load-phase blocks "
        f"{dict(collections.Counter(s['reason'] for s in summaries[:n_open]))}")
    started = len(rep["blocks"])
    say(f"the window started {started} of the backlog's {len(run)} blocks "
        f"({sum(b['txs'] for b in rep['blocks'])} of "
        f"{sum(s['txs'] for s in run)} tx): margin "
        f"{len(run) / max(1, started):.2f} x"
        + ("; BACKLOG EXHAUSTED" if rep["exhausted"] else ""))
    sources = collections.Counter(
        (b["mvcc"].get("source"), b["mvcc"].get("walk"))
        for b in rep["blocks"])
    ranged = [b for b in rep["blocks"] if "range_ms" in b["mvcc"]]
    took = sorted(b["end"] - b["start"] for b in rep["blocks"])
    say(f"the window's blocks by (source, walk): {dict(sources)}; range "
        f"replays a block that had one: "
        f"{sum(b['mvcc']['range_queries'] for b in ranged) / max(1, len(ranged)):.1f}"
        f" queries, "
        f"{sum(b['mvcc']['range_reads'] for b in ranged) / max(1, len(ranged)):.0f}"
        f" results, "
        f"{sum(b['mvcc']['range_ms'] for b in ranged) / max(1, len(ranged)):.2f}"
        f" ms; ledger phases a block, ms: "
        + ", ".join(
            f"{k} {1e3 * sum(b['ledger_s'][k] for b in rep['blocks']) / max(1, started):.1f}"
            for k in ("mvcc", "block", "state", "history"))
        + f"; blocks: median {1e3 * took[len(took) // 2]:.1f} ms, p95 "
        f"{1e3 * took[int(0.95 * (len(took) - 1))]:.1f} ms, longest "
        f"{1e3 * took[-1]:.1f} ms")
