"""Driver `privdata_catchup`: a member peer's committer working off the
chain of a registry whose assets trade at a private price.

`drivers/queries_catchup.py` with another generator, private write-sets
beside the blocks, two software peers and a state of hashes to compare:
the chain comes from `gen/privdata.py` (a pure function of the seed and
of where the cutter ends each block) — the load phase that creates every
asset and its owner's appraisal, replayed in set-up by the device peer
(its first block is the pilot that makes the two endorsers' tables
resident and runs the ladder lane once) and by the software peers, then
the backlog of the mix the window works off.  A block's transactions are
simulated against the state the block before it left, so the chain is
formed block by block: candidates simulated, built into envelopes by the
worker processes, cut by the cutter an orderer of this deployment runs,
judged by the model's serial block rule, and only then the next block's.
Beside each block goes, for every org that is a member of a collection
the block writes, the file of private write-sets its peer was pushed at
endorsement; a peer stages them in its transient store before the blocks
(`dissemination` in the configuration's `reduced`).  What `catchup.judge`
checks is checked by it, against the member software peer (flags against
the generator's serial block rule, flags and commit hash against the
software peer, the provider, the rate); this driver adds the load phase,
the cut itself, what the mix must make happen in every run (a collection's
policy failing a wrong-org agreement, a transfer ordered after its
appraisal's purge, a plain conflict, a tampered endorsement), the second
software peer — a member of no collection — and, on all three, the
hashed state, the private store, the transient store and the counters
against the model.  `setup_s` ends when the device peer holds the
backlog; the software peers' longer replay is waited for after that,
before the window.

Cell parameters (`workloads/<cell>.json`): `run_tx` (the backlog, in
transactions), `reference_orgs`, `reference_blocks` (of the backlog,
after the load phase), `warm_generic`, `warm_rows`, `generator_workers`,
`trace_blocks`.
"""

from __future__ import annotations

import collections
import json
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
from gen import privdata as gen
from gen.deployment import Deployment
from harness import BenchFailure, prom_delta, say

CHILD = os.path.join(harness.BENCH, "drivers", "privdata_child.py")
MAX_TRACES = 4096                # a traced window's blocks, all kept


class Child(catchup.Child):
    """privdata_child.py as a subprocess speaking JSON lines."""

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
    # a program from before the contract or the shim's private verbs:
    # nothing to measure, said before anything is started
    gen.require_program_support(cfg["chaincode"]["contract"])
    assets, run_tx = int(cfg["assets"]), int(wl["run_tx"])
    n_ref_run = int(wl["reference_blocks"])
    workers = int(wl["generator_workers"])
    namespace = cfg["chaincode"]["name"]
    orgs, traders = tuple(cfg["peer_orgs"]), tuple(cfg["trading_orgs"])
    ref_orgs = list(wl["reference_orgs"])
    colls = gen.collections(traders)
    members = sorted({o for c in colls.values() for o in c["members"]})
    harness.build_native()
    base = tempfile.mkdtemp(prefix="bench_privdata_")
    trace_dir = os.path.join(base, "trace")
    children = []
    pool = ProcessPoolExecutor(
        max_workers=workers,
        mp_context=multiprocessing.get_context("spawn"))
    threads = ThreadPoolExecutor(1 + len(ref_orgs))
    try:
        tracing = {"enabled": bool(ctx.trace), "sample_rate": 1.0,
                   "max_traces": MAX_TRACES}
        dep = Deployment(base, cfg, harness.REPO, {"tracing": tracing})
        # the expiry control alters how the device peer commits; the
        # software peers are left sound
        device = Child("device_peer", dep, dep.device_org, ctx.trace,
                       trace_dir, sorted(ctx.faults))
        children.append(device)
        say(f"device peer started (pid {device.proc.pid}); forming the "
            f"chain of {assets} creates + {run_tx} of the mix from seed "
            f"{ctx.seed}")
        summaries, paths = [], []
        private = {org: [] for org in orgs}      # org -> a path | None a block
        leaked = [0]
        opened, shared = threading.Event(), threading.Event()
        n_open = None            # how many blocks the load phase was cut into

        def n_ref() -> int:
            return n_open + n_ref_run

        def reference_replay(org: str) -> dict:
            """A software peer replays its share as soon as it exists."""
            ref = Child("reference_peer_" + org, dep, org, False, trace_dir)
            children.append(ref)
            ref.expect("init")
            shared.wait()
            if n_open is None or len(paths) < n_ref():
                raise BenchFailure("the generator stopped before the "
                                   "software peers' share was written")
            ref.send("replay", blocks=paths[:n_ref()],
                     private=private[org][:n_ref()], namespace=namespace,
                     collections=sorted(colls))
            report = ref.expect("replayed")
            ref.stop()
            return report

        def build(txs: list) -> tuple:
            """A block's candidates as envelopes and their txids, in
            order: one chunk a worker."""
            size = -(-len(txs) // workers)
            chunks = [pool.submit(gen.worker_build, dep.file, dep.channel,
                                  dep.chaincode, txs[at:at + size])
                      for at in range(0, len(txs), size)]
            raws, txids = [], []
            for chunk in chunks:
                r, t = chunk.result()
                raws.extend(r)
                txids.extend(t)
            return raws, txids

        def write_private(block: dict) -> None:
            """What each member org's peer was pushed for this block's
            transactions, valid or not: pushed at endorsement."""
            for org in orgs:
                pushed = []
                for tx, txid in zip(block["txs"], block["txids"]):
                    sets = {}
                    for coll, key, value in tx["private"]:
                        if org in colls[coll]["members"]:
                            sets.setdefault(coll, {})[key] = value
                    if sets:
                        pushed.append([txid, sets])
                path = None
                # a software peer replays the first n_ref() blocks only
                if pushed and (org == dep.device_org or n_open is None
                               or len(paths) < n_ref()):
                    path = os.path.join(
                        base, f"private_{org}_{block['number']}.json")
                    with open(path, "w") as f:
                        json.dump(pushed, f)
                private[org].append(path)

        def generate() -> None:
            """The chain formed block by block, chained and written in
            order."""
            nonlocal n_open
            t = time.monotonic()
            try:
                chain = gen.Chain(ctx.seed, assets, run_tx,
                                  int(cfg["client_identities"]),
                                  int(cfg["tamper_every"]), orgs, traders)
                prev = gen_backlog.GENESIS_PREVIOUS_HASH
                for block in gen.form_chain(chain, build, cfg["batch"]):
                    raw, prev = gen_backlog.chain_block(
                        block.pop("data"), block["number"], prev)
                    leaked[0] += b"appraisedValue" in raw
                    path = os.path.join(base, f"block_{block['number']}.bin")
                    with open(path, "wb") as f:
                        f.write(raw)
                    if block["phase"] == "run" and n_open is None:
                        n_open = block["number"]
                        opened.set()
                        say(f"load phase: {n_open} blocks written "
                            f"({time.monotonic() - t:.1f} s)")
                    write_private(block)
                    summaries.append(dict(gen.summary(block, orgs, traders),
                                          bytes=len(raw),
                                          pushed={o: sum(
                                              1 for tx in block["txs"]
                                              if any(o in colls[c]["members"]
                                                     for c, _, _
                                                     in tx["private"]))
                                              for o in orgs}))
                    paths.append(path)
                    if n_open is not None and len(paths) == n_ref():
                        shared.set()
            finally:
                opened.set()             # never leave a thread waiting
                shared.set()
            say(f"{len(paths)} blocks written ({time.monotonic() - t:.1f} s); "
                f"the mix as realised {dict(chain.kinds)}")

        generated = threads.submit(generate)
        references = {org: threads.submit(reference_replay, org)
                      for org in ref_orgs}
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
        device.send("open", blocks=paths[:n_open], namespace=namespace,
                    private=private[dep.device_org][:n_open])
        opened_rep = device.expect("opened")
        opening, staged = opened_rep["blocks"], opened_rep["staged"]
        took = [b["end"] - b["start"] for b in opening]
        say(f"load phase replayed by the device peer: {len(opening)} blocks "
            f"in {time.monotonic() - t:.1f} s (pilot {took[0]:.2f} s, then "
            f"{1e3 * sum(took[1:]) / max(1, len(took) - 1):.1f} ms a block)")
        generated.result()
        device.send("load", blocks=paths[n_open:],
                    private=private[dep.device_org][n_open:])
        loaded = device.expect("loaded")
        staged += loaded["staged"]
        say(f"backlog of {loaded['blocks']} blocks loaded "
            f"({loaded['bytes']} bytes); {staged} transactions' private "
            "write-sets staged in the device peer's transient store")
        # the device peer is ready: set-up ends here.  The software
        # peers' replay is the comparison's, so the seconds still spent
        # waiting for it (the window starts only once the cores are the
        # device peer's alone) are no part of `setup_s`
        setup_s = time.monotonic() - harness.T0
        ref_reports = {org: f.result() for org, f in references.items()}
        say(f"references replayed "
            f"{[len(r['blocks']) for r in ref_reports.values()]} blocks "
            f"({time.monotonic() - harness.T0 - setup_s:.1f} s after "
            "set-up's end)")

        # ---- the window ----------------------------------------------------
        device.send("go", seconds=ctx.seconds,
                    trace_blocks=wl["trace_blocks"])
        rep = device.expect("done")
        device.send("state", collections=sorted(colls))
        held = device.expect("state")
        device.stop()
        # catchup.judge: the window's blocks and the pilot (here the first
        # block of the load phase) against the plan and the member
        # software peer, the provider's checks, the rate, the observations
        plan = []
        for s in summaries:
            kinds = {(c, t): {"code": c, "tampered": t}
                     for c in set(s["codes"]) for t in (False, True)}
            tampered = set(s["tampered"])
            plan.append({"number": s["number"],
                         "txs": [kinds[c, n in tampered]
                                 for n, c in enumerate(s["codes"])]})
        member_ref = next(o for o in ref_orgs if o in members)
        out = catchup.judge(ctx, plan, opening[0], rep,
                            ref_reports[member_ref], setup_s, trace_dir)
        judge_privdata(ctx, summaries, opening, rep, ref_reports, held,
                       staged, leaked[0], out["obs"], dep.device_org,
                       members, colls)
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


def compare_state(ctx, who: str, org: str, held: dict, summaries: list,
                  last: int, colls: dict, members: list) -> None:
    """A peer's hashed state and private store against the model's after
    block `last`."""
    hashed, view = gen.state_after(summaries, last, org)
    ctx.check(f"{who}'s height against the last block it stored",
              held["height"], "==", last + 1)
    differ = sum(held["hashed"][c] != list(gen.hashed_digest(hashed, c))
                 for c in colls)
    ctx.check(f"collections whose hashed state differs from the model's "
              f"after block {last} ({who}: keys held and one SHA-256 over "
              f"key, value hash and version; the model holds "
              f"{[gen.hashed_digest(hashed, c)[0] for c in sorted(colls)]}"
              f", the peer {[held['hashed'][c][0] for c in sorted(colls)]})",
              differ, "==", 0)
    want_held, want_digest = gen.view_digest(view)
    ctx.check(f"{who}'s private store against the model's {org} view after "
              f"block {last} (1 where keys by collection or the SHA-256 "
              f"over collection, key and value differ; the model holds "
              f"{want_held}, the peer {held['private'][0]})",
              int(held["private"] != [want_held, want_digest]), "==", 0)
    foreign = sum(n for c, n in held["private"][0].items()
                  if org not in colls[c]["members"])
    ctx.check(f"keys in {who}'s private store of collections {org} is no "
              "member of", foreign, "==", 0)
    if org not in members:
        ctx.check(f"keys in {who}'s private store ({org} is a member of no "
                  "collection)", sum(held["private"][0].values()), "==", 0)
    ctx.check(f"private write-sets {who} recorded missing",
              held["missing"], "==", 0)
    ctx.check(f"pulls of a private write-set {who} made from other peers",
              held["counts"]["fetches"], "==", 0)
    ctx.check(f"blocks {who} stored whose bytes hold \"appraisedValue\"",
              held["leaked_blocks"], "==", 0)


def judge_privdata(ctx, summaries, opening, rep, ref_reports, held, staged,
                   leaked, obs, device_org, members, colls) -> None:
    """What a deployment with private data collections adds to
    `correct`."""
    by_number = {s["number"]: s for s in summaries}
    batch = ctx.config["batch"]

    def wrong_flags(blocks) -> int:
        return sum(1 for b in blocks
                   if bytes.fromhex(b["flags"]) != by_number[b["number"]]["codes"])
    ctx.check("load-phase blocks whose flags differ from the generator's "
              "(device peer)", wrong_flags(opening), "==", 0)
    ctx.check("transactions of the load phase not VALID (device peer)",
              sum(1 for b in opening for c in bytes.fromhex(b["flags"])
                  if c != gen.VALID), "==", 0)
    ctx.check("blocks stored in or after the window whose flags differ "
              "from the generator's (device peer)",
              wrong_flags(rep["blocks"]), "==", 0)
    dev_by_number = {b["number"]: b for b in opening + rep["blocks"]}
    for org, report in ref_reports.items():
        both = [r for r in report["blocks"] if r["number"] in dev_by_number]
        ctx.check(f"blocks {org}'s software peer and the device peer both "
                  "hold", len(both), ">=", len(opening))
        ctx.check(f"blocks whose flags or commit hash differ between the "
                  f"device peer and {org}'s software peer ("
                  + ("a member of " + ", ".join(
                      c for c in sorted(colls) if org in colls[c]["members"])
                     if org in members else "a member of no collection: "
                     "hashes only") + ")",
                  sum(1 for r in both
                      if (r["flags"], r["commit_hash"])
                      != (dev_by_number[r["number"]]["flags"],
                          dev_by_number[r["number"]]["commit_hash"])),
                  "==", 0)
        ctx.check(f"blocks whose flags differ from the generator's "
                  f"({org}'s software peer)", wrong_flags(report["blocks"]),
                  "==", 0)
        ctx.check(f"{org}'s software peer's process imported jax",
                  int(report["jax_imported"]), "==", 0)

    # the cut: the program's BlockCutter under the configuration's batch
    run = [s for s in summaries if s["phase"] == "run"]
    ctx.check("load-phase transactions in the load phase's blocks",
              sum(s["txs"] for s in summaries if s["phase"] == "load"), "==",
              int(ctx.config["assets"]))
    # the stream's end is the batch timer's cut, and so is the block of
    # the late transfers held past it
    ctx.check("backlog blocks before the last two cut neither by bytes nor "
              "by count",
              sum(1 for s in run[:-2] if s["reason"] not in ("bytes", "count")),
              "==", 0)
    ctx.check("largest block of the chain, transactions, against "
              "max_message_count", max(s["txs"] for s in summaries), "<=",
              int(batch["max_message_count"]))
    ctx.check("largest block, bytes, against absolute_max_bytes",
              max(s["bytes"] for s in summaries), "<=",
              int(batch["absolute_max_bytes"]))
    ctx.check("blocks of the chain whose bytes hold \"appraisedValue\"",
              leaked, "==", 0)

    # what the mix must make happen, in the blocks the window started:
    # the model's count > 0, and the peer's own flags say the same
    window = [by_number[b["number"]] for b in rep["blocks"]]

    def model_count(what: str, org: str = device_org, blocks=window) -> int:
        return sum(s["counts"][org][what] for s in blocks)
    for cause in gen.CAUSES:
        ctx.check(f"transactions lost to `{cause}` in the window's blocks, "
                  "by the model", model_count(cause), ">=", 1)
    got = collections.Counter(
        c for b in rep["blocks"] for c in bytes.fromhex(b["flags"]))
    ctx.check("ENDORSEMENT_POLICY_FAILURE in the window's blocks by the "
              "device peer's flags against the model's tampered + "
              "collection_policy", got[gen.POLICY_FAILURE], "==",
              model_count("tampered") + model_count("collection_policy"))
    ctx.check("MVCC_READ_CONFLICT in the window's blocks by the device "
              "peer's flags against the model's conflict + expired",
              got[gen.MVCC_CONFLICT], "==",
              model_count("conflict") + model_count("expired"))

    # the always-on account of the window against the model's
    before, after = obs["prom_before"], obs["prom_after"]

    def moved(name, **labels) -> float:
        return prom_delta(before, after, name, **labels)
    ctx.check("ledger_pvt_expired_keys_total over the window against the "
              "hashed keys the model expired",
              moved("ledger_pvt_expired_keys_total"), "==",
              model_count("expired_keys"))
    stored = opening + rep["blocks"]
    ctx.check("hashed keys expired over every block the device peer stored, "
              "by the counter beside each block, against the model's",
              sum(b["counts"]["expired_keys"] for b in stored), "==",
              sum(s["counts"][device_org]["expired_keys"]
                  for s in summaries[:len(stored)]))
    ctx.check("privdata_txs_total{result=resolved} over the window against "
              f"the private write-sets of VALID transactions {device_org} is "
              "entitled to", moved("privdata_txs_total", result="resolved"),
              "==", model_count("sets_resolved"))
    ctx.check("privdata_txs_total{result=not_member} over the window against "
              "the model's", moved("privdata_txs_total", result="not_member"),
              "==", model_count("sets_not_member"))
    ctx.check("privdata_txs_total{result=missing} over the window",
              moved("privdata_txs_total", result="missing"), "==", 0)
    ctx.check("privdata_decoded_txs_total over the window against the VALID "
              "transactions that write under a collection",
              moved("privdata_decoded_txs_total"), "==",
              model_count("private_writers"))
    ctx.check("privdata_fetch_total over the window",
              moved("privdata_fetch_total"), "==", 0)

    # every peer's hashed state and private store, and the stores around
    last = stored[-1]["number"]
    compare_state(ctx, "the device peer", device_org, held, summaries, last,
                  colls, members)
    pushed = sum(s["pushed"][device_org] for s in summaries)
    ctx.check("transactions' private write-sets staged in the device "
              "peer's transient store against those the chain pushes it",
              staged, "==", pushed)
    committed = sum(s["counts"][device_org]["private_writers"]
                    for s in summaries[:len(stored)])
    ctx.check("entries left in the device peer's transient store against "
              "staged - the VALID transactions of the blocks it stored "
              "(none of a committed transaction)", held["transient"], "==",
              staged - committed)
    for org, report in ref_reports.items():
        ref_last = report["height"] - 1
        compare_state(ctx, f"{org}'s software peer", org, report["state"],
                      summaries, ref_last, colls, members)
        blocks = summaries[:ref_last + 1]
        ctx.check(f"entries left in the transient store of {org}'s software "
                  "peer against staged - committed",
                  report["state"]["transient"], "==",
                  report["staged"] - (sum(
                      s["counts"][org]["private_writers"] for s in blocks)
                      if org in members else 0))
        ctx.check(f"hashed keys expired on {org}'s software peer against "
                  "the model's",
                  report["state"]["counts"]["expired_keys"], "==",
                  sum(s["counts"][org]["expired_keys"] for s in blocks))
    n = sum(len(s["codes"]) for s in window)
    say(f"window's blocks by the model: "
        f"{100.0 * sum(s['codes'].count(gen.VALID) for s in window) / n:.2f}"
        f"% VALID; a block: "
        + ", ".join(f"{k} {model_count(k) / len(window):.2f}" for k in (
            "creates", "agrees", "transfers", "deletes", "tampered",
            "collection_policy", "conflict", "expired", "expired_keys",
            "sets_resolved", "sets_not_member"))
        + "; the mix as drawn a block: "
        + ", ".join(f"{k} {sum(s['kinds'][k] for s in window) / len(window):.1f}"
                    for k in window[0]["kinds"])
        + f", redrawn as a create "
        f"{sum(s['redrawn'] for s in window) / len(window):.2f}")


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
    took = sorted(b["end"] - b["start"] for b in rep["blocks"])
    say(f"the window's blocks by (source, walk): {dict(sources)}; ledger "
        "phases a block, ms: "
        + ", ".join(
            f"{k} {1e3 * sum(b['ledger_s'][k] for b in rep['blocks']) / max(1, started):.1f}"
            for k in ("mvcc", "expiry", "block", "state", "history"))
        + f"; blocks: median {1e3 * took[len(took) // 2]:.1f} ms, p95 "
        f"{1e3 * took[int(0.95 * (len(took) - 1))]:.1f} ms, longest "
        f"{1e3 * took[-1]:.1f} ms")
