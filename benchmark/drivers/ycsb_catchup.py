"""Driver `ycsb_catchup`: a peer's committer working off the chain of a
key-value channel, YCSB's 1 KB records in blocks cut by bytes.

`drivers/catchup.py` with another generator, blocks that the program's
own `BlockCutter` cuts, and a state to compare: the chain comes from
`gen/ycsb.py` (a pure function of the seed) — the load phase that
inserts every record, replayed in set-up by the device peer (its first
block is the pilot that makes the endorsers' tables resident and runs
the ladder lane once) and by the software peer, then the backlog of
updates the window works off.  Envelopes are built in chunks by worker
processes and cut in order, under the configuration's `batch`, by the
cutter an orderer of this deployment runs.  What `catchup.judge` checks
is checked by it (flags against the generator's serial block rule,
flags and commit hash against the software peer, the provider, the
rate); this driver adds the load phase, the cut itself, the absence of
MVCC conflicts, and every record on both peers against the model.
`setup_s` ends when the device peer holds the backlog; the software
peer's longer replay is waited for after that, before the window.

Cell parameters (`workloads/<cell>.json`): `updates` (the backlog, in
transactions), `reference_blocks` (of the backlog, after the load
phase), `warm_generic`, `warm_rows`, `generator_workers`, `chunk_tx`,
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
from drivers.smallbank_catchup import say_block_account
from gen import backlog as gen_backlog
from gen import ycsb as gen
from gen.deployment import Deployment
from harness import BenchFailure, say

CHILD = os.path.join(harness.BENCH, "drivers", "ycsb_child.py")
IN_FLIGHT = 48                   # chunks built ahead of the cutter
MAX_TRACES = 4096                # a traced window's blocks, all kept


class Child(catchup.Child):
    """ycsb_child.py as a subprocess speaking JSON lines."""

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
    recordcount, updates = int(cfg["recordcount"]), int(wl["updates"])
    n_ref_run = int(wl["reference_blocks"])
    chunk_tx = int(wl["chunk_tx"])
    harness.build_native()
    base = tempfile.mkdtemp(prefix="bench_ycsb_")
    trace_dir = os.path.join(base, "trace")
    keys_path = os.path.join(base, "keys.json")
    ask = {"namespace": cfg["chaincode"]["name"], "keys": keys_path}
    children = []
    pool = ProcessPoolExecutor(
        max_workers=int(wl["generator_workers"]),
        mp_context=multiprocessing.get_context("spawn"))
    threads = ThreadPoolExecutor(2)
    try:
        tracing = {"enabled": bool(ctx.trace), "sample_rate": 1.0,
                   "max_traces": MAX_TRACES}
        dep = Deployment(base, cfg, harness.REPO, {"tracing": tracing})
        with open(keys_path, "w") as f:
            json.dump([gen.key_name(n) for n in range(recordcount)], f)
        # the record control alters what the device peer applies; the
        # software peer is left sound
        device = Child("device_peer", dep, dep.device_org, ctx.trace,
                       trace_dir, sorted(ctx.faults))
        children.append(device)
        say(f"device peer started (pid {device.proc.pid}); planning "
            f"{recordcount} inserts + {updates} updates from seed {ctx.seed}")
        blocks, paths = [], []   # cut_chain's blocks without their data
        written = []             # by serial: the record written, -1 tampered
        # on disk: the load phase, the software peer's share
        opened, shared = threading.Event(), threading.Event()
        n_open = None            # how many blocks the load phase was cut into

        def n_ref() -> int:
            return n_open + n_ref_run

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
            ref.send("replay", blocks=paths[:n_ref()], **ask)
            report = ref.expect("replayed")
            ref.stop()
            return report

        def envelopes():
            """(phase, envelope) of the whole chain in order, built chunk
            by chunk in the workers, at most IN_FLIGHT chunks ahead of
            the cutter."""
            pending = collections.deque()

            def submit(chunk):
                pending.append(([tx["phase"] for tx in chunk], pool.submit(
                    gen.worker_build, dep.file, dep.channel, dep.chaincode,
                    ctx.seed, chunk)))

            def collect():
                phases, built = pending.popleft()
                return zip(phases, built.result())
            chunk = []
            for tx in gen.iter_txs(ctx.seed, recordcount, updates,
                                   int(cfg["client_identities"]),
                                   int(cfg["tamper_every"])):
                written.append(-1 if tx["tampered"] else tx["record"])
                chunk.append(tx)
                if len(chunk) == chunk_tx:
                    submit(chunk)
                    chunk = []
                    if len(pending) >= IN_FLIGHT:
                        yield from collect()
            if chunk:
                submit(chunk)
            while pending:
                yield from collect()

        def generate() -> None:
            """Transactions planned, built by the workers, cut by the
            program's cutter, chained and written in order."""
            nonlocal n_open
            t = time.monotonic()
            try:
                prev = gen_backlog.GENESIS_PREVIOUS_HASH
                for block in gen.cut_chain(envelopes(), cfg["batch"]):
                    raw, prev = gen_backlog.chain_block(
                        block.pop("data"), block["number"], prev)
                    block["bytes"] = len(raw)
                    path = os.path.join(base, f"block_{block['number']}.bin")
                    with open(path, "wb") as f:
                        f.write(raw)
                    if block["phase"] == "run" and n_open is None:
                        n_open = block["number"]
                        opened.set()
                        say(f"load phase: {n_open} blocks written "
                            f"({time.monotonic() - t:.1f} s)")
                    blocks.append(block)
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
        device.send("records", **ask)
        held = device.expect("records")
        device.stop()
        # catchup.judge: the window's blocks and the pilot (here the first
        # block of the load phase) against the plan and the software peer,
        # the provider's checks, the rate, the observations
        kinds = {t: {"code": gen.code_of({"tampered": t}), "tampered": t}
                 for t in (False, True)}
        plan = [{"number": b["number"],
                 "txs": [kinds[written[s] < 0]
                         for s in range(b["first"], b["first"] + b["txs"])]}
                for b in blocks]
        out = catchup.judge(ctx, plan, opening[0], rep, ref_report, setup_s,
                            trace_dir)
        judge_chain(ctx, plan, blocks, written, opening, rep, ref_report,
                    held, recordcount, n_open, cfg["batch"])
        say_window(blocks, rep, n_open)
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


def judge_chain(ctx, plan, blocks, written, opening, rep, ref_report, held,
                recordcount, n_open, batch) -> None:
    """What a key-value deployment cut by bytes adds to `correct`."""
    want = {p["number"]: bytes(tx["code"] for tx in p["txs"]) for p in plan}

    def wrong_flags(stored) -> int:
        return sum(1 for b in stored
                   if bytes.fromhex(b["flags"]) != want[b["number"]])
    # the load phase beyond the pilot, and a block the window started and
    # finished after its end: their writes are in the state compared
    ctx.check("load-phase blocks whose flags differ from the generator's "
              "(device peer)", wrong_flags(opening), "==", 0)
    ctx.check("transactions of the load phase not VALID (device peer)",
              sum(1 for b in opening for c in bytes.fromhex(b["flags"])
                  if c != gen.VALID), "==", 0)
    ctx.check("blocks stored in or after the window whose flags differ "
              "from the generator's (device peer)",
              wrong_flags(rep["blocks"]), "==", 0)
    ctx.check("MVCC_READ_CONFLICT flags on the device peer (a chain of "
              "blind writes has nothing to conflict on)",
              sum(bytes.fromhex(b["flags"]).count(gen.MVCC_CONFLICT)
                  for b in opening + rep["blocks"]), "==", 0)
    ref_by_number = {r["number"]: r for r in ref_report["blocks"]}
    ctx.check("load-phase blocks whose flags or commit hash differ between "
              "the device peer and the software peer",
              sum(1 for b in opening
                  if (b["flags"], b["commit_hash"])
                  != (ref_by_number[b["number"]]["flags"],
                      ref_by_number[b["number"]]["commit_hash"])), "==", 0)

    # the cut: the program's BlockCutter under the configuration's batch
    run = [b for b in blocks if b["phase"] == "run"]
    ctx.check("load-phase transactions in the load phase's blocks",
              sum(b["txs"] for b in blocks[:n_open]), "==", recordcount)
    ctx.check("backlog blocks but the last not cut by bytes",
              sum(1 for b in run[:-1] if b["reason"] != "bytes"), "==", 0)
    ctx.check("blocks of the chain that hold max_message_count "
              "transactions (cut by count)",
              sum(1 for b in blocks
                  if b["txs"] >= int(batch["max_message_count"])), "==", 0)
    ctx.check("largest block, bytes, against absolute_max_bytes",
              max(b["bytes"] for b in blocks), "<=",
              int(batch["absolute_max_bytes"]))

    # the records: every one, on both peers, against the model
    seed = ctx.seed
    last = (rep["blocks"] or opening)[-1]["number"]
    ctx.check("device peer's height against the last block it stored",
              held["height"], "==", last + 1)
    ctx.check("records compared on the device peer", len(held["records"]),
              "==", recordcount)
    model = gen.records_after(written, blocks, last, seed, recordcount)
    ctx.check(f"records that differ from the model's after block {last} "
              f"(device peer, SHA-256 of each of {recordcount})",
              sum(a != b for a, b in zip(held["records"], model)), "==", 0)
    ref_last = ref_report["height"] - 1
    ref_model = gen.records_after(written, blocks, ref_last, seed,
                                  recordcount)
    ctx.check(f"records that differ from the model's after block {ref_last} "
              f"(software peer, SHA-256 of each of {recordcount})",
              sum(a != b for a, b in zip(ref_report["records"], ref_model))
              + abs(len(ref_report["records"]) - recordcount), "==", 0)
    ctx.check("records the model holds after the load phase",
              sum(1 for d in ref_model if d is not None), "==", recordcount)


def say_window(blocks, rep, n_open) -> None:
    """The cut as it came out, and whether the window or the backlog
    ended the run."""
    sizes = collections.Counter(b["reason"] for b in blocks)
    run = [b for b in blocks if b["phase"] == "run"]
    txs = sorted(b["txs"] for b in run[:-1]) or [0]
    say(f"the cut: {n_open} load-phase + {len(run)} backlog blocks, reasons "
        f"{dict(sizes)}; backlog blocks but the last hold {txs[0]}-{txs[-1]} "
        f"tx (median {txs[len(txs) // 2]}), "
        f"{sum(b['bytes'] for b in run[:-1]) // max(1, len(run) - 1)} bytes "
        f"a block; last block of each phase: "
        f"{blocks[n_open - 1]['txs']} / {run[-1]['txs']} tx")
    started = len(rep["blocks"])
    say(f"the window started {started} of the backlog's {len(run)} blocks "
        f"({sum(b['txs'] for b in rep['blocks'])} of "
        f"{sum(b['txs'] for b in run)} tx): margin "
        f"{len(run) / max(1, started):.2f} x"
        + ("; BACKLOG EXHAUSTED" if rep["exhausted"] else ""))
    before = harness.parse_prom(rep["prom_before"])
    after = harness.parse_prom(rep["prom_after"])

    def moved(name, **labels):
        return harness.prom_delta(before, after, name, **labels)
    say("the window's ledger account: "
        f"{moved('ledger_state_write_bytes_total'):.0f} key + value bytes "
        "applied; checkpoints (count, seconds) state "
        f"{moved('state_checkpoint_seconds_count'):.0f}, "
        f"{moved('state_checkpoint_seconds_sum'):.3f}; history "
        f"{moved('history_checkpoint_seconds_count'):.0f}, "
        f"{moved('history_checkpoint_seconds_sum'):.3f}; fsyncs (count, "
        "seconds) " + "; ".join(
            f"{store} {moved('ledger_fsync_seconds_count', store=store):.0f},"
            f" {moved('ledger_fsync_seconds_sum', store=store):.3f}"
            for store in ("blocks", "state", "history")))
    say("the window's dispatch account, by lane (dispatches, of them "
        "unobserved, mean held ms): " + "; ".join(
            f"{lane} {moved('provider_dispatch_total', lane=lane):.0f}, "
            f"{moved('provider_dispatch_unobserved_total', lane=lane):.0f}, "
            f"{1e3 * moved('provider_dispatch_held_seconds_sum', lane=lane) / max(1.0, moved('provider_dispatch_held_seconds_count', lane=lane)):.2f}"
            for lane in ("rows", "generic")))
    traced = rep["traced"]
    if "end" in traced:
        after_it = [b["start"] for b in rep["blocks"]
                    if b["start"] >= traced["end"]]
        say(f"capture: the traced blocks took "
            f"{traced['end'] - traced['start']:.3f} s; its end (stop_trace "
            "+ one exposition, until the next block starts) "
            + (f"{after_it[0] - traced['end']:.1f} s" if after_it
               else f"not before the window's end, "
                    f"{rep['t_end'] - traced['end']:.1f} s"))
    took = sorted(b["end"] - b["start"] for b in rep["blocks"])
    slow = [(b["number"], round(b["end"] - b["start"], 3), b["ledger_s"])
            for b in rep["blocks"]
            if b["end"] - b["start"] > 5 * took[len(took) // 2]]
    say(f"blocks: median {1e3 * took[len(took) // 2]:.1f} ms, p95 "
        f"{1e3 * took[int(0.95 * (len(took) - 1))]:.1f} ms, longest "
        f"{1e3 * took[-1]:.1f} ms; over five medians (number, seconds, the "
        f"ledger's phases): {slow[:8]}")
