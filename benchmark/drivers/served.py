"""Driver `served`: the path every application takes, from the client's
side of the gateway — endorse -> assemble -> submit -> commit_status
through `GatewayClient`, one connection per enrolled identity, open
loop: each request fires at its due time whether or not earlier ones
completed, and is timed from that due time.

The deployment runs as OS processes (3 orderers, one peer per org); the
device org's peer holds the chip and hosts the gateway, the other orgs'
peers verify with OpenSSL and are the plain reference: after the window
every block is fetched from all three and compared (`cross_check`,
`check_same_ledger`, copied from `chip_smoke.py`).

Cell parameters (`workloads/<cell>.json`): `arrivals`, `connections`,
`pilot_tx`, `warm_generic`, `warm_rows`, `max_in_flight`, `drain_s`,
`trace_start_s`, `trace_seconds`.
"""

from __future__ import annotations

import concurrent.futures
import random
import shutil
import sys
import tempfile
import threading
import time

import harness
from gen import arrivals
from gen.backlog import POLICY_FAILURE, VALID, flip_last_byte
from gen.deployment import Deployment, block_flags, http_json
from harness import BenchFailure, percentile, say

# a traced run's device peer: the program's, plus a profiler route
TRACED_PEER = "benchmark.drivers.profiled_peer"
# tests only: the peer module that answers yes to every signature
FAULT_PEER = {"yes_verifier": "benchmark.tests.yes_peer"}


class Traffic:
    """Requests through GatewayClient, one connection per identity,
    dealt to the identities in turn."""

    def __init__(self, dep: Deployment, n_connections: int, faults):
        from fabric_tpu.gateway import GatewayClient
        self.dep = dep
        self.faults = faults
        self.gws = [GatewayClient(dep.peer_addr[dep.device_org], signer,
                                  dep.msps, channel_id=dep.channel, seed=i)
                    for i, signer in enumerate(dep.clients[:n_connections])]

    def connect_all(self) -> None:
        with concurrent.futures.ThreadPoolExecutor(16) as pool:
            for f in [pool.submit(gw.warm) for gw in self.gws]:
                f.result()

    def close(self) -> None:
        for gw in self.gws:
            gw.close()

    def plan(self, keys: list, first: int, tamper_every: int = 0) -> list:
        """One request per key: who sends it and whether one of its
        endorsement signatures is tampered (none where tamper_every
        is 0)."""
        return [{"i": first + j, "client": (first + j) % len(self.gws),
                 "key": "k%06d" % key,
                 "tampered": tamper_every > 0
                 and (first + j) % tamper_every == tamper_every - 1}
                for j, key in enumerate(keys)]

    def request(self, req: dict) -> dict:
        """The whole life of one transaction; never raises: a request
        that cannot be answered is a failed request, and says why."""
        from fabric_tpu.endorser.proposal import (ProposalResponse,
                                                  assemble_transaction)
        from fabric_tpu.protocol import Endorsement
        req["t_fire"] = time.monotonic()
        try:
            gw = self.gws[req["client"]]
            sp, responses = gw.endorse(self.dep.chaincode, "bump",
                                       [req["key"].encode()])
            if len(responses) != len(self.dep.orgs):
                raise BenchFailure(f"{len(responses)} endorsements, want "
                                   "one per org")
            if req["tampered"]:
                r = responses[1]
                responses[1] = ProposalResponse(
                    r.status, r.message, r.payload,
                    Endorsement(r.endorsement.endorser,
                                flip_last_byte(r.endorsement.signature)))
            env = assemble_transaction(sp, responses, gw.signer)
            req["txid"] = env.header().channel_header.txid
            gw.submit_envelope(env, timeout_s=30.0)
            req["code"], req["block"] = gw.commit_status(req["txid"],
                                                         timeout_s=60.0)
            if "ack_flip" in self.faults and req["tampered"]:
                req["code"] = VALID      # an answer altered where it is produced
        except Exception as exc:         # the boundary: recorded, counted failed
            req["error"] = repr(exc)
        req["t_done"] = time.monotonic()
        return req


def expected_code(req: dict) -> int:
    """The oracle: keys are distinct within a run, so the only code
    other than VALID is the tampered envelope's."""
    return POLICY_FAILURE if req["tampered"] else VALID


def answered_right(req: dict) -> bool:
    return "error" not in req and req["code"] == expected_code(req)


def run(ctx: harness.Context) -> dict:
    wl, cfg = ctx.workload, ctx.config
    sys.path.insert(0, harness.REPO)
    from fabric_tpu.bccsp.factory import FactoryOpts, init_factories
    from fabric_tpu.testing.procnet import wait_orderer_leader, wait_status
    init_factories(FactoryOpts(default="SW"))     # the launcher's own

    base = tempfile.mkdtemp(prefix="bench_served_")
    dep = traffic = pool = probe = None
    try:
        if ctx.require_accelerator:
            probe = harness.start_probe()
        harness.build_native()
        extra = {"tracing": {"enabled": bool(ctx.trace), "sample_rate": 1.0}}
        dep = Deployment(base, cfg, harness.REPO, extra)
        dev_org = dep.device_org
        dep.start_orderers()
        for org in dep.orgs:
            if org != dev_org:
                dep.start_peer(org)
        if probe is not None:
            found = harness.finish_probe(probe, int(wl["chips"]))
            say(f"jax finds {found}")
        module = TRACED_PEER if ctx.trace else "fabric_tpu.node.peer"
        for fault in ctx.faults:
            module = FAULT_PEER.get(fault, module)
        dep.start_peer(dev_org, module)
        say(f"started {sorted(dep.procs)}")
        wait_orderer_leader(dep.orderers, dep.signer, dep.msps,
                            deadline_s=90.0)
        for org in dep.orgs:
            if org != dev_org:
                wait_status(dep.peer_addr[org], dep.signer, dep.msps,
                            lambda st: True, f"peer {org} serving", 180.0)
        # the device peer is awaited on its ops port: an RPC needs a
        # handshake, whose signature check would compile inside the
        # dial's time-out before anything is warm
        st0 = dep.wait_ops(dev_org, 300.0)
        if ctx.require_accelerator:
            if st0["device"] is None or st0["name"] != "jaxtpu":
                raise BenchFailure(f"{dev_org}'s peer runs provider "
                                   f"{st0['name']}, not jaxtpu")
            harness.require_chips(st0["device"]["platform"],
                                  st0["device"]["device_count"],
                                  int(wl["chips"]))
        if st0["device"] is not None:
            warm = http_json("POST", dep.ops[dev_org] + "/bccsp/warmup",
                             {"generic": wl["warm_generic"],
                              "rows": wl["warm_rows"]}, timeout=1100.0)
            say(f"warm-up in the device peer: {warm['timings']} "
                f"({warm['seconds']} s)")
        wait_status(dep.peer_addr[dev_org], dep.signer, dep.msps,
                    lambda st: True, f"peer {dev_org} serving", 60.0)
        traffic = Traffic(dep, int(wl["connections"]), ctx.faults)
        traffic.connect_all()
        say(f"{len(traffic.gws)} client identities connected to the gateway")

        due = arrivals.schedule(wl["arrivals"], ctx.seed, ctx.seconds)
        n_pilot = int(wl["pilot_tx"])
        tamper_every = int(cfg["tamper_every"])
        # uniform over the keyspace, distinct within the run: the oracle
        # is then exact (no chance MVCC conflict between requests)
        keys = random.Random(ctx.seed).sample(range(int(cfg["keyspace"])),
                                              n_pilot + len(due))
        pool = concurrent.futures.ThreadPoolExecutor(int(wl["max_in_flight"]))
        # the pilot: one burst of sound requests, so that one block
        # carries enough of each endorser's signatures to make its comb
        # table resident, as on a peer that has served before
        pilot = [f.result() for f in [
            pool.submit(traffic.request, r)
            for r in traffic.plan(keys[:n_pilot], 0)]]
        bad = [r for r in pilot if not answered_right(r)]
        if bad:
            raise BenchFailure(f"pilot: {len(bad)} of {n_pilot} requests "
                               f"not answered right (first: {bad[0]})")
        h_pilot = dep.wait_heights(max(r["block"] for r in pilot) + 1,
                                   60.0)[dev_org]["height"]
        st1 = dep.provider_status(dev_org)
        prom1 = harness.parse_prom(dep.metrics_text(dev_org))
        setup_s = time.monotonic() - harness.T0
        say(f"pilot: {n_pilot} tx committed; set-up {setup_s:.1f} s")

        # ---- the window ----------------------------------------------------
        reqs = traffic.plan(keys[n_pilot:], n_pilot, tamper_every)
        traced = {}
        tracer = None
        if ctx.trace and st0["device"] is not None:
            tracer = threading.Thread(
                target=capture_trace,
                args=(dep, dev_org, float(wl["trace_start_s"]),
                      float(wl["trace_seconds"]), traced))
            tracer.start()
        futures = []
        t_start = time.monotonic()
        for req, offset in zip(reqs, due):
            req["due"] = t_start + offset
            lag = req["due"] - time.monotonic()
            if lag > 0:
                time.sleep(lag)
            futures.append(pool.submit(traffic.request, req))
        t_end = t_start + ctx.seconds
        time.sleep(max(0.0, t_end - time.monotonic()))
        st2 = dep.provider_status(dev_org)
        prom2 = harness.parse_prom(dep.metrics_text(dev_org))
        done = [r for r in reqs if r.get("t_done", t_end + 1) <= t_end]
        in_flight = len(reqs) - len(done)
        say(f"window closed: {len(reqs)} requests due, {len(done)} answered "
            f"inside it, {in_flight} still in flight (neither attempted nor "
            "failed)")
        if tracer is not None:
            tracer.join()
        _, late = concurrent.futures.wait(futures,
                                          timeout=float(wl["drain_s"]))
        ctx.check(f"requests unanswered {wl['drain_s']} s after the window",
                  len(late), "==", 0)
        if late:
            # nothing below can be trusted on a ledger that still moves
            return result(ctx, st2, setup_s, t_start, reqs, done, None,
                          traced, prom1, prom2)
        answered = [r for r in reqs if "code" in r]
        dep.wait_heights(max([r["block"] for r in answered] + [h_pilot - 1])
                         + 1, 60.0)
        dep.assert_alive()
        summary = cross_check(ctx, dep, h_pilot, answered)
        check_same_ledger(ctx, dep)
        account(ctx, dep, st1, st2, done)
        return result(ctx, st2, setup_s, t_start, reqs, done, summary,
                      traced, prom1, prom2)
    except AssertionError as exc:        # procnet's waits time out this way
        tail = dep.log_tail("peer" + dep.device_org) if dep else ""
        raise BenchFailure(f"{exc}\n---- device peer's log ----\n{tail}")
    finally:
        if pool is not None:
            pool.shutdown(wait=False, cancel_futures=True)
        if traffic is not None:
            traffic.close()
        if dep is not None:
            dep.stop()
        if probe is not None and probe.poll() is None:
            probe.kill()
            probe.communicate()
        shutil.rmtree(base, ignore_errors=True)


def capture_trace(dep, org, start_s, seconds, traced) -> None:
    """A slice of the steady window, traced from the process that holds
    the chip (`profiled_peer.py`'s route).  Never raises: a run without
    a trace says so in `traced`."""
    time.sleep(start_s)
    try:
        traced["reply"] = http_json(
            "POST", dep.ops[org] + f"/bench/profile?seconds={seconds}",
            timeout=240.0)
    except OSError as exc:
        traced["error"] = repr(exc)


def cross_check(ctx, dep, lo: int, answered: list) -> dict:
    """Blocks from `lo` on, from all three peers: identical flags
    everywhere, every acknowledged commit read back with the gateway's
    code, every tampered envelope flagged."""
    hi = dep.statuses()[dep.device_org]["height"]
    per_org = {org: [block_flags(b) for b in dep.fetch_blocks(org, lo, hi)]
               for org in dep.orgs}
    dev = per_org[dep.device_org]
    differ = sum(1 for org in dep.orgs for a, b in zip(per_org[org], dev)
                 if a != b)
    ctx.check(f"blocks {lo}..{hi - 1} whose tx-filter flags differ between "
              "the device peer and a software peer", differ, "==", 0)
    where = {}
    for n, flags in enumerate(dev, start=lo):
        for txid, code in flags:
            where[txid] = (code, n)
    # block -1: the gateway answered from its block store (the commit
    # beat the notifier), which names no block
    unread = [r for r in answered
              if where.get(r["txid"], (None, None))[0] != r["code"]
              or r["block"] not in (-1, where[r["txid"]][1])]
    ctx.check(f"of {len(answered)} acknowledged commits, those not read back "
              "from all three peers with the gateway's code and block",
              len(unread), "==", 0)
    tampered = [r for r in answered if r["tampered"]]
    ctx.check(f"of {len(tampered)} tampered envelopes, those not "
              "ENDORSEMENT_POLICY_FAILURE",
              sum(1 for r in tampered if r["code"] != POLICY_FAILURE),
              "==", 0)
    ctx.check("answers with a code the oracle does not expect",
              sum(1 for r in answered if r["code"] != expected_code(r)),
              "==", 0)
    return {"block_sizes": [len(flags) for flags in dev]}


def check_same_ledger(ctx, dep) -> None:
    sts = dep.statuses()
    ctx.check("distinct (height, commit hash) over the device peer and the "
              "software peers",
              len({(s["height"], s["commit_hash"]) for s in sts.values()}),
              "==", 1)


def account(ctx, dep, st1, st2, done) -> None:
    """The device peer's own account of the window."""
    ctx.check("provider degraded", int(st2["degraded"]), "==", 0)
    if st2["device"] is None:
        return
    ctx.check("provider fallbacks", st2["stats"]["fallbacks"], "==", 0)
    ctx.check("compilations inside the window",
              st2["device"]["compile"]["compiles"]
              - st1["device"]["compile"]["compiles"], "==", 0)
    sigs = st2["stats"]["device_sigs"] - st1["stats"]["device_sigs"]
    ctx.check("signatures verified on the device per answered transaction",
              sigs / max(1, len(done)), ">=",
              float(ctx.config["signatures_per_tx"]))


def result(ctx, st2, setup_s, t_start, reqs, done, summary, traced,
           prom1, prom2) -> dict:
    ok = [r for r in done if answered_right(r)]
    failed = len(done) - len(ok)
    for r in [r for r in done if not answered_right(r)][:5]:
        say(f"failed request: {r.get('error') or (r['code'], r['tampered'])}")
    lat = sorted(1e3 * (r["t_done"] - r["due"]) for r in ok)
    end_to_end = {"committed_tps": len(ok) / ctx.seconds,
                  "commit_p50_ms": percentile(lat, 0.5),
                  "commit_p95_ms": percentile(lat, 0.95),
                  "setup_s": setup_s}
    late = [1e3 * (r["t_fire"] - r["due"]) for r in reqs if "t_fire" in r]
    say(f"latency sample: {len(lat)} requests (p95 needs "
        f"{harness.MIN_BEYOND} beyond it); generator late by p95 "
        f"{percentile(sorted(late), 0.95)} ms, max {max(late):.3f} ms")
    say(f"end to end: {end_to_end}")
    device = harness.device_report(st2)
    obs = {"lateness_ms": late, "prom_before": prom1, "prom_after": prom2,
           "block_sizes": summary["block_sizes"] if summary else None,
           "requests": [(r["due"] - t_start,
                         r["t_done"] - t_start if "t_done" in r else None,
                         answered_right(r)) for r in reqs if "due" in r],
           # a dispatch enqueued at the trace's edge may run beyond it
           "trace_edge_slack": 2}
    if "error" in traced:
        raise BenchFailure(f"the trace was not captured: {traced['error']}")
    if "reply" in traced:
        trace_dir = traced["reply"]["trace_dir"]
        if trace_dir is None:
            raise BenchFailure("no slice of the window held device work: "
                               f"{traced['reply']}")
        say(f"trace captured at attempt {traced['reply']['attempts']}; "
            f"ending it took {traced['reply']['stop_s']:.1f} s")
        obs["traced_prom_before"] = harness.parse_prom(
            traced["reply"]["prom_before"])
        obs["traced_prom_after"] = harness.parse_prom(
            traced["reply"]["prom_after"])
        obs["trace"] = harness.reduce_trace(trace_dir, {})
        shutil.rmtree(trace_dir, ignore_errors=True)
        device["busy_s"] = obs["trace"]["busy_s"]
        device["window_s"] = obs["trace"]["window_s"]
    return {"attempted": len(done), "failed": failed,
            "end_to_end": end_to_end, "obs": obs, "device": device}
