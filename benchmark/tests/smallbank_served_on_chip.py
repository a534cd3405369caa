#!/usr/bin/env python3
"""SmallBank on the served path, on the chip, once and by hand: the
contract through gateway -> endorser -> simulate -> orderers -> validator
-> ledger on the `and3-cut500` deployment with the device peer.  Not a
cell: at the served path's rates a block holds a score of transactions
and the run times what `served.steady` times.

    python3 benchmark/tests/smallbank_served_on_chip.py --seed 7

Opens 40 accounts in one burst (which also makes the endorsers' tables
resident), then fires 200 requests of a seeded mix (Pw 0.95, accounts
Zipf s 1.0) at 8 a second through `GatewayClient`.  Afterwards every
block is fetched from all three peers: flags equal everywhere, every
acknowledged commit read back with the gateway's code, and — the model
run over the chain's VALID transactions in commit order — every
account's balances on every peer (`query`, evaluated on that peer) equal
the model's.  Exit 0 when every comparison holds.
"""

from __future__ import annotations

import argparse
import concurrent.futures
import copy
import os
import random
import shutil
import statistics
import sys
import tempfile
import time

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [os.path.dirname(BENCH), BENCH]

import harness  # noqa: E402
import run as launcher  # noqa: E402
from drivers import served  # noqa: E402
from gen import smallbank as gen  # noqa: E402
from gen.deployment import Deployment, block_flags, http_json  # noqa: E402
from harness import BenchFailure, say  # noqa: E402

VALID, MVCC = gen.VALID, gen.MVCC_CONFLICT
REQUESTS, RATE, ACCOUNTS = 200, 8.0, 40


def deployment_config() -> tuple:
    """(`and3-cut500` with SmallBank installed beside nothing else, the
    served cell's warm shapes)."""
    manifest = launcher.load_json(launcher.REPO, "BENCHMARK.json")
    workload, cfg = launcher.load_cell(manifest, "served.steady")
    bank = launcher.load_json(BENCH, "configs", "smallbank-and3-cut10k.json")
    cfg = copy.deepcopy(cfg)
    cfg["chaincode"] = bank["chaincode"]
    for peer in ("device_peer", "reference_peer"):
        cfg[peer] = dict(cfg[peer], chaincodes=bank[peer]["chaincodes"])
    return cfg, workload


def submit(gw, cc: str, n_orgs: int, fn: str, args: list) -> dict:
    """One request's whole life; a refusal by the contract is an answer."""
    from fabric_tpu.endorser.proposal import assemble_transaction
    from fabric_tpu.gateway import GatewayError
    req = {"fn": fn, "args": args, "t0": time.monotonic()}
    try:
        try:
            sp, responses = gw.endorse(cc, fn, [str(a).encode() for a in args])
        except GatewayError as exc:
            req["refused"] = str(exc)
            return req
        if len(responses) < n_orgs:
            # an endorser simulated at another height (its payload
            # differs) or refused there: a client does not submit what
            # the policy cannot accept
            req["diverged"] = True
            return req
        env = assemble_transaction(sp, responses, gw.signer)
        req["txid"] = env.header().channel_header.txid
        gw.submit_envelope(env, timeout_s=30.0)
        req["code"], req["block"] = gw.commit_status(req["txid"],
                                                     timeout_s=60.0)
    except Exception as exc:             # the boundary: recorded, counted
        req["error"] = repr(exc)
    req["t1"] = time.monotonic()
    return req


def query(gw, cc: str, account) -> list:
    """[savings, checking] as the gateway's own peer holds them."""
    from fabric_tpu.protocol.types import ChaincodeAction
    from fabric_tpu.utils import serde
    endorsed = serde.decode(gw.evaluate(cc, "query", [str(account).encode()]))
    action = ChaincodeAction.from_dict(endorsed["action"])
    return [int(v) for v in action.response_payload.split(b",")]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--seed", type=int, required=True)
    args = ap.parse_args(argv)
    from fabric_tpu.bccsp.factory import FactoryOpts, init_factories
    from fabric_tpu.gateway import GatewayClient
    from fabric_tpu.testing.procnet import wait_orderer_leader, wait_status
    init_factories(FactoryOpts(default="SW"))     # the launcher's own
    cfg, wl = deployment_config()
    ctx = harness.Context(workload=wl, config=cfg, seed=args.seed,
                          seconds=REQUESTS / RATE, trace=False)
    harness.adopt_orphans()
    base = tempfile.mkdtemp(prefix="smallbank_served_")
    dep = pool = probe = None
    gws = []
    try:
        probe = harness.start_probe()
        harness.build_native()
        dep = Deployment(base, cfg, harness.REPO, {})
        dev = dep.device_org
        dep.start_orderers()
        for org in dep.orgs:
            if org != dev:
                dep.start_peer(org)
        say(f"jax finds {harness.finish_probe(probe, 1)}")
        dep.start_peer(dev)
        wait_orderer_leader(dep.orderers, dep.signer, dep.msps,
                            deadline_s=90.0)
        for org in dep.orgs:
            if org != dev:
                wait_status(dep.peer_addr[org], dep.signer, dep.msps,
                            lambda st: True, f"peer {org} serving", 180.0)
        st0 = dep.wait_ops(dev, 300.0)
        if st0["device"] is None or st0["name"] != "jaxtpu":
            raise BenchFailure(f"{dev}'s peer runs {st0['name']}, not jaxtpu")
        warm = http_json("POST", dep.ops[dev] + "/bccsp/warmup",
                         {"generic": wl["warm_generic"],
                          "rows": wl["warm_rows"]}, timeout=1100.0)
        say(f"warm-up in the device peer: {warm['timings']} "
            f"({warm['seconds']} s)")
        wait_status(dep.peer_addr[dev], dep.signer, dep.msps,
                    lambda st: True, f"peer {dev} serving", 60.0)
        gws = [GatewayClient(dep.peer_addr[dev], signer, dep.msps,
                             channel_id=dep.channel, seed=i)
               for i, signer in enumerate(dep.clients[:16])]
        for gw in gws:
            gw.warm()
        cc = dep.chaincode
        pool = concurrent.futures.ThreadPoolExecutor(64)
        opened = [f.result() for f in [
            pool.submit(submit, gws[i % len(gws)], cc, len(dep.orgs),
                        "create_account",
                        [i, f"customer{i}", gen.OPENING_BALANCE,
                         gen.OPENING_BALANCE])
            for i in range(1, ACCOUNTS + 1)]]
        bad = [r for r in opened if r.get("code") != VALID]
        if bad:
            raise BenchFailure(f"opening: {len(bad)} of {ACCOUNTS} "
                               f"accounts not VALID (first: {bad[0]})")
        st1 = dep.provider_status(dev)
        say(f"{ACCOUNTS} accounts opened; set-up "
            f"{time.monotonic() - harness.T0:.1f} s")

        rng = random.Random(args.seed)
        draw = gen.zipf_sampler(ACCOUNTS, 1.0)
        calls = [gen.draw_call(rng, draw, 0.95) for _ in range(REQUESTS)]
        futures, t_start = [], time.monotonic()
        for n, (fn, a) in enumerate(calls):
            lag = t_start + n / RATE - time.monotonic()
            if lag > 0:
                time.sleep(lag)
            futures.append(pool.submit(submit, gws[n % len(gws)], cc,
                                       len(dep.orgs), fn, a))
        reqs = [f.result() for f in futures]
        seconds = time.monotonic() - t_start
        st2 = dep.provider_status(dev)
        errors = [r for r in reqs if "error" in r]
        refused = [r for r in reqs if "refused" in r]
        diverged = [r for r in reqs if "diverged" in r]
        answered = [r for r in opened + reqs if "code" in r]
        ctx.check("requests that ended in an error", len(errors), "==", 0)
        for r in errors[:3]:
            say(f"error: {r}")
        for r in [r for r in reqs if r.get("code", VALID)
                  not in (VALID, MVCC)][:3]:
            say(f"unexpected code: {r}")
        ctx.check("requests the contract refused for anything but funds",
                  sum(1 for r in refused if "insufficient" not in r["refused"]),
                  "==", 0)
        codes = [r["code"] for r in reqs if "code" in r]
        ctx.check("answers with a code other than VALID or "
                  "MVCC_READ_CONFLICT",
                  sum(1 for c in codes if c not in (VALID, MVCC)), "==", 0)
        hi = max(r["block"] for r in answered) + 1
        dep.wait_heights(hi, 60.0)
        dep.assert_alive()

        # every block from all three peers
        hi = dep.statuses()[dev]["height"]
        per_org = {org: [block_flags(b) for b in dep.fetch_blocks(org, 0, hi)]
                   for org in dep.orgs}
        ctx.check(f"blocks 0..{hi - 1} whose flags differ between the device "
                  "peer and a software peer",
                  sum(1 for org in dep.orgs
                      for a, b in zip(per_org[org], per_org[dev]) if a != b),
                  "==", 0)
        by_txid = {r["txid"]: r for r in answered}
        bank, seen = gen.Bank(), 0
        unread = []
        for number, flags in enumerate(per_org[dev]):
            for n, (txid, code) in enumerate(flags):
                r = by_txid.get(txid)
                if r is None:
                    continue
                seen += 1
                if code != r["code"] or r["block"] not in (-1, number):
                    unread.append(r)
                if code == VALID:
                    bank.apply(number, n, r["fn"], r["args"])
        ctx.check(f"of {len(answered)} acknowledged commits, those not found "
                  "in the chain", len(answered) - seen, "==", 0)
        ctx.check("acknowledged commits not read back from all three peers "
                  "with the gateway's code and block", len(unread), "==", 0)
        served.check_same_ledger(ctx, dep)
        ctx.check("the model's money account balances",
                  int(bank.money_balances()), "==", 1)
        want = bank.accounts(range(1, ACCOUNTS + 1))
        for org in dep.orgs:
            gw = GatewayClient(dep.peer_addr[org], dep.signer, dep.msps,
                               channel_id=dep.channel)
            try:
                held = {i: query(gw, cc, i) for i in want}
            finally:
                gw.close()
            ctx.check(f"of {len(want)} accounts, those whose balances on "
                      f"{org}'s peer differ from the model's",
                      sum(1 for i in want if held[i] != want[i]), "==", 0)
        served.account(ctx, dep, st1, st2, [r for r in reqs if "code" in r])
        lat = sorted(1e3 * (r["t1"] - r["t0"]) for r in reqs if "code" in r)
        say(f"smoke readings (no metric's): {len(reqs)} requests in "
            f"{seconds:.1f} s ({len(reqs) / seconds:.2f}/s offered), "
            f"{len(codes)} committed ({codes.count(VALID)} VALID, "
            f"{codes.count(MVCC)} MVCC_READ_CONFLICT), {len(refused)} "
            f"refused by the contract (insufficient funds), "
            f"{len(diverged)} not submitted (an endorser simulated at "
            f"another height), "
            f"{len(per_org[dev])} blocks; fire to answer median "
            f"{statistics.median(lat):.0f} ms, max {lat[-1]:.0f} ms; "
            f"device {harness.device_report(st2)}")
        ok = all(c["ok"] for c in ctx.checks)
        print("smallbank served: " + ("PASSED" if ok else "FAILED"),
              flush=True)
        return 0 if ok else 1
    except (BenchFailure, AssertionError) as exc:
        tail = dep.log_tail("peer" + dep.device_org) if dep else ""
        sys.stderr.write(f"smallbank served FAILED: {exc}\n"
                         f"---- device peer's log ----\n{tail}\n")
        return 1
    finally:
        if pool is not None:
            pool.shutdown(wait=False, cancel_futures=True)
        for gw in gws:
            gw.close()
        if dep is not None:
            dep.stop()
        if probe is not None and probe.poll() is None:
            probe.kill()
            probe.communicate()
        shutil.rmtree(base, ignore_errors=True)
        left = harness.reap_descendants()
        if left:
            sys.stderr.write(f"stopped on the way out: {left}\n")


if __name__ == "__main__":
    sys.exit(main())
