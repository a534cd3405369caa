"""AOT warmup: pre-compile the provider's kernel set into the cache.

Every (kernel, bucket-shape) pair costs a cold XLA compile on first
dispatch.  This tool runs each configured kernel once per bucket shape
so the persistent compilation cache (bccsp/factory.enable_compile_cache)
is hot before a node starts serving — run it at provisioning time:

    python -m fabric_tpu.node.warmup

The cache lives where JAX_COMPILATION_CACHE_DIR says, else at
<checkout>/.cache/jax; a node started with the same setting loads every
program this compiled instead of compiling it.

`warm_lanes` is the exact-shape form a running node uses (the peer's
POST /bccsp/warmup ops route): one dispatch per named generic-lane
bucket and rows-lane bucket, in the process that will serve them.
"""

from __future__ import annotations

import argparse
import sys
import time


def gen_p256_sigs(n: int, n_keys: int, seed: int = 2026):
    import hashlib
    import random

    from fabric_tpu.crypto import hashes
    from fabric_tpu.crypto import ec
    from fabric_tpu.crypto import (
        decode_dss_signature, encode_dss_signature)
    from fabric_tpu.crypto import (
        Encoding, PublicFormat)

    from fabric_tpu.bccsp import SCHEME_P256, VerifyItem
    from fabric_tpu.ops import p256

    rng = random.Random(seed)
    keys = [ec.generate_private_key(ec.SECP256R1()) for _ in range(n_keys)]
    pubs = [k.public_key().public_bytes(Encoding.X962,
                                        PublicFormat.UncompressedPoint)
            for k in keys]
    items = []
    for i in range(n):
        msg = rng.randbytes(48)
        digest = hashlib.sha256(msg).digest()
        r, s = decode_dss_signature(
            keys[i % n_keys].sign(msg, ec.ECDSA(hashes.SHA256())))
        if s > p256.HALF_N:
            s = p256.N - s
        items.append(VerifyItem(SCHEME_P256, pubs[i % n_keys],
                                encode_dss_signature(r, s), digest))
    return items


def gen_ed25519_sigs(n: int, n_keys: int = 4, seed: int = 7):
    import random

    from fabric_tpu.crypto import (
        Ed25519PrivateKey)
    from fabric_tpu.crypto import (
        Encoding, PublicFormat)

    from fabric_tpu.bccsp import SCHEME_ED25519, VerifyItem

    rng = random.Random(seed)
    keys = [Ed25519PrivateKey.generate() for _ in range(n_keys)]
    pubs = [k.public_key().public_bytes(Encoding.Raw, PublicFormat.Raw)
            for k in keys]
    items = []
    for i in range(n):
        msg = rng.randbytes(48)
        items.append(VerifyItem(SCHEME_ED25519, pubs[i % n_keys],
                                keys[i % n_keys].sign(msg), msg))
    return items


def warmup(buckets, schemes=("p256", "p256-rows", "ed25519", "idemix"),
           verbose: bool = True) -> dict:
    from fabric_tpu.bccsp.factory import FactoryOpts, init_factories

    provider = init_factories(FactoryOpts(default="JAXTPU"))
    return _warm_kernels(provider, buckets, schemes, verbose)


def warm_lanes(provider, generic=(), rows=()) -> dict:
    """One P-256 dispatch at exactly each named shape: `generic` are
    generic-ladder buckets (powers of two from MIN_BUCKET), `rows` are
    fixed-comb row buckets (members of ROW_BUCKETS).  Returns seconds
    per shape; every verdict must be True or this raises.

    The shapes go out on one thread each: tracing a program holds the
    interpreter lock, but XLA compiles (and loads from the persistent
    cache) outside it, so the compiles of different shapes overlap."""
    from concurrent.futures import ThreadPoolExecutor

    import numpy as np

    from fabric_tpu.bccsp import SCHEME_P256
    from fabric_tpu.bccsp.jaxtpu import MIN_BUCKET

    # 128 keys, each far under fast_key_threshold per batch: these stay
    # on the generic ladder whatever the bucket
    spread = gen_p256_sigs(128, n_keys=128, seed=11)
    # one resident key filling exactly `bucket` rows
    hot = gen_p256_sigs(64, n_keys=1, seed=13)
    jobs = []
    for bucket in generic:
        n = bucket if bucket == MIN_BUCKET else bucket // 2 + 1
        if n // len(spread) >= provider.fast_key_threshold:
            raise ValueError(f"generic bucket {bucket} too large to warm")
        jobs.append((f"generic@{bucket}",
                     (spread * (n // len(spread) + 1))[:n]))
    for bucket in rows:
        if bucket not in provider.ROW_BUCKETS:
            raise ValueError(f"rows bucket {bucket} not in ROW_BUCKETS")
        n = bucket * provider.fast_row_c
        jobs.append((f"rows@{bucket}", (hot * (n // len(hot) + 1))[:n]))
    if rows:
        provider.key_tables.get_or_build(hot[0].pubkey)
    # one jitted function per lane, made before the threads race for it
    for lane in ([SCHEME_P256] if generic else []) + \
            (["p256-rows"] if rows else []):
        provider._get_fn(lane)

    def one(job):
        name, items = job
        t0 = time.perf_counter()
        ok = provider.batch_verify(items)
        if not bool(np.asarray(ok).all()):
            raise RuntimeError(f"warmup {name}: bad verdicts")
        return name, round(time.perf_counter() - t0, 3)

    if not jobs:
        return {}
    with ThreadPoolExecutor(len(jobs)) as pool:
        return dict(pool.map(one, jobs))


def _warm_kernels(provider, buckets, schemes, verbose: bool) -> dict:
    timings = {}
    if "idemix" in schemes:
        # the BN254 dual-pairing lane: the batch dimension buckets in
        # powers of two from IDEMIX_MIN_BUCKET, one program each —
        # warm the first few (covers <=64 presentations per issuer
        # per block; larger blocks pay one further compile each)
        import numpy as np
        b0 = provider.IDEMIX_MIN_BUCKET
        for b in (b0, b0 * 2, b0 * 4):
            fn, green, _red = provider.idemix_pair_probe(b)
            t0 = time.perf_counter()
            assert bool(np.asarray(fn(*green)).all())
            timings[f"idemix-pair@{b}"] = round(time.perf_counter() - t0, 1)
        if verbose:
            print("idemix-pair:", {k: v for k, v in timings.items()
                                   if k.startswith("idemix")}, flush=True)
    for bucket in buckets:
        if "p256" in schemes:
            items = gen_p256_sigs(min(bucket, 64), n_keys=8)
            reps = (bucket // len(items)) + 1
            t0 = time.perf_counter()
            provider.batch_verify((items * reps)[:bucket])
            timings[f"p256@{bucket}"] = round(time.perf_counter() - t0, 1)
        if "p256-rows" in schemes:
            items = gen_p256_sigs(min(bucket, 64), n_keys=2, seed=5)
            for it in items:
                provider.key_tables.get_or_build(it.pubkey)
            reps = (bucket // len(items)) + 1
            t0 = time.perf_counter()
            provider.batch_verify((items * reps)[:bucket])
            timings[f"p256-rows@{bucket}"] = round(
                time.perf_counter() - t0, 1)
        if "ed25519" in schemes:
            items = gen_ed25519_sigs(min(bucket, 64))
            reps = (bucket // len(items)) + 1
            t0 = time.perf_counter()
            provider.batch_verify((items * reps)[:bucket])
            timings[f"ed25519@{bucket}"] = round(time.perf_counter() - t0, 1)
        if verbose:
            print(f"bucket {bucket}: "
                  + ", ".join(f"{k.split('@')[0]}={v}s"
                              for k, v in timings.items()
                              if k.endswith(f"@{bucket}")), flush=True)
    return timings


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(prog="fabric-tpu-warmup")
    ap.add_argument("--buckets", default="12288,16384,32768",
                    help="comma-separated batch sizes (12288 lands the "
                         "96-row grid bucket; 16384/32768 the 128/256)")
    ap.add_argument("--schemes",
                    default="p256,p256-rows,ed25519,idemix")
    args = ap.parse_args(argv)
    timings = warmup([int(b) for b in args.buckets.split(",")],
                     tuple(args.schemes.split(",")))
    from fabric_tpu.bccsp.factory import enable_compile_cache
    print("warm:", timings)
    print(f"compile cache: {enable_compile_cache()}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
