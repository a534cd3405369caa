"""AOT warmup: pre-compile the provider's kernel set into the cache.

Every (kernel, program-shape) pair costs a cold XLA compile on first
dispatch.  This tool runs each configured kernel once per shape so the
persistent compilation cache (bccsp/factory.enable_compile_cache) is
hot before a node starts serving — run it at provisioning time:

    python -m fabric_tpu.node.warmup

The cache lives where JAX_COMPILATION_CACHE_DIR says, else at
<checkout>/.cache/jax; a node started with the same setting loads every
program this compiled instead of compiling it.

P-256 is warmed by exact lane and bucket (`JaxTpuProvider.warm`, which
a running peer also offers as POST /bccsp/warmup), at the shapes below;
Ed25519 here by total batch size (`--buckets`) — `JaxTpuProvider.warm`
takes its exact shapes too (`ed25519=`, `ed25519_rows=`), which is what
a serving peer is asked for; Idemix at its first three batch buckets.
"""

from __future__ import annotations

import argparse
import sys
import time

# The P-256 program shapes the served path uses (bccsp/jaxtpu.py), as
# chip_smoke.py saw them on the chip under the 3-org AND policy with 64
# client identities.  Generic ladder: single-signature handshake and
# proposal checks and <=64-tx ingress stamps ride bucket 128; a 500-tx
# block's ~8-per-key creator signatures 256 and 512.  Rows lane (three
# resident endorser keys): 4 rows for one gateway batch, 16 for a 500-tx
# block (3 x 4 rows).  A 10,000-tx block: 384 rows (3 x 79 rows of
# endorsements + 64 x 2 of creators).
SERVED_GENERIC = (128, 256, 512)
SERVED_ROWS = (4, 16)
BLOCK_10K_ROWS = (384,)


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
            print("idemix-pair:", timings, flush=True)
    p256 = provider.warm(
        generic=SERVED_GENERIC if "p256" in schemes else (),
        rows=SERVED_ROWS + BLOCK_10K_ROWS if "p256-rows" in schemes else ())
    if verbose and p256:
        print("p256:", p256, flush=True)
    timings.update(p256)
    if "ed25519" in schemes:
        for bucket in buckets:
            items = gen_ed25519_sigs(min(bucket, 64))
            reps = (bucket // len(items)) + 1
            t0 = time.perf_counter()
            provider.batch_verify((items * reps)[:bucket])
            timings[f"ed25519@{bucket}"] = round(time.perf_counter() - t0, 1)
            if verbose:
                print(f"ed25519@{bucket}: "
                      f"{timings[f'ed25519@{bucket}']}s", flush=True)
    return timings


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(prog="fabric-tpu-warmup")
    ap.add_argument("--buckets", default="12288,16384,32768",
                    help="comma-separated Ed25519 batch sizes (12288 lands "
                         "the 96-row grid bucket; 16384/32768 the 128/256)")
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
