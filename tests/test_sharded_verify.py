"""Differential fuzz: the sharded (8-virtual-device mesh) provider must
produce BIT-IDENTICAL verdicts to the single-device provider and the SW
oracle over adversarial corpora — corrupted signatures, malformed DER,
truncated keys, wrong payload lengths, lane-mix skew (hot keys riding
the rows lane beside distinct keys on the generic ladder), and batch
sizes that do not divide the mesh (forcing uneven pad tails and, at
size 1 on 8 devices, all-pad shards on 7 chips).

The provider's atomic SW fallback would MASK a broken sharded dispatch
(fall back, verdicts match, test green) — every case therefore hard-
gates on stats["fallbacks"] == 0.

Mesh dispatches always jit (minutes of XLA:CPU compile, cold) — the
module carries the slow mark, the same as test_mesh.py.
"""

import hashlib
import random

import numpy as np
import pytest

import jax

from fabric_tpu.bccsp.provider import (SCHEME_ED25519, SCHEME_P256,
                                       VerifyItem)
from fabric_tpu.bccsp.sw import SoftwareProvider

pytestmark = [pytest.mark.slow]

if len(jax.devices()) < 8:
    pytestmark = [pytest.mark.skip(reason="needs 8 (virtual) devices: set "
                  "XLA_FLAGS=--xla_force_host_platform_device_count=8")]

rng = random.Random(0xF0CC)


@pytest.fixture(scope="module")
def sw():
    return SoftwareProvider()


@pytest.fixture(scope="module")
def single():
    from fabric_tpu.bccsp.jaxtpu import JaxTpuProvider
    return JaxTpuProvider(fast_key_threshold=4, fast_row_c=8)


@pytest.fixture(scope="module")
def sharded():
    from fabric_tpu.bccsp.jaxtpu import JaxTpuProvider
    from fabric_tpu.parallel import mesh as meshmod
    mesh = meshmod.make_mesh(jax.devices()[:8])
    return JaxTpuProvider(mesh=mesh, fast_key_threshold=4, fast_row_c=8)


# -- corpus generation -------------------------------------------------------

_P256_KEYS = []
_ED_KEYS = []


def _p256_key(sw, i):
    while len(_P256_KEYS) <= i:
        _P256_KEYS.append(sw.key_gen(SCHEME_P256))
    return _P256_KEYS[i]


def _ed_key(sw, i):
    while len(_ED_KEYS) <= i:
        _ED_KEYS.append(sw.key_gen(SCHEME_ED25519))
    return _ED_KEYS[i]


def _good_p256(sw, key_idx):
    k = _p256_key(sw, key_idx)
    digest = hashlib.sha256(rng.randbytes(48)).digest()
    return VerifyItem(SCHEME_P256, k.public_bytes(), sw.sign(k, digest),
                      digest)


def _good_ed(sw, key_idx):
    k = _ed_key(sw, key_idx)
    msg = rng.randbytes(rng.randrange(0, 90))
    return VerifyItem(SCHEME_ED25519, k.public_bytes(), sw.sign(k, msg), msg)


def _adversarial(sw, i):
    """One corpus item, cycling through good and hostile shapes."""
    kind = i % 9
    if kind in (0, 1):                       # valid, distinct-ish keys
        return _good_p256(sw, i % 13)
    if kind == 2:                            # valid ed25519
        return _good_ed(sw, i % 7)
    if kind == 3:                            # corrupted payload
        it = _good_p256(sw, i % 13)
        return it._replace(payload=bytes([it.payload[0] ^ 0x5A])
                           + it.payload[1:])
    if kind == 4:                            # bit-flipped signature body
        it = _good_p256(sw, i % 13)
        sig = bytearray(it.signature)
        sig[-1] ^= 0x01
        return it._replace(signature=bytes(sig))
    if kind == 5:                            # malformed DER
        it = _good_p256(sw, i % 13)
        return it._replace(signature=b"\x30\x02\x01\x00")
    if kind == 6:                            # truncated pubkey
        it = _good_p256(sw, i % 13)
        return it._replace(pubkey=it.pubkey[:33])
    if kind == 7:                            # wrong payload length
        it = _good_p256(sw, i % 13)
        return it._replace(payload=it.payload + b"x")
    it = _good_ed(sw, i % 7)                 # corrupted ed25519 sig
    sig = bytearray(it.signature)
    sig[7] ^= 0x80
    return it._replace(signature=bytes(sig))


def _assert_identical(sw, single, sharded, items):
    want = sw.batch_verify(items)
    f1 = single.stats["fallbacks"]
    got_single = single.batch_verify(items)
    assert single.stats["fallbacks"] == f1, \
        "single-device path fell back to SW"
    f2 = sharded.stats["fallbacks"]
    got_sharded = sharded.batch_verify(items)
    assert sharded.stats["fallbacks"] == f2, \
        "sharded path fell back to SW (fallback would mask divergence)"
    np.testing.assert_array_equal(got_sharded, got_single)
    np.testing.assert_array_equal(got_sharded, want)


# -- the differential cases --------------------------------------------------

@pytest.mark.parametrize("n", [1, 3, 5, 13, 97])
def test_non_divisible_batches_bit_identical(sw, single, sharded, n):
    """Sizes that do not divide 8: uneven pad tails; n=1 leaves 7 of 8
    shards all-pad."""
    items = [_adversarial(sw, i) for i in range(n)]
    _assert_identical(sw, single, sharded, items)


def test_adversarial_corpus_bit_identical(sw, single, sharded):
    items = [_adversarial(sw, i) for i in range(64)]
    _assert_identical(sw, single, sharded, items)


def test_lane_mix_skew_bit_identical(sw, single, sharded):
    """Hot keys past fast_key_threshold ride the rows lane while
    distinct keys take the generic ladder IN THE SAME BATCH; a couple
    of corruptions keep the verdict map non-trivial."""
    items = []
    for i in range(10):                      # hot key -> rows lane
        items.append(_good_p256(sw, 0))
    for i in range(9):                       # distinct keys -> generic
        items.append(_good_p256(sw, 20 + i))
    for i in range(6):                       # hot ed25519 key
        items.append(_good_ed(sw, 0))
    bad = items[3]._replace(payload=bytes(32))
    items[3] = bad
    items[12] = items[12]._replace(signature=b"\x00")
    _assert_identical(sw, single, sharded, items)


def test_all_invalid_batch_bit_identical(sw, single, sharded):
    items = [_adversarial(sw, i) for i in range(16)
             if i % 9 in (3, 4, 5, 6, 7)]
    assert items
    _assert_identical(sw, single, sharded, items)


def test_sharded_stats_count_device_sigs(sw, sharded):
    f0 = sharded.stats["fallbacks"]
    d0 = sharded.stats["device_sigs"]
    items = [_good_p256(sw, 30 + i) for i in range(8)]
    out = sharded.batch_verify(items)
    assert bool(np.asarray(out).all())
    assert sharded.stats["fallbacks"] == f0
    assert sharded.stats["device_sigs"] - d0 >= len(items)


def test_sharded_emits_per_device_fill(sw, sharded):
    from fabric_tpu.ops_plane import registry
    sharded.batch_verify([_good_p256(sw, 40 + i) for i in range(5)])
    g = registry.get("provider_lane_fill_fraction")
    devs = {dict(k)["device"] for k, v in g.values().items()
            if dict(k).get("lane") == "generic"}
    assert len(devs) >= 8, devs
