"""Batched BN254 pairing kernel: differential pieces vs the host oracle.

The full pairing (Miller + ~2800-bit final exponentiation) is too slow
for the eager CPU path, so CPU coverage is compositional: tower ops and
a Miller-loop PREFIX match the host bit-for-bit; the host ate itself is
validated against bilinearity here; the full device pairing has no
cross-check on a real TPU in the tree.
"""
import random

import numpy as np
import pytest

from fabric_tpu.idemix import bn254 as hb
from fabric_tpu.ops import bignum as bn
from fabric_tpu.ops import bn254_batch as dev


def _fp2_to_dev(v, B):
    return (np.asarray(bn.ints_to_limbs([v[0] * dev.fpb.R % hb.P] * B),
                       np.int32),
            np.asarray(bn.ints_to_limbs([v[1] * dev.fpb.R % hb.P] * B),
                       np.int32))


def _dev_to_fp2(a, b_idx=0):
    rinv = pow(dev.fpb.R, -1, hb.P)
    c0 = bn.limbs_to_int(np.asarray(dev.fpb.canon(a[0]))[:, b_idx])
    c1 = bn.limbs_to_int(np.asarray(dev.fpb.canon(a[1]))[:, b_idx])
    return (c0 * rinv % hb.P, c1 * rinv % hb.P)


def test_f2_f12_ops_match_host():
    rng = random.Random(4)
    B = 2

    def rand2():
        return (rng.randrange(hb.P), rng.randrange(hb.P))

    a2, b2 = rand2(), rand2()
    da, db = _fp2_to_dev(a2, B), _fp2_to_dev(b2, B)
    assert _dev_to_fp2(dev.f2_mul(da, db)) == hb.f2_mul(a2, b2)
    assert _dev_to_fp2(dev.f2_add(da, db)) == hb.f2_add(a2, b2)
    assert _dev_to_fp2(dev.f2_sub(da, db, 2)) == hb.f2_sub(a2, b2)
    assert _dev_to_fp2(dev.f2_mul_xi(da, 2)) == hb.f2_mul(a2, hb.XI)

    a12 = tuple(rand2() for _ in range(6))
    b12 = tuple(rand2() for _ in range(6))
    da12 = tuple(_fp2_to_dev(c, B) for c in a12)
    db12 = tuple(_fp2_to_dev(c, B) for c in b12)
    got = dev.f12_mul(da12, db12)
    want = hb.f12_mul(a12, b12)
    assert tuple(_dev_to_fp2(c) for c in got) == want

    # sparse line mul matches the dense host product of the same element
    b0 = rng.randrange(hb.P)
    b1, b3 = rand2(), rand2()
    sparse_host = hb._sparse013(1, b1, 0, b3)           # build shape…
    sparse_host = list(sparse_host)
    sparse_host[0] = (b0, 0)
    sparse_host[1] = b1
    sparse_host[3] = b3
    db0 = np.asarray(bn.ints_to_limbs([b0 * dev.fpb.R % hb.P] * B), np.int32)
    got = dev.f12_mul_sparse013(da12, db0, _fp2_to_dev(b1, B),
                                _fp2_to_dev(b3, B))
    want = hb.f12_mul(a12, tuple(sparse_host))
    assert tuple(_dev_to_fp2(c) for c in got) == want


def test_miller_prefix_matches_host():
    """First 6 ate steps, device vs a host replica of the same loop."""
    rng = random.Random(9)
    steps = hb.ate_precompute(hb.G2_GEN)[:6]
    packed = dev.pack_steps(steps)

    pts = [hb.g1_mul(rng.randrange(2, hb.R), hb.G1_GEN) for _ in range(2)]
    xP = np.asarray(bn.ints_to_limbs([p[0] for p in pts]), np.int32)
    yP = np.asarray(bn.ints_to_limbs([p[1] for p in pts]), np.int32)
    got = dev.miller_loop(packed, xP, yP, eager=True)

    for b, p in enumerate(pts):
        f = hb.F12_ONE
        for flag, A, B in steps:
            if flag:
                f = hb.f12_sqr(f)
            f = hb.f12_mul(f, hb._sparse013(p[1], A, p[0], B))
        rinv = pow(dev.fpb.R, -1, hb.P)
        got_b = []
        for c0, c1 in got:
            v0 = bn.limbs_to_int(np.asarray(
                dev.fpb.canon(dev.fpb.reduce_to_kp(c0, 16, 2)))[:, b])
            v1 = bn.limbs_to_int(np.asarray(
                dev.fpb.canon(dev.fpb.reduce_to_kp(c1, 16, 2)))[:, b])
            got_b.append(((v0 % hb.P) * rinv % hb.P,
                          (v1 % hb.P) * rinv % hb.P))
        assert tuple(got_b) == f, f"element {b} diverged"


def test_miller_dual_prefix_matches_host():
    """Dual-loop prefix (shared squarings, two line sets) vs the host
    product of the two single-loop replicas."""
    rng = random.Random(21)
    # two distinct fixed Qs: g2 and a multiple of it (an issuer w shape)
    w = hb.g2_mul(rng.randrange(2, hb.R), hb.G2_GEN)
    steps_w = hb.ate_precompute(w)[:6]
    steps_g2 = hb.ate_precompute(hb.G2_GEN)[:6]
    packed_w = dev.pack_steps(steps_w)
    packed_g2 = dev.pack_steps(steps_g2)

    p1s = [hb.g1_mul(rng.randrange(2, hb.R), hb.G1_GEN) for _ in range(2)]
    p2s = [hb.g1_mul(rng.randrange(2, hb.R), hb.G1_GEN) for _ in range(2)]
    x1 = np.asarray(bn.ints_to_limbs([p[0] for p in p1s]), np.int32)
    y1 = np.asarray(bn.ints_to_limbs([p[1] for p in p1s]), np.int32)
    x2 = np.asarray(bn.ints_to_limbs([p[0] for p in p2s]), np.int32)
    y2 = np.asarray(bn.ints_to_limbs([p[1] for p in p2s]), np.int32)
    got = dev.miller_loop_dual(packed_w, packed_g2, x1, y1, x2, y2,
                               eager=True)

    rinv = pow(dev.fpb.R, -1, hb.P)
    for b in range(2):
        f = hb.F12_ONE
        for (fl, A1, B1), (_, A2, B2) in zip(steps_w, steps_g2):
            if fl:
                f = hb.f12_sqr(f)
            f = hb.f12_mul(f, hb._sparse013(p1s[b][1], A1, p1s[b][0], B1))
            f = hb.f12_mul(f, hb._sparse013(p2s[b][1], A2, p2s[b][0], B2))
        got_b = []
        for c0, c1 in got:
            v0 = bn.limbs_to_int(np.asarray(
                dev.fpb.canon(dev.fpb.reduce_to_kp(c0, 64, 2)))[:, b])
            v1 = bn.limbs_to_int(np.asarray(
                dev.fpb.canon(dev.fpb.reduce_to_kp(c1, 64, 2)))[:, b])
            got_b.append(((v0 % hb.P) * rinv % hb.P,
                          (v1 % hb.P) * rinv % hb.P))
        assert tuple(got_b) == f, f"element {b} diverged"
