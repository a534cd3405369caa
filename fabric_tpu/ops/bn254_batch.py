"""Batched BN254 ate pairing on TPU — the BASELINE config-4 kernel.

The host Idemix plane (fabric_tpu/idemix/bn254.py) verifies one
presentation in ~2 s because a python-int pairing runs at ~1.4
pairings/s.  This kernel evaluates e(P_i, Q) for a BATCH of G1 points
against a FIXED G2 point: the ate Miller loop's line functions depend
only on multiples of Q, so the host precomputes every step's sparse
line constants once (bn254.ate_precompute) and the device's per-element
work is pure Fp tower arithmetic on the flatfield layer —
(L, B) int32 limb arrays, Fp2 by Karatsuba, Fp12 as six Fp2
coefficients over w^6 = 1+i, one conditional-subtraction normalization
per Fp12 product (BN254's p is ~2^254 against R = 2^264, so lazily-
reduced values up to ~64p stay CIOS-safe).

Fixed-Q batching is exactly the Idemix verification shape: the pairing
checks of a presentation batch share the issuer's w / g2 on the G2 side
(credential.verify_presentation), mirroring how the P-256 fast path
keys on repeated public keys.

The final exponentiation is a plain square-and-multiply over
(p^12-1)/r (~2800 bits) — correct and compile-friendly; the known
10x-class refinements (easy/hard split with a tower inversion,
cyclotomic squarings, BN exponent chains) are documented headroom, not
yet built.

Differential testing: component ops + a Miller-loop prefix match the
host oracle on CPU (tests/test_bn254_batch.py); the full pairing has
no cross-check on the TPU in the tree (not measured on today's code).
"""

from __future__ import annotations

from typing import List, Tuple

import numpy as np
import jax.numpy as jnp

from fabric_tpu.idemix import bn254 as hb

from . import bignum as bn
from . import flatfield as ff
from .flatfield import FlatMod, L

fpb = FlatMod(hb.P, "bn254.p")

# Fp2 element: (c0, c1) of (L, B) int32 limb arrays, Montgomery form,
# lazily reduced.  Fp12: tuple of 6 Fp2.  Stable bound discipline:
# every Fp12-product component is normalized to < 8p (reduce_to_kp), so
# Karatsuba sums stay < 16p, products < 256 p^2, CIOS outputs < ~1.3p.

_RED_K = 96        # accumulated component bound before normalization
_TGT_K = 8


def f2_add(a, b):
    return (fpb.addl(a[0], b[0]), fpb.addl(a[1], b[1]))


def f2_sub(a, b, k: int):
    return (fpb.subl(a[0], b[0], k), fpb.subl(a[1], b[1], k))


def f2_neg(a, k: int):
    z = fpb.zero_bc(jnp.asarray(a[0]).shape[1:])
    return (fpb.subl(z, a[0], k), fpb.subl(z, a[1], k))


def f2_mul(a, b):
    """Karatsuba (i^2 = -1): inputs < 16p per component."""
    t0 = fpb.mul(a[0], b[0])
    t1 = fpb.mul(a[1], b[1])
    t2 = fpb.mul(fpb.addl(a[0], a[1]), fpb.addl(b[0], b[1]))
    re = fpb.subl(t0, t1, 2)                       # < ~4p
    im = fpb.subl(t2, fpb.addl(t0, t1), 4)         # < ~6p
    return (re, im)


def f2_scale(a, s):
    """Fp2 x Fp scalar (s an (L, B) Fp element)."""
    return (fpb.mul(a[0], s), fpb.mul(a[1], s))


def f2_mul_xi(a, k: int):
    """* XI = (1 + i):  (c0 - c1, c0 + c1)."""
    return (fpb.subl(a[0], a[1], k), fpb.addl(a[0], a[1]))


def f12_norm(x):
    return tuple((fpb.reduce_to_kp(c[0], _RED_K, _TGT_K),
                  fpb.reduce_to_kp(c[1], _RED_K, _TGT_K)) for c in x)


def f12_mul(a, b):
    """Schoolbook over w^6 = XI, then one normalization pass."""
    acc = [None] * 6
    for i in range(6):
        for j in range(6):
            prod = f2_mul(a[i], b[j])
            k = i + j
            if k >= 6:
                prod = f2_mul_xi(prod, 8)
                k -= 6
            acc[k] = prod if acc[k] is None else f2_add(acc[k], prod)
    return f12_norm(tuple(acc))


def f12_sqr(a):
    return f12_mul(a, a)


def f12_mul_sparse013(a, b0, b1, b3):
    """a (dense) * sparse line: components {0: Fp b0, 1: Fp2 b1,
    3: Fp2 b3} — 30 Fp muls instead of 108."""
    acc = [None] * 6
    for i in range(6):
        # j = 0 (Fp scalar)
        p0 = f2_scale(a[i], b0)
        acc[i] = p0 if acc[i] is None else f2_add(acc[i], p0)
        # j = 1
        k = i + 1
        p1 = f2_mul(a[i], b1)
        if k >= 6:
            p1 = f2_mul_xi(p1, 8)
            k -= 6
        acc[k] = p1 if acc[k] is None else f2_add(acc[k], p1)
        # j = 3
        k = i + 3
        p3 = f2_mul(a[i], b3)
        if k >= 6:
            p3 = f2_mul_xi(p3, 8)
            k -= 6
        acc[k] = p3 if acc[k] is None else f2_add(acc[k], p3)
    return f12_norm(tuple(acc))


def f12_select(cond, a, b):
    return tuple((fpb.select(cond, x[0], y[0]), fpb.select(cond, x[1], y[1]))
                 for x, y in zip(a, b))


def f12_one(bshape):
    one = fpb.one_bc(bshape)
    zero = fpb.zero_bc(bshape)
    return ((one, zero),) + (((zero, zero),) * 5)


# ---------------------------------------------------------------------------
# host-side constant packing
# ---------------------------------------------------------------------------

def _mont_limbs(x: int) -> np.ndarray:
    return bn.int_to_limbs((x % hb.P) * fpb.R % hb.P).astype(np.int32)


def pack_steps(steps) -> dict:
    """bn254.ate_precompute output -> stacked numpy constants:
    flags (S,), A/B as (S, 2, L) Montgomery limbs."""
    flags = np.asarray([s[0] for s in steps], dtype=np.int32)
    A = np.stack([[_mont_limbs(s[1][0]), _mont_limbs(s[1][1])]
                  for s in steps])
    B = np.stack([[_mont_limbs(s[2][0]), _mont_limbs(s[2][1])]
                  for s in steps])
    return {"flags": flags, "A": A, "B": B}


_EXP = (hb.P ** 12 - 1) // hb.R
_EXP_BITS = np.asarray([int(b) for b in bin(_EXP)[2:]], dtype=np.int32)

# |u| for the BN parameter (X_BN < 0), MSB-first bits after the leading 1
_ABS_U_BITS = np.asarray([int(b) for b in bin(-hb.X_BN)[3:]],
                         dtype=np.int32)

# ---------------------------------------------------------------------------
# the batched pairing
# ---------------------------------------------------------------------------

def miller_loop(packed, xP_l, yP_l, n_steps: int = None, eager: bool = None):
    """f_{lambda,Q}(P) over canonical G1 limb inputs (L, B).

    n_steps limits the loop (differential prefix tests); eager drives a
    python loop for CPU testing instead of lax.scan.
    """
    from jax import lax

    eager = ff._is_concrete(xP_l) if eager is None else eager
    bshape = jnp.asarray(xP_l).shape[1:]
    xP = fpb.to_mont(xP_l)
    yP = fpb.to_mont(yP_l)

    flags = jnp.asarray(packed["flags"])
    A = jnp.asarray(packed["A"])          # (S, 2, L)
    B = jnp.asarray(packed["B"])
    if n_steps is not None:
        flags, A, B = flags[:n_steps], A[:n_steps], B[:n_steps]

    def body(f, xs):
        flag, a_c, b_c = xs
        fsq = f12_sqr(f)
        f = f12_select(jnp.broadcast_to(flag != 0, bshape), fsq, f)
        a2 = (jnp.broadcast_to(a_c[0][:, None], (L,) + tuple(bshape)),
              jnp.broadcast_to(a_c[1][:, None], (L,) + tuple(bshape)))
        b2 = (jnp.broadcast_to(b_c[0][:, None], (L,) + tuple(bshape)),
              jnp.broadcast_to(b_c[1][:, None], (L,) + tuple(bshape)))
        line1 = f2_scale(a2, xP)          # A * xP   (component 1)
        f = f12_mul_sparse013(f, yP, line1, b2)
        return f, None

    f = f12_one(bshape)
    if eager:
        for i in range(int(flags.shape[0])):
            f, _ = body(f, (flags[i], (A[i, 0], A[i, 1]),
                            (B[i, 0], B[i, 1])))
        return f
    f, _ = lax.scan(
        lambda carry, xs: body(carry, (xs[0], (xs[1][0], xs[1][1]),
                                       (xs[2][0], xs[2][1]))),
        f, (flags, A, B))
    return f


def final_exp(f, eager: bool = None):
    """f ^ ((p^12 - 1) / r) by square-and-multiply (documented headroom:
    easy/hard split + cyclotomic arithmetic)."""
    from jax import lax

    eager = ff._is_concrete(f[0][0]) if eager is None else eager
    bshape = jnp.asarray(f[0][0]).shape[1:]
    base = f
    acc = f  # MSB of the exponent is 1

    bits = jnp.asarray(_EXP_BITS[1:])

    def body(acc, bit):
        acc = f12_sqr(acc)
        mul = f12_mul(acc, base)
        return f12_select(jnp.broadcast_to(bit != 0, bshape), mul, acc), None

    if eager:
        for i in range(int(bits.shape[0])):
            acc, _ = body(acc, bits[i])
        return acc
    acc, _ = lax.scan(body, acc, bits)
    return acc


def miller_loop_dual(packed1, packed2, x1_l, y1_l, x2_l, y2_l,
                     n_steps: int = None, eager: bool = None):
    """Combined Miller loop for TWO fixed-Q pairings with SHARED
    squarings: f_{lam,Q1}(P1) * f_{lam,Q2}(P2).

    Both precomputes come from the same loop scalar (bn254.ATE_LAMBDA),
    so their step sequences align 1:1 — each step squares f once (when
    flag=1) and multiplies BOTH sparse lines in.  This halves the f12
    squaring chain vs two separate loops and, with the single final
    exponentiation of pairing_check_batch, makes the product-equals-one
    form of an equality check ~2x cheaper than two full pairings.
    """
    from jax import lax

    eager = ff._is_concrete(x1_l) if eager is None else eager
    bshape = jnp.asarray(x1_l).shape[1:]
    xs_m = [fpb.to_mont(v) for v in (x1_l, y1_l, x2_l, y2_l)]
    x1m, y1m, x2m, y2m = xs_m

    flags = jnp.asarray(packed1["flags"])
    A1 = jnp.asarray(packed1["A"])
    B1 = jnp.asarray(packed1["B"])
    A2 = jnp.asarray(packed2["A"])
    B2 = jnp.asarray(packed2["B"])
    assert packed1["flags"].shape == packed2["flags"].shape, \
        "dual loop requires aligned step sequences"
    if n_steps is not None:
        flags, A1, B1, A2, B2 = (v[:n_steps]
                                 for v in (flags, A1, B1, A2, B2))

    def bcast(c):
        return (jnp.broadcast_to(c[0][:, None], (L,) + tuple(bshape)),
                jnp.broadcast_to(c[1][:, None], (L,) + tuple(bshape)))

    def body(f, xs):
        flag, a1, b1, a2, b2 = xs
        fsq = f12_sqr(f)
        f = f12_select(jnp.broadcast_to(flag != 0, bshape), fsq, f)
        f = f12_mul_sparse013(f, y1m, f2_scale(bcast(a1), x1m), bcast(b1))
        f = f12_mul_sparse013(f, y2m, f2_scale(bcast(a2), x2m), bcast(b2))
        return f, None

    f = f12_one(bshape)
    if eager:
        for i in range(int(flags.shape[0])):
            f, _ = body(f, (flags[i], (A1[i, 0], A1[i, 1]),
                            (B1[i, 0], B1[i, 1]),
                            (A2[i, 0], A2[i, 1]),
                            (B2[i, 0], B2[i, 1])))
        return f
    f, _ = lax.scan(
        lambda carry, xs: body(carry, (
            xs[0], (xs[1][0], xs[1][1]), (xs[2][0], xs[2][1]),
            (xs[3][0], xs[3][1]), (xs[4][0], xs[4][1]))),
        f, (flags, A1, B1, A2, B2))
    return f


def pairing_check_batch(packed1, packed2, x1_l, y1_l, x2_l, y2_l):
    """Batched equality check e(P1_i, Q1) == e(-P2_i, Q2)^-1, i.e.
    e(P1_i, Q1) * e(P2_i, Q2) == 1 — callers pass P2 = -Abar to check
    e(A', w) == e(Abar, g2), the idemix presentation pairing equation
    (fabric_tpu/idemix/credential.py verify_presentation check (1);
    reference: /root/reference/idemix/signature.go:230 Ver).

    Inputs are canonical (L, B) limb G1 coordinates; returns (B,) bool.
    On-curve membership is the CALLER's gate (idemix verify rejects
    off-curve points before collecting — soundness requires it).
    """
    f = miller_loop_dual(packed1, packed2, x1_l, y1_l, x2_l, y2_l)
    f = final_exp(f)
    one = fpb.one_bc(jnp.asarray(x1_l).shape[1:])
    ok = fpb.eq_k(f[0][0], one, 2, 18) & fpb.is_zero_k(f[0][1], 16)
    for c0, c1 in f[1:]:
        ok = ok & fpb.is_zero_k(c0, 16) & fpb.is_zero_k(c1, 16)
    return ok


def pairing_batch(packed, xP_l, yP_l):
    """Reduced ate pairing e(P_i, Q) -> Fp12 of canonical (L, B) limb
    arrays (matching the host oracle bit-for-bit after from_mont)."""
    f = miller_loop(packed, xP_l, yP_l)
    f = final_exp(f)
    return tuple((fpb.from_mont(fpb.reduce_to_kp(c[0], 16, 2)),
                  fpb.from_mont(fpb.reduce_to_kp(c[1], 16, 2)))
                 for c in f)


def to_host_ints(f12_limbs, b: int) -> tuple:
    """Canonical device output -> host Fp12 tuple for element b."""
    out = []
    for c0, c1 in f12_limbs:
        a0 = bn.limbs_to_int(np.asarray(c0)[:, b])
        a1 = bn.limbs_to_int(np.asarray(c1)[:, b])
        out.append((a0 % hb.P, a1 % hb.P))
    return tuple(out)
