"""Windowed ECDSA-P256 verify on the flat field layer (Pallas & XLA).

Round-2 rework of the hot kernel per VERDICT.md #1: replaces the 1-bit
Shamir ladder (256 complete adds) of ops/weierstrass.py with

  u1*G:  a fixed-base comb — COMB_WINDOWS windows of COMB_W bits over a
         host-precomputed table of affine points (k * 2^(COMB_W*j) * G),
         selected per batch
         element by an exact one-hot f32 matmul (MXU; limbs <= 2^12 are
         exact in f32) and accumulated with COMB_WINDOWS mixed (Z2=1) adds;
  u2*Q:  a 4-bit unsigned windowed ladder — a per-batch 16-entry Jacobian
         table (7 dbl + 7 add), then 65 windows of (4 dbl + 1 add) over
         the MSB-first digits of u2;

~4.4k field muls per verify vs ~8.6k for the round-1 ladder, with every
field op scan-free (ops/flatfield.py) so the whole verify lowers into one
flat XLA program (a fused Pallas variant was tried through round 4
and removed in round 5; there is no Pallas in fabric_tpu/).

Degenerate-case handling (adversarial completeness):
  * ladder adds: acc = v*Q with v = 16*prefix(u2) in [16, n); the addend is
    d*Q, d in [1,15].  v == d is impossible (v >= 16); v == n - d (i.e.
    P == -Q -> infinity) IS reachable for digits d with n =- d mod 16, so
    adds patch h==0 -> infinity; v == n + d is unreachable (v < n).  The
    P == Q (doubling) case therefore cannot occur for an on-curve Q of
    order n (P-256 has cofactor 1: every finite point has order n); for
    off-curve/garbage Q the formula may produce garbage, which is gated by
    the caller's on-curve verdict bit.  Infinity operands are tracked by an
    explicit flag, not by Z == 0 tests.
  * comb adds: acc = w*G with w < 2^(Wk) and addend d*2^(Wk)*G; w == +-d*2^(Wk)
    mod n requires u1 == n, excluded since u1 < n.  Only d == 0 / acc == inf
    need patching.
  * the final comb+ladder combine uses a fully complete add (P == +-Q is
    reachable there when u1*G == +-u2*Q, craftable by a key owner).

Semantics target (bit-identical accept/reject): the reference's verifyECDSA
/root/reference/bccsp/sw/ecdsa.go:41-58 with mandatory low-S
(bccsp/utils/ecdsa.go:84), digest-only inputs (msp/identities.go:178).
"""

from __future__ import annotations

import numpy as np
import jax.numpy as jnp

from . import bignum as bn
from . import flatfield as ff
from .flatfield import FlatMod, L, LB, MASK

# Curve constants (SEC2 secp256r1)
P = 0xFFFFFFFF00000001000000000000000000000000FFFFFFFFFFFFFFFFFFFFFFFF
N = 0xFFFFFFFF00000000FFFFFFFFFFFFFFFFBCE6FAADA7179E84F3B9CAC2FC632551
A = P - 3
B = 0x5AC635D8AA3A93E7B3EBBD55769886BC651D06B0CC53B0F63BCE3C3E27D2604B
GX = 0x6B17D1F2E12C4247F8BCE6E563A440F277037D812DEB33A0F4A13945D898C296
GY = 0x4FE342E2FE1A7F9B8EE7EB4A7C0F9E162BCE33576B315ECECBB6406837BF51F5
HALF_N = (N - 1) // 2

# 8-bit comb windows: 32 windows x 256 entries.  Vs the round-2..4
# 6-bit comb (43 windows), each verify saves 22 of its 86 mixed adds
# (~25% of the field muls); the wider one-hot lookup matmul is MXU-cheap
# and still exact (table limbs < 2^12, exact in f32).  Table cost:
# (8192, 44) f32 = 1.44 MB/key in the device bank, ~3x the host build
# time — amortized by residency (ops/device_bank.py).
COMB_W = 8
COMB_WINDOWS = 32            # 32*8 = 256 bits
COMB_ENTRIES = 1 << COMB_W
LADDER_W = 4
LADDER_WINDOWS = 64          # u2 < n < 2^256

fp = FlatMod(P, "p256.p")
fn = FlatMod(N, "p256.n")

_B_M = fp.const_mont(B)
_A_M = fp.const_mont(A)


# ---------------------------------------------------------------------------
# Host-side affine arithmetic + comb table (pure python ints)
# ---------------------------------------------------------------------------

def _aff_add(p1, p2):
    if p1 is None:
        return p2
    if p2 is None:
        return p1
    x1, y1 = p1
    x2, y2 = p2
    if x1 == x2:
        if (y1 + y2) % P == 0:
            return None
        lam = (3 * x1 * x1 + A) * pow(2 * y1, -1, P) % P
    else:
        lam = (y2 - y1) * pow(x2 - x1, -1, P) % P
    x3 = (lam * lam - x1 - x2) % P
    return x3, (lam * (x1 - x3) - y1) % P


def _aff_mul(k, pt):
    acc = None
    while k:
        if k & 1:
            acc = _aff_add(acc, pt)
        pt = _aff_add(pt, pt)
        k >>= 1
    return acc


_COMB_CACHE = {}


def comb_table_f32() -> np.ndarray:
    """(COMB_WINDOWS * COMB_ENTRIES, 2 * L) f32: rows of Montgomery-form
    affine limbs [x limbs || y limbs] for k * 2^(COMB_W*j) * G; row
    j*COMB_ENTRIES+k.  k=0 rows are zero (patched at lookup time via the
    digit==0 select).

    Exactness: limbs < 2^12 are exactly representable in f32, and a one-hot
    matmul sums exactly one row — no rounding anywhere.
    """
    if "t" in _COMB_CACHE:
        return _COMB_CACHE["t"]
    from . import p256_tables
    _COMB_CACHE["t"] = p256_tables.comb_table_for_point(GX, GY)
    return _COMB_CACHE["t"]


# ---------------------------------------------------------------------------
# Jacobian point ops (lazy-reduction flat field, explicit infinity flags)
# ---------------------------------------------------------------------------
# A point is (X, Y, Z, inf) with inf a (B,) int32 flag; X,Y,Z Montgomery-
# form LAZILY-REDUCED limbs with the static per-coordinate invariant
#
#     value(X) < 11p,  value(Y) < 4p,  value(Z) < 6p
#
# maintained by every op below with ZERO conditional subtractions (the
# round-2 formulas paid one ~70-op Kogge-Stone cond-sub per mod_add /
# mod_sub / mul_small — about half the cost of a dbl again on top of its
# muls).  Safety rests on two CIOS facts (flatfield mul): operands may
# carry values up to ~16p, and a product a*b <= 256*p^2 emerges < 2p
# (out < p + ab/R with p < R/256).  Each op's bound is derived in a
# trailing comment: "# <k.kp" means value < k.k * p at that point.

def dbl(Pt):
    """Jacobian doubling (dbl-2001-b shape, a = -3), lazy reduction.
    Input invariant (11p, 4p, 6p) -> output (10.2p, 3.4p, 4.5p).  8 muls,
    no cond-subs.  Doubling a 2-torsion point can't arise on P-256 (odd
    order); a Z3=0 output would still be safe downstream."""
    X, Y, Z, inf = Pt
    delta = fp.sqr(Z)                    # 36p^2   -> <1.15p
    gamma = fp.sqr(Y)                    # 16p^2   -> <1.07p
    beta = fp.mul(X, gamma)              # 11.8p^2 -> <1.05p
    t1 = fp.subl(X, delta, 2)            # <13p
    t2 = fp.addl(X, delta)               # <12.2p
    alpha = fp.smalll(fp.mul(t1, t2), 3)  # 159p^2 -> <1.63p; x3 -> <4.9p
    X3 = fp.subl(fp.sqr(alpha), fp.smalll(beta, 8), 9)   # <1.1p + 9p = 10.1p
    w = fp.subl(fp.smalll(beta, 4), X3, 11)              # <15.2p
    # 8*gamma^2 as a MUL output (not a post-scale) keeps Y3's bound small
    m3 = fp.mul(gamma, fp.smalll(gamma, 8))              # 9.2p^2 -> <1.04p
    Y3 = fp.subl(fp.mul(alpha, w), m3, 2)                # <1.3p + 2p = 3.3p
    s = fp.sqr(fp.addl(Y, Z))                            # 100p^2 -> <1.4p
    Z3 = fp.subl(s, fp.addl(gamma, delta), 3)            # <4.4p
    return X3, Y3, Z3, inf


def add_nodbl(Pt, Qt):
    """Complete-except-doubling Jacobian add (see module docstring for the
    reachability argument).  Patches: P inf, Q inf, P == -Q -> infinity.
    P == Q would produce Z3 = 0 (treated as infinity downstream) — only
    possible for inputs outside the guaranteed domain (garbage Q, gated).
    Lazy bounds: inputs (11p, 4p, 6p) -> outputs (5.1p, 3.1p, 1.1p)."""
    X1, Y1, Z1, inf1 = Pt
    X2, Y2, Z2, inf2 = Qt
    z1z1 = fp.sqr(Z1)                    # <1.15p
    z2z2 = fp.sqr(Z2)                    # <1.15p
    u1 = fp.mul(X1, z2z2)                # 12.7p^2 -> <1.05p
    u2 = fp.mul(X2, z1z1)                # <1.05p
    s1 = fp.mul(Y1, fp.mul(Z2, z2z2))    # 6.9p^2 -> <1.03p; then <1.02p
    s2 = fp.mul(Y2, fp.mul(Z1, z1z1))    # <1.02p
    h = fp.subl(u2, u1, 2)               # <3.05p
    r = fp.subl(s2, s1, 2)               # <3.04p
    h2 = fp.sqr(h)                       # 9.3p^2 -> <1.04p
    h3 = fp.mul(h, h2)                   # <1.02p
    u1h2 = fp.mul(u1, h2)                # <1.01p
    X3 = fp.subl(fp.sqr(r),
                 fp.addl(h3, fp.smalll(u1h2, 2)), 4)     # <1.04p + 4p = 5.04p
    w = fp.subl(u1h2, X3, 6)                             # <7.05p
    Y3 = fp.subl(fp.mul(r, w), fp.mul(s1, h3), 2)        # 21.4p^2 -> <3.1p
    Z3 = fp.mul(fp.mul(Z1, Z2), h)       # 36p^2 -> <1.15p; 3.5p^2 -> <1.02p

    # h == 0 means P == -Q (cancel) for in-domain inputs; P == Q is
    # unreachable (module docstring) and maps to infinity too, which is
    # wrong only for garbage Q already gated by the on-curve bit.
    h_zero = fp.is_zero_k(h, 4)
    i1b, i2b = inf1 != 0, inf2 != 0
    cancel = h_zero & ~i1b & ~i2b
    inf3 = (cancel | (i1b & i2b)).astype(jnp.int32)
    sel = fp.select
    X3 = sel(i1b, X2, sel(i2b, X1, X3))
    Y3 = sel(i1b, Y2, sel(i2b, Y1, Y3))
    Z3 = sel(i1b, Z2, sel(i2b, Z1, Z3))
    return X3, Y3, Z3, inf3


def add_complete(Pt, Qt):
    """Fully complete add: also handles P == Q via an embedded doubling.
    Same lazy bounds as add_nodbl; output X bound is max(5.1p, dbl's
    10.2p, the 11p inputs) = 11p."""
    X1, Y1, Z1, inf1 = Pt
    X2, Y2, Z2, inf2 = Qt
    z1z1 = fp.sqr(Z1)
    z2z2 = fp.sqr(Z2)
    u1 = fp.mul(X1, z2z2)
    u2 = fp.mul(X2, z1z1)
    s1 = fp.mul(Y1, fp.mul(Z2, z2z2))
    s2 = fp.mul(Y2, fp.mul(Z1, z1z1))
    h = fp.subl(u2, u1, 2)               # <3.05p
    r = fp.subl(s2, s1, 2)               # <3.04p
    h2 = fp.sqr(h)
    h3 = fp.mul(h, h2)
    u1h2 = fp.mul(u1, h2)
    X3 = fp.subl(fp.sqr(r),
                 fp.addl(h3, fp.smalll(u1h2, 2)), 4)
    w = fp.subl(u1h2, X3, 6)
    Y3 = fp.subl(fp.mul(r, w), fp.mul(s1, h3), 2)
    Z3 = fp.mul(fp.mul(Z1, Z2), h)

    h_zero = fp.is_zero_k(h, 4)
    r_zero = fp.is_zero_k(r, 4)
    Dx, Dy, Dz, _ = dbl(Qt)
    i1b, i2b = inf1 != 0, inf2 != 0
    is_dbl = h_zero & r_zero & ~i1b & ~i2b
    cancel = h_zero & ~r_zero & ~i1b & ~i2b
    sel = fp.select
    X3 = sel(is_dbl, Dx, X3)
    Y3 = sel(is_dbl, Dy, Y3)
    Z3 = sel(is_dbl, Dz, Z3)
    inf3 = (cancel | (i1b & i2b)).astype(jnp.int32)
    X3 = sel(i1b, X2, sel(i2b, X1, X3))
    Y3 = sel(i1b, Y2, sel(i2b, Y1, Y3))
    Z3 = sel(i1b, Z2, sel(i2b, Z1, Z3))
    return X3, Y3, Z3, inf3


def add_mixed(Pt, x2, y2, q_absent):
    """Mixed add (Z2 = 1) for the comb: addend is an affine table entry
    with canonical (< p) coordinates.

    q_absent: (B,) bool — digit == 0, addend is the identity.
    No P == +-Q patches (unreachable; module docstring).  11 muls.
    Lazy bounds: input (11p, 4p, 6p) -> output (5.2p, 3.2p, 1.3p)."""
    X1, Y1, Z1, inf1 = Pt
    z1z1 = fp.sqr(Z1)                    # <1.15p
    u2 = fp.mul(x2, z1z1)                # <1.01p
    s2 = fp.mul(y2, fp.mul(Z1, z1z1))    # <1.01p
    h = fp.subl(u2, X1, 11)              # <12.01p
    r = fp.subl(s2, Y1, 4)               # <5.01p
    h2 = fp.sqr(h)                       # 144p^2 -> <1.57p
    h3 = fp.mul(h, h2)                   # 18.9p^2 -> <1.08p
    u1h2 = fp.mul(X1, h2)                # 17.3p^2 -> <1.07p
    X3 = fp.subl(fp.sqr(r),
                 fp.addl(h3, fp.smalll(u1h2, 2)), 4)     # <1.1p + 4p = 5.1p
    w = fp.subl(u1h2, X3, 6)                             # <7.17p
    Y3 = fp.subl(fp.mul(r, w), fp.mul(Y1, h3), 2)        # 35.9p^2 -> <3.2p
    Z3 = fp.mul(Z1, h)                   # 72p^2 -> <1.3p
    one = fp.one_bc(X1.shape[1:])
    sel = fp.select
    i1b = inf1 != 0
    # P infinite -> take the affine addend; digit 0 -> keep P unchanged.
    X3 = sel(i1b, x2, X3)
    Y3 = sel(i1b, y2, Y3)
    Z3 = sel(i1b, one, Z3)
    X3 = sel(q_absent, X1, X3)
    Y3 = sel(q_absent, Y1, Y3)
    Z3 = sel(q_absent, Z1, Z3)
    inf3 = (i1b & q_absent).astype(jnp.int32)
    return X3, Y3, Z3, inf3


def select_point(cond, Pt, Qt):
    sel = fp.select
    return (sel(cond, Pt[0], Qt[0]), sel(cond, Pt[1], Qt[1]),
            sel(cond, Pt[2], Qt[2]), jnp.where(cond, Pt[3], Qt[3]))


def infinity(bshape):
    # the inf flag is int32 0/1, not bool: Mosaic cannot select i1 vectors
    one = fp.one_bc(bshape)
    return one, one, fp.zero_bc(bshape), jnp.ones(bshape, jnp.int32)


def _infinity_like(bshape, like):
    """infinity() made data-dependent on `like` ((L, B) limbs) by adding
    zeros derived from it: under shard_map, scan carries must share the
    body output's varying-axis type, which constants lack."""
    z = like[0] * 0
    X, Y, Z, inf = infinity(bshape)
    return X + z[None], Y + z[None], Z + z[None], inf + z


# ---------------------------------------------------------------------------
# Digit extraction (flat)
# ---------------------------------------------------------------------------

def ladder_digits(u2_can):
    """(L, B) canonical limbs -> list of LADDER_WINDOWS (B,) int32 digits,
    MSB-first.  4-bit windows align with 12-bit limbs (3 per limb)."""
    digits = []
    for w in range(LADDER_WINDOWS):
        limb = w // 3
        shift = (w % 3) * 4
        digits.append((u2_can[limb] >> shift) & 0xF)
    return digits[::-1]


def comb_digits(u1_can):
    """(L, B) canonical -> list of COMB_WINDOWS (B,) int32 COMB_W-bit
    digits, LSB-first (window j covers bits [W*j, W*j+W))."""
    out = []
    for j in range(COMB_WINDOWS):
        bitpos = COMB_W * j
        limb = bitpos // LB
        off = bitpos % LB
        v = u1_can[limb] >> off
        if off > LB - COMB_W and limb + 1 < L:
            v = v | (u1_can[limb + 1] << (LB - off))
        out.append(v & (COMB_ENTRIES - 1))
    return out


# ---------------------------------------------------------------------------
# Fixed-base comb accumulation (shared by the G half of every verify and
# by the per-key fast path in ops/p256_fixed.py)
# ---------------------------------------------------------------------------

def comb_accumulate(tab_f32, u_can, bshape):
    """u * T for a canonical scalar u (< n, (L, B) limbs) against a comb
    table (COMB_WINDOWS*COMB_ENTRIES, 2L) whose base point T has order n.

    Table lookups are exact one-hot f32 matmuls (MXU; limbs <= 2^12 are
    exactly representable, and one-hot sums select a single row).  Runs
    as a lax.scan when traced; eagerly (python loop over per-primitive
    jits) on concrete inputs — XLA:CPU cannot compile the big scan bodies
    in reasonable time.
    """
    from jax import lax as _lax
    eager = ff._is_concrete(u_can)
    cd = jnp.stack(comb_digits(u_can))                       # (W, B)
    tab = jnp.asarray(tab_f32).reshape(COMB_WINDOWS, COMB_ENTRIES, 2 * L)

    if eager:
        def comb_body(acc, d, rows):
            iota = jnp.arange(COMB_ENTRIES, dtype=jnp.int32).reshape(
                COMB_ENTRIES, *([1] * len(bshape)))
            onehot = (iota == d[None]).astype(jnp.float32)
            # HIGHEST: TPU f32 matmuls default to bf16 passes, which
            # cannot represent 12-bit limbs exactly
            sel = jnp.tensordot(
                rows.T, onehot, axes=1,
                precision=_lax.Precision.HIGHEST).astype(jnp.int32)
            return add_mixed(acc, sel[:L], sel[L:], d == 0)

        acc = infinity(bshape)
        for j in range(COMB_WINDOWS):
            acc = comb_body(acc, cd[j], tab[j])
        return acc

    # Traced: ALL window lookups ride ONE batched matmul up front (43
    # small per-window matmuls inside the scan measured ~26 ms/comb at
    # B=16k — half the fixed-path step — the batched form keeps the MXU
    # busy instead of paying 43 tiny dispatches).
    iota = jnp.arange(COMB_ENTRIES, dtype=jnp.int32).reshape(1, COMB_ENTRIES, 1)
    onehot = (iota == cd[:, None, :]).astype(jnp.float32)    # (W, E, B)
    sel = _lax.dot_general(
        tab, onehot,
        dimension_numbers=(((1,), (1,)), ((0,), (0,))),
        precision=_lax.Precision.HIGHEST).astype(jnp.int32)  # (W, 2L, B)

    def body(acc, xs):
        s, d = xs
        return add_mixed(acc, s[:L], s[L:], d == 0), None

    acc, _ = _lax.scan(body, _infinity_like(bshape, u_can), (sel, cd))
    return acc


def comb_accumulate_rows(bank_f32, row_key, u_can, bshape):
    """Row-grouped multikey comb: u * T[row_key[r]] over a (R, C) grid.

    The round-3 multikey kernel (comb_accumulate_multikey) one-hots over
    the JOINT (key, digit) index, so its lookup matmul cost scales with
    NK — the provider capped NK at 4 and spilled real networks' dozens
    of endorser/client keys to the generic ladder (VERDICT r03 weak #1).
    This kernel removes the cap: the host packs signatures key-MAJOR
    into rows of C lanes where every element of row r shares one key,
    the per-row tables are gathered ONCE per dispatch (R coalesced
    table-row reads — nothing like the catastrophic per-element gather),
    and the digit lookup is a batched one-hot matmul whose cost per
    element is IDENTICAL to the single-key comb, independent of how
    many distinct keys the dispatch carries.

    bank_f32: (K, COMB_WINDOWS*COMB_ENTRIES, 2L) stacked per-key comb
    tables (KeyTableCache layout); row_key: (R,) int32 into the bank;
    u_can: (L, R, C) canonical scalars; bshape == (R, C).
    """
    from jax import lax as _lax
    eager = ff._is_concrete(u_can)
    R, C = bshape
    bank = jnp.asarray(bank_f32, jnp.float32)
    rows = bank[row_key].reshape(R, COMB_WINDOWS, COMB_ENTRIES, 2 * L)
    rows = rows.transpose(1, 0, 3, 2)                    # (W, R, 2L, E)
    cd = jnp.stack(comb_digits(u_can))                   # (W, R, C)
    iota = jnp.arange(COMB_ENTRIES, dtype=jnp.int32).reshape(
        1, 1, COMB_ENTRIES, 1)
    if eager:
        acc = infinity(bshape)
        for j in range(COMB_WINDOWS):
            onehot = (iota[0] == cd[j][:, None, :]).astype(jnp.float32)
            sel = _lax.dot_general(
                rows[j], onehot,
                dimension_numbers=(((2,), (1,)), ((0,), (0,))),
                precision=_lax.Precision.HIGHEST).astype(jnp.int32)
            sel = sel.transpose(1, 0, 2)                 # (2L, R, C)
            acc = add_mixed(acc, sel[:L], sel[L:], cd[j] == 0)
        return acc

    onehot = (iota == cd[:, :, None, :]).astype(jnp.float32)  # (W, R, E, C)
    sel = _lax.dot_general(
        rows, onehot,
        dimension_numbers=(((3,), (2,)), ((0, 1), (0, 1))),
        precision=_lax.Precision.HIGHEST)                # (W, R, 2L, C)
    sel = sel.transpose(0, 2, 1, 3).astype(jnp.int32)    # (W, 2L, R, C)

    def body(acc, xs):
        s, d = xs
        return add_mixed(acc, s[:L], s[L:], d == 0), None

    acc, _ = _lax.scan(body, _infinity_like(bshape, u_can), (sel, cd))
    return acc


# ---------------------------------------------------------------------------
# The verify body (flat jnp; runs under XLA or inside a Pallas kernel)
# ---------------------------------------------------------------------------

def verify_body(qx_l, qy_l, r_l, s_l, e_l, comb_tab_f32, require_low_s=True):
    """Batched ECDSA-P256 verify over canonical integer limbs (L, B).

    comb_tab_f32: (COMB_WINDOWS*COMB_ENTRIES, 2L) f32 table.
    Returns (B,) bool.
    """
    bshape = qx_l.shape[1:]

    # --- range/key checks (reference: ecdsa.go:44-53, utils/ecdsa.go:84) ---
    r_ok = ff.lt_const(r_l, N) & ~ff.is_zero_limbs(r_l)
    s_ok = ff.lt_const(s_l, N) & ~ff.is_zero_limbs(s_l)
    if require_low_s:
        s_ok = s_ok & ff.lt_const(s_l, HALF_N + 1)
    q_ok = ff.lt_const(qx_l, P) & ff.lt_const(qy_l, P)

    qx_m = fp.to_mont(qx_l)
    qy_m = fp.to_mont(qy_l)
    # on-curve: y^2 == x^3 - 3x + b  (lazy: lhs <1.01p, rhs <2.01p)
    lhs = fp.sqr(qy_m)
    rhs = fp.addl(
        fp.mul(fp.addl(fp.sqr(qx_m), ff.const_col(_A_M, 2)), qx_m),
        ff.const_col(_B_M, 2))
    q_ok = q_ok & fp.eq_k(lhs, rhs, 3, 5)

    # --- u1 = e/s, u2 = r/s mod n ---
    s_mn = fn.to_mont(s_l)
    e_mn = fn.to_mont(e_l)
    r_mn = fn.to_mont(r_l)
    w = _inv_n(s_mn, bshape)
    u1 = fn.from_mont(fn.mul(e_mn, w))
    u2 = fn.from_mont(fn.mul(r_mn, w))

    # --- u1*G via comb (lax.scan when traced, python loop when eager) ---
    from jax import lax as _lax
    eager = ff._is_concrete(u1)
    acc_g = comb_accumulate(comb_tab_f32, u1, bshape)

    # --- u2*Q via 4-bit windowed ladder (lax.scan over 64 windows) ---
    # The 16-entry table is built as 2Q = dbl(Q), then a scan of kQ =
    # (k-1)Q + Q for k = 3..15 — the k-1 == +-1 doubling/cancel cases are
    # unreachable there (k-1 >= 2) for an order-n Q, and the scan keeps
    # the traced program small (13 adds compile as ONE body; the round-2
    # unrolled dbl/add tree was ~20k extra HLO ops of pure compile time).
    Q1 = (qx_m, qy_m, fp.one_bc(bshape), jnp.zeros(bshape, jnp.int32))
    T0 = infinity(bshape) if eager else _infinity_like(bshape, qx_m)
    T2 = dbl(Q1)
    if eager:
        T = [T0, Q1, T2]
        for k in range(3, 16):
            T.append(add_nodbl(T[k - 1], Q1))
        TX = jnp.stack([t[0] for t in T])
        TY = jnp.stack([t[1] for t in T])
        TZ = jnp.stack([t[2] for t in T])
        TI = jnp.stack([t[3] for t in T])
    else:
        def tab_body(acc, _):
            nxt = add_nodbl(acc, Q1)
            return nxt, nxt

        _, rest = _lax.scan(tab_body, T2, None, length=13)
        TX, TY, TZ, TI = (
            jnp.concatenate([jnp.stack([a, b, c]), r], axis=0)
            for a, b, c, r in zip(T0, Q1, T2, rest))

    ld = jnp.stack(ladder_digits(u2))                        # (64, B) MSB first

    def ladder_body(acc, d):
        if eager:
            for _ in range(LADDER_W):
                acc = dbl(acc)
        else:
            # fori_loop: the dbl body compiles once, not LADDER_W times
            acc = _lax.fori_loop(0, LADDER_W, lambda _, a: dbl(a), acc)
        ent = (TX[0], TY[0], TZ[0], TI[0])
        for k in range(1, 16):
            ent = select_point(d == k, (TX[k], TY[k], TZ[k], TI[k]), ent)
        return add_nodbl(acc, ent), None

    # first window: no doublings needed (acc starts at infinity, and
    # dbl(infinity) stays infinity anyway — uniform body is correct)
    if eager:
        acc = infinity(bshape)
        for i in range(LADDER_WINDOWS):
            acc, _ = ladder_body(acc, ld[i])
    else:
        acc, _ = _lax.scan(ladder_body, _infinity_like(bshape, u2), ld)
    # --- combine (fully complete: u1*G == +-u2*Q is reachable) ---
    X, Y, Z, inf = add_complete(acc_g, acc)

    nonzero = (inf == 0) & ~fp.is_zero_k(Z, 6)

    # --- projective x-coordinate check: X == (r + k*n)*Z^2, k in {0,1} ---
    # X carries the lazy 11p bound; the mul results are < 2p.
    z2 = fp.sqr(Z)
    r_mp = fp.to_mont(r_l)
    eq1 = fp.eq_k(X, fp.mul(r_mp, z2), 2, 13)
    rn_l = ff.split_rounds(r_l + ff.const_col(bn.int_to_limbs(N),
                                              len(bshape) + 1), 3)
    rn_lt_p = ff.lt_const(rn_l, P)
    eq2 = rn_lt_p & fp.eq_k(X, fp.mul(fp.to_mont(rn_l), z2), 2, 13)

    return r_ok & s_ok & q_ok & nonzero & (eq1 | eq2)


def _inv_n(s_mn, bshape):
    """w = s^-1 mod n on Montgomery forms.

    Traced 1-D batches use the Montgomery-trick product tree (~3 muls per
    element instead of a ~330-mul Fermat ladder); zero elements (s == 0
    mod n — always rejected by the range checks) are pre-selected to 1 so
    they cannot poison the tree, their garbage inverse being gated by
    s_ok.  2-D (row-grid) batches flatten through the same tree.
    Eager/odd-shaped inputs keep the Fermat path.
    """
    if not ff._is_concrete(s_mn):
        if len(bshape) == 2:
            total = bshape[0] * bshape[1]
            if total >= 128 and total % 2 == 0:
                flat = s_mn.reshape(s_mn.shape[0], total)
                s_zero = fn.is_zero_k(flat, 2)
                s_safe = fn.select(s_zero, fn.one_bc((total,)), flat)
                return fn.inv_tree(s_safe).reshape(s_mn.shape)
        elif (len(bshape) == 1 and bshape[0] >= 128
                and bshape[0] % 2 == 0):
            s_zero = fn.is_zero_k(s_mn, 2)
            s_safe = fn.select(s_zero, fn.one_bc(bshape), s_mn)
            return fn.inv_tree(s_safe)
    return fn.inv(s_mn)


def verify_words_xla(qx, qy, r, s, e, require_low_s: bool = True):
    """Plain-XLA entry point: (8, B) uint32 big-endian words -> (B,) bool.

    Deliberately NOT jitted: XLA:CPU's algebraic simplifier loops
    pathologically on the fully-inlined flat graph (minutes per compile).
    Eagerly the scans' bodies still compile, and this path only serves
    CPU tests / functional fallback; TPU production jits verify_body via
    the provider (bccsp/jaxtpu.py)."""
    args = [bn.words_be_to_limbs(v) for v in (qx, qy, r, s, e)]
    return verify_body(*args, comb_table_f32(), require_low_s=require_low_s)
