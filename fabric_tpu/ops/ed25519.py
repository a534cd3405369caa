"""Batched ed25519 (RFC 8032) signature verification on TPU.

NEW capability vs the reference (no ed25519 exists in /root/reference —
SURVEY.md §2 bccsp/sw note); required by BASELINE.json configs 2-3.

Split of labor:
- host (provider layer): SHA-512(R || A || M) over the variable-length
  message, reduced mod L — hashing never goes on device (mirrors the
  reference's design where bccsp.Verify receives a fixed-size digest,
  msp/identities.go:178);
- device (this module): the cofactorless equation [S]B == R + [k]A,
  matching RFC 8032 / OpenSSL / Go crypto/ed25519, computed as
  [S]B + [k](-A) and compared against the ENCODED R by recompression
  (one batch-amortized inversion instead of a ~250-squaring sqrt per
  signature — R never needs decompressing).

Two lanes (the P-256 two-lane design, bccsp/jaxtpu.py):
  verify_words       — generic: decompress A on device, [S]B via the
                       fixed-base signed comb, [k](-A) via a 4-bit
                       windowed ladder of complete adds;
  verify_words_rows  — fast: A's table is cached (ops/ed25519_tables),
                       BOTH halves are fixed-base combs; signatures
                       pack key-major into a (R, C) row grid exactly
                       like ops/p256_fixed.verify_words_rows.

Kernel inputs are (8, B) uint32 big-endian words of the *integer values*
(the host unpacks the little-endian wire encoding) plus (B,) sign bits.
"""

from __future__ import annotations

import hashlib

import numpy as np
import jax.numpy as jnp

from . import bignum as bn
from . import edwards as ed
from . import flatfield as ff


def _sb_comb(s_l, bshape):
    from . import ed25519_tables as tabs
    return ed.comb_accumulate(tabs.basepoint_table(), s_l, bshape)


def verify_words(ay, a_sign, ry, r_sign, s, k) -> jnp.ndarray:
    """Generic-lane batched ed25519 verify (uncached A).

    ay, ry: (8, B) uint32 big-endian words of the A / R y-coordinates
    a_sign, r_sign: (B,) int32 x-parity bits from the encodings
    s: (8, B) words of S (checked < L here)
    k: (8, B) words of SHA512(R||A||M) already reduced mod L by the host
    Returns (B,) bool.
    """
    ay_l = bn.words_be_to_limbs(ay)
    ry_l = bn.words_be_to_limbs(ry)
    s_l = bn.words_be_to_limbs(s)
    k_l = bn.words_be_to_limbs(k)
    bshape = s_l.shape[1:]

    s_ok = ff.lt_const(s_l, ed.L)
    (ax_m, ay_m), a_ok = ed.decompress(ay_l, a_sign)

    lhs = ed.add(_sb_comb(s_l, bshape),
                 ed.windowed_mul(k_l, ed.neg(ed.from_affine(ax_m, ay_m)),
                                 bshape))
    # gate the inversion on a_ok: garbage "points" from a failed
    # decompression may break the completeness guarantee (Z == 0 would
    # poison the product tree); their verdict is False regardless.
    zinv = ed.batch_zinv(lhs[2], a_ok)
    return s_ok & a_ok & ed.compressed_equals(lhs, ry_l, r_sign, zinv)


def verify_words_rows(bank_f32, row_key, ry, r_sign, s, k) -> jnp.ndarray:
    """Fast-lane batched verify over a key-major (R, C) row grid.

    bank_f32: (K, COMB_WINDOWS*COMB_ROWS, 3L) stacked niels tables of
    the NEGATED public keys (Ed25519KeyTableCache layout); row_key:
    (R,) int32; ry/s/k: (8, R, C) uint32 words; r_sign: (R, C) int32.
    Returns (R, C) bool.  A-validity was established at table build.
    """
    ry_l = bn.words_be_to_limbs(ry)
    s_l = bn.words_be_to_limbs(s)
    k_l = bn.words_be_to_limbs(k)
    R, C = s_l.shape[1], s_l.shape[2]

    def flat(x):
        return x.reshape(x.shape[0], R * C)

    s_ok = ff.lt_const(flat(s_l), ed.L)
    acc_b = _sb_comb(flat(s_l), (R * C,))
    acc_a = ed.comb_accumulate_rows(bank_f32, row_key, k_l, (R, C))
    lhs = ed.add(acc_b, tuple(
        flat(c) if c.ndim == 3 else c.reshape(R * C) for c in acc_a))
    # every point here is a valid curve point (tables are built from
    # validated keys; combs of valid points stay valid): completeness
    # guarantees Z != 0, so the tree is safe ungated.
    ones = jnp.ones((R * C,), bool)
    zinv = ed.batch_zinv(lhs[2], ones)
    ok = s_ok & ed.compressed_equals(lhs, flat(ry_l),
                                     r_sign.reshape(R * C), zinv)
    return ok.reshape(R, C)


# ---------------------------------------------------------------------------
# Host-side packing: RFC 8032 wire format -> kernel inputs
# ---------------------------------------------------------------------------

def key_words(pubkeys: list):
    """32B public keys A -> (ay, a_sign): A's y-coordinate as (B, 8)
    uint32 big-endian words, one row a key, and its x-parity bits (B,)
    int32."""
    pkw = np.frombuffer(b"".join(pubkeys), "<u4").reshape(len(pubkeys), 8)
    a_sign = (pkw[:, 7] >> 31).astype(np.int32)
    ay = np.ascontiguousarray(pkw[:, ::-1]).astype(np.uint32)
    ay[:, 0] &= 0x7FFFFFFF
    return ay, a_sign


def sig_words(sigs: list):
    """64B signatures R || S -> (ry, r_sign, s): R's y-coordinate and S
    as (B, 8) uint32 big-endian words of the integer values, one row a
    signature, and R's x-parity bits (B,) int32.  Numpy end to end."""
    B = len(sigs)
    sgw = np.frombuffer(b"".join(sigs), "<u4").reshape(B, 16)
    r_sign = (sgw[:, 7] >> 31).astype(np.int32)
    ry = np.ascontiguousarray(sgw[:, 7::-1]).astype(np.uint32)
    ry[:, 0] &= 0x7FFFFFFF
    s = np.ascontiguousarray(sgw[:, :7:-1]).astype(np.uint32)
    return ry, r_sign, s


def challenge_words(pubkeys: list, sigs: list, msgs: list) -> np.ndarray:
    """k = SHA-512(R || A || M) mod L of each signature, exact, as
    (B, 8) uint32 big-endian words, one row a signature.

    The hash runs over the whole message (RFC 8032 signs the message,
    not a digest), so this is the Ed25519 lanes' host cost: OpenSSL's
    SHA-512 fed in three pieces (no concatenated copy of the message)
    and one Python-int reduction a signature."""
    sha512 = hashlib.sha512
    from_bytes = int.from_bytes
    Lmod = ed.L
    kb = bytearray(32 * len(sigs))
    at = 0
    for pk, sig, msg in zip(pubkeys, sigs, msgs):
        h = sha512(sig[:32])
        h.update(pk)
        h.update(msg)
        kb[at:at + 32] = (from_bytes(h.digest(), "little")
                          % Lmod).to_bytes(32, "big")
        at += 32
    return np.frombuffer(kb, ">u4").reshape(-1, 8).astype(np.uint32)


def pack_verify_inputs(pubkeys: list, sigs: list, msgs: list):
    """(32B pubkey, 64B sig, message) triples -> kernel input arrays.

    Returns (ay, a_sign, ry, r_sign, s, k) ready for verify_words.
    Malformed-length inputs raise ValueError (callers pre-screen).
    """
    B = len(pubkeys)
    if B == 0:
        z = np.zeros((8, 0), dtype=np.uint32)
        zb = np.zeros((0,), dtype=np.int32)
        return z, zb, z, zb, z.copy(), z.copy()
    for pk, sig in zip(pubkeys, sigs):
        if len(pk) != 32 or len(sig) != 64:
            raise ValueError("ed25519: bad pubkey/signature length")
    ay, a_sign = key_words(pubkeys)
    ry, r_sign, s = sig_words(sigs)
    k = challenge_words(pubkeys, sigs, msgs)
    return (np.ascontiguousarray(ay.T), a_sign, np.ascontiguousarray(ry.T),
            r_sign, np.ascontiguousarray(s.T), np.ascontiguousarray(k.T))
