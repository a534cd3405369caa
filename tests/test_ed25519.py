"""Differential tests of batched TPU ed25519 verify vs the OpenSSL oracle."""
import random

import numpy as np
import jax
import pytest

# CPU tier-1 note: this module jit-compiles full device kernels on the
# CPU backend (minutes of XLA compile, no TPU involved) -- slow-marked so
# the quick gate stays inside its budget; the full suite still runs it.
# Unconditionally: which tests the gate selects must not depend on what
# a compile cache on disk happens to hold.
pytestmark = [pytest.mark.slow]


from fabric_tpu.crypto import Ed25519PrivateKey
from fabric_tpu.crypto import serialization
from fabric_tpu.crypto import InvalidSignature

from fabric_tpu.ops import ed25519 as ed_verify
from fabric_tpu.ops import edwards as ed

rng = random.Random(4242)


def make_sig(msg=None):
    key = Ed25519PrivateKey.generate()
    pub = key.public_key().public_bytes(
        serialization.Encoding.Raw, serialization.PublicFormat.Raw)
    msg = msg if msg is not None else rng.randbytes(rng.randrange(0, 200))
    sig = key.sign(msg)
    return pub, sig, msg


def oracle(pub, sig, msg) -> bool:
    from fabric_tpu.crypto import Ed25519PublicKey
    try:
        Ed25519PublicKey.from_public_bytes(pub).verify(sig, msg)
        return True
    except (InvalidSignature, ValueError):
        return False


@pytest.fixture(scope="module")
def verify_jit():
    return jax.jit(ed_verify.verify_words)


def run(verify_jit, triples):
    args = ed_verify.pack_verify_inputs(*zip(*triples))
    return np.asarray(verify_jit(*args))


def test_valid_and_mutated(verify_jit):
    cases = []
    for mutate in [None, "flip_msg", "flip_sig", "swap_key", None, "s_plus_l"]:
        pub, sig, msg = make_sig()
        if mutate == "flip_msg":
            msg = msg + b"x"
        elif mutate == "flip_sig":
            sig = bytes([sig[0] ^ 1]) + sig[1:]
        elif mutate == "swap_key":
            pub = make_sig()[0]
        elif mutate == "s_plus_l":
            s_int = int.from_bytes(sig[32:], "little") + ed.L
            sig = sig[:32] + s_int.to_bytes(32, "little")
        cases.append((pub, sig, msg))
    got = run(verify_jit, cases)
    want = [oracle(*c) for c in cases]
    assert want == [True, False, False, False, True, False]
    np.testing.assert_array_equal(got, want)


def test_noncanonical_y(verify_jit):
    """A / R encodings with y >= p must be rejected (RFC 8032 decode rule)."""
    pub, sig, msg = make_sig()
    # y = p + 1 with sign bit 0: a non-canonical encoding of y = 1
    bad_y = (ed.P + 1).to_bytes(32, "little")
    cases = [
        (bad_y, sig, msg),                     # bad A
        (pub, bad_y + sig[32:], msg),          # bad R
        (pub, sig, msg),                       # control
    ]
    got = run(verify_jit, cases)
    want = [oracle(*c) for c in cases]
    np.testing.assert_array_equal(got, want)
    assert list(got) == [False, False, True]


def test_empty_and_long_messages(verify_jit):
    cases = [make_sig(b""), make_sig(rng.randbytes(5000))]
    got = run(verify_jit, cases)
    np.testing.assert_array_equal(got, [True, True])
