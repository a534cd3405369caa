"""Sharded batch verification on the virtual 8-device CPU mesh."""
import hashlib
import random

import numpy as np
import pytest

# CPU tier-1 note: this module jit-compiles full device kernels on the
# CPU backend (minutes of XLA compile, no TPU involved) -- slow-marked so
# the quick gate stays inside its budget; the full suite still runs it.
# Unconditionally: which tests the gate selects must not depend on what
# a compile cache on disk happens to hold.
pytestmark = [pytest.mark.slow]

import jax

from fabric_tpu.crypto import ec
from fabric_tpu.crypto import Ed25519PrivateKey
from fabric_tpu.crypto import decode_dss_signature
from fabric_tpu.crypto import hashes, serialization

from fabric_tpu.ops import p256, ed25519 as edv
from fabric_tpu.parallel import mesh as meshmod

rng = random.Random(7)


def test_mesh_has_8_devices():
    assert len(jax.devices()) == 8


def test_sharded_p256():
    m = meshmod.make_mesh()
    verify = meshmod.sharded_p256_verify(m)
    key = ec.generate_private_key(ec.SECP256R1())
    pub = key.public_key().public_numbers()
    cases = []
    want = []
    for i in range(13):  # deliberately not divisible by 8
        msg = rng.randbytes(32)
        digest = int.from_bytes(hashlib.sha256(msg).digest(), "big")
        r, s = decode_dss_signature(key.sign(msg, ec.ECDSA(hashes.SHA256())))
        if s > p256.HALF_N:
            s = p256.N - s
        if i % 3 == 2:
            digest ^= 1  # corrupt
        cases.append((pub.x, pub.y, r, s, digest))
        want.append(i % 3 != 2)
    qx, qy, r, s, e = (p256.ints_to_words(list(v)) for v in zip(*cases))
    (arrs, padded) = meshmod.pad_batch([qx, qy, r, s, e], 13, 8)
    verdicts, count = verify(*arrs)
    np.testing.assert_array_equal(np.asarray(verdicts)[:13], want)
    assert int(count) == sum(want)
    # padding rows must all reject
    assert not np.asarray(verdicts)[13:].any()


def test_sharded_ed25519():
    m = meshmod.make_mesh()
    verify = meshmod.sharded_ed25519_verify(m)
    triples = []
    want = []
    for i in range(8):
        key = Ed25519PrivateKey.generate()
        pk = key.public_key().public_bytes(
            serialization.Encoding.Raw, serialization.PublicFormat.Raw)
        msg = rng.randbytes(40)
        sig = key.sign(msg)
        if i == 5:
            msg = msg + b"!"
        triples.append((pk, sig, msg))
        want.append(i != 5)
    args = edv.pack_verify_inputs(*zip(*triples))
    verdicts, count = verify(*[np.asarray(a) for a in args])
    np.testing.assert_array_equal(np.asarray(verdicts), want)
    assert int(count) == sum(want)
