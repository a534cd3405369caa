"""Differential tests: jaxtpu provider vs sw provider (the reference's
sw-vs-pkcs11 idiom, bccsp test strategy per SURVEY.md §4)."""
import hashlib
import random

import numpy as np
import pytest

from fabric_tpu.bccsp import (VerifyItem, SCHEME_P256, SCHEME_ED25519,
                              init_factories, FactoryOpts)
from fabric_tpu.bccsp.sw import SoftwareProvider
from fabric_tpu.bccsp.jaxtpu import JaxTpuProvider

rng = random.Random(11)


@pytest.fixture(scope="module")
def sw():
    return SoftwareProvider()


@pytest.fixture(scope="module")
def tpu():
    return JaxTpuProvider()


def make_items(sw, n_p256=4, n_ed=3):
    items = []
    for _ in range(n_p256):
        k = sw.key_gen(SCHEME_P256)
        digest = hashlib.sha256(rng.randbytes(50)).digest()
        items.append(VerifyItem(SCHEME_P256, k.public_bytes(),
                                sw.sign(k, digest), digest))
    for _ in range(n_ed):
        k = sw.key_gen(SCHEME_ED25519)
        msg = rng.randbytes(rng.randrange(0, 99))
        items.append(VerifyItem(SCHEME_ED25519, k.public_bytes(),
                                sw.sign(k, msg), msg))
    return items


def test_mixed_scheme_batch_matches_sw(sw, tpu):
    items = make_items(sw)
    # corrupt a couple
    bad1 = items[1]
    items[1] = VerifyItem(bad1.scheme, bad1.pubkey, bad1.signature,
                          hashlib.sha256(b"other").digest())
    bad2 = items[5]
    items[5] = VerifyItem(bad2.scheme, bad2.pubkey, bad2.signature,
                          bad2.payload + b"x")
    want = sw.batch_verify(items)
    got = tpu.batch_verify(items)
    np.testing.assert_array_equal(got, want)
    assert want.sum() == len(items) - 2


def test_malformed_items_are_false_not_fatal(sw, tpu):
    k = sw.key_gen(SCHEME_P256)
    digest = hashlib.sha256(b"m").digest()
    good = VerifyItem(SCHEME_P256, k.public_bytes(), sw.sign(k, digest), digest)
    items = [
        good,
        VerifyItem(SCHEME_P256, b"\x04" + b"\x00" * 10, good.signature, digest),  # short point
        VerifyItem(SCHEME_P256, good.pubkey, b"\x30\x01\x00", digest),  # bad DER
        VerifyItem(SCHEME_P256, good.pubkey, good.signature, b"short"),  # bad digest len
        VerifyItem(SCHEME_ED25519, b"\x00" * 31, b"\x00" * 64, b""),  # short key
        VerifyItem("rsa-4096", good.pubkey, good.signature, digest),  # unknown scheme
        good,
    ]
    want = sw.batch_verify(items)
    got = tpu.batch_verify(items)
    np.testing.assert_array_equal(got, want)
    np.testing.assert_array_equal(got, [True, False, False, False, False, False, True])


def test_high_s_rejected_by_both(sw, tpu):
    from fabric_tpu.crypto import (
        decode_dss_signature, encode_dss_signature)
    from fabric_tpu.bccsp.sw import P256_N
    k = sw.key_gen(SCHEME_P256)
    digest = hashlib.sha256(b"hs").digest()
    sig = sw.sign(k, digest)
    r, s = decode_dss_signature(sig)
    high = encode_dss_signature(r, P256_N - s)
    items = [VerifyItem(SCHEME_P256, k.public_bytes(), high, digest),
             VerifyItem(SCHEME_P256, k.public_bytes(), sig, digest)]
    np.testing.assert_array_equal(sw.batch_verify(items), [False, True])
    np.testing.assert_array_equal(tpu.batch_verify(items), [False, True])


def test_factory_gate(sw):
    p = init_factories(FactoryOpts(default="SW"))
    assert p.name == "sw"
    p = init_factories(FactoryOpts(default="JAXTPU"))
    assert p.name == "jaxtpu"
    with pytest.raises(ValueError):
        init_factories(FactoryOpts(default="HSM"))


def test_factory_degrade_defaults_on_under_jaxtpu():
    from fabric_tpu.bccsp.degrade import DegradingProvider

    # auto (degrade=None): the TPU provider gets the breaker + SW
    # fallback by default — losing the accelerator must not stop commits
    p = init_factories(FactoryOpts(default="JAXTPU"))
    assert isinstance(p, DegradingProvider)
    assert p.backend == "jaxtpu"            # healthy: primary fronts

    # auto: SW needs no fallback-to-SW wrapper
    p = init_factories(FactoryOpts(default="SW"))
    assert not isinstance(p, DegradingProvider)

    # the escape hatch: explicit False means fail-stop
    p = init_factories(FactoryOpts(default="JAXTPU", degrade=False))
    assert not isinstance(p, DegradingProvider)


def test_fail_stop_lane_error_reaches_the_caller(monkeypatch):
    """degrade=False: an exception inside a lane is the caller's, as a
    DeviceError; nothing is recomputed on the software provider."""
    from fabric_tpu.bccsp.provider import DeviceError

    boom = RuntimeError("injected lane failure")

    def broken(items, idxs, pending):
        raise boom

    p = init_factories(FactoryOpts(default="JAXTPU", degrade=False))
    assert isinstance(p, JaxTpuProvider) and not p.degrade
    monkeypatch.setattr(p, "_verify_p256", broken)
    items = make_items(SoftwareProvider(), n_p256=3, n_ed=0)
    with pytest.raises(DeviceError) as err:
        p.batch_verify(items)
    assert err.value.__cause__ is boom
    assert p.stats["fallbacks"] == 0

    # a provider that was asked to degrade still answers, and counts it
    q = JaxTpuProvider(degrade=True)
    monkeypatch.setattr(q, "_verify_p256", broken)
    assert q.batch_verify(items).all()
    assert q.stats["fallbacks"] == 1


def test_fail_stop_resolve_error_reaches_the_caller(monkeypatch):
    from fabric_tpu.bccsp.provider import DeviceError

    def raising():
        raise RuntimeError("injected resolve failure")

    def lane(items, idxs, pending):
        pending.append((idxs, raising, None, None))

    p = JaxTpuProvider()
    monkeypatch.setattr(p, "_verify_p256", lane)
    resolve = p.batch_verify_async(
        make_items(SoftwareProvider(), n_p256=2, n_ed=0))
    with pytest.raises(DeviceError):
        resolve()
    assert p.stats["fallbacks"] == 0


def test_device_pins_banks_and_dispatches():
    """JaxTpuProvider(device=d) — the placement scheduler's one-chip
    span — runs the meshless program on d and keeps its banks there,
    not on devices()[0]."""
    import jax
    dev = jax.devices()[3]
    p = JaxTpuProvider(device=dev, max_cached_keys=2)
    assert p.device_labels == (f"{dev.platform}:{dev.id}",)
    assert p.key_tables.array().devices() == {dev}
    items = make_items(SoftwareProvider(), n_p256=2, n_ed=0)
    slot = p.key_tables.get_or_build(items[0].pubkey)
    assert slot is not None and p.key_tables.array().devices() == {dev}
    keep, arrays = p._pack_p256(items, range(len(items)))
    out = p._get_fn(SCHEME_P256)(*p._pad(arrays, len(keep)))
    assert out.devices() == {dev}
    assert np.asarray(out)[:len(keep)].all()
    with pytest.raises(ValueError):
        JaxTpuProvider(device=dev, mesh=object())


def test_jaxtpu_refuses_a_cpu_nobody_asked_for(monkeypatch):
    """Where JAX finds no accelerator it falls back to the CPU silently;
    only JAX_PLATFORMS naming the CPU makes that the provider's device."""
    from fabric_tpu.bccsp import jaxtpu

    monkeypatch.setattr(jaxtpu, "_requested_platforms", lambda: "")
    with pytest.raises(RuntimeError, match="no accelerator"):
        JaxTpuProvider()
    with pytest.raises(RuntimeError, match="no accelerator"):
        init_factories(FactoryOpts(default="JAXTPU"))
    monkeypatch.setattr(jaxtpu, "_requested_platforms", lambda: "tpu,cpu")
    assert JaxTpuProvider().name == "jaxtpu"
    init_factories(FactoryOpts(default="SW"))


def test_compile_cache_dir_comes_from_the_environment(monkeypatch):
    import os

    import jax

    from fabric_tpu.bccsp import factory

    def refuse(*a, **kw):
        raise AssertionError("jax.config.update called although "
                             "JAX_COMPILATION_CACHE_DIR is set")

    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/some/where")
    monkeypatch.setattr(jax.config, "update", refuse)
    assert factory.enable_compile_cache() == "/some/where"
    monkeypatch.undo()

    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    before = jax.config.jax_compilation_cache_dir
    try:
        assert factory.enable_compile_cache() == os.path.join(
            repo, ".cache", "jax")
        assert jax.config.jax_compilation_cache_dir == os.path.join(
            repo, ".cache", "jax")
    finally:
        jax.config.update("jax_compilation_cache_dir", before)


def test_get_default_before_init_stays_off_jax():
    """Clients, admin tools and launchers reach get_default() through
    every handshake; that must not import jax (and take the chip)."""
    import os
    import subprocess
    import sys

    code = ("import sys\n"
            "from fabric_tpu.bccsp.factory import get_default\n"
            "from fabric_tpu.msp.ca import DevOrg\n"
            "ident = DevOrg('O').new_identity('a')\n"
            "assert ident.verify(b'm', ident.sign(b'm'))\n"
            "assert get_default().name == 'sw', get_default().name\n"
            "assert 'jax' not in sys.modules, 'jax imported'\n")
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    proc = subprocess.run([sys.executable, "-c", code], cwd=repo,
                          capture_output=True, text=True, timeout=120)
    assert proc.returncode == 0, proc.stderr


def test_degrading_provider_delegates_primary_attributes():
    from fabric_tpu.bccsp.degrade import DegradingProvider
    primary = JaxTpuProvider()
    deg = DegradingProvider(primary, SoftwareProvider())
    assert deg.stats is primary.stats       # /state and the benchmark read provider.stats


def test_empty_batch(tpu):
    assert tpu.batch_verify([]).shape == (0,)


def test_warm_dispatches_exactly_the_named_shapes(tpu):
    """provider.warm lands one dispatch on each named lane and bucket —
    what POST /bccsp/warmup and chip_smoke.py rely on to keep compiles
    out of the serving window."""
    before = dict(tpu.stats)
    timings = tpu.warm(generic=[128, 256], rows=[4])
    assert sorted(timings) == ["generic@128", "generic@256", "rows@4"]
    assert tpu.stats["dispatches"] - before["dispatches"] == 3
    assert tpu.stats["device_sigs"] - before["device_sigs"] == (
        128 + 129 + 4 * tpu.fast_row_c)
    assert tpu.stats["fast_key_sigs"] - before["fast_key_sigs"] == (
        4 * tpu.fast_row_c)
    assert tpu.stats["fallbacks"] == 0
    with pytest.raises(ValueError):
        tpu.warm(generic=[300])
    with pytest.raises(ValueError):
        tpu.warm(rows=[5])
    assert tpu.warm() == {}
