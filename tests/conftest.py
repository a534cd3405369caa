"""Test configuration: run everything on a virtual 8-device CPU mesh.

Must set env vars BEFORE jax is imported anywhere (mirrors the driver's
dryrun_multichip environment).  Measurement on the chip is the benchmark's
(`BENCHMARK.json`, `benchmark/run.py`), not pytest's.
"""
import os

os.environ["JAX_PLATFORMS"] = "cpu"
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (_flags + " --xla_force_host_platform_device_count=8").strip()

# Persistent XLA compilation cache, where JAX_COMPILATION_CACHE_DIR says
# or at <checkout>/.cache/jax: a later run loads what this one compiled.
import pytest

from fabric_tpu.bccsp.factory import enable_compile_cache

enable_compile_cache()


def pytest_configure(config):
    config.addinivalue_line(
        "markers", "slow: multi-process topology tests excluded from the "
        "tier-1 'not slow' gate")


@pytest.fixture
def same_exposition():
    """check(earlier, later): two /metrics texts of one registry differ
    in nothing but the clock each exposition reads itself
    (`process_uptime_seconds`), and that one has not run backwards."""
    stamp = "process_uptime_seconds "

    def check(earlier: str, later: str) -> None:
        a, b = earlier.splitlines(), later.splitlines()
        assert len(a) == len(b), (earlier, later)
        differing = [(x, y) for x, y in zip(a, b) if x != y]
        assert all(x.startswith(stamp) and y.startswith(stamp)
                   for x, y in differing), differing
        for x, y in differing:
            assert float(y[len(stamp):]) >= float(x[len(stamp):])
    return check


@pytest.fixture
def counting():
    """counting(inner) -> a provider that hands everything to `inner` and
    keeps what it was asked to verify: `.items`, every item in order, and
    `.calls`, the number of dispatches (`verify` is one of one item)."""
    class Counting:
        def __init__(self, inner):
            self.inner = inner
            self.items = []
            self.calls = 0

        def verify(self, item):
            return bool(self.batch_verify([item])[0])

        def batch_verify(self, items):
            items = list(items)
            self.items.extend(items)
            self.calls += 1
            return self.inner.batch_verify(items)

        def __getattr__(self, name):
            return getattr(self.inner, name)
    return Counting


@pytest.fixture
def handed_over():
    """handed_over() -> `validator_handoff_sigs_total` on channel `ch` by
    (form, reason); handed_over(before) -> what moved since `before`."""
    from fabric_tpu.ops_plane import registry
    pairs = [("arrays", "bypassed")] + [("items", r) for r in (
        "small_block", "cache_answered", "scheme", "classic_tail",
        "no_verb")]

    def read(before=None):
        c = registry.counter("validator_handoff_sigs_total")
        now = {p: int(c.value(channel="ch", form=p[0], reason=p[1]))
               for p in pairs}
        if before is None:
            return now
        return {p: n - before[p] for p, n in now.items() if n != before[p]}
    return read


@pytest.fixture
def lanes_opened():
    """lanes_opened() -> `ledger_lane_table_opened_total` on channel `ch`
    by `at`; lanes_opened(before) -> what moved since `before`."""
    from fabric_tpu.ops_plane import registry

    def read(before=None):
        c = registry.counter("ledger_lane_table_opened_total")
        now = {at: int(c.value(channel="ch", at=at))
               for at in ("validator_wait", "commit")}
        if before is None:
            return now
        return {at: n - before[at] for at, n in now.items()
                if n != before[at]}
    return read
