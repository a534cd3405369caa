"""Host-logic tests for the device-placement plane: partition-rule
resolution, power-of-two device allocation, sub-mesh carving, the
placement scheduler's hysteresis, and the provider's per-device slot
attribution.  Everything here is pure host bookkeeping — no kernel is
compiled or dispatched, so the module never needs the slow mark."""

import numpy as np
import pytest

from fabric_tpu.parallel import mesh as meshmod
from fabric_tpu.parallel.placement import PlacementScheduler


class FakeDev:
    def __init__(self, i):
        self.platform = "cpu"
        self.id = i


class FakeProvider:
    def __init__(self, mesh, device=None):
        self.mesh = mesh
        self.device = device


def _scheduler(n=8, **kw):
    return PlacementScheduler(devices=[FakeDev(i) for i in range(n)],
                              provider_factory=FakeProvider, **kw)


# -- partition rules ---------------------------------------------------------

def test_lane_specs_cover_every_lane():
    from jax.sharding import PartitionSpec as PSpec
    for lane, names in meshmod.LANE_ARGS.items():
        specs = meshmod.lane_specs(lane)
        assert len(specs) == len(names)
        for name, spec in zip(names, specs):
            if any(t in name for t in ("bank", "lines", "flags")):
                assert spec == PSpec(), (lane, name)
            else:
                assert meshmod.BATCH_AXIS in tuple(spec), (lane, name)


def test_unmatched_arg_name_is_hard_error():
    with pytest.raises(ValueError, match="no partition rule"):
        meshmod.match_partition_rules(meshmod.PARTITION_RULES,
                                      ("mystery_arg",))


def test_sign_rows_rule_orders_before_sign():
    # sign_rows is 2-D (R, C) and must shard dim 0 with dim 1 explicit;
    # the bare `sign` rule would also match, so rule order is load-bearing
    from jax.sharding import PartitionSpec as PSpec
    (spec,) = meshmod.match_partition_rules(
        meshmod.PARTITION_RULES, ("r_sign_rows",))
    assert spec == PSpec(meshmod.BATCH_AXIS, None)


# -- allocation --------------------------------------------------------------

def test_allocate_single_consumer_gets_everything():
    assert meshmod.allocate_devices(8, [1.0]) == [8]


def test_allocate_even_three_way():
    assert meshmod.allocate_devices(8, [1, 1, 1]) == [4, 2, 2]


def test_allocate_skew_absorbs_leftovers():
    assert meshmod.allocate_devices(8, [10, 1]) == [4, 4]


def test_allocate_non_power_of_two_pool():
    assert meshmod.allocate_devices(7, [5, 1, 1]) == [4, 2, 1]


def test_allocate_sizes_are_powers_of_two_and_fit():
    for n in (4, 7, 8, 16):
        for w in ([1], [3, 1], [1, 1, 1, 1], [9, 3, 1]):
            sizes = meshmod.allocate_devices(n, w)
            assert sum(sizes) <= n
            assert all(s & (s - 1) == 0 for s in sizes), sizes


def test_allocate_more_consumers_than_devices_raises():
    with pytest.raises(ValueError):
        meshmod.allocate_devices(2, [1, 1, 1])


def test_carve_submeshes_disjoint_contiguous():
    import jax
    devs = jax.devices()
    if len(devs) < 8:
        pytest.skip("needs 8 virtual devices")
    meshes = meshmod.carve_submeshes(devs[:8], [1, 1, 1])
    seen = []
    for m in meshes:
        seen.extend(d.id for d in np.asarray(m.devices).flat)
    assert len(seen) == len(set(seen))      # disjoint
    assert seen == sorted(seen)             # contiguous spans in order


# -- scheduler ---------------------------------------------------------------

def test_scheduler_single_channel_owns_all_devices():
    ps = _scheduler()
    ps.provider_for("ch")
    assert ps.snapshot()["channels"]["ch"]["devices"] == 8


def test_scheduler_registration_recarves_and_caches_providers():
    ps = _scheduler()
    p1 = ps.provider_for("a", demand=100)
    p2 = ps.provider_for("b", demand=100)
    assert ps.snapshot()["channels"]["a"]["devices"] == 4
    assert ps.snapshot()["channels"]["b"]["devices"] == 4
    assert p1 is not p2
    # same span -> same cached provider instance
    assert ps.provider_for("a", demand=100) is ps.provider_for(
        "a", demand=100)


def test_scheduler_hysteresis_ignores_small_drift():
    ps = _scheduler()
    for ch in ("a", "b", "c"):
        ps.provider_for(ch, demand=100)
    r0 = ps.rebalances
    for _ in range(10):
        ps.provider_for("a", demand=120)     # < rebalance_ratio drift
    assert ps.rebalances == r0


def test_scheduler_drift_without_allocation_change_skips_recarve():
    ps = _scheduler()
    ps.provider_for("a", demand=100)
    ps.provider_for("b", demand=100)
    r0 = ps.rebalances
    # 30x skew still allocates [4, 4] on 8 devices: no carve
    for _ in range(20):
        ps.provider_for("a", demand=3000)
    assert ps.rebalances == r0


def test_scheduler_demand_skew_resizes_spans():
    ps = _scheduler()
    for ch in ("a", "b", "c"):
        ps.provider_for(ch, demand=100)
    assert ps.snapshot()["channels"]["a"]["devices"] == 4
    r0 = ps.rebalances
    for _ in range(20):
        ps.provider_for("b", demand=3000)
    snap = ps.snapshot()
    assert ps.rebalances > r0
    assert snap["channels"]["b"]["devices"] == 4
    assert snap["channels"]["a"]["devices"] == 2


def test_scheduler_spans_disjoint_after_rebalance():
    ps = _scheduler()
    for ch in ("a", "b", "c"):
        ps.provider_for(ch, demand=100)
    for _ in range(20):
        ps.provider_for("b", demand=5000)
    spans = sorted((v["span_start"], v["devices"])
                   for v in ps.snapshot()["channels"].values())
    lo = 0
    for start, size in spans:
        assert start == lo
        lo = start + size
    assert lo <= 8


def test_scheduler_idle_channel_decays_and_releases_span():
    clock = [0.0]
    ps = _scheduler(idle_halflife_s=10.0, clock=lambda: clock[0])
    for _ in range(20):
        ps.provider_for("a", demand=100)
        ps.provider_for("b", demand=100)
        ps.provider_for("quiet", demand=3000)
    assert ps.snapshot()["channels"]["quiet"]["devices"] == 4
    # "quiet" goes silent; a and b keep flushing.  After enough
    # half-lives its EWMA decays past the rebalance ratio and a busy
    # flush recarves WITHOUT any new channel registering, handing the
    # wide span to a busy channel.
    for _ in range(10):
        clock[0] += 10.0
        ps.provider_for("a", demand=100)
        ps.provider_for("b", demand=100)
    snap = ps.snapshot()
    assert snap["channels"]["quiet"]["demand_ewma"] < 100.0
    assert snap["channels"]["quiet"]["devices"] == 2
    assert snap["channels"]["a"]["devices"] == 4


def test_scheduler_decay_is_idempotent_within_a_halflife():
    clock = [0.0]
    ps = _scheduler(idle_halflife_s=10.0, clock=lambda: clock[0])
    ps.provider_for("a", demand=100)
    ps.provider_for("b", demand=100)
    clock[0] += 15.0
    # many calls inside one elapsed window must decay "b" exactly once
    for _ in range(50):
        ps.provider_for("a", demand=100)
    assert ps.snapshot()["channels"]["b"]["demand_ewma"] == \
        pytest.approx(50.0)


def test_scheduler_decay_disabled_with_nonpositive_halflife():
    clock = [0.0]
    ps = _scheduler(idle_halflife_s=0.0, clock=lambda: clock[0])
    ps.provider_for("a", demand=100)
    ps.provider_for("b", demand=100)
    clock[0] += 1e6
    ps.provider_for("a", demand=100)
    assert ps.snapshot()["channels"]["b"]["demand_ewma"] == \
        pytest.approx(100.0)


def test_scheduler_wrap_applied_once_per_span():
    wrapped = []

    def wrap(p):
        wrapped.append(p)
        return ("wrapped", p)

    ps = _scheduler(wrap=wrap)
    w1 = ps.provider_for("ch")
    w2 = ps.provider_for("ch")
    assert w1 == w2 and w1[0] == "wrapped"
    assert len(wrapped) == 1


def test_single_device_span_is_meshless_on_its_own_chip():
    ps = _scheduler(n=2)
    ps.provider_for("a", demand=1)
    for _ in range(20):
        ps.provider_for("b", demand=1)
    ps.provider_for("a", demand=1)   # materialize a's span provider too
    # both channels at 1 device each: each span provider is meshless
    # (the programs a one-chip node runs) and is handed its own chip —
    # tests/test_bccsp.py shows the provider keeps banks and dispatches
    # there rather than on devices()[0]
    spans = {v["span_start"] for v in ps.snapshot()["channels"].values()
             if v["devices"] == 1}
    assert spans == {0, 1}
    for lo in spans:
        p = ps._providers[(lo, 1)]
        assert p.mesh is None and p.device.id == lo


# -- factory wiring ----------------------------------------------------------

def test_factory_placement_disabled_returns_none():
    from fabric_tpu.bccsp import factory
    factory.init_factories(factory.FactoryOpts(default="SW"))
    assert factory.get_placement() is None
    assert factory.provider_for_channel("ch") is None


# -- per-device slot attribution --------------------------------------------

def _provider_shell(n_dev=8):
    from fabric_tpu.bccsp.jaxtpu import JaxTpuProvider
    p = JaxTpuProvider.__new__(JaxTpuProvider)
    p.device_labels = tuple(f"cpu:{i}" for i in range(n_dev))
    return p


def test_per_device_prefix_split():
    p = _provider_shell()
    split = p._per_device_slots(100, 128)
    assert [r for _, r, _ in split] == [16, 16, 16, 16, 16, 16, 4, 0]
    assert all(s == 16 for _, _, s in split)
    assert sum(r for _, r, _ in split) == 100


def test_per_device_non_divisible_charges_first_device():
    p = _provider_shell()
    assert p._per_device_slots(3, 5) == [("cpu:0", 3, 5)]


def test_per_device_explicit_counts_pass_through():
    p = _provider_shell()
    counts = [("cpu:0", 1, 4), ("cpu:1", 4, 4)]
    assert p._per_device_slots(5, 8, per_device=counts) is counts


def test_observe_lane_emits_device_labeled_series():
    from fabric_tpu.bccsp.jaxtpu import JaxTpuProvider
    from fabric_tpu.ops_plane import registry
    p = _provider_shell(4)
    p._FILL_BUCKETS = JaxTpuProvider._FILL_BUCKETS
    p._observe_lane("testlane", 10, 16)
    g = registry.get("provider_lane_fill_fraction")
    by_dev = {dict(k)["device"]: v for k, v in g.values().items()
              if dict(k).get("lane") == "testlane"}
    assert set(by_dev) == {f"cpu:{i}" for i in range(4)}
    assert by_dev["cpu:0"] == 1.0 and by_dev["cpu:3"] == 0.0
    assert by_dev["cpu:2"] == pytest.approx(0.5)


def test_mesh_pad_rounds_to_mesh_multiple():
    import jax
    devs = jax.devices()
    if len(devs) < 8:
        pytest.skip("needs 8 virtual devices")
    from fabric_tpu.bccsp.jaxtpu import JaxTpuProvider
    p = JaxTpuProvider.__new__(JaxTpuProvider)
    p.mesh = meshmod.make_mesh(devs[:8])
    arrays = [np.zeros((8, 130), np.uint32)]
    padded = p._pad(arrays, 130)
    b = padded[0].shape[-1]
    assert b % 8 == 0 and b >= 130


def test_scheduler_demand_folds_in_dispatch_backlog():
    # a flush landing behind unresolved device work reports more
    # pressure than its batch size alone (provider_dispatch_queue_depth
    # is folded into the EWMA sample at report time)
    from fabric_tpu.ops_plane.metrics import registry
    g = registry.gauge("provider_dispatch_queue_depth",
                       "device dispatches enqueued, not yet resolved")
    try:
        g.set(0.0)
        ps = _scheduler()
        ps.provider_for("a", demand=100)
        assert ps.snapshot()["channels"]["a"]["demand_ewma"] == 100.0
        g.set(900.0)
        ps2 = _scheduler()
        ps2.provider_for("a", demand=100)
        assert ps2.snapshot()["channels"]["a"]["demand_ewma"] == 1000.0
    finally:
        g.set(0.0)
