"""JAX/TPU batched BCCSP provider — the hardware slot of the framework.

Occupies the position the reference gives PKCS#11 HSMs (bccsp/pkcs11,
gated by bccsp/factory — SURVEY.md §2.1.1), but instead of one-at-a-time
HSM calls it dispatches the whole batch to the TPU kernels in
fabric_tpu.ops.  Signing and key-gen delegate to the software provider
(private keys never touch the TPU).

Host/device split per the reference's own design (msp/identities.go:178):
variable-length parsing (DER signatures, SEC1 points, RFC 8032 encodings,
SHA-512 for ed25519) happens on host; the device sees only fixed-size
word arrays.

Batching strategy: items are grouped by scheme, packed into word arrays,
and padded to power-of-two buckets so XLA compiles a small, reusable set
of programs.  Malformed items short-circuit to False on the host.
A device failure reaches the caller.  Only a provider built with
`degrade=True` recomputes the whole batch on the software provider
instead, atomically (SURVEY.md §7 hard-part #5: fallback must be atomic
to keep determinism).

Device placement: with a mesh (parallel/mesh.py) every lane — generic
ladder, fixed-comb rows, idemix pairing — shards its flat batch across
the 1-D 'batch' axis via shard_map, buckets padded to a multiple of the
mesh size so each device holds an equal tile; verdict bitmaps and the
psum'd valid count stay on-device until resolve.  The lane-fill gauges
carry a `device` label so per-chip tile occupancy is observable live.
Independent channels can pin to disjoint sub-meshes through
parallel/placement.py (one provider per device subset).
"""

from __future__ import annotations

import logging
import time
from dataclasses import dataclass, field
from typing import Optional, Sequence

import numpy as np

from fabric_tpu.crypto import decode_dss_signature

from . import provider as prov
from .dispatch_account import DispatchAccount
from .provider import (VerifyItem, SCHEME_P256, SCHEME_ED25519,
                       SCHEME_IDEMIX)
from .sw import SoftwareProvider

logger = logging.getLogger("fabric_tpu.bccsp.jaxtpu")

MIN_BUCKET = 128
MAX_BUCKET = 1 << 17

_ZERO32 = b"\x00" * 32
_ZERO64 = b"\x00" * 64

_DER_PARSE = []


def _parse_der_sigs():
    """The C batch DER parser, or None without the extension."""
    if not _DER_PARSE:
        try:
            from fabric_tpu.native import load as _load
            _DER_PARSE.append(_load("_fastcollect").parse_der_sigs)
        except Exception:       # pragma: no cover - broken toolchain
            _DER_PARSE.append(None)
    return _DER_PARSE[0]


def _requested_platforms() -> str:
    """JAX_PLATFORMS as jax took it ("" when nobody chose)."""
    import jax
    return jax.config.jax_platforms or ""


def accelerator_devices():
    """jax.devices(), refusing a CPU nobody asked for.  Where JAX finds
    no accelerator it falls back to the CPU without a word, and a node
    configured JAXTPU would then verify on XLA:CPU; the CPU backend is
    for tests and drills that name it (JAX_PLATFORMS=cpu)."""
    import jax
    devices = jax.devices()
    if (devices[0].platform == "cpu"
            and "cpu" not in _requested_platforms().split(",")):
        raise RuntimeError(
            "BCCSP JAXTPU: JAX found no accelerator and fell back to "
            "the CPU; set JAX_PLATFORMS=cpu to run the device provider "
            "on the CPU on purpose")
    return devices


# what XLA compiled in this process, fed by jax.monitoring: every
# program a window compiles (or loads from the persistent cache) shows
# here, so a served window can prove it compiled nothing
COMPILE_STATS = {"compiles": 0, "compile_s": 0.0,
                 "cache_hits": 0, "cache_writes": 0}
_watching_compiles = False


def _watch_compiles() -> None:
    """Register the jax.monitoring listeners, once per process."""
    global _watching_compiles
    if _watching_compiles:
        return
    _watching_compiles = True
    from jax import monitoring

    def on_event(event, **_kw):
        if event == "/jax/compilation_cache/cache_hits":
            COMPILE_STATS["cache_hits"] += 1
        elif event == "/jax/compilation_cache/cache_misses":
            COMPILE_STATS["cache_writes"] += 1

    def on_duration(event, duration, **_kw):
        if event == "/jax/core/compile/backend_compile_duration":
            COMPILE_STATS["compiles"] += 1
            COMPILE_STATS["compile_s"] += duration

    monitoring.register_event_listener(on_event)
    monitoring.register_event_duration_secs_listener(on_duration)


def device_report() -> dict:
    """The installation and the devices as JAX reports them, plus the
    compile counters and each device's memory — for the node's /state.
    Only for a process that owns a device provider (it initializes the
    backend if nothing has yet)."""
    from importlib import metadata

    import jax
    import jaxlib
    devices = jax.devices()
    try:
        libtpu = metadata.version("libtpu")
    except metadata.PackageNotFoundError:
        libtpu = None
    memory = []
    for d in devices:
        ms = d.memory_stats() or {}
        memory.append({"id": d.id,
                       "bytes_in_use": ms.get("bytes_in_use"),
                       "peak_bytes_in_use": ms.get("peak_bytes_in_use"),
                       "bytes_limit": ms.get("bytes_limit")})
    return {"jax": jax.__version__, "jaxlib": jaxlib.__version__,
            "libtpu": libtpu,
            "platform": devices[0].platform,
            "device_kind": devices[0].device_kind,
            "device_count": len(devices),
            "devices": [str(d) for d in devices],
            "memory": memory,
            "compile_cache_dir": jax.config.jax_compilation_cache_dir,
            "compile": dict(COMPILE_STATS,
                            compile_s=round(COMPILE_STATS["compile_s"], 3))}


def _bucket(n: int) -> int:
    b = MIN_BUCKET
    while b < n:
        b <<= 1
    return b


class _TableBuilds:
    """The comb tables one batch makes resident: each build timed
    (`provider_table_build_seconds`), and the stretch they took recorded
    as one `provider.table_build` span under the batch's span."""

    def __init__(self, bank, clock):
        self.bank = bank
        self.clock = clock
        self.built = []              # (start, end) of each build

    def get_or_build(self, pubkey: bytes):
        n0 = self.bank.stats["builds"]
        t0 = self.clock()
        slot = self.bank.get_or_build(pubkey, pin=True)
        if self.bank.stats["builds"] > n0:
            self.built.append((t0, self.clock()))
        return slot

    def record(self, pending: "_Pending") -> None:
        if not self.built:
            return
        build_s = sum(t1 - t0 for t0, t1 in self.built)
        # the builds are not the next dispatch's packing
        pending.t_mark += build_s
        try:
            from fabric_tpu.ops_plane import registry, tracing
            hist = registry.histogram(
                "provider_table_build_seconds",
                "host build + upload of one key's comb table")
            for t0, t1 in self.built:
                hist.observe(t1 - t0)
            if pending.span.recording:
                tracing.tracer.record_span(
                    "provider.table_build", self.built[0][0],
                    self.built[-1][1], parent=pending.span.context,
                    attributes={"keys": len(self.built),
                                "build_s": round(build_s, 6)})
        except Exception:
            pass


class _Pending(list):
    """The dispatches of one batch awaiting resolve — (keep, out, post,
    record) each — with what the dispatch account needs of the batch:
    who asked, and the time its host packing is counted from."""

    def __init__(self, site: str, t_call: float):
        super().__init__()
        self.site = site
        self.t_mark = t_call
        self.span = None             # the batch's bccsp.batch_verify span
        self.snap0 = None            # the stats at enqueue, span recording


@dataclass(frozen=True)
class ProviderStats:
    """Immutable point-in-time snapshot of a JaxTpuProvider's counters
    and effective tuning — the public observability surface."""
    dispatches: int = 0
    device_sigs: int = 0
    host_rejects: int = 0
    fallbacks: int = 0
    fast_key_sigs: int = 0        # sigs that rode the fixed-comb lane
    h2d_bytes: int = 0
    p256_table_builds: int = 0
    ed25519_table_builds: int = 0
    tuning: dict = field(default_factory=dict)


class JaxTpuProvider(prov.Provider):
    name = "jaxtpu"

    def __init__(self, require_low_s: bool = True, mesh=None,
                 device=None,
                 fallback: Optional[SoftwareProvider] = None,
                 degrade: bool = False,
                 fast_row_c: Optional[int] = None,
                 rows_chunk: Optional[int] = None,
                 fast_key_threshold: Optional[int] = None,
                 max_cached_keys: Optional[int] = None):
        """Tuning knobs are per-instance constructor parameters (the
        public surface — no class-attribute monkeypatching needed);
        None means the FABRIC_TPU_* env default for that knob.

          fast_row_c          lanes per row in the fixed-base comb grid
          rows_chunk          soft per-dispatch row cap (pack/compute
                              overlap vs per-dispatch round-trip cost)
          fast_key_threshold  sigs/batch a key must bring to earn a
                              device-resident table slot
          max_cached_keys     table-bank slots (HBM residency cap)

        `mesh`: batches are sharded over it (the `sharded_*` programs).
        `device` (meshless only): the one device the banks live on and
        every dispatch runs on — the placement scheduler's one-chip
        span; None is jax's default device.

        `degrade`: on a device failure recompute the batch on
        `fallback` (counted in stats["fallbacks"]) instead of raising.
        `fallback` always serves the host-side verbs (sign, key_gen).
        """
        import os
        self.require_low_s = require_low_s
        if mesh is not None and device is not None:
            raise ValueError("a provider takes a mesh or a device, not both")
        self.mesh = mesh
        self.device = device
        self.degrade = bool(degrade)
        self.fallback = fallback or SoftwareProvider(require_low_s=require_low_s)
        devices = accelerator_devices()
        _watch_compiles()
        # the CPU backend (tests, drills) runs the kernels eagerly: the
        # whole-program scan bodies hit an XLA:CPU compile pathology
        self._on_cpu = devices[0].platform == "cpu"
        self._fns = {}
        self.stats = {"dispatches": 0, "device_sigs": 0, "host_rejects": 0,
                      "fallbacks": 0, "fast_key_sigs": 0, "h2d_bytes": 0}
        # per-key fixed-base fast path (ops/p256_fixed.py): keys whose comb
        # table is DEVICE-RESIDENT (ops/device_bank.py) skip the variable-
        # point ladder entirely; dispatches carry only slot indices, never
        # tables.  A table build costs ~150 ms host + one 1.4 MB upload, so
        # uncached keys only earn a slot when a single batch brings at
        # least `fast_key_threshold` signatures — repeat identities (org
        # endorsers, enrolled clients: the same assumption behind the
        # reference's msp/cache) amortize the build across blocks; true
        # one-off keys ride the generic ladder.
        from fabric_tpu.ops.device_bank import DeviceBank
        from fabric_tpu.ops import p256_tables as _pt
        from fabric_tpu.ops import ed25519_tables as _et
        # 256 slots ~ 370 MB HBM on TPU; the CPU test backend holds the
        # bank in host RAM, so default smaller there (still above the
        # realistic ~67-hot-key block workload: pinning makes the slot
        # count a PER-BATCH fast-lane cap)
        _default_keys = "96" if self._on_cpu else "256"
        max_keys = int(max_cached_keys if max_cached_keys is not None
                       else os.environ.get("FABRIC_TPU_KEY_CACHE",
                                           _default_keys))
        self.max_cached_keys = max_keys
        # instance geometry shadows the env-derived class defaults
        self.fast_row_c = int(fast_row_c if fast_row_c is not None
                              else self.FAST_ROW_C)
        self.rows_chunk = int(rows_chunk if rows_chunk is not None
                              else self.ROWS_CHUNK)

        def _build_p256(pk: bytes):
            if len(pk) != 65 or pk[0] != 0x04:
                return None
            qx = int.from_bytes(pk[1:33], "big")
            qy = int.from_bytes(pk[33:65], "big")
            try:
                return _pt.comb_table_for_point(qx, qy)
            except ValueError:
                return None

        def _build_ed(pk: bytes):
            aff = _et.decompress_int(bytes(pk))
            if aff is None:
                return None
            ax, ay = aff
            return _et.comb_table_for_point((-ax) % _et.P, ay)  # -A

        self.key_tables = DeviceBank(
            max_keys, (_pt.COMB_WINDOWS * _pt.COMB_ENTRIES, 2 * _pt.L),
            _build_p256, mesh=mesh, device=device)
        self.ed_key_tables = DeviceBank(
            max_keys, (_et.COMB_WINDOWS * _et.COMB_ROWS, 3 * _et.L),
            _build_ed, mesh=mesh, device=device)
        self.fast_key_threshold = int(
            fast_key_threshold if fast_key_threshold is not None
            else os.environ.get("FABRIC_TPU_FAST_KEY_THRESHOLD", "64"))
        # telemetry identity of each tile: sharded dispatches lay the
        # batch out contiguously across the mesh, so slot accounting can
        # attribute real/pad slots per chip without touching the device
        if mesh is not None:
            devs = list(np.asarray(mesh.devices).flat)
        else:
            devs = [device] if device is not None else devices[:1]
        self.device_labels = tuple(
            f"{d.platform}:{d.id}" for d in devs)
        # the account of every dispatch (dispatch_account.py); the clock
        # is an attribute so a test can script it
        self.account = DispatchAccount(self.device_labels)
        self._clock = time.perf_counter

    def stats_snapshot(self) -> ProviderStats:
        """Point-in-time copy of the provider's counters plus the table
        banks' build accounting — callers observe through this instead
        of reaching into the live mutable dicts."""
        return ProviderStats(
            **self.stats,
            p256_table_builds=self.key_tables.stats.get("builds", 0),
            ed25519_table_builds=self.ed_key_tables.stats.get("builds", 0),
            tuning={"fast_row_c": self.fast_row_c,
                    "rows_chunk": self.rows_chunk,
                    "fast_key_threshold": self.fast_key_threshold,
                    "max_cached_keys": self.max_cached_keys})

    # signing / key-gen are host-side: delegate
    def key_gen(self, scheme: str):
        return self.fallback.key_gen(scheme)

    def sign(self, private_key, payload: bytes) -> bytes:
        return self.fallback.sign(private_key, payload)

    # -- device plumbing ----------------------------------------------------

    def _get_fn(self, scheme: str):
        key = scheme
        if key not in self._fns:
            import jax
            if scheme == SCHEME_P256:
                low_s = self.require_low_s
                if self.mesh is not None:
                    from fabric_tpu.parallel import mesh as meshmod
                    f = meshmod.sharded_p256_verify(self.mesh, self.require_low_s)
                    self._fns[key] = lambda *a: f(*a)[0]
                else:
                    # windowed flat path (ops/ecp256); eager on the CPU
                    # (per-primitive jits, see flatfield)
                    from fabric_tpu.ops import ecp256
                    if self._on_cpu:
                        self._fns[key] = lambda *a: ecp256.verify_words_xla(
                            *a, require_low_s=low_s)
                    else:
                        from fabric_tpu.ops import bignum as _bn
                        tab = ecp256.comb_table_f32()

                        # words->limbs conversion inside the jit: done
                        # eagerly it is five more dispatches per call
                        def whole(qx, qy, r, s, e, _tab=tab):
                            args = [_bn.words_be_to_limbs(v)
                                    for v in (qx, qy, r, s, e)]
                            return ecp256.verify_body(
                                *args, _tab, require_low_s=low_s)
                        self._fns[key] = jax.jit(whole)
            elif scheme == "p256-rows":
                from fabric_tpu.ops import p256_fixed
                low_s = self.require_low_s
                if self.mesh is not None:
                    from fabric_tpu.parallel import mesh as meshmod
                    f = meshmod.sharded_p256_rows_verify(
                        self.mesh, self.require_low_s)
                    self._fns[key] = lambda *a: f(*a)[0]
                elif self._on_cpu:
                    self._fns[key] = (
                        lambda *a: p256_fixed.verify_words_rows(
                            *a, require_low_s=low_s))
                else:
                    self._fns[key] = jax.jit(
                        lambda *a: p256_fixed.verify_words_rows(
                            *a, require_low_s=low_s))
            elif scheme == SCHEME_ED25519:
                from fabric_tpu.ops import ed25519
                if self.mesh is not None:
                    from fabric_tpu.parallel import mesh as meshmod
                    f = meshmod.sharded_ed25519_verify(self.mesh)
                    self._fns[key] = lambda *a: f(*a)[0]
                elif self._on_cpu:
                    self._fns[key] = ed25519.verify_words
                else:
                    self._fns[key] = jax.jit(ed25519.verify_words)
            elif scheme == "idemix-pair":
                from fabric_tpu.ops import bn254_batch as bb

                def pair_fn(flags, A1, B1, A2, B2, x1, y1, x2, y2):
                    return bb.pairing_check_batch(
                        {"flags": flags, "A": A1, "B": B1},
                        {"flags": flags, "A": A2, "B": B2},
                        x1, y1, x2, y2)
                if self.mesh is not None:
                    from fabric_tpu.parallel import mesh as meshmod
                    f = meshmod.sharded_idemix_pair_verify(self.mesh)
                    self._fns[key] = lambda *a: f(*a)[0]
                elif self._on_cpu:
                    self._fns[key] = pair_fn
                else:
                    self._fns[key] = jax.jit(pair_fn)
            elif scheme == "ed25519-rows":
                from fabric_tpu.ops import ed25519
                if self.mesh is not None:
                    from fabric_tpu.parallel import mesh as meshmod
                    f = meshmod.sharded_ed25519_rows_verify(self.mesh)
                    self._fns[key] = lambda *a: f(*a)[0]
                elif self._on_cpu:
                    self._fns[key] = ed25519.verify_words_rows
                else:
                    self._fns[key] = jax.jit(ed25519.verify_words_rows)
            else:
                raise ValueError(f"unsupported scheme {scheme!r}")
            if self.device is not None:
                # host arrays go to jax's default device, and a program
                # runs where its arguments are
                fn, dev = self._fns[key], self.device

                def pinned(*a):
                    with jax.default_device(dev):
                        return fn(*a)
                self._fns[key] = pinned
        return self._fns[key]

    def _parse_p256(self, items, idxs):
        """Host-side parse: -> list of (idx, pubkey, r32, s32, e32) with
        malformed items dropped (verdict stays False).  The DER walk
        rides one C call over the whole batch when the extension is
        available (native/fastcollect.parse_der_sigs — strict DER +
        range gate, semantics mirrored by the fallback below and tested
        differentially)."""
        parse = _parse_der_sigs()
        if parse is not None:
            ok, rs = parse([items[i].signature for i in idxs])
            out = []
            for j, i in enumerate(idxs):
                it = items[i]
                pk = it.pubkey
                if (not ok[j] or len(pk) != 65 or pk[0] != 0x04
                        or len(it.payload) != 32):
                    self.stats["host_rejects"] += 1
                    continue
                out.append((i, pk, rs[64 * j:64 * j + 32],
                            rs[64 * j + 32:64 * j + 64], it.payload))
            return out
        out = []
        for i in idxs:
            it = items[i]
            try:
                pk = it.pubkey
                if len(pk) != 65 or pk[0] != 0x04:
                    raise ValueError("bad SEC1 point")
                if len(it.payload) != 32:
                    raise ValueError("p256 payload must be a 32B digest")
                ri, si = decode_dss_signature(it.signature)
                if not (0 < ri < (1 << 256) and 0 < si < (1 << 256)):
                    raise ValueError("r/s out of range")
            except Exception:
                self.stats["host_rejects"] += 1
                continue
            out.append((i, pk, ri.to_bytes(32, "big"),
                        si.to_bytes(32, "big"), it.payload))
        return out

    def _pack_p256(self, items, idxs):
        """Generic-lane packing: -> (ok_idx, [qx qy r s e] word arrays)."""
        recs = self._parse_p256(items, idxs)
        return self._pack_p256_recs(recs)

    @staticmethod
    def _pack_p256_recs(recs):
        if not recs:
            return [], None
        from fabric_tpu.ops import p256 as p256mod
        keep = [rec[0] for rec in recs]
        qx = p256mod.bytes32_to_words([rec[1][1:33] for rec in recs])
        qy = p256mod.bytes32_to_words([rec[1][33:65] for rec in recs])
        r = p256mod.bytes32_to_words([rec[2] for rec in recs])
        s = p256mod.bytes32_to_words([rec[3] for rec in recs])
        e = p256mod.bytes32_to_words([rec[4] for rec in recs])
        return keep, [qx, qy, r, s, e]

    def _pad(self, arrays, n: int):
        b = _bucket(n)
        if self.mesh is not None:
            # equal per-device tiles: the bucket must split evenly over
            # the mesh (power-of-two buckets already divide power-of-two
            # meshes; the rounding covers odd carved sub-mesh sizes)
            size = self.mesh.devices.size
            b = max(b, size)
            b += (-b) % size
        out = []
        for a in arrays:
            a = np.asarray(a)
            pad = b - a.shape[-1]
            widths = [(0, 0)] * (a.ndim - 1) + [(0, pad)]
            out.append(np.pad(a, widths))
        return out

    # -- dispatch helpers ---------------------------------------------------

    # lane-fill histogram bins: how full the padded device buckets run
    _FILL_BUCKETS = (0.125, 0.25, 0.375, 0.5, 0.625, 0.75, 0.875, 1.0,
                     float("inf"))

    def _per_device_slots(self, real: int, padded: int,
                          per_device=None) -> list:
        """[(device_label, real_d, slots_d)] for one dispatch.  Sharded
        batches are laid out contiguously over the mesh, real slots
        first, so each device's real count is a clamped prefix share;
        lanes whose pad slots interleave (rows) pass explicit counts."""
        if per_device is not None:
            return per_device
        labels = self.device_labels
        tile, rem = divmod(padded, len(labels))
        if rem:        # non-mesh-divisible dispatch: charge device 0
            return [(labels[0], real, padded)]
        return [(dev, min(max(real - i * tile, 0), tile), tile)
                for i, dev in enumerate(labels)]

    def _observe_lane(self, lane: str, real: int, padded: int,
                      per_device=None) -> None:
        """Per-dispatch batching-economics telemetry: lane fill fraction
        and padded-slot waste into the ops_plane registry (what the
        benchmark's `provider.lane_fill.*` metrics read), broken
        out per device tile so a chip running empty shards is visible.
        Guarded: observability must never break the dispatch hot path."""
        try:
            from fabric_tpu.ops_plane import registry
            fill = (real / padded) if padded else 1.0
            fill_g = registry.gauge(
                "provider_lane_fill_fraction",
                "real signatures / padded device slots, last dispatch")
            pad_c = registry.counter(
                "provider_pad_slots_total",
                "padded device slots carrying no real signature")
            slot_c = registry.counter(
                "provider_lane_slots_total",
                "device slots dispatched (real + pad)")
            for dev, r_d, s_d in self._per_device_slots(
                    real, padded, per_device):
                fill_g.set((r_d / s_d) if s_d else 1.0,
                           lane=lane, device=dev)
                pad_c.add(float(s_d - r_d), lane=lane, device=dev)
                slot_c.add(float(s_d), lane=lane, device=dev)
            registry.histogram(
                "provider_lane_fill",
                "per-dispatch lane fill fraction",
                buckets=self._FILL_BUCKETS).observe(fill, lane=lane)
        except Exception:
            pass

    def _dispatched(self, pending, lane: str, program: str, sigs: int,
                    t_enq0: float):
        """A lane's compiled program was just called (at `t_enq0`, the
        call has returned): count it and leave its record in the
        account.  Every dispatch comes through here, so the account's
        totals are the stats' own.  The call itself stays in its lane's
        own function: a first call traces and lowers the program, and
        that was measured 14 s slower for rows@384 when the call went
        through a shared `fn(*args)` helper (PERF.md §6, PR 24)."""
        t_enq1 = self._clock()
        self.stats["dispatches"] += 1
        self.stats["device_sigs"] += sigs
        rec = self.account.enqueued(lane, program, pending.site, sigs,
                                    pending.t_mark, t_enq0, t_enq1)
        pending.t_mark = t_enq1
        return rec

    def _dispatch(self, fn, keep, arrays, pending, lane="generic"):
        """Pad to buckets, chunk beyond MAX_BUCKET (bounds the compiled-
        program set while arbitrarily large blocks still use the device),
        ENQUEUE the device calls (jax dispatch is async), and record
        (keep, out, post, record) for the resolve step.  `lane` names
        the kernel, in the account and in the program (`generic@128` is
        the P-256 ladder, `ed25519@128` the Ed25519 one)."""
        for lo in range(0, len(keep), MAX_BUCKET):
            hi = min(lo + MAX_BUCKET, len(keep))
            chunk = [a[..., lo:hi] for a in arrays]
            padded = self._pad(chunk, hi - lo)
            bucket = int(np.asarray(padded[0]).shape[-1])
            t_enq0 = self._clock()
            out = fn(*padded)
            rec = self._dispatched(pending, lane, f"{lane}@{bucket}",
                                   hi - lo, t_enq0)
            self.stats["h2d_bytes"] += sum(
                np.asarray(a).nbytes for a in padded)
            self._observe_lane(lane, hi - lo, bucket)
            pending.append((keep[lo:hi], out, None, rec))

    # Row-grid geometry for the fast lane (ops/p256_fixed.verify_words_
    # rows): signatures pack key-major into rows of FAST_ROW_C lanes, so
    # ANY number of cached keys rides the comb path at constant per-sig
    # cost (the round-3 joint-one-hot kernel capped NK at 4 and spilled
    # the rest to the generic ladder).  Row counts bucket in ~1.5x steps,
    # bounding the compiled-program set; the table bank is device-
    # resident with a FIXED shape (ops/device_bank.py), so it never
    # enters the program signature and row_key values are bank slot
    # indices.  Padding rows repeat real signatures and their slots are
    # dropped at resolve time.
    FAST_ROW_C = int(__import__("os").environ.get(
        "FABRIC_TPU_FAST_ROW_C", "128"))
    # deliberately coarse (~9 programs): every bucket is a multi-minute
    # cold XLA compile; padding waste at most ~2x on small dispatches
    # where the device is idle anyway.  96 exists because ~10k-sig
    # single-key-family batches land at ~80 rows (128 would pad +60%).
    ROW_BUCKETS = (4, 16, 64, 96, 128, 256, 384, 512, 1024)
    # Soft per-dispatch row cap.  Default = the top bucket (one merged
    # dispatch).  Lowering it splits a block into several dispatches so
    # host packing overlaps device compute; whether that wins on a
    # directly attached chip is not measured (ROADMAP D6).
    ROWS_CHUNK = int(__import__("os").environ.get(
        "FABRIC_TPU_ROWS_CHUNK", "1024"))

    def _verify_p256(self, items, idxs, pending, table=None):
        """Two-lane P-256 dispatch: signatures under device-resident (or
        residency-worthy) public keys take the row-grouped fixed-base
        comb kernel in ONE merged dispatch — the key-repetitive
        endorsement workload of SURVEY.md §3.2 — and the rest take the
        generic windowed-ladder kernel.

        Lane cost model: a resident key's signatures always ride the
        comb lane (zero marginal transfer — the bank lives in HBM and
        dispatches carry slot indices only); a non-resident key earns a
        slot only when this batch brings >= fast_key_threshold
        signatures, amortizing the ~150 ms host table build + 1.4 MB
        one-time upload.

        Packing is numpy-vectorized end to end (the C DER batch parse +
        array gathers): per-signature Python work was ~60% of the
        steady-state host time at 40k sigs/block.  The rec-based path
        below remains as the no-compiler fallback and differential
        oracle.

        Two entries, one body.  From `items` at `idxs` the four fields
        are taken apart here, an item at a time, and the signatures
        parsed in one C call.  A signature `table` (the packed verb:
        `native/fastcollect.c` SigTable) IS that loop's output — the
        digests, r || s with its parse flag, the key ids into the
        block's unique keys and the batch positions, written where the
        block was walked — and enters below it; `items` and `idxs` are
        then not looked at.  From `pk_ok` on nothing knows which."""
        if table is not None:
            n = table.n_rows
            ok, rs, digests, pks = (table.ok, table.rs, table.digest,
                                    table.keys)
            sig_ok = np.frombuffer(ok, np.uint8).astype(bool)
            key_ids = np.frombuffer(table.key, np.int32).astype(np.int64)
            idxs_np = np.frombuffer(table.pos, np.int32).astype(np.int64)
        else:
            parse = _parse_der_sigs()
            if parse is None:
                return self._verify_p256_recs(items, idxs, pending)
            n = len(idxs)
            sigs = [None] * n
            pays = [None] * n
            key_ids = np.empty(n, np.int64)
            pay_ok = np.empty(n, bool)
            pk_map = {}
            pks = []
            for j, i in enumerate(idxs):
                it = items[i]
                sigs[j] = it.signature
                p = it.payload
                if len(p) == 32:
                    pays[j] = p
                    pay_ok[j] = True
                else:
                    pays[j] = _ZERO32
                    pay_ok[j] = False
                gid = pk_map.get(it.pubkey)
                if gid is None:
                    gid = pk_map[it.pubkey] = len(pks)
                    pks.append(it.pubkey)
                key_ids[j] = gid
            ok, rs = parse(sigs)
            sig_ok = np.frombuffer(ok, np.uint8).astype(bool) & pay_ok
            digests = b"".join(pays)
            idxs_np = np.asarray(idxs, np.int64)
        G = len(pks)
        pk_ok = np.empty(G, bool)
        for g, pk in enumerate(pks):
            pk_ok[g] = len(pk) == 65 and pk[0] == 0x04
        valid = sig_ok & pk_ok[key_ids]
        self.stats["host_rejects"] += n - int(valid.sum())
        if not valid.any():
            return
        rsw = np.frombuffer(rs, ">u4").reshape(n, 16).astype(np.uint32)
        ew = np.frombuffer(digests, ">u4").reshape(n, 8).astype(np.uint32)
        counts = np.bincount(key_ids[valid], minlength=G)
        slots = np.full(G, -1, np.int64)
        pinned = set()
        try:
            self._claim_slots(self.key_tables, pks, pk_ok, counts, slots,
                              pinned, pending)
            fsel = np.nonzero(valid & (slots[key_ids] >= 0))[0]
            if fsel.size:
                self._dispatch_rows_vec(fsel, key_ids, slots, rsw, ew,
                                        idxs_np, pending)
        finally:
            self.key_tables.unpin(pinned)
        gsel = np.nonzero(valid & (slots[key_ids] < 0))[0]
        if gsel.size:
            gids = np.unique(key_ids[gsel])
            remap = np.full(G, -1, np.int64)
            remap[gids] = np.arange(gids.size)
            pkb = np.frombuffer(
                b"".join(pks[g] for g in gids), np.uint8).reshape(-1, 65)
            qxw = np.ascontiguousarray(pkb[:, 1:33]).reshape(-1).view(
                ">u4").astype(np.uint32).reshape(-1, 8)
            qyw = np.ascontiguousarray(pkb[:, 33:65]).reshape(-1).view(
                ">u4").astype(np.uint32).reshape(-1, 8)
            rows = remap[key_ids[gsel]]
            arrays = [np.ascontiguousarray(qxw[rows].T),
                      np.ascontiguousarray(qyw[rows].T),
                      np.ascontiguousarray(rsw[gsel, :8].T),
                      np.ascontiguousarray(rsw[gsel, 8:].T),
                      np.ascontiguousarray(ew[gsel].T)]
            self._dispatch(self._get_fn(SCHEME_P256), idxs_np[gsel],
                           arrays, pending)

    def _claim_slots(self, bank, pks, pk_ok, counts, slots, pinned,
                     pending) -> None:
        """Fill `slots[g]` with the bank slot of each key group that
        rides the fixed-comb lane: a resident key always, a new one
        when this batch brings `fast_key_threshold` signatures under
        it.  Biggest groups claim first; each claimed slot is PINNED
        (and added to `pinned`, for the caller's `finally`) until the
        rows dispatch has captured the bank array — a later build (this
        batch or a concurrent one on another thread) must not evict it,
        or its rows would verify against the wrong table."""
        builds = _TableBuilds(bank, self._clock)
        for g in np.argsort(-counts, kind="stable"):
            g = int(g)
            if not pk_ok[g] or not counts[g]:
                continue
            pk = pks[g]
            slot = bank.lookup(pk, pin=True)
            if slot is None and counts[g] >= self.fast_key_threshold:
                slot = builds.get_or_build(pk)
            if slot is not None:
                pinned.add(slot)
                slots[g] = slot
        builds.record(pending)

    def _row_grids(self, sel, key_ids, slots, idxs_np):
        """The key-major (R, C) grids of one scheme's fast-lane items,
        built by numpy gathers and chunked by ROWS_CHUNK / ROW_BUCKETS
        like the rec path.  Yields (flat, row_key, positions, Rb) a
        dispatch: `flat` indexes the batch's word arrays cell by cell
        (padding repeats a real signature), `row_key` is each row's
        bank slot, `positions` the cells' batch positions with -1 for
        padding.  Shared by both curves' rows lanes."""
        C = self.fast_row_c
        order = sel[np.argsort(key_ids[sel], kind="stable")]
        gids, starts, ngs = np.unique(key_ids[order], return_index=True,
                                      return_counts=True)
        sel_rows, slot_rows, row_key = [], [], []
        # largest groups first: keeps per-dispatch row chunks dense
        for t in np.argsort(-ngs, kind="stable"):
            g = int(gids[t])
            s0 = int(starts[t])
            ng = int(ngs[t])
            grp = order[s0:s0 + ng]
            n_rows = -(-ng // C)
            pad = n_rows * C - ng
            so = idxs_np[grp]
            if pad:
                grp = np.concatenate([grp, np.full(pad, grp[0], np.int64)])
                so = np.concatenate([so, np.full(pad, -1, np.int64)])
            sel_rows.append(grp.reshape(n_rows, C))
            slot_rows.append(so.reshape(n_rows, C))
            row_key.extend([int(slots[g])] * n_rows)
        sel_grid = np.concatenate(sel_rows)
        slot_grid = np.concatenate(slot_rows)
        row_key = np.asarray(row_key, np.int32)
        R = sel_grid.shape[0]
        max_rows = min(self.ROW_BUCKETS[-1], max(self.rows_chunk, 1))
        for lo in range(0, R, max_rows):
            hi = min(lo + max_rows, R)
            sg, rk, og = sel_grid[lo:hi], row_key[lo:hi], slot_grid[lo:hi]
            Rb = next(b for b in self.ROW_BUCKETS if b >= hi - lo)
            if self.mesh is not None:
                size = self.mesh.devices.size
                while Rb % size:
                    Rb += 1
            if Rb > hi - lo:
                padrows = Rb - (hi - lo)
                sg = np.concatenate([sg, np.repeat(sg[:1], padrows, 0)])
                rk = np.concatenate([rk, np.repeat(rk[:1], padrows)])
                og = np.concatenate(
                    [og, np.full((padrows, C), -1, np.int64)])
            yield sg.reshape(-1), rk, og.reshape(-1), Rb

    def _dispatch_rows_vec(self, sel, key_ids, slots, rsw, ew, idxs_np,
                           pending):
        """Vectorized P-256 rows-lane packing and dispatch."""
        C = self.fast_row_c
        fn = self._get_fn("p256-rows")
        bank = self.key_tables.array()
        for flat, rk, positions, Rb in self._row_grids(sel, key_ids, slots,
                                                       idxs_np):
            words = [
                np.ascontiguousarray(rsw[flat, :8].T).reshape(8, Rb, C),
                np.ascontiguousarray(rsw[flat, 8:].T).reshape(8, Rb, C),
                np.ascontiguousarray(ew[flat].T).reshape(8, Rb, C)]
            t_enq0 = self._clock()
            out = fn(bank, rk, *words)
            self.stats["h2d_bytes"] += (
                sum(w.nbytes for w in words) + rk.nbytes)
            self._enqueue_rows_out(out, positions, pending, "rows", Rb,
                                   t_enq0)

    def _verify_p256_recs(self, items, idxs, pending):
        """Rec-based fallback lane split (no C extension)."""
        recs = self._parse_p256(items, idxs)
        groups = {}
        for rec in recs:
            groups.setdefault(rec[1], []).append(rec)
        generic, fast = [], []
        pinned = set()
        builds = _TableBuilds(self.key_tables, self._clock)
        try:
            for pk, g in sorted(groups.items(),
                                key=lambda kv: -len(kv[1])):
                slot = self.key_tables.lookup(pk, pin=True)
                if slot is None and len(g) >= self.fast_key_threshold:
                    slot = builds.get_or_build(pk)
                if slot is None:
                    generic.extend(g)
                else:
                    pinned.add(slot)
                    fast.append((slot, g))
            builds.record(pending)
            # largest groups first: keeps per-dispatch row chunks dense
            fast.sort(key=lambda t: -len(t[1]))
            if fast:
                self._dispatch_rows(fast, pending)
        finally:
            self.key_tables.unpin(pinned)
        generic.sort(key=lambda rec: rec[0])
        keep, arrays = self._pack_p256_recs(generic)
        if keep:
            self._dispatch(self._get_fn(SCHEME_P256), keep, arrays, pending)

    def _row_chunks(self, fast):
        """Pack (bank_slot, group) pairs into row-grid chunks:
        [(row_key, flat_recs, slots, Rb)], each at most the top row
        bucket, row counts padded to a bucket (and to the mesh size),
        padding slots marked -1 (dropped at resolve).  row_key entries
        are device-bank slot indices — no per-chunk table list."""
        C = self.fast_row_c
        max_rows = min(self.ROW_BUCKETS[-1], max(self.rows_chunk, 1))
        chunks = []
        cur = {"row_key": [], "recs": [], "slots": []}

        def close():
            if cur["row_key"]:
                chunks.append((cur["row_key"], cur["recs"], cur["slots"]))
                cur.update(row_key=[], recs=[], slots=[])

        for bank_slot, g in fast:
            gi = 0
            while gi < len(g):
                room = max_rows - len(cur["row_key"])
                if room == 0:
                    close()
                    room = max_rows
                take = min(len(g) - gi, room * C)
                part = g[gi:gi + take]
                gi += take
                n_rows = -(-len(part) // C)
                pad = n_rows * C - len(part)
                cur["row_key"].extend([bank_slot] * n_rows)
                cur["recs"].extend(part)
                cur["recs"].extend([part[0]] * pad)   # repeat; dropped
                cur["slots"].extend([rec[0] for rec in part])
                cur["slots"].extend([-1] * pad)
        close()

        out = []
        for row_key, frecs, slots in chunks:
            R = len(row_key)
            Rb = next(b for b in self.ROW_BUCKETS if b >= R)
            if self.mesh is not None:
                size = self.mesh.devices.size
                while Rb % size:
                    Rb += 1
            if Rb > R:
                frecs = frecs + [frecs[0]] * ((Rb - R) * C)
                slots = slots + [-1] * ((Rb - R) * C)
                row_key = row_key + [row_key[0]] * (Rb - R)
            out.append((row_key, frecs, slots, Rb))
        return out

    def _enqueue_rows_out(self, out, slots, pending, lane, rows, t_enq0):
        """One row-grid dispatch of `rows` rows, just called on `lane`
        (`rows`: P-256, `ed25519-rows`): `slots` are the batch positions
        of the grid's cells, -1 for padding (dropped at resolve)."""
        slots_np = np.asarray(slots)
        valid = slots_np >= 0
        keep = slots_np[valid]
        rec = self._dispatched(pending, lane, f"{lane}@{rows}", len(keep),
                               t_enq0)
        self.stats["fast_key_sigs"] += len(keep)
        # rows-lane pad slots interleave (within-row pad + pad rows), so
        # the per-device split counts the valid mask over each device's
        # contiguous row range instead of assuming a real-slot prefix
        per_device = None
        n_dev = len(self.device_labels)
        if len(slots_np) % n_dev == 0:
            chunk = len(slots_np) // n_dev
            per_device = [
                (dev, int(valid[i * chunk:(i + 1) * chunk].sum()), chunk)
                for i, dev in enumerate(self.device_labels)]
        self._observe_lane(lane, len(keep), len(slots_np),
                           per_device=per_device)
        pending.append(
            (keep, out, lambda a, valid=valid: a.reshape(-1)[valid], rec))

    def _dispatch_rows(self, fast, pending):
        """P-256 row-grid dispatches (fast: [(bank_slot, recs)], recs:
        (idx, pk, r32, s32, e32)).  The table bank is already in HBM —
        only r/s/e words and the slot vector cross host->device."""
        from fabric_tpu.ops import p256 as p256mod
        C = self.fast_row_c
        fn = self._get_fn("p256-rows")
        bank = self.key_tables.array()
        for row_key, frecs, slots, Rb in self._row_chunks(fast):
            words = [p256mod.bytes32_to_words(
                [rec[j] for rec in frecs]).reshape(8, Rb, C)
                for j in (2, 3, 4)]
            rk = np.asarray(row_key, dtype=np.int32)
            t_enq0 = self._clock()
            out = fn(bank, rk, *words)
            self.stats["h2d_bytes"] += (
                sum(w.nbytes for w in words) + rk.nbytes)
            self._enqueue_rows_out(out, slots, pending, "rows", Rb, t_enq0)

    def _verify_ed25519(self, items, idxs, pending):
        """Two-lane Ed25519 dispatch (the P-256 design): signatures
        under a device-resident (or residency-worthy) key ride the
        all-comb row kernel, lane `ed25519-rows`, in one merged
        dispatch; the rest decompress A on device and take the
        comb+ladder kernel, lane `ed25519`.

        Packing is numpy over the whole scheme's items, as for P-256,
        but for k = SHA-512(R || A || M) mod L: the hash runs over each
        whole message and the reduction is exact, one signature at a
        time (ops/ed25519.challenge_words) — the lanes' host cost, 13k
        messages of 1-3 KB in a mixed 10,000-tx block."""
        from fabric_tpu.ops import ed25519 as edmod
        n = len(idxs)
        sigs = [None] * n
        msgs = [None] * n
        pk_of = [None] * n
        key_ids = np.empty(n, np.int64)
        sig_ok = np.empty(n, bool)
        pk_map = {}
        pks = []
        for j, i in enumerate(idxs):
            it = items[i]
            pk_of[j] = it.pubkey
            sig = it.signature
            if len(sig) == 64:
                sigs[j] = sig
                sig_ok[j] = True
            else:
                sigs[j] = _ZERO64
                sig_ok[j] = False
            msgs[j] = it.payload
            gid = pk_map.get(pk_of[j])
            if gid is None:
                gid = pk_map[pk_of[j]] = len(pks)
                pks.append(pk_of[j])
            key_ids[j] = gid
        G = len(pks)
        pk_ok = np.fromiter((len(pk) == 32 for pk in pks), bool, G)
        valid = sig_ok & pk_ok[key_ids]
        self.stats["host_rejects"] += n - int(valid.sum())
        if not valid.any():
            return
        ry, r_sign, sw = edmod.sig_words(sigs)
        kw = edmod.challenge_words(pk_of, sigs, msgs)
        idxs_np = np.asarray(idxs, np.int64)
        counts = np.bincount(key_ids[valid], minlength=G)
        slots = np.full(G, -1, np.int64)
        pinned = set()
        try:
            self._claim_slots(self.ed_key_tables, pks, pk_ok, counts, slots,
                              pinned, pending)
            fsel = np.nonzero(valid & (slots[key_ids] >= 0))[0]
            if fsel.size:
                C = self.fast_row_c
                fn = self._get_fn("ed25519-rows")
                bank = self.ed_key_tables.array()
                for flat, rk, positions, Rb in self._row_grids(
                        fsel, key_ids, slots, idxs_np):
                    args = (
                        np.ascontiguousarray(ry[flat].T).reshape(8, Rb, C),
                        r_sign[flat].reshape(Rb, C),
                        np.ascontiguousarray(sw[flat].T).reshape(8, Rb, C),
                        np.ascontiguousarray(kw[flat].T).reshape(8, Rb, C))
                    t_enq0 = self._clock()
                    out = fn(bank, rk, *args)
                    self.stats["h2d_bytes"] += (
                        sum(a.nbytes for a in args) + rk.nbytes)
                    self._enqueue_rows_out(out, positions, pending,
                                           "ed25519-rows", Rb, t_enq0)
        finally:
            self.ed_key_tables.unpin(pinned)
        gsel = np.nonzero(valid & (slots[key_ids] < 0))[0]
        if gsel.size:
            ay, a_sign = edmod.key_words(
                [pks[g] if pk_ok[g] else _ZERO32 for g in range(G)])
            gk = key_ids[gsel]
            arrays = [np.ascontiguousarray(ay[gk].T), a_sign[gk],
                      np.ascontiguousarray(ry[gsel].T), r_sign[gsel],
                      np.ascontiguousarray(sw[gsel].T),
                      np.ascontiguousarray(kw[gsel].T)]
            self._dispatch(self._get_fn(SCHEME_ED25519), idxs_np[gsel],
                           arrays, pending, lane="ed25519")

    # -- idemix: batched BN254 pairing checks (BASELINE config 4) -----------

    IDEMIX_MIN_BUCKET = 16

    def _idemix_packed(self, ipk_bytes: bytes):
        """Per-issuer Miller-loop line precompute (w side), cached; the
        g2 side is global.  ~0.2 s host build per issuer, amortized."""
        cache = getattr(self, "_idemix_pack_cache", None)
        if cache is None:
            cache = self._idemix_pack_cache = {}
        packed = cache.get(ipk_bytes)
        if packed is None:
            from fabric_tpu.idemix import bn254 as hb
            from fabric_tpu.idemix.msp import deserialize_ipk
            from fabric_tpu.ops import bn254_batch as bb
            ipk = deserialize_ipk(ipk_bytes)
            packed = bb.pack_steps(hb.ate_precompute(ipk.w))
            cache[ipk_bytes] = packed
        return packed

    def _idemix_g2_packed(self):
        packed = getattr(self, "_idemix_g2_pack", None)
        if packed is None:
            from fabric_tpu.idemix import bn254 as hb
            from fabric_tpu.ops import bn254_batch as bb
            packed = bb.pack_steps(hb.ate_precompute(hb.G2_GEN))
            self._idemix_g2_pack = packed
        return packed

    def _verify_idemix(self, items, idxs, pending):
        """Host structural/ZK checks + ONE batched device dispatch per
        issuer for the pairing equation e(A', w) == e(Abar, g2) —
        replacing ~1.3 s of host pairing per presentation
        (/root/reference/idemix/signature.go:230 Ver's pairing check;
        the reference runs it in amcl Go loops per signature)."""
        import os
        if (self._on_cpu
                and os.environ.get("FABRIC_TPU_IDEMIX_DEVICE") != "1"):
            # CPU backend: the eager tower-field kernel is slower than
            # host python ints — keep the host path
            idemix_items = [items[i] for i in idxs]

            def _idemix_out(its=idemix_items):
                from fabric_tpu.idemix.msp import verify_item_host
                return np.array([verify_item_host(it) for it in its],
                                dtype=bool)
            pending.append((idxs, _idemix_out, None, None))
            return

        from fabric_tpu.idemix import bn254 as hb
        from fabric_tpu.idemix.msp import collect_item_parts
        from fabric_tpu.ops import bignum as bnmod

        groups = {}
        for i in idxs:
            ok, key, pair = collect_item_parts(items[i])
            if not ok:
                continue              # verdict stays False
            groups.setdefault(key, []).append((i, pair[0], pair[1]))
        fn = self._get_fn("idemix-pair")
        packed_g2 = self._idemix_g2_packed()
        for key, g in groups.items():
            packed_w = self._idemix_packed(key)
            b = self.IDEMIX_MIN_BUCKET
            while b < len(g):
                b <<= 1
            if self.mesh is not None:
                size = int(np.asarray(self.mesh.devices).size)
                b = max(b, size)
                b += (-b) % size
            padded = g + [g[0]] * (b - len(g))
            # P2 = -Abar: the kernel checks e(P1, w) * e(P2, g2) == 1
            x1 = np.stack([bnmod.int_to_limbs(p[1][0]) for p in padded], 1)
            y1 = np.stack([bnmod.int_to_limbs(p[1][1]) for p in padded], 1)
            x2 = np.stack([bnmod.int_to_limbs(p[2][0]) for p in padded], 1)
            y2 = np.stack([bnmod.int_to_limbs((hb.P - p[2][1]) % hb.P)
                           for p in padded], 1)
            t_enq0 = self._clock()
            out = fn(packed_w["flags"], packed_w["A"], packed_w["B"],
                     packed_g2["A"], packed_g2["B"], x1, y1, x2, y2)
            rec = self._dispatched(pending, "idemix", f"idemix@{b}", len(g),
                                   t_enq0)
            self._observe_lane("idemix", len(g), b)
            pending.append(([p[0] for p in g], out, None, rec))

    def idemix_pair_probe(self, batch: int = None):
        """(fn, green_args, red_args) for the BN254 dual-pairing lane:
        green checks e(G1,g2)*e(-G1,g2)==1, red e(G1,g2)^2==1 (both
        on-curve).  The probe `node/warmup.py` dispatches — callers
        must not reach into the kernel privates."""
        from fabric_tpu.idemix import bn254 as hbn
        from fabric_tpu.ops import bignum as bnmod
        b = batch or self.IDEMIX_MIN_BUCKET
        fn = self._get_fn("idemix-pair")
        packed = self._idemix_g2_packed()
        g1 = hbn.G1_GEN
        x1 = np.stack([bnmod.int_to_limbs(g1[0])] * b, 1)
        y1 = np.stack([bnmod.int_to_limbs(g1[1])] * b, 1)
        y2 = np.stack([bnmod.int_to_limbs((hbn.P - g1[1]) % hbn.P)] * b, 1)
        base = (packed["flags"], packed["A"], packed["B"],
                packed["A"], packed["B"], x1, y1, x1)
        return fn, base + (y2,), base + (y1,)

    def warm(self, generic=(), rows=(), ed25519=(), ed25519_rows=()) -> dict:
        """One dispatch at exactly each named program shape, so a
        serving process compiles nothing later.  `generic` / `ed25519`
        are the buckets of the P-256 / Ed25519 ladder lanes (powers of
        two from MIN_BUCKET), `rows` / `ed25519_rows` the row buckets
        of the two fixed-comb lanes (members of ROW_BUCKETS).  Returns
        seconds per shape, named as the account names the programs
        (`generic@128`, `rows@256`, `ed25519@128`, `ed25519-rows@128`);
        a wrong verdict raises.

        The shapes go out on one thread each: tracing a program holds
        the interpreter lock, but XLA compiles (and loads from the
        persistent cache) outside it, so the compiles of different
        shapes overlap."""
        import hashlib
        from concurrent.futures import ThreadPoolExecutor

        def signed(scheme: str, n_keys: int) -> list:
            out = []
            for i in range(n_keys):
                key = self.fallback.key_gen(scheme)
                payload = b"warm %d" % i     # Ed25519 signs the message
                if scheme == SCHEME_P256:
                    payload = hashlib.sha256(payload).digest()
                out.append(VerifyItem(scheme, key.public_bytes(),
                                      self.fallback.sign(key, payload),
                                      payload))
            return out

        jobs = []
        for lane, scheme, buckets in (("generic", SCHEME_P256, generic),
                                      ("ed25519", SCHEME_ED25519, ed25519)):
            if not buckets:
                continue
            # 128 keys, each far under fast_key_threshold per batch:
            # these stay on the ladder lane whatever the bucket
            spread = signed(scheme, MIN_BUCKET)
            for bucket in buckets:
                n = bucket if bucket == MIN_BUCKET else bucket // 2 + 1
                if (bucket != _bucket(n)
                        or -(-n // len(spread)) >= self.fast_key_threshold):
                    raise ValueError(f"{lane} bucket {bucket} cannot be "
                                     "warmed")
                jobs.append((f"{lane}@{bucket}",
                             (spread * -(-n // len(spread)))[:n]))
            # one jitted function per lane, made before the threads
            # race for it
            self._get_fn(scheme)
        for lane, scheme, bank, fn_key, buckets in (
                ("rows", SCHEME_P256, self.key_tables, "p256-rows", rows),
                ("ed25519-rows", SCHEME_ED25519, self.ed_key_tables,
                 "ed25519-rows", ed25519_rows)):
            if not buckets:
                continue
            # one resident key filling exactly `bucket` rows
            hot = signed(scheme, 1)
            bank.get_or_build(hot[0].pubkey)
            for bucket in buckets:
                if bucket not in self.ROW_BUCKETS:
                    raise ValueError(f"{lane} bucket {bucket} not in "
                                     "ROW_BUCKETS")
                jobs.append((f"{lane}@{bucket}",
                             hot * (bucket * self.fast_row_c)))
            self._get_fn(fn_key)

        def one(job):
            name, items = job
            t0 = time.perf_counter()
            with prov.dispatch_site("warmup"):
                ok = self.batch_verify(items).all()
            if not ok:
                raise RuntimeError(f"warm {name}: bad verdicts")
            return name, round(time.perf_counter() - t0, 3)

        if not jobs:
            return {}
        with ThreadPoolExecutor(len(jobs)) as pool:
            return dict(pool.map(one, jobs))

    # -- the batch verbs ----------------------------------------------------

    # The order a batch's schemes are packed and enqueued in — a
    # decision, not the scheme of whichever item came first.  One chip
    # runs its programs in order, and while one runs the host packs the
    # next, so a batch of two programs costs
    #     pack(1st) + max(device(1st), pack(2nd)) + device(2nd)
    # and is shortest with the cheaper pack first and the longer program
    # under the dearer pack.  P-256 goes first on both counts: its pack
    # is numpy over 32-byte digests the collector already made (~1
    # us/sig), Ed25519's hashes every whole message (SHA-512(R||A||M),
    # ~5 us/sig at 2 KB), and in a mixed block of one org in three on
    # Ed25519 the P-256 program is the longer one.  Idemix last: its
    # host-side checks are the dearest of all.
    SCHEME_ORDER = (SCHEME_P256, SCHEME_ED25519, SCHEME_IDEMIX)
    _VERBS = {SCHEME_P256: "_verify_p256",
              SCHEME_ED25519: "_verify_ed25519",
              SCHEME_IDEMIX: "_verify_idemix"}

    def batch_verify_async(self, items: Sequence[VerifyItem]):
        """Enqueue device verification and return resolve() -> bool[N].

        The device work races ahead while the caller keeps collecting
        (SURVEY.md §7 hard-part #3 overlap); resolve() blocks on the
        results.  A device failure — at enqueue or at resolve — raises;
        with `degrade` it recomputes the whole batch on the sw provider
        instead (atomic: never a mix of device and sw verdicts)."""
        items = prov.as_list(items)
        pending = self._open_batch(len(items))
        try:
            by_scheme = {}
            for i, it in enumerate(items):
                by_scheme.setdefault(it.scheme, []).append(i)
            for scheme in self.SCHEME_ORDER:
                idxs = by_scheme.pop(scheme, None)
                if idxs:
                    getattr(self, self._VERBS[scheme])(items, idxs, pending)
            for idxs in by_scheme.values():      # a scheme nobody verifies
                self.stats["host_rejects"] += len(idxs)
        except Exception as exc:
            return self._enqueue_failed(exc, pending, items)
        return self._resolver(pending, items)

    def batch_verify_packed_async(self, batch):
        """The same for a block's signature table (`Provider.
        batch_verify_packed_async`): the rows enter the P-256 pack as
        the arrays they are, first, as `SCHEME_ORDER` has it; the items
        of any other shape in `batch.rest` go scheme by scheme like any
        batch's, at the positions `batch.rest_pos` gives them.  One
        `_Pending`, one span, one resolve.  The lanes' programs are
        called at the depth `batch_verify_async` calls them (see
        `_dispatched`), so the scheme loop is written out in both."""
        pending = self._open_batch(len(batch))
        try:
            if batch.n_rows:
                self._verify_p256(None, None, pending, batch)
            at = np.frombuffer(batch.rest_pos, np.int32).tolist()
            items = dict(zip(at, batch.rest))       # position -> item
            by_scheme = {}
            for i, it in items.items():
                by_scheme.setdefault(it.scheme, []).append(i)
            for scheme in self.SCHEME_ORDER:
                idxs = by_scheme.pop(scheme, None)
                if idxs:
                    getattr(self, self._VERBS[scheme])(items, idxs, pending)
            for idxs in by_scheme.values():      # a scheme nobody verifies
                self.stats["host_rejects"] += len(idxs)
        except Exception as exc:
            return self._enqueue_failed(exc, pending, batch)
        return self._resolver(pending, batch)

    def _open_batch(self, n: int) -> _Pending:
        """A batch of `n` items begins: its dispatch list, stamped with
        who asked and when, and its span."""
        from fabric_tpu.ops_plane import tracing
        pending = _Pending(prov.current_site(), self._clock())
        # device-time bridge: one span per dispatched batch, started at
        # enqueue on the caller's trace and ended from whichever thread
        # resolves it, carrying batch size, block_until_ready wall time
        # and the cache-hit deltas from stats_snapshot()
        span = tracing.tracer.start_span(
            "bccsp.batch_verify", require_parent=True,
            attributes={"provider": self.name, "batch_size": n})
        pending.span = span
        pending.snap0 = self.stats_snapshot() if span.recording else None
        return pending

    def _enqueue_failed(self, exc, pending: _Pending, items):
        """A lane's pack or call raised: raise DeviceError, or with
        `degrade` verify the whole batch (`items`: a list or a signature
        table) on the sw provider at resolve."""
        span = pending.span
        if not self.degrade:
            span.end(status="ERROR")
            raise prov.DeviceError(f"device dispatch failed: {exc!r}") \
                from exc
        logger.exception(
            "TPU dispatch failed; falling back to sw provider")
        self.stats["fallbacks"] += 1
        span.set_attribute("fallback", "dispatch")

        def resolve_fallback():
            try:
                return self.fallback.batch_verify(prov.as_list(items))
            finally:
                span.end(status="ERROR")

        return resolve_fallback

    def _resolver(self, pending: _Pending, items):
        """resolve() for an enqueued batch: waits for each dispatch,
        books it, and puts the verdicts at the items' positions."""
        span = pending.span
        snap0 = pending.snap0
        n = len(items)
        verdicts = np.zeros(n, dtype=bool)
        # in-flight device work between enqueue and resolve (decremented
        # once in resolve, success or fallback)
        try:
            from fabric_tpu.ops_plane import registry as _reg
            _reg.gauge("provider_dispatch_queue_depth",
                       "device dispatches enqueued, not yet resolved"
                       ).add(float(len(pending)))
        except Exception:
            pass

        def resolve():
            t0 = time.perf_counter()
            try:
                for keep, out, post, rec in pending:
                    if callable(out):        # host-side lane (idemix on cpu)
                        out = out()
                    if rec is not None:
                        observed = self._await(out)
                        self.account.ready(rec, self._clock(), observed)
                    out = np.asarray(out)
                    if post is not None:
                        out = post(out)
                    verdicts[np.asarray(keep)] = out[:len(keep)]
            except Exception as exc:
                if not self.degrade:
                    span.end(status="ERROR")
                    self._drain_queue_depth(len(pending))
                    raise prov.DeviceError(
                        f"device resolve failed: {exc!r}") from exc
                logger.exception(
                    "TPU resolve failed; falling back to sw provider")
                self.stats["fallbacks"] += 1
                span.set_attribute("fallback", "resolve")
                span.end(status="ERROR")
                self._drain_queue_depth(len(pending))
                return self.fallback.batch_verify(prov.as_list(items))
            wall = time.perf_counter() - t0
            self._drain_queue_depth(len(pending))
            if span.recording:
                snap1 = self.stats_snapshot()
                span.set_attribute(
                    "dispatch_records",
                    [rec.as_attribute() for _, _, _, rec in pending
                     if rec is not None])
                span.set_attribute("block_until_ready_s", round(wall, 6))
                span.set_attribute(
                    "dispatches", snap1.dispatches - snap0.dispatches)
                span.set_attribute(
                    "device_sigs", snap1.device_sigs - snap0.device_sigs)
                span.set_attribute(
                    "fast_key_sigs",
                    snap1.fast_key_sigs - snap0.fast_key_sigs)
                span.set_attribute(
                    "table_builds",
                    (snap1.p256_table_builds - snap0.p256_table_builds)
                    + (snap1.ed25519_table_builds
                       - snap0.ed25519_table_builds))
                span.end()
            try:
                # device-phase observability (the jax.profiler trace is
                # the deep view; these are the always-on numbers):
                # resolve wall time ~= device tail not hidden by overlap
                from fabric_tpu.ops_plane import registry
                registry.histogram(
                    "provider_resolve_seconds",
                    "batch_verify device resolve wait").observe(wall)
                registry.counter(
                    "provider_device_sigs_total",
                    "signatures resolved on device").add(n)
            except Exception:
                pass
            return verdicts

        return resolve

    @staticmethod
    def _await(out) -> bool:
        """Block until a program's output is ready.  -> True when the
        wait saw it become ready; False when it already was, so that
        its end is only known to lie before now (or the lane computed
        on the host and there was nothing to wait for)."""
        is_ready = getattr(out, "is_ready", None)
        if is_ready is None or is_ready():
            return False
        out.block_until_ready()
        return True

    def _drain_queue_depth(self, n: int) -> None:
        if not n:
            return
        try:
            from fabric_tpu.ops_plane import registry
            registry.gauge("provider_dispatch_queue_depth",
                           "device dispatches enqueued, not yet resolved"
                           ).add(-float(n))
        except Exception:
            pass

    def batch_verify(self, items: Sequence[VerifyItem]) -> np.ndarray:
        return self.batch_verify_async(items)()
