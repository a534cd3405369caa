"""Device-mesh sharding of signature batches (the framework's ICI tier).

The reference scales block validation with per-tx goroutines capped by
`peer.validatorPoolSize` (core/committer/txvalidator/v20/validator.go:194-209,
common/semaphore) and communicates exclusively over gRPC/mTLS (SURVEY.md
§2.2).  The TPU-native design replaces the goroutine pool with a sharded
data-parallel batch: signatures are laid out on a 1-D `Mesh` over the
'batch' axis, every chip verifies its shard, and the accept/reject bitmap
plus a psum'd valid-count ride XLA collectives over ICI — no host round
trips inside a dispatch.

This module is deliberately tiny: pick a mesh, annotate shardings, let XLA
insert the collectives (the scaling-book recipe).  Every verify lane's
arguments are named, and one regex rule table maps names to
PartitionSpecs (the match_partition_rules idiom) — adding a lane means
naming its arguments, not hand-writing another spec tuple.

Sub-mesh carving (`carve_submeshes` / `allocate_devices`) splits the
device list into disjoint contiguous groups so independent channels can
each own a slice of the chips (parallel/placement.py schedules them).
"""

from __future__ import annotations

import re

import numpy as np
import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as PSpec

from fabric_tpu.ops import p256, ed25519

BATCH_AXIS = "batch"

# -- partition rules ---------------------------------------------------------
# First regex match wins.  Three placements cover every lane:
#   replicated      device-resident inputs identical on every chip (comb /
#                   niels table banks, Miller-loop line precomputes)
#   batch @ dim 0   1-D per-row / per-signature vectors (row_key, sign bits)
#   batch @ dim 1   word/limb arrays laid out (words, B) or (words, R, C)
PARTITION_RULES = (
    (r"(bank|lines|flags)", PSpec()),
    (r"sign_rows", PSpec(BATCH_AXIS, None)),
    (r"(row_key|sign|bits)", PSpec(BATCH_AXIS)),
    (r"(words|rows|limbs)", PSpec(None, BATCH_AXIS)),
)

# argument names per lane; specs are derived, never hand-listed
LANE_ARGS = {
    "p256": ("qx_words", "qy_words", "r_words", "s_words", "e_words"),
    "p256-rows": ("table_bank", "row_key", "r_rows", "s_rows", "e_rows"),
    "ed25519": ("ay_words", "a_sign", "ry_words", "r_sign", "s_words",
                "k_words"),
    "ed25519-rows": ("table_bank", "row_key", "ry_rows", "r_sign_rows",
                     "s_rows", "k_rows"),
    "idemix-pair": ("w_flags", "w_lines_a", "w_lines_b", "g2_lines_a",
                    "g2_lines_b", "x1_limbs", "y1_limbs", "x2_limbs",
                    "y2_limbs"),
}


def match_partition_rules(rules, names):
    """Resolve each argument name to its PartitionSpec via the first
    matching regex rule; unmatched names are a hard error (a silently
    replicated batch input would verify garbage on 7 of 8 chips)."""
    specs = []
    for name in names:
        for pat, spec in rules:
            if re.search(pat, name):
                specs.append(spec)
                break
        else:
            raise ValueError(f"no partition rule matches arg {name!r}")
    return tuple(specs)


def lane_specs(lane: str):
    """The in_specs tuple for a named verify lane."""
    return match_partition_rules(PARTITION_RULES, LANE_ARGS[lane])


def make_mesh(devices=None) -> Mesh:
    """1-D mesh over all (or the given) devices, batch-parallel."""
    devices = jax.devices() if devices is None else devices
    return Mesh(np.asarray(devices), (BATCH_AXIS,))


# -- sub-mesh carving (per-channel device placement) -------------------------

def allocate_devices(n_devices: int, weights) -> list:
    """Split `n_devices` into one power-of-two share per weight.

    Greedy doubling: every consumer starts at 1 device, then the most
    under-served one (highest weight per device) doubles while devices
    remain.  Power-of-two shares keep the padded-bucket series (and so
    the compiled-program set) identical across rebalances; deterministic
    tie-break by position.  Returns sizes summing to <= n_devices.
    """
    k = len(weights)
    if k == 0:
        return []
    if k > n_devices:
        raise ValueError(f"{k} consumers > {n_devices} devices")
    sizes = [1] * k
    free = n_devices - k
    while True:
        best, best_load = None, 0.0
        for i, w in enumerate(weights):
            if sizes[i] > free:
                continue
            load = max(float(w), 1e-9) / sizes[i]
            if load > best_load:
                best, best_load = i, load
        if best is None:
            return sizes
        free -= sizes[best]
        sizes[best] *= 2


def carve_submeshes(devices, weights) -> list:
    """Disjoint contiguous sub-meshes over `devices`, one per weight,
    sized by `allocate_devices`.  Contiguous spans keep each sub-mesh on
    neighbouring chips (ICI locality on a real slice)."""
    sizes = allocate_devices(len(devices), weights)
    out, lo = [], 0
    for sz in sizes:
        out.append(make_mesh(list(devices)[lo:lo + sz]))
        lo += sz
    return out


def pad_batch(arrays, batch: int, multiple: int):
    """Pad the trailing batch dim of each (.., B) array up to a multiple.

    Returns (padded_arrays, padded_batch).  Padding rows are zeros, which
    always verify False — harmless for verdict consumers that slice [:batch].
    """
    rem = batch % multiple
    if rem == 0:
        return arrays, batch
    pad = multiple - rem
    out = []
    for a in arrays:
        widths = [(0, 0)] * (a.ndim - 1) + [(0, pad)]
        out.append(np.pad(np.asarray(a), widths))
    return out, batch + pad


def sharded_p256_verify(mesh: Mesh, require_low_s: bool = True):
    """Build a jitted sharded ECDSA-P256 batch verifier over `mesh`.

    Returns fn(qx, qy, r, s, e) -> (verdicts (B,), valid_count ()) where all
    inputs are (8, B) uint32 with B divisible by mesh size.  The count is
    all-reduced with psum across the mesh (the verdict bitmap equivalent of
    the reference's TRANSACTIONS_FILTER aggregation).
    """
    def local(qx, qy, r, s, e):
        v = p256.verify_words(qx, qy, r, s, e, require_low_s=require_low_s)
        count = jax.lax.psum(jnp.sum(v.astype(jnp.int32)), BATCH_AXIS)
        return v, count

    fn = jax.shard_map(
        local, mesh=mesh,
        in_specs=lane_specs("p256"),
        out_specs=(PSpec(BATCH_AXIS), PSpec()))
    return jax.jit(fn)


def sharded_p256_rows_verify(mesh: Mesh, require_low_s: bool = True):
    """Sharded row-grouped multikey P-256 verifier (the production fast
    lane, ops/p256_fixed.verify_words_rows).

    fn(bank, row_key, r, s, e) -> (verdicts (R, C), valid_count ()): the
    stacked per-key table bank replicates to every device; rows shard
    over the batch axis (R divisible by mesh size — the provider pads).
    """
    from fabric_tpu.ops import p256_fixed

    def local(bank, row_key, r, s, e):
        v = p256_fixed.verify_words_rows(
            bank, row_key, r, s, e, require_low_s=require_low_s)
        count = jax.lax.psum(jnp.sum(v.astype(jnp.int32)), BATCH_AXIS)
        return v, count

    fn = jax.shard_map(
        local, mesh=mesh,
        in_specs=lane_specs("p256-rows"),
        out_specs=(PSpec(BATCH_AXIS), PSpec()))
    return jax.jit(fn)


def sharded_ed25519_rows_verify(mesh: Mesh):
    """Sharded row-grouped multikey ed25519 verifier (the fast lane,
    ops/ed25519.verify_words_rows): the niels table bank replicates;
    rows shard over the batch axis."""
    from fabric_tpu.ops import ed25519

    def local(bank, row_key, ry, r_sign, s, k):
        v = ed25519.verify_words_rows(bank, row_key, ry, r_sign, s, k)
        count = jax.lax.psum(jnp.sum(v.astype(jnp.int32)), BATCH_AXIS)
        return v, count

    fn = jax.shard_map(
        local, mesh=mesh,
        in_specs=lane_specs("ed25519-rows"),
        out_specs=(PSpec(BATCH_AXIS), PSpec()))
    return jax.jit(fn)


def sharded_ed25519_verify(mesh: Mesh):
    """Build a jitted sharded ed25519 batch verifier over `mesh`.

    fn(ay, a_sign, ry, r_sign, s, k) -> (verdicts (B,), valid_count ()).
    """
    def local(ay, a_sign, ry, r_sign, s, k):
        v = ed25519.verify_words(ay, a_sign, ry, r_sign, s, k)
        count = jax.lax.psum(jnp.sum(v.astype(jnp.int32)), BATCH_AXIS)
        return v, count

    fn = jax.shard_map(
        local, mesh=mesh,
        in_specs=lane_specs("ed25519"),
        out_specs=(PSpec(BATCH_AXIS), PSpec()))
    return jax.jit(fn)


def sharded_idemix_pair_verify(mesh: Mesh):
    """Sharded BN254 dual-pairing batch check (the idemix lane,
    ops/bn254_batch.pairing_check_batch).

    fn(flags, A1, B1, A2, B2, x1, y1, x2, y2) -> (verdicts (B,),
    valid_count ()): the Miller-loop line precomputes (w and g2 sides)
    replicate to every device; the per-presentation G1 limb coordinates
    (L, B) shard over the batch axis, B divisible by mesh size.
    """
    from fabric_tpu.ops import bn254_batch as bb

    def local(flags, A1, B1, A2, B2, x1, y1, x2, y2):
        v = bb.pairing_check_batch(
            {"flags": flags, "A": A1, "B": B1},
            {"flags": flags, "A": A2, "B": B2}, x1, y1, x2, y2)
        count = jax.lax.psum(jnp.sum(v.astype(jnp.int32)), BATCH_AXIS)
        return v, count

    fn = jax.shard_map(
        local, mesh=mesh,
        in_specs=lane_specs("idemix-pair"),
        out_specs=(PSpec(BATCH_AXIS), PSpec()))
    return jax.jit(fn)
