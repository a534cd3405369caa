"""BCCSP factory: config-gated provider selection.

Mirror of the reference's bccsp/factory (factory.go:42 GetDefault,
nopkcs11.go:19-28 FactoryOpts / InitFactories, selected by the BCCSP
section of core.yaml — sampleconfig/core.yaml:287-303).  Here the options
are `SW` and `JAXTPU` (the latter replacing the PKCS11 hardware slot).
"""

from __future__ import annotations

import logging
import os
from dataclasses import dataclass
from typing import Optional

from .provider import Provider
from .sw import SoftwareProvider

logger = logging.getLogger("fabric_tpu.bccsp.factory")

_default: Optional[Provider] = None


@dataclass
class FactoryOpts:
    """The BCCSP config block (core.yaml `bccsp:` equivalent)."""
    default: str = "JAXTPU"          # "SW" | "JAXTPU"
    require_low_s: bool = True
    use_mesh: bool = False           # shard batches over all visible devices
    placement: bool = False          # per-channel device placement: carve
    #                                  the mesh into sub-meshes sized by
    #                                  channel queue depth (parallel/placement)
    mesh_devices: Optional[int] = None   # cap the device count the mesh /
    #                                  placement scheduler may use (None: all)
    degrade: Optional[bool] = None   # on device failure re-verify the
    #                                  batch on SW (breaker in front).
    #                                  None = auto: ON for JAXTPU (a node
    #                                  that loses its accelerator keeps
    #                                  committing on SW, healthz flags it),
    #                                  OFF for SW.  Explicit False is
    #                                  fail-stop: a device error reaches
    #                                  the caller, nothing is recomputed.


# the one in-code home of the persistent compilation cache: a fixed path
# inside the checkout (the path is part of the cache key — a directory
# that moves never hits)
_CHECKOUT_CACHE = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))), ".cache", "jax")


def enable_compile_cache() -> str:
    """Where compiled programs persist across processes; returns the
    directory.  JAX_COMPILATION_CACHE_DIR is the outside handle: when it
    is set jax reads it itself and nothing is set in code.  Otherwise
    the cache lives at <checkout>/.cache/jax."""
    env_dir = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env_dir:
        return env_dir
    import jax
    jax.config.update("jax_compilation_cache_dir", _CHECKOUT_CACHE)
    return _CHECKOUT_CACHE


_placement = None                # PlacementScheduler when opts.placement


def init_factories(opts: Optional[FactoryOpts] = None) -> Provider:
    """Initialize the default provider (InitFactories equivalent)."""
    global _default, _placement
    opts = opts or FactoryOpts()
    kind = opts.default.upper()
    degrade = (kind == "JAXTPU") if opts.degrade is None else \
        bool(opts.degrade)
    _placement = None
    if kind == "SW":
        _default = SoftwareProvider(require_low_s=opts.require_low_s)
    elif kind == "JAXTPU":
        enable_compile_cache()
        from .jaxtpu import JaxTpuProvider, accelerator_devices
        devices = accelerator_devices()
        if opts.mesh_devices:
            devices = devices[:opts.mesh_devices]
        mesh = None
        if opts.use_mesh and len(devices) > 1:
            from fabric_tpu.parallel import mesh as meshmod
            mesh = meshmod.make_mesh(devices)
        _default = JaxTpuProvider(require_low_s=opts.require_low_s,
                                  mesh=mesh, degrade=degrade)
        if opts.placement and len(devices) > 1:
            from fabric_tpu.parallel.placement import PlacementScheduler
            wrap = None
            if degrade:
                from .degrade import DegradingProvider
                low_s = opts.require_low_s

                def wrap(p):
                    return DegradingProvider(
                        p, SoftwareProvider(require_low_s=low_s))
            _placement = PlacementScheduler(
                devices=devices,
                provider_factory=lambda m, d: JaxTpuProvider(
                    require_low_s=opts.require_low_s, mesh=m, device=d,
                    degrade=degrade),
                wrap=wrap)
    else:
        raise ValueError(f"unknown BCCSP provider {opts.default!r}")
    if degrade:
        from .degrade import DegradingProvider
        _default = DegradingProvider(
            _default, SoftwareProvider(require_low_s=opts.require_low_s))
    logger.info("BCCSP default provider: %s", _default.name)
    return _default


def get_placement():
    """The PlacementScheduler, or None when placement is off / SW."""
    return _placement


def provider_for_channel(channel_id: str,
                         demand: Optional[int] = None) -> Optional[Provider]:
    """Per-channel provider from the placement scheduler, or None when
    placement is disabled (callers fall back to the default provider).
    `demand` is the caller's current queue depth — it sizes the
    channel's device span on the next carve."""
    if _placement is None:
        return None
    return _placement.provider_for(channel_id, demand=demand)


def get_default() -> Provider:
    """GetDefault equivalent.  A process that never called
    init_factories gets the software provider: clients, admin tools and
    launchers reach this through every handshake and identity check,
    and must not take the chip from the node that asked for it."""
    global _default
    if _default is None:
        init_factories(FactoryOpts(default="SW"))
    return _default


def set_default(p: Provider) -> None:
    global _default
    _default = p
