"""Kernel backend selection: compiled Pallas vs interpret mode.

Every kernel package's public wrapper takes ``interpret=None`` and resolves
it here: interpret mode (the kernel body runs in Python) is only the right
default on CPU, where Mosaic/Triton lowering is unavailable — on TPU/GPU the
compiled Pallas path is selected automatically, so the kernels we wrote are
actually the ones that run in production.

Selection matrix (first match wins):

    explicit ``interpret=...`` at the call site   -> as given
    ``set_interpret_override(...)`` (config hook) -> the override
    ``REPRO_KERNEL_INTERPRET`` env var            -> truthy/falsy value
    ``jax.default_backend() == "cpu"``            -> interpret
    otherwise (tpu, gpu, ...)                     -> compiled
"""
from __future__ import annotations

import os
from typing import Optional

import jax

_TRUTHY = ("1", "true", "yes", "on", "interpret")
_FALSY = ("0", "false", "no", "off", "compiled")

# process-wide config override (set_interpret_override); None = auto
_override: Optional[bool] = None


def set_interpret_override(value: Optional[bool]) -> None:
    """Force interpret (True), compiled (False), or auto (None) for every
    kernel call that does not pass ``interpret`` explicitly."""
    global _override
    _override = value


def get_interpret_override() -> Optional[bool]:
    return _override


def _env_override() -> Optional[bool]:
    raw = os.environ.get("REPRO_KERNEL_INTERPRET")
    if raw is None:
        return None
    v = raw.strip().lower()
    if v in _TRUTHY:
        return True
    if v in _FALSY:
        return False
    raise ValueError(
        f"REPRO_KERNEL_INTERPRET={raw!r}: expected one of {_TRUTHY + _FALSY}"
    )


def resolve_interpret(interpret: Optional[bool] = None) -> bool:
    """Resolve the effective interpret flag for one kernel dispatch."""
    if interpret is not None:
        return bool(interpret)
    if _override is not None:
        return _override
    env = _env_override()
    if env is not None:
        return env
    return jax.default_backend() == "cpu"


# platforms whose runtimes expose a pinned_host memory space worth staging
# through (the CPU backend's host memory is the device memory already)
_PINNED_PLATFORMS = ("tpu", "gpu")


def pinned_host_supported(platform: Optional[str] = None) -> bool:
    """Whether host blocks bound for ``platform`` (default: the default
    backend) stage through pinned host memory. Chosen from the platform: TPU
    and GPU runtimes DMA from pinned pages; on CPU there is nothing to gain."""
    return (platform or jax.default_backend()) in _PINNED_PLATFORMS


def _replicated_like(like, memory_kind: Optional[str] = None):
    """A sharding that replicates a block over the devices ``like`` lives on
    (its mesh for a named sharding, else its single device)."""
    from jax.sharding import NamedSharding, PartitionSpec, SingleDeviceSharding

    sh = like.sharding
    if isinstance(sh, NamedSharding):
        return NamedSharding(sh.mesh, PartitionSpec(), memory_kind=memory_kind)
    (dev,) = sh.device_set
    return SingleDeviceSharding(dev, memory_kind=memory_kind)


def stage_pinned(rows, like):
    """Stage a host block for an upcoming device scatter into the array
    ``like`` (the destination buffer): on TPU/GPU the block is copied into
    pinned host memory on ``like``'s own devices and DMA'd from there,
    replicated over them the way the scatter consumes it; on CPU the pageable
    numpy block is returned unchanged."""
    platform = next(iter(like.sharding.device_set)).platform
    if not pinned_host_supported(platform):
        return rows
    pinned = jax.device_put(rows, _replicated_like(like, "pinned_host"))
    return jax.device_put(pinned, _replicated_like(like))
