"""Production mesh construction.

A FUNCTION, not a module-level constant: importing this module never touches
jax device state (device count is locked at first jax init, and only
dryrun.py is allowed to force 512 host devices).

Axes:
  pod   — DCN axis across pods (multi-pod only)
  data  — in-pod data-parallel / FSDP / context-parallel axis
  model — tensor/expert-parallel axis
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def _mesh(shape, axes):
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _mesh(shape, axes)


def make_test_mesh(shape=(2, 2), axes=("data", "model")):
    """Small mesh for CI-scale sharding tests (requires >= prod(shape) devices)."""
    return _mesh(shape, axes)


def make_cache_mesh(n_shards=None):
    """1-axis ("data",) mesh for a sharded cache DB: the store's key-sharded
    lanes spread over ``n_shards`` devices (default: all available). The
    sharded read path only collectives over pod/data axes, so a cache-only
    deployment never needs a model axis."""
    n = len(jax.devices()) if n_shards is None else int(n_shards)
    return _mesh((n,), ("data",))
