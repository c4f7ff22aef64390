"""Public wrappers for the fused similarity+top-k lookup.

Two entry points:

  * ``similarity_topk``        — one store: db [N, D] -> (scores, idx) [Q, k]
  * ``similarity_topk_lanes``  — a whole StoreBank: db [L, N, D] -> [Q, L, k],
    every hierarchy level / shard lane scored in ONE kernel dispatch.

``interpret=None`` (the default) auto-selects the backend via
``repro.kernels.backend``: interpret mode on CPU, the compiled Pallas kernel
on TPU/GPU. ``block_n=None`` resolves to the ``REPRO_TOPK_BLOCK_N`` env
override (else 512 — a CPU-friendly default; sweep ``benchmarks/tune_topk.py``
on real TPU/GPU hardware and export the winner). ``grid_order`` likewise
honors ``REPRO_TOPK_GRID_ORDER`` (``lanes_outer`` | ``blocks_outer``).

The lanes entry point accepts a per-lane *metric tag* tuple (mixed
cosine/dot hierarchies): scores are computed as raw dots against
unit-normalized cosine rows, then cosine lanes are rescaled by 1/|q| — a
positive per-query scale, so per-lane rankings (and therefore the top-k
indices) are exact, and the returned scores are true cosines.

Both wrappers hand the kernel its valid flags as one f32 row per lane
([1, N], or [L, 1, N]), not as an [N, 1] column: under the TPU's (8, 128)
tile a trailing 1 pads to 128 lanes, so the column costs 512 bytes of HBM a
row, written on every read and streamed back beside the rows.

Each wrapper counts its host-level invocations so tests and benchmarks can
assert dispatch budgets (``dispatch_count`` / ``reset_dispatch_count``).
"""
from __future__ import annotations

import functools
import os
from typing import Optional, Tuple, Union

import jax
import jax.numpy as jnp

from repro.kernels.backend import resolve_interpret
from repro.kernels.similarity_topk.kernel import (
    similarity_topk_blocks,
    similarity_topk_lanes_blocks,
)

_dispatches = 0  # host-level kernel dispatches (single + lanes)

_GRID_ORDERS = ("lanes_outer", "blocks_outer")


def record_dispatch(n: int = 1) -> None:
    """Count a dispatch issued outside these wrappers (e.g. a StoreBank
    search that inlines the kernel body under its own jit)."""
    global _dispatches
    _dispatches += n


def dispatch_count() -> int:
    return _dispatches


def reset_dispatch_count() -> None:
    global _dispatches
    _dispatches = 0


def default_block_n() -> int:
    """The lanes/blocks tile size: ``REPRO_TOPK_BLOCK_N`` env override, else
    512 (the CPU-interpret default). Must be a multiple of 128 (MXU lanes)."""
    raw = os.environ.get("REPRO_TOPK_BLOCK_N")
    if raw is None:
        return 512
    v = int(raw)  # repro: noqa[RA101] — env string at trace time, not a traced value
    if v <= 0 or v % 128:
        raise ValueError(
            f"REPRO_TOPK_BLOCK_N={raw!r}: expected a positive multiple of 128"
        )
    return v


def default_grid_order() -> str:
    raw = os.environ.get("REPRO_TOPK_GRID_ORDER")
    if raw is None:
        return "lanes_outer"
    v = raw.strip().lower()
    if v not in _GRID_ORDERS:
        raise ValueError(
            f"REPRO_TOPK_GRID_ORDER={raw!r}: expected one of {_GRID_ORDERS}"
        )
    return v


def apply_topk_tuning(
    block_n: "int | None" = None, grid_order: "str | None" = None
) -> None:
    """Install config-level tuning defaults for the top-k kernels.

    The launch configs bake the winners of the ``benchmarks/tune_topk.py``
    sweep here (``ModelConfig.topk_block_n`` / ``topk_grid_order``). Values
    land via ``os.environ.setdefault``, so an explicit
    ``REPRO_TOPK_BLOCK_N`` / ``REPRO_TOPK_GRID_ORDER`` in the environment
    always wins over the config. Invalid values fail fast here rather than
    at first kernel trace."""
    if block_n is not None:
        if block_n <= 0 or block_n % 128:
            raise ValueError(
                f"topk_block_n={block_n!r}: expected a positive multiple of 128"
            )
        os.environ.setdefault("REPRO_TOPK_BLOCK_N", str(block_n))
    if grid_order is not None:
        if grid_order not in _GRID_ORDERS:
            raise ValueError(
                f"topk_grid_order={grid_order!r}: expected one of {_GRID_ORDERS}"
            )
        os.environ.setdefault("REPRO_TOPK_GRID_ORDER", grid_order)


def _block_for(N: int, block_n: int) -> int:
    bn = min(block_n, max(128, 1 << (N - 1).bit_length()))
    return min(bn, block_n)


@functools.partial(jax.jit, static_argnames=("k", "metric", "block_n", "interpret"))
def _similarity_topk(db, valid, q, *, k: int, metric: str, block_n: int, interpret: bool):
    """db [N, D], valid [N] bool, q [Q, D] -> (scores [Q,k], idx [Q,k]).

    cosine is handled by pre-normalizing both sides (dot == cosine on unit
    vectors), keeping the kernel a pure MXU dot. N is padded to a block
    multiple with invalid entries.
    """
    db = db.astype(jnp.float32)
    q = q.astype(jnp.float32)
    if metric == "cosine":
        db = db / jnp.maximum(jnp.linalg.norm(db, axis=-1, keepdims=True), 1e-9)
        q = q / jnp.maximum(jnp.linalg.norm(q, axis=-1, keepdims=True), 1e-9)
    elif metric != "dot":
        raise ValueError(f"kernel path supports cosine/dot; got {metric!r}")

    N, D = db.shape
    bn = _block_for(N, block_n)
    pad_n = (-N) % bn
    if pad_n:
        db = jnp.pad(db, ((0, pad_n), (0, 0)))
        valid = jnp.pad(valid, (0, pad_n))
    valid_f32 = valid.astype(jnp.float32)[None, :]  # [1, N]: lane-dense (kernel.py)

    bs, bi = similarity_topk_blocks(db, valid_f32, q, k=k, block_n=bn, interpret=interpret)
    # merge the [nb, Q, k] candidates: one tiny global top-k
    Q = q.shape[0]
    flat_s = bs.transpose(1, 0, 2).reshape(Q, -1)
    flat_i = bi.transpose(1, 0, 2).reshape(Q, -1)
    top_s, pos = jax.lax.top_k(flat_s, k)
    top_i = jnp.take_along_axis(flat_i, pos, axis=1)
    top_s = jnp.where(top_s <= jnp.float32(-1.0e38), -jnp.inf, top_s)
    return top_s, top_i


def similarity_topk(db, valid, q, *, k: int, metric: str = "cosine",
                    block_n: Optional[int] = None, interpret: Optional[bool] = None):
    """db [N, D], valid [N] bool, q [Q, D] -> (scores [Q,k], idx [Q,k]).

    ``interpret=None`` auto-selects: interpret on CPU, compiled elsewhere.
    ``block_n=None`` resolves the ``REPRO_TOPK_BLOCK_N`` override.
    """
    record_dispatch()
    return _similarity_topk(
        db, valid, q, k=k, metric=metric,
        block_n=default_block_n() if block_n is None else block_n,
        interpret=resolve_interpret(interpret),
    )


@functools.partial(jax.jit, static_argnames=(
    "k", "metric", "block_n", "interpret", "prenormalized", "grid_order"))
def _similarity_topk_lanes(db, valid, q, *, k: int, metric: Tuple[str, ...],
                           block_n: Optional[int], interpret: bool,
                           prenormalized: bool, grid_order: Optional[str] = None):
    """db [L, N, D], valid [L, N] bool, q [Q, D] -> ([Q, L, k], [Q, L, k]).

    Lane indices are lane-local (0..N), matching what L separate
    ``similarity_topk`` calls would return — candidates are never merged
    across lanes; the caller (the hierarchy / bank) owns cross-lane policy.
    ``metric`` is a per-lane tuple (a 1-tuple broadcasts to every lane);
    uniform-cosine banks pre-normalize q once, while mixed cosine/dot banks
    require ``prenormalized=True`` (unit cosine rows — StoreBank's insert
    invariant) and rescale cosine lanes' dot scores by 1/|q| after the
    kernel, which preserves per-lane rankings exactly.
    """
    L = db.shape[0]
    metrics = tuple(metric) if len(metric) > 1 else tuple(metric) * L
    bad = [m for m in metrics if m not in ("cosine", "dot")]
    if bad:
        raise ValueError(f"kernel path supports cosine/dot; got {bad!r}")
    mixed = len(set(metrics)) > 1

    db = db.astype(jnp.float32)
    q = q.astype(jnp.float32)
    cos_scale = None
    if mixed:
        if not prenormalized:
            raise ValueError(
                "mixed-metric lanes require prenormalized (unit) cosine rows"
            )
        # raw q against unit cosine rows: dot / |q| == cosine; dot lanes raw
        cos_scale = 1.0 / jnp.maximum(jnp.linalg.norm(q, axis=-1), 1e-9)  # [Q]
    elif metrics[0] == "cosine":
        if not prenormalized:
            db = db / jnp.maximum(jnp.linalg.norm(db, axis=-1, keepdims=True), 1e-9)
        q = q / jnp.maximum(jnp.linalg.norm(q, axis=-1, keepdims=True), 1e-9)

    _, N, D = db.shape
    block_n = default_block_n() if block_n is None else block_n
    bn = _block_for(N, block_n)
    pad_n = (-N) % bn
    if pad_n:
        db = jnp.pad(db, ((0, 0), (0, pad_n), (0, 0)))
        valid = jnp.pad(valid, ((0, 0), (0, pad_n)))
    valid_f32 = valid.astype(jnp.float32)[:, None, :]  # [L, 1, N]: lane-dense (kernel.py)

    bs, bi = similarity_topk_lanes_blocks(
        db, valid_f32, q, k=k, block_n=bn, interpret=interpret,
        grid_order=default_grid_order() if grid_order is None else grid_order,
    )
    # merge per lane: [L, nb, Q, k] -> [L, Q, nb*k] -> top-k -> [Q, L, k]
    Q = q.shape[0]
    flat_s = bs.transpose(0, 2, 1, 3).reshape(L, Q, -1)
    flat_i = bi.transpose(0, 2, 1, 3).reshape(L, Q, -1)
    top_s, pos = jax.lax.top_k(flat_s, k)
    top_i = jnp.take_along_axis(flat_i, pos, axis=2)
    top_s = jnp.where(top_s <= jnp.float32(-1.0e38), -jnp.inf, top_s)
    top_s = top_s.transpose(1, 0, 2)  # [Q, L, k]
    top_i = top_i.transpose(1, 0, 2)
    if cos_scale is not None:
        is_cos = jnp.asarray([m == "cosine" for m in metrics])  # [L]
        top_s = jnp.where(
            is_cos[None, :, None], top_s * cos_scale[:, None, None], top_s
        )
    return top_s, top_i


def similarity_topk_lanes(db, valid, q, *, k: int,
                          metric: Union[str, Tuple[str, ...]] = "cosine",
                          block_n: Optional[int] = None,
                          interpret: Optional[bool] = None,
                          prenormalized: bool = False,
                          grid_order: Optional[str] = None):
    """Fused multi-lane lookup: db [L, N, D], valid [L, N], q [Q, D] ->
    (scores [Q, L, k], lane-local idx [Q, L, k]) in ONE kernel dispatch.
    ``metric`` may be one name for every lane or a per-lane tuple."""
    record_dispatch()
    metrics = (metric,) if isinstance(metric, str) else tuple(metric)
    return _similarity_topk_lanes(
        db, valid, q, k=k, metric=metrics,
        block_n=default_block_n() if block_n is None else block_n,
        interpret=resolve_interpret(interpret), prenormalized=prenormalized,
        grid_order=default_grid_order() if grid_order is None else grid_order,
    )
