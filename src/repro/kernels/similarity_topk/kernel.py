"""Pallas TPU kernel: fused similarity scoring + per-block top-k.

The cache database [N, D] streams through VMEM in [block_n, D] tiles; the
query block [Q, D] stays resident. Each grid step computes the [Q, block_n]
score tile on the MXU and extracts its top-k by k rounds of masked max
(k is small — 4..16 — so this beats a sort and needs no sort primitive,
which Mosaic does not provide). The tiny [nb, Q, k] candidate tensor is
merged by ops.py.

The valid flags arrive lane-dense: f32 [1, N] (single) or [L, 1, N] (lanes),
one [1, block_n] tile a step, which broadcasts against the [Q, block_n] score
tile as it stands. A column layout ([N, 1]) would cost 128x its size: the
TPU's (8, 128) tile pads a trailing dimension of 1 to 128 lanes, so 1M flags
become a 512 MiB buffer in HBM that the wrapper writes and every step streams
back (256 KB of padding per 1.5 MB row tile at D=768).

VMEM budget per step: block_n*D*4 + Q*D*4 + Q*block_n*4 + 8*block_n*4 bytes
(the flag tile's one row pads to 8 sublanes); block_n=512, D=1024, Q<=16 =>
~2.1 MB + 64 KB + 32 KB + 16 KB — comfortably resident, and block_n is a
lane-aligned multiple of 128 for the MXU.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

NEG = -3.0e38  # python literal: jnp constants would be captured consts in the kernel


def _topk_block_kernel(db_ref, valid_ref, q_ref, out_s_ref, out_i_ref, *, k: int, block_n: int):
    j = pl.program_id(0)
    db = db_ref[...]  # [block_n, D]
    q = q_ref[...]  # [Q, D]
    valid = valid_ref[...]  # [1, block_n] f32 (1.0 = valid)

    s = jax.lax.dot_general(
        q.astype(jnp.float32),
        db.astype(jnp.float32),
        (((1,), (1,)), ((), ())),
        precision=jax.lax.Precision.HIGHEST,  # f32 scores: thresholds sit on them
        preferred_element_type=jnp.float32,
    )  # [Q, block_n]
    s = jnp.where(valid > 0.5, s, NEG)  # [1, block_n] row broadcasts over Q

    Q = s.shape[0]
    col = jax.lax.broadcasted_iota(jnp.int32, (Q, block_n), 1)
    base = j * block_n
    for t in range(k):  # static unroll: k rounds of masked max-extract
        m = jnp.max(s, axis=1)  # [Q]
        hit = s >= m[:, None]
        idx = jnp.min(jnp.where(hit, col, jnp.int32(2**30)), axis=1)  # first argmax
        out_s_ref[0, :, t] = m
        out_i_ref[0, :, t] = idx + base
        s = jnp.where(col == idx[:, None], NEG, s)


def _topk_lanes_kernel(db_ref, valid_ref, q_ref, out_s_ref, out_i_ref, *, k: int,
                       block_n: int, block_axis: int = 1):
    """Batched-lanes variant: grid (L, nb) — one lane (hierarchy level or DB
    shard) per row of the grid, so L levels x nb blocks stream through VMEM
    in ONE pallas dispatch instead of L sequential kernel launches.
    ``block_axis`` names which grid axis walks the blocks (1 for the default
    lanes-outer order, 0 for blocks-outer)."""
    j = pl.program_id(block_axis)  # block within the lane
    db = db_ref[0]  # [block_n, D] (lane-sliced by the BlockSpec)
    q = q_ref[...]  # [Q, D]
    valid = valid_ref[0]  # [1, block_n] f32 (1.0 = valid)

    s = jax.lax.dot_general(
        q.astype(jnp.float32),
        db.astype(jnp.float32),
        (((1,), (1,)), ((), ())),
        precision=jax.lax.Precision.HIGHEST,  # f32 scores: thresholds sit on them
        preferred_element_type=jnp.float32,
    )  # [Q, block_n]
    s = jnp.where(valid > 0.5, s, NEG)  # [1, block_n] row broadcasts over Q

    Q = s.shape[0]
    col = jax.lax.broadcasted_iota(jnp.int32, (Q, block_n), 1)
    base = j * block_n  # indices stay lane-local; ops.py keeps lanes separate
    for t in range(k):
        m = jnp.max(s, axis=1)
        hit = s >= m[:, None]
        idx = jnp.min(jnp.where(hit, col, jnp.int32(2**30)), axis=1)
        out_s_ref[0, 0, :, t] = m
        out_i_ref[0, 0, :, t] = idx + base
        s = jnp.where(col == idx[:, None], NEG, s)


@functools.partial(jax.jit, static_argnames=("k", "block_n", "interpret", "grid_order"))
def similarity_topk_lanes_blocks(db, valid_f32, q, *, k: int, interpret: bool,
                                 block_n: int = 512,
                                 grid_order: str = "lanes_outer"):
    """db [L, N, D], valid_f32 [L, 1, N], q [Q, D] -> per-lane per-block
    candidates (scores [L, nb, Q, k], lane-local idx [L, nb, Q, k]).

    ``grid_order`` picks the grid iteration layout: ``lanes_outer`` walks
    (L, nb) — all of a lane's blocks stream consecutively — while
    ``blocks_outer`` walks (nb, L) — block j of every lane before block
    j+1, which can pipeline better when lanes are few and blocks are many.
    Sweep both with ``benchmarks/tune_topk.py`` on real hardware; results
    are identical either way."""
    L, N, D = db.shape
    Q = q.shape[0]
    assert N % block_n == 0, f"N={N} must be a multiple of block_n={block_n}"
    nb = N // block_n

    out_shape = (
        jax.ShapeDtypeStruct((L, nb, Q, k), jnp.float32),
        jax.ShapeDtypeStruct((L, nb, Q, k), jnp.int32),
    )
    if grid_order == "lanes_outer":
        grid = (L, nb)
        block_axis = 1
        lane_map = lambda l, j: (l, j, 0)  # noqa: E731
        valid_map = lambda l, j: (l, 0, j)  # noqa: E731
        out_map = lambda l, j: (l, j, 0, 0)  # noqa: E731
        q_map = lambda l, j: (0, 0)  # noqa: E731
    elif grid_order == "blocks_outer":
        grid = (nb, L)
        block_axis = 0
        lane_map = lambda j, l: (l, j, 0)  # noqa: E731
        valid_map = lambda j, l: (l, 0, j)  # noqa: E731
        out_map = lambda j, l: (l, j, 0, 0)  # noqa: E731
        q_map = lambda j, l: (0, 0)  # noqa: E731
    else:
        raise ValueError(f"unknown grid_order {grid_order!r}")

    kernel = functools.partial(
        _topk_lanes_kernel, k=k, block_n=block_n, block_axis=block_axis
    )
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, block_n, D), lane_map),  # lane tile streams
            pl.BlockSpec((1, 1, block_n), valid_map),  # validity row, lane-dense
            pl.BlockSpec((Q, D), q_map),  # queries resident
        ],
        out_specs=(
            pl.BlockSpec((1, 1, Q, k), out_map),
            pl.BlockSpec((1, 1, Q, k), out_map),
        ),
        out_shape=out_shape,
        interpret=interpret,
    )(db, valid_f32, q)


@functools.partial(jax.jit, static_argnames=("k", "block_n", "interpret"))
def similarity_topk_blocks(db, valid_f32, q, *, k: int, interpret: bool, block_n: int = 512):
    """db [N, D], valid_f32 [1, N], q [Q, D] -> per-block candidates
    (scores [nb, Q, k], idx [nb, Q, k])."""
    N, D = db.shape
    Q = q.shape[0]
    assert N % block_n == 0, f"N={N} must be a multiple of block_n={block_n}"
    nb = N // block_n

    kernel = functools.partial(_topk_block_kernel, k=k, block_n=block_n)
    out_shape = (
        jax.ShapeDtypeStruct((nb, Q, k), jnp.float32),
        jax.ShapeDtypeStruct((nb, Q, k), jnp.int32),
    )
    return pl.pallas_call(
        kernel,
        grid=(nb,),
        in_specs=[
            pl.BlockSpec((block_n, D), lambda j: (j, 0)),  # db tile streams
            pl.BlockSpec((1, block_n), lambda j: (0, j)),  # validity row, lane-dense
            pl.BlockSpec((Q, D), lambda j: (0, 0)),  # queries resident
        ],
        out_specs=(
            pl.BlockSpec((1, Q, k), lambda j: (j, 0, 0)),
            pl.BlockSpec((1, Q, k), lambda j: (j, 0, 0)),
        ),
        out_shape=out_shape,
        interpret=interpret,
    )(db, valid_f32, q)
