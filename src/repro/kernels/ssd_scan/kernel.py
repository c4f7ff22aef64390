"""Pallas TPU kernel: Mamba2 SSD chunked scan.

Grid (B, nc) with the chunk axis innermost: the inter-chunk SSM state
[H, P, N] lives in VMEM scratch and is carried across sequential grid steps
(reset at chunk 0 of each sequence). Per chunk the kernel computes the
quadratic intra-chunk term — an [L, L] decay-masked score matrix per head —
and the state contribution, all in f32.

Head-level work is expressed as 2-D dot_generals per head (a static unroll):
Mosaic's MXU path wants plain 2-D dots, and L, N, P are 64..256 so each dot
is already hardware-shaped. VMEM per step: x/B/C tiles L*(H/unit)*(P|N)*4
plus the [H, P, N] state — with L=128, H=8-per-call, P=64, N=128 that is
~0.8 MB (models with larger H shard heads over the TP axis first; the
kernel is invoked per head shard).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _ssd_kernel(x_ref, b_ref, c_ref, dt_ref, a_ref, d_ref, y_ref, st_out_ref, state_scr,
                *, L: int, H: int, P: int, N: int, nc: int):
    ci = pl.program_id(1)

    @pl.when(ci == 0)
    def _init():
        state_scr[...] = jnp.zeros_like(state_scr)

    tril = jax.lax.broadcasted_iota(jnp.int32, (L, L), 0) >= jax.lax.broadcasted_iota(
        jnp.int32, (L, L), 1
    )

    for h in range(H):  # static unroll: per-head 2-D dots (MXU-shaped)
        x = x_ref[0, :, h, :].astype(jnp.float32)  # [L, P]
        Bm = b_ref[0, :, h, :].astype(jnp.float32)  # [L, N]
        Cm = c_ref[0, :, h, :].astype(jnp.float32)  # [L, N]
        dt = dt_ref[0, :, h].astype(jnp.float32)  # [L]
        A = a_ref[h]
        dA = dt * A  # <= 0
        cs = jnp.cumsum(dA)  # [L]

        state = state_scr[h]  # [P, N]
        # inter-chunk contribution
        y_off = jax.lax.dot_general(Cm, state, (((1,), (1,)), ((), ())),
                                    preferred_element_type=jnp.float32)  # [L, P]
        y_off = y_off * jnp.exp(cs)[:, None]
        # intra-chunk quadratic term
        decay = jnp.exp(cs[:, None] - cs[None, :])
        scores = jax.lax.dot_general(Cm, Bm, (((1,), (1,)), ((), ())),
                                     preferred_element_type=jnp.float32)  # [L, L]
        scores = scores * decay * dt[None, :]
        scores = jnp.where(tril, scores, 0.0)
        y_diag = jax.lax.dot_general(scores, x, (((1,), (0,)), ((), ())),
                                     preferred_element_type=jnp.float32)
        # state update
        last = cs[L - 1]
        w = jnp.exp(last - cs) * dt  # [L]
        state_new = state * jnp.exp(last) + jax.lax.dot_general(
            x * w[:, None], Bm, (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32
        )  # [P, N]
        state_scr[h] = state_new
        y_ref[0, :, h, :] = (y_diag + y_off + d_ref[h] * x).astype(y_ref.dtype)

    @pl.when(ci == nc - 1)
    def _emit_state():
        st_out_ref[0] = state_scr[...]


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def ssd_scan_kernel(x, Bm, Cm, dt, A, D, *, interpret: bool, chunk: int = 128):
    B, S, H, P = x.shape
    N = Bm.shape[-1]
    L = min(chunk, S)
    assert S % L == 0, (S, L)
    nc = S // L

    kernel = functools.partial(_ssd_kernel, L=L, H=H, P=P, N=N, nc=nc)
    y, st = pl.pallas_call(
        kernel,
        grid=(B, nc),
        in_specs=[
            pl.BlockSpec((1, L, H, P), lambda b, c: (b, c, 0, 0)),
            pl.BlockSpec((1, L, H, N), lambda b, c: (b, c, 0, 0)),
            pl.BlockSpec((1, L, H, N), lambda b, c: (b, c, 0, 0)),
            pl.BlockSpec((1, L, H), lambda b, c: (b, c, 0)),
            pl.BlockSpec((H,), lambda b, c: (0,)),
            pl.BlockSpec((H,), lambda b, c: (0,)),
        ],
        out_specs=(
            pl.BlockSpec((1, L, H, P), lambda b, c: (b, c, 0, 0)),
            pl.BlockSpec((1, H, P, N), lambda b, c: (b, 0, 0, 0)),
        ),
        out_shape=(
            jax.ShapeDtypeStruct(x.shape, x.dtype),
            jax.ShapeDtypeStruct((B, H, P, N), jnp.float32),
        ),
        scratch_shapes=[pltpu.VMEM((H, P, N), jnp.float32)],
        interpret=interpret,
    )(x, Bm, Cm, dt.astype(jnp.float32), A.astype(jnp.float32), D.astype(jnp.float32))
    return y, st
