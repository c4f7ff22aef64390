"""Pallas TPU kernel: split-K decode attention (FlashDecoding-style).

One new token attends over a long KV cache. Work is split over KV blocks:
grid (B, H, ns) with the KV axis innermost; partial online-softmax state
(m, l, acc) carried in VMEM scratch and normalized on the last block. On a
real v5e the ns axis would be re-mapped to parallel cores with an LSE-merge
epilogue (split-K proper); the sequential-grid form here shares the same
block math, and the cross-device variant of that merge is exercised by the
context-parallel decode path in the dry-run.

The q tile is [1, Dh] per (b, h); KV tiles [block_s, Dh] stream. Validity
comes from `lengths` (per-sequence cache fill) and the sliding window.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG = -2.3e38


def _decode_kernel(
    len_ref, q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr,
    *, block_s: int, ns: int, window: int, softcap: float, scale: float,
):
    b = pl.program_id(0)
    si = pl.program_id(2)

    @pl.when(si == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    length = len_ref[b]
    s_start = si * block_s
    live = s_start < length
    if window:
        live = jnp.logical_and(live, s_start + block_s - 1 > length - 1 - window)

    @pl.when(live)
    def _compute():
        q = q_ref[0, 0, :].astype(jnp.float32)[None, :]  # [1, Dh]
        k = k_ref[0, :, 0, :].astype(jnp.float32)  # [block_s, Dh]
        v = v_ref[0, :, 0, :].astype(jnp.float32)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale  # [1, block_s]
        if softcap:
            s = softcap * jnp.tanh(s / softcap)
        pos = s_start + jax.lax.broadcasted_iota(jnp.int32, (1, block_s), 1)
        mask = pos < length
        if window:
            mask = jnp.logical_and(mask, pos > length - 1 - window)
        s = jnp.where(mask, s, NEG)
        m_prev, l_prev = m_scr[...], l_scr[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        p = jnp.where(mask, jnp.exp(s - m_new), 0.0)
        alpha = jnp.exp(m_prev - m_new)
        l_scr[...] = alpha * l_prev + jnp.sum(p, axis=1, keepdims=True)
        acc_scr[...] = acc_scr[...] * alpha + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
        )
        m_scr[...] = m_new

    @pl.when(si == ns - 1)
    def _writeback():
        l = jnp.maximum(l_scr[...], 1e-30)
        o_ref[0, 0, :] = (acc_scr[...] / l)[0].astype(o_ref.dtype)


@functools.partial(
    jax.jit, static_argnames=("window", "softcap", "scale", "block_s", "interpret")
)
def decode_attention_kernel(
    q, k, v, lengths, *, interpret: bool, window=0, softcap=0.0, scale=None, block_s=256
):
    B, H, Dh = q.shape
    S, KH = k.shape[1], k.shape[2]
    G = H // KH
    scale = scale if scale is not None else Dh ** -0.5
    block_s = min(block_s, S)
    assert S % block_s == 0, (S, block_s)
    ns = S // block_s

    kernel = functools.partial(
        _decode_kernel, block_s=block_s, ns=ns, window=window, softcap=softcap, scale=scale
    )
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,  # lengths prefetch enables (future) block skipping
        grid=(B, H, ns),
        in_specs=[
            pl.BlockSpec((1, 1, Dh), lambda b, h, si, lens: (b, h, 0)),
            pl.BlockSpec((1, block_s, 1, Dh), lambda b, h, si, lens: (b, si, h // G, 0)),
            pl.BlockSpec((1, block_s, 1, Dh), lambda b, h, si, lens: (b, si, h // G, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, Dh), lambda b, h, si, lens: (b, h, 0)),
        scratch_shapes=[
            pltpu.VMEM((1, 1), jnp.float32),
            pltpu.VMEM((1, 1), jnp.float32),
            pltpu.VMEM((1, Dh), jnp.float32),
        ],
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        interpret=interpret,
    )(lengths.astype(jnp.int32), q, k, v)
