"""Pallas TPU kernel: FlashAttention-style prefill attention.

Tiling: grid (B, H, nq, nk) with the KV axis innermost; the online-softmax
running state (m, l, acc) lives in VMEM scratch and is carried across the nk
grid steps (TPU grids iterate sequentially, so scratch persists — the
canonical Pallas flash pattern). The [block_q, Dh] query tile is read once
per (b, h, qi); [block_k, Dh] K/V tiles stream through VMEM.

GQA is free: the K/V BlockSpec index_map maps query head h to KV head
h // group_size, so grouped heads re-read the same KV tile instead of
materializing repeated KV in HBM.

Sliding-window + causal masking is applied per tile; fully-masked tiles
skip their compute via pl.when (their DMA is still scheduled — the
scalar-prefetch skip that also elides the DMA is recorded as a §Perf item).

VMEM: (block_q + 2*block_k) * Dh * 4 + block_q*block_k*4 + scratch
   = (128 + 256)*128*4 + 64 KB + ~70 KB  ≈ 0.33 MB at the default tiles.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG = -2.3e38


def _flash_kernel(
    q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr,
    *, block_q: int, block_k: int, nk: int, causal: bool, window: int,
    softcap: float, scale: float,
):
    qi = pl.program_id(2)
    ki = pl.program_id(3)

    @pl.when(ki == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q_start = qi * block_q
    k_start = ki * block_k
    # tile-level reachability: causal upper bound and window lower bound
    conds = []
    if causal:
        conds.append(k_start <= q_start + block_q - 1)
    if window:
        conds.append(k_start + block_k - 1 > q_start - window)
    reachable = functools.reduce(jnp.logical_and, conds) if conds else (ki >= 0)

    @pl.when(reachable)
    def _compute():
        q = q_ref[0, :, 0, :].astype(jnp.float32)  # [block_q, Dh]
        k = k_ref[0, :, 0, :].astype(jnp.float32)  # [block_k, Dh]
        v = v_ref[0, :, 0, :].astype(jnp.float32)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        if softcap:
            s = softcap * jnp.tanh(s / softcap)
        q_pos = q_start + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 0)
        k_pos = k_start + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 1)
        mask = jnp.ones((block_q, block_k), bool)
        if causal:
            mask &= k_pos <= q_pos
        if window:
            mask &= k_pos > q_pos - window
        s = jnp.where(mask, s, NEG)

        m_prev = m_scr[...]  # [block_q, 1]
        l_prev = l_scr[...]
        m_cur = jnp.max(s, axis=1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
        p = jnp.exp(s - m_new)
        p = jnp.where(mask, p, 0.0)
        alpha = jnp.exp(m_prev - m_new)
        l_new = alpha * l_prev + jnp.sum(p, axis=1, keepdims=True)
        acc_scr[...] = acc_scr[...] * alpha + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
        )
        m_scr[...] = m_new
        l_scr[...] = l_new

    @pl.when(ki == nk - 1)
    def _writeback():
        l = jnp.maximum(l_scr[...], 1e-30)
        o_ref[0, :, 0, :] = (acc_scr[...] / l).astype(o_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=("causal", "window", "softcap", "scale", "block_q", "block_k", "interpret"),
)
def flash_attention_kernel(
    q, k, v, *, causal=True, window=0, softcap=0.0, scale=None,
    block_q=128, block_k=128, interpret: bool,
):
    B, S, H, Dh = q.shape
    KH = k.shape[2]
    G = H // KH
    scale = scale if scale is not None else Dh ** -0.5
    block_q = min(block_q, S)
    block_k = min(block_k, S)
    assert S % block_q == 0 and S % block_k == 0, (S, block_q, block_k)
    nq, nk = S // block_q, S // block_k

    kernel = functools.partial(
        _flash_kernel, block_q=block_q, block_k=block_k, nk=nk,
        causal=causal, window=window, softcap=softcap, scale=scale,
    )
    return pl.pallas_call(
        kernel,
        grid=(B, H, nq, nk),
        in_specs=[
            pl.BlockSpec((1, block_q, 1, Dh), lambda b, h, qi, ki: (b, qi, h, 0)),
            pl.BlockSpec((1, block_k, 1, Dh), lambda b, h, qi, ki: (b, ki, h // G, 0)),
            pl.BlockSpec((1, block_k, 1, Dh), lambda b, h, qi, ki: (b, ki, h // G, 0)),
        ],
        out_specs=pl.BlockSpec((1, block_q, 1, Dh), lambda b, h, qi, ki: (b, qi, h, 0)),
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q, 1), jnp.float32),  # running max m
            pltpu.VMEM((block_q, 1), jnp.float32),  # running sum l
            pltpu.VMEM((block_q, Dh), jnp.float32),  # output accumulator
        ],
        interpret=interpret,
    )(q, k, v)
