"""Qwen2-style decoder (``model_type`` "qwen2": Qwen1.5 and Qwen2): pre-norm
RMSNorm blocks, multi-head attention with QKV bias and rotary embeddings
(rotate-half, ``rope_theta``), a SwiGLU feed-forward, tied embeddings.

What the benchmark needs of a decoder, by name:

- ``weights(key, backend)``: the seeded weight tree in the program's layout
  (``repro.models.transformer``: layer-stacked, bfloat16), made on the device
  inside the caller's jitted call;
- ``forward(params, ids, backend, quant="")``: the plain reference, logits
  ``[n, S, V]`` of a causal forward over ``ids [n, S]``: float32 at the
  highest matmul precision, no cache, no batching across sequences; with
  ``quant`` the control, the precision below the served bfloat16: every
  weight matrix (the tied table too) in int8 or fp8 with one scale per
  output channel, activations and compute in bfloat16, as the program
  serves them;
- ``sizes(backend)`` and ``program_sizes(model_cfg)``: what ``deploy.build``
  compares, from the configuration and from the program's ``ModelConfig``.
"""
from __future__ import annotations

from contextlib import nullcontext
from functools import partial

import jax
import jax.numpy as jnp

from reference import HIGHEST, quantize
from weights import _normal


def weights(key, dec: dict) -> dict:
    """bf16, layer-stacked, tied embeddings, QKV bias."""
    d, F, V, n = (dec["hidden_size"], dec["intermediate_size"],
                  dec["vocab_size"], dec["num_hidden_layers"])
    H, K = dec["num_attention_heads"], dec["num_key_value_heads"]
    Dh = d // H
    bf = jnp.bfloat16
    ks = iter(jax.random.split(key, 16))
    return {
        "embed": {"table": _normal(next(ks), (V, d), d ** -0.5, bf)},
        "final_norm": jnp.ones((d,), bf),
        "layers": {
            "ln1": jnp.ones((n, d), bf),
            "ln2": jnp.ones((n, d), bf),
            "attn": {
                "wq": _normal(next(ks), (n, d, H, Dh), d ** -0.5, bf),
                "wk": _normal(next(ks), (n, d, K, Dh), d ** -0.5, bf),
                "wv": _normal(next(ks), (n, d, K, Dh), d ** -0.5, bf),
                "wo": _normal(next(ks), (n, H, Dh, d), (H * Dh) ** -0.5, bf),
                "bq": _normal(next(ks), (n, H, Dh), 0.02, bf),
                "bk": _normal(next(ks), (n, K, Dh), 0.02, bf),
                "bv": _normal(next(ks), (n, K, Dh), 0.02, bf),
            },
            "ffn": {
                "wi_gate": _normal(next(ks), (n, d, F), d ** -0.5, bf),
                "wi_up": _normal(next(ks), (n, d, F), d ** -0.5, bf),
                "wo": _normal(next(ks), (n, F, d), F ** -0.5, bf),
            },
        },
    }


def sizes(dec: dict) -> tuple:
    return (dec["num_hidden_layers"], dec["hidden_size"], dec["num_attention_heads"],
            dec["num_key_value_heads"], dec["intermediate_size"], dec["vocab_size"],
            float(dec["rope_theta"]), float(dec["rms_norm_eps"]),
            bool(dec["tie_word_embeddings"]))


def program_sizes(m) -> tuple:
    return (m.num_layers, m.d_model, m.num_heads, m.num_kv_heads, m.d_ff, m.vocab_size,
            float(m.rope_theta), float(m.norm_eps), bool(m.tie_embeddings))


def _rms(x, w, eps):
    x = x.astype(jnp.float32)
    y = x * jax.lax.rsqrt(jnp.mean(jnp.square(x), -1, keepdims=True) + eps)
    return y * w.astype(jnp.float32)


def _rope(x, theta):
    """Rotate-half rotary embedding over ``x [n, S, H, Dh]`` at positions
    0..S-1, in float32."""
    S, Dh = x.shape[1], x.shape[-1]
    half = Dh // 2
    inv = theta ** -(jnp.arange(half, dtype=jnp.float32) / half)
    ang = jnp.arange(S, dtype=jnp.float32)[:, None] * inv  # [S, half]
    cos, sin = jnp.cos(ang)[None, :, None], jnp.sin(ang)[None, :, None]
    x1, x2 = x[..., :half].astype(jnp.float32), x[..., half:].astype(jnp.float32)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


# matrices quantized by the control, with their input (contracted) axes
# after the layer axis is taken off
_QUANT_AXES = {"wq": 0, "wk": 0, "wv": 0, "wo": (0, 1)}
_QUANT_FFN = {"wi_gate": 0, "wi_up": 0, "wo": 0}


def _quantized(params, kind: str):
    lay = params["layers"]
    per_layer = lambda w, ax: jax.vmap(lambda m: quantize(m, kind, ax))(w)  # noqa: E731
    attn = {k: (per_layer(v, _QUANT_AXES[k]) if k in _QUANT_AXES else v)
            for k, v in lay["attn"].items()}
    ffn = {k: per_layer(v, _QUANT_FFN[k]) for k, v in lay["ffn"].items()}
    # the tied table is also the output projection: one scale per vocab row
    table = quantize(params["embed"]["table"], kind, 1)
    return dict(params, embed={"table": table}, layers=dict(lay, attn=attn, ffn=ffn))


@partial(jax.jit, static_argnames=("theta", "eps", "quant"))
def _forward(params, ids, *, theta: float, eps: float, quant: str = ""):
    dt = jnp.bfloat16 if quant else jnp.float32
    prec = None if quant else HIGHEST
    if quant:
        params = _quantized(params, quant)
    p = jax.tree.map(lambda a: a.astype(dt), params)
    n, S = ids.shape
    causal = jnp.tril(jnp.ones((S, S), bool))

    def layer(x, lp):
        a = lp["attn"]
        h = _rms(x, lp["ln1"], eps).astype(dt)
        q = jnp.einsum("nsd,dhk->nshk", h, a["wq"], precision=prec) + a["bq"]
        k = jnp.einsum("nsd,dhk->nshk", h, a["wk"], precision=prec) + a["bk"]
        v = jnp.einsum("nsd,dhk->nshk", h, a["wv"], precision=prec) + a["bv"]
        q, k = _rope(q, theta).astype(dt), _rope(k, theta).astype(dt)
        G = q.shape[2] // k.shape[2]
        k, v = jnp.repeat(k, G, axis=2), jnp.repeat(v, G, axis=2)
        s = jnp.einsum("nqhk,nshk->nhqs", q, k, precision=prec,
                       preferred_element_type=jnp.float32) / jnp.sqrt(
                           jnp.float32(q.shape[-1]))
        w = jax.nn.softmax(jnp.where(causal, s, -jnp.inf), -1).astype(dt)
        o = jnp.einsum("nhqs,nshk->nqhk", w, v, precision=prec)
        x = x + jnp.einsum("nqhk,hkd->nqd", o, a["wo"], precision=prec)
        f = lp["ffn"]
        h = _rms(x, lp["ln2"], eps).astype(dt)
        g = jnp.einsum("nsd,df->nsf", h, f["wi_gate"], precision=prec)
        u = jnp.einsum("nsd,df->nsf", h, f["wi_up"], precision=prec)
        x = x + jnp.einsum("nsf,fd->nsd", jax.nn.silu(g) * u, f["wo"], precision=prec)
        return x, None

    x = p["embed"]["table"][ids]
    x, _ = jax.lax.scan(layer, x, p["layers"])
    h = _rms(x, p["final_norm"], eps).astype(dt)
    return jnp.einsum("nsd,vd->nsv", h, p["embed"]["table"], precision=prec,
                      preferred_element_type=jnp.float32)


def forward(params, ids, dec: dict, quant: str = ""):
    """Logits ``[n, S, V]`` (float32) of the causal forward over ``ids``."""
    ctx = nullcontext() if quant else jax.default_matmul_precision("highest")
    with ctx:
        return _forward(params, jnp.asarray(ids, jnp.int32),
                        theta=float(dec["rope_theta"]), eps=float(dec["rms_norm_eps"]),
                        quant=quant)
