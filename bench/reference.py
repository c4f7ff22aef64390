"""Plain references for what the timed path produces. Imports nothing of the
program; takes the weights that ``weights.py`` made from the seed.

- ``encode``: the encoder's hashed word tokenizer (contriever's input as the
  system defines it: lowercased words and punctuation, blake2b ids);
  ``backend_ids`` the backend's (whitespace words, blake2b ids, a fixed
  length). The decoders' references are ``bench/archs/<model_type>.py``.
- ``bert_embed``: a BERT-style post-LN encoder with mean pooling and L2
  normalization, in float32 at the highest matmul precision, with the
  activation the configuration states (``hidden_act``).
- ``scores`` and ``decide``: float32 scores and the cache's decide rule (a
  semantic hit above t_s; the generative rule over the sources above
  t_single).
- ``combine_template``: the text of a generative hit.

The control lowers each one step below the configuration: ``bert_embed``
with ``quant`` (int8 or fp8 weights, bfloat16 compute at the default
precision), ``scores`` at ``high`` (three bf16 passes).
"""
from __future__ import annotations

import hashlib
import math
import re
from functools import partial
from typing import List, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

_WORD_RE = re.compile(r"\w+|[^\w\s]", re.UNICODE)
HIGHEST = jax.lax.Precision.HIGHEST


# -- tokenizers -----------------------------------------------------------------


def encode(text: str, vocab_size: int, max_len: int) -> List[int]:
    ids = [1]  # CLS
    for w in _WORD_RE.findall(text.lower())[: max_len - 1]:
        h = hashlib.blake2b(w.encode("utf-8"), digest_size=8).digest()
        ids.append(256 + int.from_bytes(h, "little") % (vocab_size - 256))
    return ids


def n_tokens(text: str, max_len: int = 512) -> int:
    return min(1 + len(_WORD_RE.findall(text)), max_len)


BACKEND_PROMPT_TOKENS = 32  # the backend reads a prompt's first 32 words
BACKEND_PAD = 7  # and pads the front of a shorter one with this id


def backend_ids(text: str, vocab_size: int, n: int = BACKEND_PROMPT_TOKENS) -> List[int]:
    """The backend's prompt ids as the system defines them: the first ``n``
    whitespace-separated words, each a blake2b id, padded at the front to
    exactly ``n``."""
    words = text.split()[:n] or ["empty"]
    ids = [int.from_bytes(hashlib.blake2b(w.encode(), digest_size=4).digest(), "little")
           % vocab_size for w in words]
    return [BACKEND_PAD] * (n - len(ids)) + ids


# -- encoder --------------------------------------------------------------------


def _ln(x, p, eps):
    mu = jnp.mean(x, -1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), -1, keepdims=True)
    return (x - mu) / jnp.sqrt(var + eps) * p["w"] + p["b"]


# the configuration's ``hidden_act`` -> whether GELU is the tanh approximation
GELU_TANH = {"gelu": False, "gelu_pytorch_tanh": True}


@partial(jax.jit, static_argnames=("heads", "eps", "tanh", "quant"))
def _bert(params, ids, mask, *, heads: int, eps: float, tanh: bool, quant: str = ""):
    dt = jnp.bfloat16 if quant else jnp.float32
    prec = None if quant else HIGHEST
    if quant:  # weight-only: every matrix of a layer, one scale per output
        params = dict(params, layers=[
            {k: (quantize(v, quant, 0) if getattr(v, "ndim", 0) == 2 else v)
             for k, v in lp.items()}
            for lp in params["layers"]])
    p = jax.tree.map(lambda a: a.astype(dt), params)
    mm = lambda a, b: jnp.matmul(a, b, precision=prec)  # noqa: E731
    n, L = ids.shape
    d = p["tok_embed"].shape[1]
    dh = d // heads
    x = _ln(p["tok_embed"][ids] + p["pos_embed"][:L][None], p["ln_embed"], eps)
    neg = ((1.0 - mask) * -1e9).astype(dt)[:, None, None, :]
    for lp in p["layers"]:
        q = mm(x, lp["wq"]).reshape(n, L, heads, dh)
        k = mm(x, lp["wk"]).reshape(n, L, heads, dh)
        v = mm(x, lp["wv"]).reshape(n, L, heads, dh)
        s = jnp.einsum("nqhd,nkhd->nhqk", q, k, precision=prec) / jnp.asarray(
            math.sqrt(dh), dt) + neg
        w = jax.nn.softmax(s, axis=-1)
        o = jnp.einsum("nhqk,nkhd->nqhd", w, v, precision=prec).reshape(n, L, d)
        x = _ln(x + mm(o, lp["wo"]), lp["ln1"], eps)
        h = jax.nn.gelu(mm(x, lp["wi"]) + lp["bi"], approximate=tanh)
        x = _ln(x + mm(h, lp["wo2"]) + lp["bo2"], lp["ln2"], eps)
    m = mask.astype(dt)[..., None]
    pooled = (jnp.sum(x * m, 1) / jnp.maximum(jnp.sum(m, 1), 1.0)).astype(jnp.float32)
    return pooled / jnp.linalg.norm(pooled, axis=-1, keepdims=True)


def bert_embed(params, texts: Sequence[str], enc: dict, *, quant: str = "",
               batch: int = 256) -> np.ndarray:
    """[n, d] unit embeddings. Texts go in blocks of ``batch`` at padded
    lengths 32 or 128 (padding is masked, so the result does not depend on
    it); the block shapes repeat, so the reference compiles few programs."""
    V, P = enc["vocab_size"], enc["max_position_embeddings"]
    toks = [encode(t, V, P) for t in texts]
    out = np.zeros((len(texts), enc["hidden_size"]), np.float32)
    for Lb in (32, 128, P):
        idx = [i for i, t in enumerate(toks)
               if len(t) <= Lb and (Lb == 32 or len(t) > (32 if Lb == 128 else 128))]
        for s in range(0, len(idx), batch):
            part = idx[s:s + batch]
            ids = np.zeros((batch, Lb), np.int32)
            mask = np.zeros((batch, Lb), np.float32)
            for j, i in enumerate(part):
                ids[j, : len(toks[i])] = toks[i]
                mask[j, : len(toks[i])] = 1.0
            mask[len(part):, 0] = 1.0  # padding rows: one token, discarded
            e = _bert(params, ids, mask, heads=enc["num_attention_heads"],
                      eps=enc["layer_norm_eps"], tanh=GELU_TANH[enc["hidden_act"]],
                      quant=quant)
            out[part] = np.asarray(e)[: len(part)]
    return out


def quantize(w, kind: str, axis: int):
    """Weight-only quantization along the input axis ``axis`` with one scale
    per output channel: int8 (symmetric, 127 levels) or fp8 (e4m3)."""
    w = w.astype(jnp.float32)
    amax = jnp.max(jnp.abs(w), axis=axis, keepdims=True)
    if kind == "int8":
        sc = jnp.maximum(amax, 1e-12) / 127.0
        return jnp.clip(jnp.round(w / sc), -127, 127) * sc
    sc = jnp.maximum(amax, 1e-12) / 448.0
    # the fp8 array is stored: inside a fusion the TPU compiler may keep the
    # excess precision of a convert pair and skip the rounding, which left
    # the decoder's fp8 control as exact as bfloat16 weights on the chip
    q = jax.lax.optimization_barrier((w / sc).astype(jnp.float8_e4m3fn))
    return q.astype(jnp.float32) * sc


# -- search and decide -----------------------------------------------------------


def scores(rows, q, prec=HIGHEST) -> np.ndarray:
    """[n, N] scores (q and rows unit-norm), float32 at ``prec``."""
    return np.asarray(jnp.matmul(jnp.asarray(q), jnp.asarray(rows).T, precision=prec))


def decide(s: np.ndarray, t_s: float, t_single: float, t_combined: float,
           max_sources: int) -> str:
    """The cache's decision for one query from its score-ordered candidates:
    'hit' (best above t_s), 'generative' (the sources above t_single sum above
    t_combined), or 'miss'."""
    best = s[0] if len(s) else -1.0
    if best > t_s:
        return "hit"
    x = [v for v in s[:max_sources] if v > t_single]
    if x and sum(x) > t_combined:
        return "hit" if x[0] > t_s else "generative"
    return "miss"


def ambiguous(s: np.ndarray, t_s: float, t_single: float, t_combined: float,
              max_sources: int, delta: float) -> bool:
    """True when a score lies within ``delta`` of a threshold it is compared
    with, so that rounding within the score limit may flip the decision."""
    s = np.asarray(s[:max_sources], np.float64)
    near = np.abs(s - t_single) <= delta
    near |= np.abs(s - t_s) <= delta
    x = s[s > t_single - delta]
    return bool(near.any() or abs(x.sum() - t_combined) <= delta * max(len(x), 1))


def combine_template(sources: List[Tuple[float, str, str]]) -> str:
    """The generative hit's answer from its (score, cached prompt, cached
    answer) sources."""
    ordered = sorted(sources, key=lambda se: -se[0])
    parts = [f"[combined from {len(ordered)} cached answers]"]
    for s, q, a in ordered:
        parts.append(f"- (sim={s:.3f}) Re: {q}\n{a}")
    return "\n".join(parts)
