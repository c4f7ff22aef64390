"""Semantic similarity calculator (pluggable metrics, jitted batch scoring)."""
from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np

METRICS = ("cosine", "dot", "euclidean")

# Every score matmul states its precision. Thresholds compare f32 scores, and
# TPU's default for an f32 matmul is one bf16 pass, which moves a score near a
# threshold far enough to flip a decision against the f32 reference.
SCORE_PRECISION = jax.lax.Precision.HIGHEST


def scores(db: jax.Array, q: jax.Array, metric: str = "cosine") -> jax.Array:
    """db [N, D], q [Q, D] -> similarity scores [Q, N] (higher = more similar)."""
    def dot(a, b):
        return jnp.matmul(a, b.T, precision=SCORE_PRECISION)

    if metric == "cosine":
        dbn = db / jnp.maximum(jnp.linalg.norm(db, axis=-1, keepdims=True), 1e-9)
        qn = q / jnp.maximum(jnp.linalg.norm(q, axis=-1, keepdims=True), 1e-9)
        return dot(qn, dbn)
    if metric == "dot":
        return dot(q, db)
    if metric == "euclidean":
        d2 = jnp.sum(q * q, -1)[:, None] - 2 * dot(q, db) + jnp.sum(db * db, -1)[None, :]
        return -jnp.sqrt(jnp.maximum(d2, 0.0))
    raise ValueError(f"unknown metric {metric!r}")


def top_k_scores(
    db: jax.Array, valid: jax.Array, q: jax.Array, k: int, metric: str = "cosine"
) -> Tuple[jax.Array, jax.Array]:
    """Masked top-k search. valid [N] bool. Returns (scores [Q,k], idx [Q,k])."""
    s = scores(db, q, metric)
    s = jnp.where(valid[None, :], s, -jnp.inf)
    return jax.lax.top_k(s, k)


def pairwise_similarity(a: np.ndarray, b: np.ndarray, metric: str = "cosine") -> float:
    return float(np.asarray(scores(jnp.asarray(b[None]), jnp.asarray(a[None]), metric))[0, 0])
