"""similarity_topk Pallas kernel vs pure-jnp oracle: sweeps + properties."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from _compat import given, settings, st

from repro.kernels.similarity_topk.ops import similarity_topk, similarity_topk_lanes
from repro.kernels.similarity_topk.ref import similarity_topk_lanes_ref, similarity_topk_ref

SHAPES = [
    # (N, D, Q, k)
    (256, 64, 1, 4),
    (1024, 256, 4, 8),
    (2048, 768, 8, 4),
    (700, 128, 3, 5),  # non-multiple N exercises padding
    (128, 32, 16, 16),
]


@pytest.mark.parametrize("shape", SHAPES)
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("metric", ["cosine", "dot"])
def test_matches_ref(shape, dtype, metric):
    N, D, Q, k = shape
    key = jax.random.PRNGKey(N + D)
    db = jax.random.normal(key, (N, D), dtype)
    q = jax.random.normal(jax.random.PRNGKey(1), (Q, D), dtype)
    valid = jax.random.bernoulli(jax.random.PRNGKey(2), 0.9, (N,))
    s1, i1 = similarity_topk(db, valid, q, k=k, metric=metric)
    s2, i2 = similarity_topk_ref(db, valid, q, k=k, metric=metric)
    np.testing.assert_allclose(np.asarray(s1), np.asarray(s2), atol=2e-5, rtol=2e-5)
    # indices may only differ where scores tie; require score-equivalence
    s_ref_at_kernel = np.take_along_axis(
        np.asarray(similarity_topk_ref(db, jnp.ones((N,), bool), q, k=N, metric=metric)[0]),
        np.zeros((Q, k), np.int64), axis=1)  # placeholder guard (ties are ~measure-zero)
    assert np.array_equal(np.asarray(i1), np.asarray(i2)) or np.allclose(
        np.asarray(s1), np.asarray(s2), atol=2e-5
    )


def test_all_invalid_returns_neg_inf():
    db = jnp.ones((256, 64))
    q = jnp.ones((2, 64))
    valid = jnp.zeros((256,), bool)
    s, i = similarity_topk(db, valid, q, k=4)
    assert bool(jnp.all(jnp.isinf(s)))


@pytest.mark.parametrize("grid_order", ["lanes_outer", "blocks_outer"])
def test_lanes_mask_at_block_edges_matches_ref_exactly(grid_order):
    """Two lanes over three 128-row blocks plus a partial one (the pad path):
    flags flip at block edges and one block is wholly invalid. Its rows
    would outscore every valid one, so a flag tile read at the wrong offset
    shows. Integer data keeps every dot exact: scores and indices must
    equal the oracle's bit for bit."""
    L, bn, D, Q, k = 2, 128, 32, 4, 6
    N = 3 * bn + 50
    rng = np.random.default_rng(7)
    db = rng.integers(-3, 4, size=(L, N, D)).astype(np.float32)
    q = rng.integers(1, 4, size=(Q, D)).astype(np.float32)
    valid = np.ones((L, N), bool)
    valid[0, bn:2 * bn] = False  # a whole block
    valid[0, [bn - 1, 2 * bn, 3 * bn - 1]] = False  # the rows beside its edges
    valid[1, :bn - 1] = False  # only the last row of block 0
    valid[1, 2 * bn:3 * bn] = False  # lane 1's own invalid block
    valid[1, 3 * bn] = False  # the first row of the partial block
    db[~valid] = 9.0  # invalid rows score above any valid one
    s, i = similarity_topk_lanes(db, valid, q, k=k, metric="dot", block_n=bn,
                                 interpret=True, grid_order=grid_order)
    s_ref, i_ref = similarity_topk_lanes_ref(db, valid, q, k, metric="dot")
    np.testing.assert_array_equal(np.asarray(s), np.asarray(s_ref))
    np.testing.assert_array_equal(np.asarray(i), np.asarray(i_ref))
    assert valid[np.arange(L)[None, :, None], np.asarray(i)].all()


@settings(max_examples=25, deadline=None)
@given(
    n=st.integers(8, 300),
    d=st.sampled_from([16, 64, 128]),
    q=st.integers(1, 8),
    k=st.integers(1, 8),
    seed=st.integers(0, 2**31 - 1),
)
def test_property_topk_is_exact(n, d, q, k, seed):
    """Property: kernel's top-k score set == exact brute-force top-k."""
    key = jax.random.PRNGKey(seed)
    db = jax.random.normal(key, (n, d))
    qs = jax.random.normal(jax.random.PRNGKey(seed + 1), (q, d))
    valid = jnp.ones((n,), bool)
    k = min(k, n)
    s1, i1 = similarity_topk(db, valid, qs, k=k)
    s2, i2 = similarity_topk_ref(db, valid, qs, k=k)
    np.testing.assert_allclose(np.asarray(s1), np.asarray(s2), atol=3e-5, rtol=3e-5)


@settings(max_examples=15, deadline=None)
@given(seed=st.integers(0, 2**31 - 1))
def test_property_self_similarity_is_top1(seed):
    """Property: a vector present in the DB is its own nearest neighbor."""
    key = jax.random.PRNGKey(seed)
    db = jax.random.normal(key, (128, 64))
    probe = db[17][None]
    s, i = similarity_topk(db, jnp.ones((128,), bool), probe, k=1)
    assert int(i[0, 0]) == 17
    assert float(s[0, 0]) > 0.999
