"""Weights from the run's seed, made on the device in one jitted call.

The served stack takes these in place of the ones it builds for itself, so
the plain references in ``reference.py`` can run on the same numbers without
taking anything the program made. Both trees use the program's parameter
layout (``repro.core.embeddings`` for the encoder, here; the decoder's
comes from its file under ``bench/archs/``); ``deploy.install_weights`` checks every leaf's
path, shape and dtype against the program's own before it swaps them in.
"""
from __future__ import annotations

import importlib.util
from pathlib import Path

import jax
import jax.numpy as jnp


def seed_key(seed: int):
    """A PRNG key from a seed of any size up to 64 bits."""
    k = jax.random.PRNGKey(seed % (1 << 32))
    return jax.random.fold_in(k, (seed >> 32) % (1 << 32))


def _normal(key, shape, scale, dtype):
    return (jax.random.normal(key, shape, jnp.float32) * scale).astype(dtype)


def encoder_tree(key, enc: dict) -> dict:
    """BERT-style encoder (contriever): f32, as the program serves it."""
    d, F, V, P, n = (enc["hidden_size"], enc["intermediate_size"],
                     enc["vocab_size"], enc["max_position_embeddings"],
                     enc["num_hidden_layers"])
    ks = iter(jax.random.split(key, 2 + 6 * n))
    f32 = jnp.float32
    ln = lambda: {"w": jnp.ones((d,), f32), "b": jnp.zeros((d,), f32)}  # noqa: E731
    tree = {
        "tok_embed": _normal(next(ks), (V, d), d ** -0.5, f32),
        "pos_embed": _normal(next(ks), (P, d), d ** -0.5, f32),
        "ln_embed": ln(),
        "layers": [],
    }
    for _ in range(n):
        tree["layers"].append({
            "wq": _normal(next(ks), (d, d), d ** -0.5, f32),
            "wk": _normal(next(ks), (d, d), d ** -0.5, f32),
            "wv": _normal(next(ks), (d, d), d ** -0.5, f32),
            "wo": _normal(next(ks), (d, d), d ** -0.5, f32),
            "ln1": ln(),
            "wi": _normal(next(ks), (d, F), d ** -0.5, f32),
            "bi": jnp.zeros((F,), f32),
            "wo2": _normal(next(ks), (F, d), F ** -0.5, f32),
            "bo2": jnp.zeros((d,), f32),
            "ln2": ln(),
        })
    return tree


def load_arch(bench: Path, model_type: str):
    """``bench/archs/<model_type>.py``: the decoder the configuration's
    ``backend.model_type`` names (the published ``config.json``'s own key)."""
    path = bench / "archs" / f"{model_type}.py"
    if not path.exists():
        raise FileNotFoundError(f"no decoder {path} for model_type {model_type!r}")
    sp = importlib.util.spec_from_file_location(f"bench_arch_{model_type}", path)
    mod = importlib.util.module_from_spec(sp)
    sp.loader.exec_module(mod)
    return mod


def make_weights(seed: int, enc: dict, dec: dict, arch):
    """(encoder, decoder) weight trees, generated on the device in one call;
    ``arch`` (``load_arch``) makes the decoder's."""
    @jax.jit
    def gen(key):
        ke, kd = jax.random.split(key)
        return encoder_tree(ke, enc), arch.weights(kd, dec)

    return gen(seed_key(seed))


def filler_rows(seed: int, start: int, n: int, dim: int):
    """Rows [start, start + n) of the seeded unit-norm filler, on the device.
    Any block of rows can be made again alone, so set-up and the reference
    need not hold the whole filler at once."""
    key = jax.random.fold_in(seed_key(seed), 0xF111)
    return _filler_block(key, start, n, dim)


@jax.jit
def _block(key, starts, dim_proto):
    rows = jax.vmap(lambda s: jax.random.normal(
        jax.random.fold_in(key, s), (dim_proto.shape[0],), jnp.float32))(starts)
    return rows / jnp.linalg.norm(rows, axis=-1, keepdims=True)


def _filler_block(key, start, n, dim):
    starts = jnp.arange(start, start + n, dtype=jnp.uint32)
    return _block(key, starts, jnp.zeros((dim,), jnp.float32))
