"""Compile the served path's device programs for a described TPU v5e.

Nothing runs: each test lowers a program over ``jax.eval_shape`` shapes
placed on a v5e chip that is described, not attached, and compiles it with
the TPU compiler, which refuses what the chip would refuse (unaligned kernel
blocks, too much VMEM, programs that do not fit). Shapes are the published
widths: the top-k kernel over 65,536 rows at D=768 (and its valid-flag
layout at 65,536 and 1,048,576 rows), contriever-msmarco at
[64, 128] tokens, qwen1.5-0.5b's decode step, and the collective read
program over a 4-chip cache mesh.

The topology is described inside a module fixture: only the worker that
runs this file loads the TPU compiler, and a machine that cannot describe it
skips these tests rather than failing to collect them.
"""
from __future__ import annotations

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

D, ROWS = 768, 65536


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    prev_log_dir = os.environ.get("TPU_LOG_DIR")
    os.environ["TPU_LOG_DIR"] = "disabled"  # the compiler logs nowhere else
    try:
        desc = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — no TPU compiler here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache but
    # cannot be read back without one: keep the cache out of it
    prev_cache = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", prev_cache)
    compilation_cache.reset_cache()
    if prev_log_dir is None:
        os.environ.pop("TPU_LOG_DIR", None)
    else:
        os.environ["TPU_LOG_DIR"] = prev_log_dir


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.sharding import SingleDeviceSharding

    return SingleDeviceSharding(topo.devices[0])


def _on(sharding, tree):
    """Shapes of ``tree`` placed on ``sharding``."""
    return jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=sharding), tree
    )


@pytest.mark.parametrize("q_rows", [1, 64])
def test_similarity_topk_lanes_compiles(one_chip, q_rows):
    from repro.kernels.similarity_topk.ops import _similarity_topk_lanes

    args = _on(one_chip, (
        jax.ShapeDtypeStruct((1, ROWS, D), jnp.float32),
        jax.ShapeDtypeStruct((1, ROWS), jnp.bool_),
        jax.ShapeDtypeStruct((q_rows, D), jnp.float32),
    ))
    compiled = _similarity_topk_lanes.lower(
        *args, k=4, metric=("cosine",), block_n=512, interpret=False,
        prenormalized=True, grid_order="lanes_outer",
    ).compile()
    assert "tpu_custom_call" in compiled.as_text()  # the kernel, not a fallback


@pytest.mark.parametrize("rows", [65536, 1048576])
def test_similarity_topk_lanes_mask_is_lane_dense(one_chip, rows):
    """The valid flags reach the kernel as a row, not a padded column: an
    f32[1, rows, 1] buffer takes 512 bytes a row under the (8, 128) tile
    (536,967,680 temp bytes at 1M rows), held in HBM and streamed per read."""
    from repro.kernels.similarity_topk.ops import _similarity_topk_lanes

    args = _on(one_chip, (
        jax.ShapeDtypeStruct((1, rows, D), jnp.float32),
        jax.ShapeDtypeStruct((1, rows), jnp.bool_),
        jax.ShapeDtypeStruct((8, D), jnp.float32),
    ))
    compiled = _similarity_topk_lanes.lower(
        *args, k=4, metric=("cosine",), block_n=512, interpret=False,
        prenormalized=True, grid_order="lanes_outer",
    ).compile()
    assert f"f32[1,{rows},1]" not in compiled.as_text()
    assert compiled.memory_analysis().temp_size_in_bytes < rows * 32


def test_contriever_forward_compiles(one_chip):
    from repro.configs.contriever import CONTRIEVER_MSMARCO as cfg
    from repro.core.embeddings import _encoder_forward, _init_encoder

    params = _on(one_chip, jax.eval_shape(
        lambda: _init_encoder(cfg, jax.random.PRNGKey(0))
    ))
    ids, mask = _on(one_chip, (
        jax.ShapeDtypeStruct((64, 128), jnp.int32),
        jax.ShapeDtypeStruct((64, 128), jnp.float32),
    ))
    fwd = jax.jit(lambda p, i, m: _encoder_forward(p, cfg, i, m))
    compiled = fwd.lower(params, ids, mask).compile()
    out = compiled.out_info
    assert out.shape == (64, cfg.d_model) and out.dtype == jnp.float32


def test_qwen_decode_step_compiles(one_chip):
    from repro.configs import get_config
    from repro.models import transformer as T

    cfg = get_config("qwen1.5-0.5b")
    assert (cfg.num_layers, cfg.d_model) == (24, 1024)
    params = _on(one_chip, jax.eval_shape(
        lambda: T.init_params(cfg, jax.random.PRNGKey(0))[0]
    ))
    cache = _on(one_chip, jax.eval_shape(lambda: T.init_cache(cfg, 4, 256)[0]))
    tokens, pos = _on(one_chip, (
        jax.ShapeDtypeStruct((4, 1), jnp.int32),
        jax.ShapeDtypeStruct((4,), jnp.int32),
    ))
    step = jax.jit(lambda p, t, q, c: T.decode_step(p, cfg, t, q, c))
    compiled = step.lower(params, tokens, pos, cache).compile()
    logits = jax.tree.leaves(compiled.out_info)[0]
    assert logits.shape[0] == 4 and logits.shape[-1] == cfg.vocab_size


def test_sharded_read_program_compiles_on_four_chips(topo):
    """The collective read program: a replicated L1 over a key-sharded L2 of
    262,144 rows at D=768 on a 4-chip cache mesh."""
    from jax.sharding import Mesh, NamedSharding
    from jax.sharding import PartitionSpec as P

    from repro.core.embeddings import _identity_forward
    from repro.core.read_path import LevelSpec
    from repro.distributed.sharded_read import _build_sharded_program

    mesh = Mesh(np.array(topo.devices).reshape(4), ("data",))
    rep = NamedSharding(mesh, P())
    sh2 = NamedSharding(mesh, P("data", None))
    sh3 = NamedSharding(mesh, P("data", None, None))
    spec = LevelSpec(True, True, 0.95, 1.9, 4, 4)
    prog = _build_sharded_program(
        _identity_forward, mesh, (("rep", 0), ("sh", 0)), (spec, spec), 4,
        (("cosine",), (True,)), (("cosine", True),), False, True,
    )
    B, l1, capl = 64, 4096, 262144 // 4

    def s(shape, dtype, sharding=rep):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)

    counters = (
        (s((1, l1), jnp.int32), s((1, l1), jnp.int32)),
        ((s((4, capl), jnp.int32, sh2), s((4, capl), jnp.int32, sh2)),),
    )
    compiled = prog.lower(
        (s((B, D), jnp.float32),), s((B, 2), jnp.float32), s((B,), jnp.bool_),
        s((B, 2), jnp.bool_),
        (s((1, l1, D), jnp.float32), s((1, l1), jnp.bool_)), (),
        ((s((4, capl, D), jnp.float32, sh3), s((4, capl), jnp.bool_, sh2)),), (),
        s((), jnp.float32), counters, (s((), jnp.int32), s((), jnp.int32)),
        s((4,), jnp.bool_),
    ).compile()
    assert "all-gather" in compiled.as_text()  # only candidates cross chips
