"""Mixture-of-Experts FFN with capacity-based sort dispatch.

Design (TPU-native, GShard-descended but without the [T, E, C] one-hot
dispatch blow-up):

  1. router logits -> top-k (expert id, gate weight) per token
  2. flatten (token, k) assignments, argsort by expert id
  3. rank-within-expert via exclusive cumulative counts (O(T*k), no [T,E])
  4. scatter tokens into an [E, C, D] buffer (slots >= capacity drop)
  5. dense per-expert GEMMs: einsum('ecd,edf->ecf') — MXU-aligned
  6. gather back, weight by gate, sum over k; add shared experts

Every step is differentiable (integer argsort/bincount paths carry no
gradient; gathers/scatters are linear; gate weights multiply outputs).

Distribution: GSPMD cannot partition a scatter whose operand is
expert-sharded while its updates are token-sharded — it falls back to
replicated [E, C, D] buffers (~10 GiB/layer for deepseek-v3). So under a
mesh, ``moe_ffn_sharded`` runs the dispatch inside shard_map: activations
are data-sharded and *replicated over the model axis*, so each (data, model)
device routes its local tokens, keeps only the assignments that hit its own
E/TP experts, dispatches into a purely-local [E_loc, C_loc, D] buffer, GEMMs
its local experts, and psums the partial token outputs over `model` (the
same all-reduce a TP FFN needs). Expert weights stay ZeRO-3-sharded over
`data`; jit all-gathers them per layer, overlapped with the previous layer
under scan.

DeepSeek-style "sigmoid_bias" routing implements aux-loss-free load
balancing: routing chooses by sigmoid score + per-expert bias (bias is
stop-gradient, updated outside the step by the trainer from drop statistics),
while gate *weights* use the unbiased scores.
"""
from __future__ import annotations

import math
from typing import Tuple

import jax
import jax.numpy as jnp

from repro.distributed.sharding import FSDP, TP, constrain
from repro.models.layers import F32, activation, dense_init, param_dtype, stack_spec, zeros_init


def init_moe(key, cfg, stacked: int = 0):
    mo = cfg.moe
    D, E, Fd = cfg.d_model, mo.num_experts, mo.d_ff_expert
    dt = param_dtype(cfg)
    ks = jax.random.split(key, 7)
    params = {
        "router": dense_init(ks[0], (D, E), dtype=jnp.float32, stacked=stacked),
        "w_gate": dense_init(ks[1], (E, D, Fd), fan_in=D, dtype=dt, stacked=stacked),
        "w_up": dense_init(ks[2], (E, D, Fd), fan_in=D, dtype=dt, stacked=stacked),
        "w_down": dense_init(ks[3], (E, Fd, D), fan_in=Fd, dtype=dt, stacked=stacked),
    }
    specs = {
        "router": stack_spec((FSDP, None), stacked),
        "w_gate": stack_spec((TP, FSDP, None), stacked),
        "w_up": stack_spec((TP, FSDP, None), stacked),
        "w_down": stack_spec((TP, None, FSDP), stacked),
    }
    if mo.router == "sigmoid_bias":
        params["router_bias"] = zeros_init((E,), jnp.float32, stacked)
        specs["router_bias"] = stack_spec((None,), stacked)
    if mo.num_shared_experts:
        Fs = mo.d_ff_shared * mo.num_shared_experts
        params["shared_gate"] = dense_init(ks[4], (D, Fs), dtype=dt, stacked=stacked)
        params["shared_up"] = dense_init(ks[5], (D, Fs), dtype=dt, stacked=stacked)
        params["shared_down"] = dense_init(ks[6], (Fs, D), fan_in=Fs, dtype=dt, stacked=stacked)
        specs["shared_gate"] = stack_spec((FSDP, TP), stacked)
        specs["shared_up"] = stack_spec((FSDP, TP), stacked)
        specs["shared_down"] = stack_spec((TP, FSDP), stacked)
    return params, specs


def _route(params, cfg, x_flat: jax.Array) -> Tuple[jax.Array, jax.Array]:
    """Return (expert_idx [T,k] int32, gate_weights [T,k] f32)."""
    mo = cfg.moe
    logits = (x_flat.astype(F32) @ params["router"].astype(F32))  # [T, E]
    if mo.router == "sigmoid_bias":
        scores = jax.nn.sigmoid(logits)
        biased = scores + jax.lax.stop_gradient(params["router_bias"])[None, :]
        _, idx = jax.lax.top_k(biased, mo.top_k)
        gates = jnp.take_along_axis(scores, idx, axis=-1)
        gates = gates / jnp.maximum(jnp.sum(gates, axis=-1, keepdims=True), 1e-9)
        gates = gates * mo.routed_scaling
    else:
        probs = jax.nn.softmax(logits, axis=-1)
        gates, idx = jax.lax.top_k(probs, mo.top_k)
        gates = gates / jnp.maximum(jnp.sum(gates, axis=-1, keepdims=True), 1e-9)
    return idx.astype(jnp.int32), gates


def _dispatch_compute(params, cfg, x_flat, expert_idx, gates, capacity: int,
                      e_lo: int = 0, num_local_experts: int = 0):
    """Capacity dispatch + expert GEMMs over a token set.

    e_lo / num_local_experts restrict to an expert shard (shard_map path):
    assignments outside [e_lo, e_lo + n_loc) are dropped locally (they are
    served by another model-rank's copy of the same tokens).
    """
    mo = cfg.moe
    T, D = x_flat.shape
    K = mo.top_k
    E_loc = num_local_experts or mo.num_experts

    rel = expert_idx - e_lo  # [T, K]
    in_shard = (rel >= 0) & (rel < E_loc)
    flat_e = jnp.where(in_shard, rel, E_loc).reshape(-1)  # E_loc = drop bucket
    order = jnp.argsort(flat_e, stable=True)
    sorted_e = flat_e[order]
    counts = jnp.bincount(flat_e, length=E_loc + 1)
    offsets = jnp.cumsum(counts) - counts
    rank = jnp.arange(T * K, dtype=jnp.int32) - offsets[sorted_e].astype(jnp.int32)
    slot = jnp.where((rank < capacity) & (sorted_e < E_loc), rank, capacity)

    token_of = (order // K).astype(jnp.int32)
    buf = jnp.zeros((E_loc, capacity, D), x_flat.dtype)
    buf = buf.at[sorted_e, slot].set(x_flat[token_of], mode="drop")

    h = activation(jnp.einsum("ecd,edf->ecf", buf, params["w_gate"]), cfg.act)
    h = h * jnp.einsum("ecd,edf->ecf", buf, params["w_up"])
    out_buf = jnp.einsum("ecf,efd->ecd", h, params["w_down"])

    kept = (rank < capacity) & (sorted_e < E_loc)
    y_sorted = out_buf[jnp.minimum(sorted_e, E_loc - 1), jnp.minimum(slot, capacity - 1)]
    y_sorted = jnp.where(kept[:, None], y_sorted, 0)
    inv = jnp.argsort(order, stable=True)
    y_flat = y_sorted[inv].reshape(T, K, D)
    y = jnp.sum(y_flat.astype(F32) * gates[..., None], axis=1).astype(x_flat.dtype)

    assigned = in_shard.reshape(-1)[order]
    dropped = jnp.sum((assigned & (rank >= capacity)).astype(F32))
    total_assigned = jnp.maximum(jnp.sum(assigned.astype(F32)), 1.0)
    return y, dropped, total_assigned


def moe_ffn(params, cfg, x: jax.Array, capacity_factor: float = 0.0):
    """x: [B, S, D] -> [B, S, D] plus aux metrics dict.

    Under an active mesh with a `model` axis this runs the shard_map
    expert-parallel path; otherwise (unit tests, single device) everything
    is local.
    """
    from repro.distributed.sharding import current_mesh

    mesh = current_mesh()
    if mesh is not None and "model" in mesh.axis_names and cfg.moe.num_experts % mesh.shape["model"] == 0:
        return _moe_ffn_sharded(params, cfg, x, mesh, capacity_factor)

    mo = cfg.moe
    B, S, D = x.shape
    T = B * S
    cf = capacity_factor or mo.capacity_factor
    capacity = max(int(math.ceil(T * mo.top_k / mo.num_experts * cf)), min(8, T))  # repro: noqa[RA101] — shape math on Python ints at trace time

    x_flat = x.reshape(T, D)
    expert_idx, gates = _route(params, cfg, x_flat)
    y, dropped, assigned = _dispatch_compute(params, cfg, x_flat, expert_idx, gates, capacity)

    if mo.num_shared_experts:
        hs = activation(x_flat @ params["shared_gate"], cfg.act) * (x_flat @ params["shared_up"])
        y = y + hs @ params["shared_down"]

    metrics = {"moe_drop_fraction": dropped / assigned}
    return y.reshape(B, S, D), metrics


def _moe_ffn_sharded(params, cfg, x: jax.Array, mesh, capacity_factor: float = 0.0):
    """shard_map expert-parallel MoE (see module docstring)."""
    from jax.sharding import PartitionSpec as P

    mo = cfg.moe
    B, S, D = x.shape
    batch_axes = []
    n_batch_shards = 1
    for a in ("pod", "data"):  # keep axes while the cumulative product divides B
        if a in mesh.axis_names and B % (n_batch_shards * mesh.shape[a]) == 0:
            batch_axes.append(a)
            n_batch_shards *= mesh.shape[a]
    batch_axes = tuple(batch_axes)
    n_model = mesh.shape["model"]
    E_loc = mo.num_experts // n_model
    T_loc = (B // n_batch_shards) * S
    cf = capacity_factor or mo.capacity_factor
    capacity = max(int(math.ceil(T_loc * mo.top_k / mo.num_experts * cf)), min(8, T_loc))  # repro: noqa[RA101] — shape math on Python ints at trace time

    batch_spec = batch_axes if len(batch_axes) > 1 else (batch_axes[0] if batch_axes else None)

    def local_fn(x_loc, rp, w_gate, w_up, w_down, shared):
        # x_loc: [B_loc, S, D] (replicated over `model`); w_*: local expert shard
        b_loc = x_loc.shape[0]
        x_flat = x_loc.reshape(b_loc * S, D)
        expert_idx, gates = _route(rp, cfg, x_flat)
        m_rank = jax.lax.axis_index("model")
        lp = {"w_gate": w_gate, "w_up": w_up, "w_down": w_down}
        y, dropped, assigned = _dispatch_compute(
            lp, cfg, x_flat, expert_idx, gates, capacity,
            e_lo=m_rank * E_loc, num_local_experts=E_loc,
        )
        if mo.num_shared_experts:
            hs = activation(x_flat @ shared["gate"], cfg.act) * (x_flat @ shared["up"])
            y = y + hs @ shared["down"]
        y = jax.lax.psum(y, "model")  # partial expert (+F-sharded shared) outputs
        drop_frac = jax.lax.psum(dropped, "model") / jax.lax.psum(assigned, "model")
        if batch_axes:
            drop_frac = jax.lax.pmean(drop_frac, batch_axes)
        return y.reshape(b_loc, S, D), drop_frac

    rp = {"router": params["router"]}
    rp_specs = {"router": P(None, None)}  # routing needs the full table
    if "router_bias" in params:
        rp["router_bias"] = params["router_bias"]
        rp_specs["router_bias"] = P(None)
    shared_in = None
    shared_specs = P()
    if mo.num_shared_experts:
        shared_in = {
            "gate": params["shared_gate"],
            "up": params["shared_up"],
            "down": params["shared_down"],
        }
        # shared experts: F sharded over model -> partial sums join the psum
        shared_specs = {"gate": P(None, "model"), "up": P(None, "model"), "down": P("model", None)}
    fn = jax.shard_map(
        local_fn,
        mesh=mesh,
        in_specs=(
            P(batch_spec, None, None),
            rp_specs,
            P("model", None, None),
            P("model", None, None),
            P("model", None, None),
            shared_specs,
        ),
        out_specs=(P(batch_spec, None, None), P()),
        check_vma=False,
    )
    y, drop_frac = fn(x, rp, params["w_gate"], params["w_up"], params["w_down"], shared_in)
    return y, {"moe_drop_fraction": drop_frac}
