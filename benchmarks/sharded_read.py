"""Sharded zero-host-hop read path vs the host-decide sharded pipeline.

Measures a lookup against the key-sharded DB two ways over the same mesh
(every device JAX sees, or ``--virtual-devices N`` CPU devices) and entry
set:

  * host_decide — the pre-sharded-read shape (``*_host`` methods): one
    banked search dispatch downloads [B, shards*k] merged candidates, then
    host Python rescores/sorts, applies thresholds, joins payloads, and
    issues a separate counter-touch scatter
  * fused       — ONE collective ``shard_map`` program
    (repro.distributed.sharded_read): local per-shard top-k, the tiny
    [B, k] candidate all-gather, threshold + generative decide, winner
    walk, and ownership-masked counter scatters all in-jit; only compact
    decision tensors return to host

Two scenarios, both parity-checked:

  * sharded_store (GATED) — ``ShardedVectorStore.lookup_batch`` vs
    ``lookup_batch_host``: the exact serving surface CacheService hits.
    CI enforces peak speedup >=1.5x across serving batch sizes, exactly
    one collective dispatch per lookup, and zero host hops.
  * hierarchy (reported) — replicated-L1 + sharded-L2
    ``HierarchicalCache.lookup_batch`` through the ShardedReadBank tier vs
    the same topology pinned to the host tiers (``fused=False`` stores and
    hierarchy), including promotion writebacks.

Results land in ``BENCH_sharded_read.json``.

Run:  PYTHONPATH=src python benchmarks/sharded_read.py [--smoke] [--virtual-devices 8]
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time


# --virtual-devices acts before JAX starts: the CPU device count is fixed
# when the backend initializes
_pre = argparse.ArgumentParser(add_help=False)
_pre.add_argument("--virtual-devices", type=int, default=0)
_N_VIRTUAL = _pre.parse_known_args()[0].virtual_devices
if _N_VIRTUAL:
    os.environ["XLA_FLAGS"] = (
        os.environ.get("XLA_FLAGS", "")
        + f" --xla_force_host_platform_device_count={_N_VIRTUAL}"
    ).strip()
    os.environ["JAX_PLATFORMS"] = "cpu"

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax  # noqa: E402

from benchmarks.common import emit  # noqa: E402
from repro.core import GenerativeCache, HierarchicalCache, NgramHashEmbedder  # noqa: E402
from repro.distributed.sharded_store import ShardedVectorStore  # noqa: E402
from repro.launch.mesh import make_cache_mesh  # noqa: E402

DIM = 256
K = 4


def _unit(rng, n, dim):
    v = rng.normal(size=(n, dim)).astype(np.float32)
    return v / np.linalg.norm(v, axis=1, keepdims=True)


def _median_pair(fn_a, fn_b, repeats, sync=None, warmup=3):
    """Median seconds per variant, samples interleaved a/b/a/b so machine
    load drift lands on both equally. ``sync`` runs INSIDE each timed
    window: the host path's counter-touch scatter is dispatched async, so
    without a barrier its device time would bleed into the next variant's
    sample instead of being charged to the path that issued it."""
    sync = sync or (lambda: None)
    for _ in range(warmup):
        fn_a()
        sync()
        fn_b()
        sync()
    ta, tb = [], []
    for _ in range(repeats):
        t0 = time.perf_counter()
        fn_a()
        sync()
        ta.append(time.perf_counter() - t0)
        t0 = time.perf_counter()
        fn_b()
        sync()
        tb.append(time.perf_counter() - t0)
    ta.sort()
    tb.sort()
    return ta[len(ta) // 2], tb[len(tb) // 2]


def _probes(rng, base, b):
    """~2/3 near-duplicates of stored rows (clear hits), ~1/3 novel."""
    out = []
    for j in range(b):
        if j % 3 < 2:
            v = base[j % len(base)] + 0.02 * rng.normal(size=DIM).astype(np.float32)
        else:
            v = rng.normal(size=DIM).astype(np.float32)
        out.append(v / np.linalg.norm(v))
    return np.stack(out).astype(np.float32)


def bench_sharded_store(batch_sizes, n_entries, capacity, repeats) -> dict:
    """GATED scenario: the store's serving lookup, fused vs host-decide."""
    mesh = make_cache_mesh()
    store = ShardedVectorStore(mesh, dim=DIM, capacity=capacity, k=K)
    rng = np.random.default_rng(0)
    base = _unit(rng, n_entries, DIM)
    store.add_batch(
        base,
        [f"query {i}" for i in range(n_entries)],
        [f"answer {i}" for i in range(n_entries)],
    )

    def sync():
        # both paths mutate the same LRU/LFU counters; blocking on them
        # charges each path's (possibly async) scatter to its own sample
        store.bank.d_last_access.block_until_ready()
        store.bank.d_access_count.block_until_ready()

    out = {"n_devices": len(jax.devices()), "n_shards": store.n_shards}
    for b in batch_sizes:
        probes = _probes(np.random.default_rng(7), base, b)
        thr = np.full(b, 0.8, np.float32)

        def run_host():
            return store.lookup_batch_host(probes, thr)

        def run_fused():
            return store.lookup_batch(probes, thr)

        ref, got = run_host(), run_fused()  # warm both programs + parity
        for r, g in zip(ref, got):
            assert (r is None) == (g is None), (r, g)
            if r is not None:
                assert r[1] == g[1] and abs(r[0] - g[0]) < 1e-5, (r, g)
        host_s, fused_s = _median_pair(run_host, run_fused, repeats, sync=sync)
        speedup = host_s / fused_s
        out[f"b{b}"] = {
            "host_decide_ms": host_s * 1e3,
            "fused_ms": fused_s * 1e3,
            "speedup": speedup,
            "hit_fraction": sum(1 for g in got if g is not None) / b,
        }
        emit(f"sharded_read_s{store.n_shards}_b{b}", fused_s * 1e6,
             f"vs host-decide {host_s * 1e6:.0f}us = {speedup:.2f}x")

    # the headline dataflow claim, measured on the serving lookup
    bank = store.bank
    d0, h0 = bank.dispatches, bank.host_hops
    sd0 = store._srb.dispatches
    store.lookup_batch(probes, thr)
    out["dataflow"] = {
        "fused": {
            "dispatches": bank.dispatches - d0,
            "collective_dispatches": store._srb.dispatches - sd0,
            "host_hops_between_search_and_decide": bank.host_hops - h0,
        }
    }
    d0, h0 = bank.dispatches, bank.host_hops
    store.lookup_batch_host(probes, thr)
    out["dataflow"]["host_decide"] = {
        "dispatches": bank.dispatches - d0,
        "host_hops_between_search_and_decide": bank.host_hops - h0,
    }
    return out


THRESH = 0.85


def _l1(emb, base, n_entries, capacity):
    """Hot L1 holding the first quarter of the corpus (semantic-only:
    t_combined=inf keeps the generative rule out of the parity contract)."""
    l1 = GenerativeCache(emb, threshold=THRESH, t_single=0.45,
                         t_combined=float("inf"), capacity=capacity // 4,
                         max_sources=K)
    hot = n_entries // 4
    l1.insert_batch(
        [f"query {i}" for i in range(hot)],
        [f"answer {i}" for i in range(hot)],
        vecs=base[:hot],
    )
    return l1


def bench_hierarchy(batch_sizes, n_entries, capacity, repeats) -> dict:
    """Reported scenario: replicated-L1 + sharded-L2 through the
    ShardedReadBank collective tier vs the pre-PR composition — a host L1
    walk, then the sharded store's host-decide lookup on the residue (a
    GenerativeCache over a sharded store had no fused hierarchy path)."""
    emb = NgramHashEmbedder(DIM)
    rng = np.random.default_rng(0)
    base = _unit(rng, n_entries, DIM)
    mesh = make_cache_mesh()

    def sharded_l2(fused):
        s = ShardedVectorStore(mesh, dim=DIM, capacity=capacity, k=K,
                               fused=fused)
        s.add_batch(base, [f"query {i}" for i in range(n_entries)],
                    [f"answer {i}" for i in range(n_entries)])
        return s

    l1_host = _l1(emb, base, n_entries, capacity)
    s_host = sharded_l2(False)
    l1_f = _l1(emb, base, n_entries, capacity)
    l2_f = GenerativeCache(emb, threshold=THRESH, t_single=0.45,
                           t_combined=float("inf"), max_sources=K,
                           store=sharded_l2(True))
    h_fused = HierarchicalCache(l1_f, l2_f, promote=False,
                                generative_across_levels=False)
    srb = h_fused.ensure_sharded_bank()
    assert srb is not None

    def sync():
        banks = list(srb.banks()) + [s_host.bank]
        l1b = getattr(l1_host.store, "_bank", None)
        if l1b is not None:
            banks.append(l1b)
        for bk in banks:
            bk.d_last_access.block_until_ready()
            bk.d_access_count.block_until_ready()

    out = {}
    for b in batch_sizes:
        probes = _probes(np.random.default_rng(7), base, b)
        queries = [f"probe {j}" for j in range(b)]

        def run_host():
            res = l1_host.lookup_batch(queries, vecs=probes)
            miss = [i for i, r in enumerate(res) if not r.hit]
            l2 = s_host.lookup_batch_host(
                probes[np.asarray(miss)], np.full(len(miss), THRESH, np.float32)
            ) if miss else []
            return res, dict(zip(miss, l2))

        def run_fused():
            return h_fused.lookup_batch(queries, vecs=probes)

        (ref1, ref2), got = run_host(), run_fused()
        for i, g in enumerate(got):
            if ref1[i].hit:
                assert g.hit and g.response == ref1[i].response, (i, g)
            elif ref2.get(i) is not None:
                assert g.hit and g.response == ref2[i][1][1], (i, g)
            else:
                assert not g.hit, (i, g)
        host_s, fused_s = _median_pair(run_host, run_fused, repeats, sync=sync)
        out[f"b{b}"] = {
            "host_walk_ms": host_s * 1e3,
            "fused_ms": fused_s * 1e3,
            "speedup": host_s / fused_s,
            "hit_fraction": sum(1 for g in got if g.hit) / b,
        }
        emit(f"sharded_hier_b{b}", fused_s * 1e6,
             f"vs host walk {host_s * 1e6:.0f}us = {host_s / fused_s:.2f}x")

    d0, h0 = srb.dispatches, srb.host_hops
    bd0 = [bk.dispatches for bk in srb.banks()]
    h_fused.lookup_batch(queries, vecs=probes)
    out["dataflow"] = {
        "collective_dispatches": srb.dispatches - d0,
        "host_hops": srb.host_hops - h0,
        "member_bank_dispatches": sum(
            bk.dispatches - d for bk, d in zip(srb.banks(), bd0)
        ),
    }
    return out


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true", help="CI-sized run")
    ap.add_argument("--virtual-devices", type=int, default=0, metavar="N",
                    help="run on N virtual CPU devices instead of the devices "
                         "JAX sees (CI: 8)")
    args = ap.parse_args()

    if args.smoke:
        batch_sizes, n_entries, capacity, repeats = [1, 8, 64], 1024, 2048, 21
        hier_batches = [8, 64]
    else:
        batch_sizes, n_entries, capacity, repeats = [1, 4, 8, 64, 256], 1024, 2048, 21
        hier_batches = [1, 8, 64]

    results = {
        "config": {"k": K, "dim": DIM, "batch_sizes": batch_sizes,
                   "n_entries": n_entries, "capacity": capacity,
                   "repeats": repeats, "n_devices": len(jax.devices())},
        "sharded_store": bench_sharded_store(batch_sizes, n_entries, capacity,
                                             repeats),
        "hierarchy": bench_hierarchy(hier_batches, n_entries, capacity, repeats),
    }
    # the gate: peak fused-over-host speedup across serving batch sizes —
    # on a 1-core 8-virtual-device CI box large batches are pure-compute
    # bound (both paths serialize the same FLOPs), so the dispatch saving
    # the fused path exists to prove shows up at the latency-sensitive end
    per_batch = {b: results["sharded_store"][f"b{b}"]["speedup"]
                 for b in batch_sizes}
    results["fused_speedup"] = max(per_batch.values())
    results["fused_speedup_batch"] = max(per_batch, key=per_batch.get)
    flow = results["sharded_store"]["dataflow"]["fused"]
    results["fused_dispatches_per_batch"] = flow["collective_dispatches"]
    results["fused_host_hops"] = flow["host_hops_between_search_and_decide"]

    path = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
                        "BENCH_sharded_read.json")
    with open(path, "w") as f:
        json.dump(results, f, indent=2)
    print(f"\nwrote {path}")
    print(f"sharded fused read speedup vs host-decide on "
          f"{len(jax.devices())} devices: {results['fused_speedup']:.2f}x at "
          f"batch {results['fused_speedup_batch']} "
          f"({', '.join(f'b{b}={v:.2f}x' for b, v in per_batch.items())}; "
          f"collective dispatches={results['fused_dispatches_per_batch']}, "
          f"host hops={results['fused_host_hops']})")


if __name__ == "__main__":
    main()
