"""Serving driver: a zoo model behind the GenerativeCache-fronted client.

Runs batched requests (paraphrase-clustered synthetic queries) through the
full stack — embed -> semantic/generative lookup -> miss -> continuous-
batching engine -> insert — and prints hit-rate / latency / cost stats.

With ``--coalesce`` the driver simulates concurrent users against the
async-first ``CacheService``: each user submits a ``CacheRequest`` and gets
a future; the priority-aware front scheduler micro-batches the lookups (one
embed forward + one store search per admitted batch), hit futures resolve
immediately, and the miss residue coalesces by priority into engine passes
in the background. ``--deadline-ms`` attaches a deadline to every request:
misses that would outwait it resolve with a typed ``deadline_exceeded``
response instead of generating.

Without ``--smoke`` the launcher serves the arch at its published config
(random weights from a fixed seed) behind the paper's embedder,
contriever-msmarco (12 x 768), traced into the fused read program, over a
store searched by the compiled top-k kernel. ``--smoke`` keeps the tiny
CPU-runnable sizes: the arch's smoke config behind the host-side n-gram
embedder. ``build_stack`` is the one place the stack is assembled
(``chip_smoke.py`` builds through it too).

Usage:
  PYTHONPATH=src python -m repro.launch.serve --smoke --requests 40
  PYTHONPATH=src python -m repro.launch.serve --smoke --coalesce --coalesce-batch 8
  PYTHONPATH=src python -m repro.launch.serve --smoke --coalesce --deadline-ms 2000
  python chip_smoke.py                  # full width, on the chip
"""
from __future__ import annotations

import argparse
import time
from concurrent.futures import ThreadPoolExecutor
from dataclasses import dataclass
from typing import Optional

from repro.configs import ModelConfig, get_config
from repro.core import (
    CacheRequest,
    EnhancedClient,
    GenerativeCache,
    HierarchicalCache,
    NgramHashEmbedder,
)
from repro.core.adaptive import ModelCostInfo
from repro.core.vector_store import InMemoryVectorStore
from repro.data.synthetic import squad_like_qa
from repro.launch.compile_cache import enable_compile_cache
from repro.serving.engine import ModelBackend, ServingEngine
from repro.serving.service import CacheService


@dataclass
class Stack:
    """One assembled serving stack: the engine behind the cache client."""

    cfg: ModelConfig
    engine: ServingEngine
    cache: GenerativeCache  # L1
    hierarchy: Optional[HierarchicalCache]  # replicated L1 over a sharded L2
    client: EnhancedClient


def build_stack(
    arch: str = "qwen1.5-0.5b",
    *,
    smoke: bool = False,
    threshold: float = 0.6,
    t_single: float = 0.45,
    t_combined: float = 1.0,
    max_batch: int = 4,
    capacity: int = 4096,
    tier1_rows: int = 0,
    shards: int = 0,
    l2_capacity: int = 4096,
) -> Stack:
    """Assemble the serving stack. ``smoke=False``: the arch's published
    config and contriever-msmarco on the device, searched by the compiled
    top-k kernel; ``smoke=True``: the smoke config behind the n-gram
    embedder. ``tier1_rows`` attaches a host-RAM demotion ring to the L1
    store; ``shards`` key-shards an L2 over that many devices behind the
    replicated L1 and fails when JAX sees fewer devices."""
    cfg = get_config(arch, smoke=smoke)
    if cfg.kernel_interpret is not None:
        # config override for the kernel backend matrix (default: interpret
        # on CPU, compiled Pallas on TPU/GPU — repro.kernels.backend)
        from repro.kernels.backend import set_interpret_override

        set_interpret_override(cfg.kernel_interpret)
    # top-k kernel tuning defaults from the benchmarks/tune_topk.py sweep
    # (CPU-interpret winners are a smoke signal only — re-sweep on real
    # hardware); explicit REPRO_TOPK_* env vars win over the config
    from repro.kernels.similarity_topk.ops import apply_topk_tuning

    apply_topk_tuning(cfg.topk_block_n, cfg.topk_grid_order)
    engine = ServingEngine(cfg, max_batch=max_batch, max_seq=256)

    if smoke:
        embedder, use_pallas = NgramHashEmbedder(), False
    else:
        from repro.configs.contriever import CONTRIEVER_MSMARCO
        from repro.core.embeddings import ContrieverEncoder

        embedder, use_pallas = ContrieverEncoder(CONTRIEVER_MSMARCO), True
    tier1 = None
    if tier1_rows:
        from repro.core.tiers import HostRamTier

        tier1 = HostRamTier(embedder.dim, tier1_rows)
    store = InMemoryVectorStore(
        embedder.dim, capacity, use_pallas=use_pallas, tier1=tier1
    )
    cache = GenerativeCache(
        embedder, threshold=threshold, t_single=t_single, t_combined=t_combined,
        store=store,
    )
    hierarchy = None
    if shards > 0:
        # sharded deployment: the hot L1 stays replicated, the shared L2's
        # DB lanes are key-sharded over a cache mesh, and the hierarchy
        # serves both through ONE collective read program
        # (repro.distributed.sharded_read)
        import jax

        from repro.distributed.sharded_store import ShardedVectorStore
        from repro.launch.mesh import make_cache_mesh

        n_dev = len(jax.devices())
        if shards > n_dev:
            raise ValueError(
                f"shards={shards} needs {shards} devices; JAX sees {n_dev}"
            )
        l2 = GenerativeCache(
            embedder, threshold=threshold, t_single=t_single,
            t_combined=t_combined,
            store=ShardedVectorStore(
                make_cache_mesh(shards), embedder.dim, l2_capacity, k=4
            ),
        )
        hierarchy = HierarchicalCache(cache, l2)
    client = EnhancedClient(cache=cache, hierarchy=hierarchy)
    client.register_backend(ModelBackend(arch, engine), ModelCostInfo(0.5, 1.5, 3.0))
    return Stack(cfg, engine, cache, hierarchy, client)


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen1.5-0.5b")
    ap.add_argument("--smoke", action="store_true",
                    help="smoke config behind the n-gram embedder (CPU-runnable); "
                         "default: published widths with contriever-msmarco")
    ap.add_argument("--requests", type=int, default=40)
    ap.add_argument("--max-batch", type=int, default=4)
    ap.add_argument("--max-new-tokens", type=int, default=16)
    ap.add_argument("--threshold", type=float, default=0.6)
    ap.add_argument("--coalesce", action="store_true",
                    help="serve concurrent requests through the async CacheService")
    ap.add_argument("--coalesce-batch", type=int, default=8)
    ap.add_argument("--max-wait-ms", type=float, default=4.0)
    ap.add_argument("--concurrency", type=int, default=16,
                    help="simulated concurrent users (--coalesce only)")
    ap.add_argument("--deadline-ms", type=float, default=0.0,
                    help="per-request deadline; 0 disables (--coalesce only)")
    ap.add_argument("--http", type=int, default=None, metavar="PORT",
                    help="serve the OpenAI-compatible HTTP gateway on PORT "
                         "instead of running the replay driver")
    ap.add_argument("--http-pace-ms", type=float, default=0.0,
                    help="SSE pacing between streamed chunks of a cached "
                         "replay (--http only)")
    ap.add_argument("--shards", type=int, default=0,
                    help="key-shard a shared L2 store over an N-device cache "
                         "mesh behind the replicated L1 (0 = L1 only); reads "
                         "go through the one-dispatch collective program; "
                         "fails when JAX sees fewer than N devices")
    args = ap.parse_args(argv)

    enable_compile_cache()
    stack = build_stack(
        args.arch, smoke=args.smoke, threshold=args.threshold,
        max_batch=args.max_batch, shards=args.shards,
    )
    client, cache, engine = stack.client, stack.cache, stack.engine

    if args.http is not None:
        # real serving surface: the gateway owns the service and drains it
        # (in-flight futures resolve) on Ctrl-C
        from repro.gateway.app import serve_in_thread

        service = CacheService(
            client, max_batch=args.coalesce_batch, max_wait_ms=args.max_wait_ms
        )
        runner = serve_in_thread(
            service, port=args.http, pace_ms=args.http_pace_ms, own_service=True
        )
        host, port = runner.gateway.http.host, runner.gateway.port
        print(f"gateway listening on http://{host}:{port}")
        print(f"  POST http://{host}:{port}/v1/chat/completions")
        print(f"  POST http://{host}:{port}/v1/completions")
        print(f"  GET  http://{host}:{port}/healthz")
        print(f"  GET  http://{host}:{port}/v1/cache/stats")
        try:
            while True:
                time.sleep(0.5)
        except KeyboardInterrupt:
            pass
        clean = runner.stop()
        print(f"drained {'clean' if clean else 'DIRTY'}; "
              f"served={runner.gateway.http.requests_served}")
        return

    qa = squad_like_qa(n_clusters=max(args.requests // 4, 2), paraphrases=4)
    queries = [q for q, _, _ in qa][: args.requests]

    t0 = time.perf_counter()
    if args.coalesce:
        service = CacheService(
            client, max_batch=args.coalesce_batch, max_wait_ms=args.max_wait_ms
        )
        deadline_s = args.deadline_ms / 1e3 if args.deadline_ms > 0 else None

        def one(q: str):
            t = time.perf_counter()
            resp = service.submit(
                CacheRequest(q, max_tokens=args.max_new_tokens, deadline_s=deadline_s)
            ).result()
            return resp, time.perf_counter() - t

        with service, ThreadPoolExecutor(max_workers=args.concurrency) as users:
            results = list(users.map(one, queries))
        for i, (q, (r, wall)) in enumerate(zip(queries, results)):
            tag = {"hit": "HIT ", "generated": "MISS", "deadline_exceeded": "EXPD"}[r.status]
            print(f"[{i:3d}] {tag} {wall*1e3:7.1f} ms  {q[:60]}")
        sst = service.stats
        lk, dp = service.scheduler_stats
        print(f"service: hits={sst.hits} generated={sst.generated} "
              f"deduped={sst.deduped} expired={sst.expired} "
              f"rejected={sst.rejected} lookup_avg_batch={lk.avg_batch:.1f} "
              f"dispatch_avg_batch={dp.avg_batch if dp else 0.0:.1f}")
    else:
        for i, q in enumerate(queries):
            r = client.query(q, max_tokens=args.max_new_tokens)
            tag = "HIT " if r.from_cache else "MISS"
            print(f"[{i:3d}] {tag} {r.latency_s*1e3:7.1f} ms  {q[:60]}")
    wall = time.perf_counter() - t0

    s = client.stats
    print(f"\nrequests={s.requests} hits={s.cache_hits} "
          f"hit_rate={s.cache_hits / max(s.requests, 1):.2f} "
          f"llm_calls={s.llm_calls} cost=${s.total_cost_usd:.6f} wall={wall:.1f}s")
    print(f"engine: {engine.metrics}")
    cs = cache.stats
    print(f"cache: lookups={cs.lookups} generative_hits={cs.generative_hits}")


if __name__ == "__main__":
    main()
