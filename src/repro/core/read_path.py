"""Zero-host-hop read path: ONE device program for embed -> search -> decide
-> touch.

Before this module, a batched cache lookup made three host<->device round
trips: the embedding forward materialized [B, D] on host, ``search_lanes``
re-uploaded it and pulled [B, L, k] scores back, and the per-level
threshold/winner walk plus every LRU/LFU bump ran in host Python. The fused
read program moves the whole hot path into a single jitted dispatch
(bucketed per batch size):

    token ids / raw vectors
        -> embedding forward                      (in-program)
        -> banked [L, cap, D] lane top-k          (jnp einsum or Pallas kernel)
        -> per-query/per-level threshold + generative-rule decide masks
        -> L1 > L2 > peers winner walk            (masked argmax over [B, L])
        -> recency/frequency scatter-add into the bank's device counters,
           gated to the levels a sequential walk would have probed
        -> compact decision tensors back to host

Only the decision tensors (winner lane, hit/generative class, top-k
scores/slots, and the embeddings for backfill) cross back to host — there
are ZERO host hops between embed and decide, and the touch updates that
used to be a host loop are a donated scatter inside the same program.

Decision semantics are those of ``SemanticCache._decide_batch`` /
``GenerativeCache._decide_batch`` (hit iff best > t_s; generative hit iff
the §3 rule fires), expressed as masks; the host *materialization* stage
(``_materialize_batch`` on the caches) turns masks + joined candidates into
``CacheResult``s for exactly the rows that need them. The only permissible
divergence from the host loop is the generative rule's combined-similarity
sum, accumulated in device float32 instead of host float64 — meaningful
only for scores within float32 epsilon of ``t_combined``.
"""
from __future__ import annotations

import functools
from dataclasses import dataclass
from typing import List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.profiler import TraceAnnotation

from repro.core.store_bank import StoreBank, fused_search_body, pad_to_bucket

_INT32_MIN = np.iinfo(np.int32).min
_NEG_FINITE = -3.0e38  # anything below is an invalid-slot sentinel (-inf / NEG)


@dataclass(frozen=True)
class LevelSpec:
    """Static per-level decision parameters baked into the read program
    (hashable: part of the program's compile-cache key)."""

    generative: bool  # GenerativeCache level (the §3 rule applies)
    secondary: bool  # direct best>t_s check first (semantic levels: always)
    t_single: float
    t_combined: float
    max_sources: int  # X-set cap for the generative rule
    k: int  # candidates searched & touched for this level


def level_spec(cache, k: int) -> Optional[LevelSpec]:
    """Build the device decide spec for one cache level, or None when the
    cache customizes ``_decide_batch`` (its semantics cannot be assumed —
    the caller must stay on the host decide path)."""
    from repro.core.generative_cache import GenerativeCache
    from repro.core.semantic_cache import SemanticCache

    cls = type(cache)
    if isinstance(cache, GenerativeCache):
        if cls._decide_batch is not GenerativeCache._decide_batch:
            return None
        return LevelSpec(
            True, cache.mode == "secondary", float(cache.t_single),
            float(cache.t_combined), int(cache.max_sources), int(k),
        )
    if isinstance(cache, SemanticCache):
        if cls._decide_batch is not SemanticCache._decide_batch:
            return None
        return LevelSpec(False, True, 0.0, float("inf"), 0, int(k))
    return None


def store_bankable(store) -> bool:
    """The store's device rows/counters live in a StoreBank lane and its
    search/join semantics are the stock ones (a subclass overriding either
    must keep running its own code)."""
    from repro.core.vector_store import InMemoryVectorStore

    return (
        isinstance(store, InMemoryVectorStore)
        and type(store).search_batch is InMemoryVectorStore.search_batch
        and type(store).join_candidates is InMemoryVectorStore.join_candidates
    )


@dataclass
class ReadDecision:
    """Host-side view of one fused read: everything the materialization
    stage needs, already sliced back to the real batch size."""

    vecs: np.ndarray  # [n, D] embeddings (reused for promotions/backfill)
    scores: np.ndarray  # [n, L, K]
    idx: np.ndarray  # [n, L, K] lane-local slots
    winner: np.ndarray  # [n] winning level index; L = miss everywhere
    hit: np.ndarray  # [n, L] per-level hit mask (semantic or generative)
    generative: np.ndarray  # [n, L] generative-hit mask (subset of hit)


def make_decide(specs: Tuple[LevelSpec, ...], K: int):
    """Shared trace of the decide stage: the ``_decide_batch`` semantics as
    [B, L] masks, the L1 > L2 > peers winner walk, and the probed-levels
    touch mask. ONE body with two callers — the single-host fused program
    below and the sharded shard_map program
    (``repro.distributed.sharded_read``) — so their decisions cannot drift.

    Returns ``decide(s, thresholds, qmask) -> (winner, hit, generative,
    tmask)`` where ``s`` is [B, L, K] score-desc candidates and ``tmask``
    is the [B, L, K] bump mask (levels a sequential walk would have probed,
    finite candidates only, capped at each level's own k)."""
    L = len(specs)
    t_single = np.asarray([s.t_single for s in specs], np.float32)
    t_comb = np.asarray(
        [s.t_combined if s.generative else np.inf for s in specs], np.float32
    )
    msl = np.asarray([min(s.max_sources, s.k) for s in specs], np.int32)
    ks = np.asarray([s.k for s in specs], np.int32)
    gen_l = np.asarray([s.generative for s in specs])
    sec_l = np.asarray([(not s.generative) or s.secondary for s in specs])

    def decide(s, thresholds, qmask):
        # -- decide: the _decide_batch semantics as [B, L] masks -------------
        colK = jnp.arange(K)
        finite = s > jnp.float32(_NEG_FINITE)
        best = s[:, :, 0]  # scores sorted desc, so [.., 0] is each lane's best
        sem_direct = jnp.asarray(sec_l)[None, :] & (best > thresholds)
        in_x = (
            finite
            & (s > jnp.asarray(t_single)[None, :, None])
            & (colK[None, None, :] < jnp.asarray(msl)[None, :, None])
            & jnp.asarray(gen_l)[None, :, None]
        )
        combined = jnp.sum(jnp.where(in_x, s, 0.0), axis=-1)
        gen_ok = in_x.any(-1) & (combined > jnp.asarray(t_comb)[None, :])
        # X[0] == best whenever X is nonempty (desc order), so the rule's
        # "single overwhelming match" branch is best > t_s under gen_ok
        semantic = sem_direct | (gen_ok & (best > thresholds))
        hit = (semantic | gen_ok) & qmask[:, None]
        generative = gen_ok & ~semantic & qmask[:, None]
        # -- winner walk: first hitting level in L1 > L2 > peers order --------
        winner = jnp.where(hit.any(1), jnp.argmax(hit, axis=1), L).astype(jnp.int32)
        # -- touch: bump exactly what the sequential walk would have probed --
        probed = (jnp.arange(L)[None, :] <= winner[:, None]) & qmask[:, None]
        tmask = (
            probed[:, :, None]
            & finite
            & (colK[None, None, :] < jnp.asarray(ks)[None, :, None])
        )
        return winner, hit, generative, tmask

    return decide


@functools.lru_cache(maxsize=64)
def _build_program(forward, specs: Tuple[LevelSpec, ...], K: int,
                   metrics: Tuple[str, ...], prenorm: Tuple[bool, ...],
                   use_pallas: bool, interpret: bool, block_n: int,
                   grid_order: str, lifecycle: bool = False):
    """Compile-cached fused read program. Keyed on the forward fn identity
    (stable per embedder instance — host embedders share one module-level
    identity forward), the level specs, and the bank layout; jax.jit adds
    the shape bucketing on top. Bounded: the key pins the forward closure
    (and through it the embedder), so an unbounded cache would leak
    programs in processes that churn through cache/embedder instances."""
    L = len(specs)
    mixed = len(set(metrics)) > 1
    decide = make_decide(specs, K)

    def search(q, buf, valid):
        if use_pallas:
            from repro.kernels.similarity_topk.ops import _similarity_topk_lanes

            return _similarity_topk_lanes(
                buf, valid, q, k=K, metric=metrics, block_n=block_n,
                interpret=interpret,
                prenormalized=True if mixed else all(prenorm),
                grid_order=grid_order,
            )
        return fused_search_body(buf, valid, q, K, metrics, prenorm)

    def decide_and_touch(s, idx, thresholds, qmask, last, cnt, tick):
        winner, hit, generative, tmask = decide(s, thresholds, qmask)
        lanes3 = jnp.broadcast_to(jnp.arange(L)[None, :, None], s.shape)
        cnt = cnt.at[lanes3, idx].add(tmask.astype(jnp.int32))
        stamp = jnp.where(tmask, tick, jnp.int32(_INT32_MIN))
        last = last.at[lanes3, idx].max(stamp)
        return s, idx, winner, hit, generative, last, cnt

    # the stages' named scopes give their device ops stable names in a
    # profile; the module and kernel names are left as they are
    if not lifecycle:
        # TTL-free deployments compile a program without the lifecycle
        # inputs: no expiry mask, no staleness rescore
        def program(embed_args, thresholds, qmask, buf, valid, last, cnt, tick):
            with jax.named_scope("encoder"):
                q = forward(*embed_args)  # [B, D] — embeds never leave the device
            with jax.named_scope("search"):
                s, idx = search(q, buf, valid)
            with jax.named_scope("decide"):
                s, idx, winner, hit, generative, last, cnt = decide_and_touch(
                    s, idx, thresholds, qmask, last, cnt, tick
                )
            return q, s, idx, winner, hit, generative, last, cnt

        return jax.jit(program, donate_argnums=(5, 6))

    def program_lc(embed_args, thresholds, qmask, buf, valid, created,
                   expires, w, now, last, cnt, tick):
        with jax.named_scope("encoder"):
            q = forward(*embed_args)
        with jax.named_scope("search"):
            # expiry mask INSIDE the decide stage: a dead row is invalid for
            # this dispatch, so it can never surface as a candidate, let
            # alone win
            s, idx = search(q, buf, valid & (expires > now))
            finite = s > jnp.float32(_NEG_FINITE)
            lanes3 = jnp.broadcast_to(jnp.arange(L)[None, :, None], s.shape)
            c = created[lanes3, idx]
            e = expires[lanes3, idx]
            # staleness-aware scoring: an aging entry must beat a higher bar —
            # w[lane] * clip(age/ttl, 0, 1) comes off its similarity
            frac = jnp.clip((now - c) / jnp.maximum(e - c, 1e-6), 0.0, 1.0)
            pen = jnp.where(
                finite & jnp.isfinite(e), w[None, :, None] * frac, 0.0
            )
            s = s - pen
            # re-establish descending order (decide assumes best-first
            # candidates)
            s, order = jax.lax.top_k(s, K)
            idx = jnp.take_along_axis(idx, order, axis=-1)
        with jax.named_scope("decide"):
            s, idx, winner, hit, generative, last, cnt = decide_and_touch(
                s, idx, thresholds, qmask, last, cnt, tick
            )
        return q, s, idx, winner, hit, generative, last, cnt

    return jax.jit(program_lc, donate_argnums=(9, 10))


def fused_read(
    bank: StoreBank,
    embedder,
    texts: Sequence[str],
    thresholds: np.ndarray,  # [n, L] per-query/per-level effective t_s
    specs: Sequence[LevelSpec],
    vecs: Optional[np.ndarray] = None,
) -> ReadDecision:
    """Run one fused read over a bank: ONE device dispatch end-to-end,
    including the eviction-counter touches. ``vecs`` short-circuits the
    embed stage (callers that already hold embeddings upload them once).

    Profiler spans: ``read.tokenize`` (the host's input prep),
    ``read.dispatch`` (enqueueing the program) and ``read.fetch`` (the host
    blocked on the device until the decision tensors arrive)."""
    from repro.core.embeddings import _identity_forward
    from repro.kernels.similarity_topk import ops as st_ops

    n = len(texts)
    specs = tuple(specs)
    L = len(specs)
    K = max(s.k for s in specs)
    with TraceAnnotation("read.tokenize"):
        if vecs is not None:
            v, _ = pad_to_bucket(np.asarray(vecs, np.float32).reshape(n, bank.dim))
            args, B, forward = (v,), v.shape[0], _identity_forward
        else:
            prepare, forward = embedder.fused_forward()
            args, n_prep, B = prepare(list(texts))
            assert n_prep == n
        qmask = np.arange(B) < n
        thr = np.full((B, L), np.inf, np.float32)
        thr[:n] = np.asarray(thresholds, np.float32).reshape(n, L)

    with TraceAnnotation("read.dispatch"):
        bank.flush_pending()
        use_pallas = bank.use_pallas and bank._kernel_ok()
        lifecycle = bank.lifecycle_active()
        program = _build_program(
            forward, specs, K, bank.metrics, bank.prenorm, use_pallas,
            bank._resolved_interpret(), st_ops.default_block_n(),
            st_ops.default_grid_order(), lifecycle,
        )
        tick = bank.next_tick()
        bank.dispatches += 1
        if use_pallas:
            st_ops.record_dispatch()
        if lifecycle:
            q, s, idx, winner, hit, gen, last, cnt = program(
                args, thr, qmask, bank.buf, bank.valid,
                bank.d_created, bank.d_expires, bank.d_staleness(),
                np.float32(bank.rel_now()),
                bank.d_last_access, bank.d_access_count, np.int32(tick),
            )
        else:
            q, s, idx, winner, hit, gen, last, cnt = program(
                args, thr, qmask, bank.buf, bank.valid,
                bank.d_last_access, bank.d_access_count, np.int32(tick),
            )
        bank.adopt_fused_counters(last, cnt)
    # ONE host fetch for all decision tensors (the counters stay on device)
    with TraceAnnotation("read.fetch"):
        q, s, idx, winner, hit, gen = jax.device_get((q, s, idx, winner, hit, gen))
    return ReadDecision(q[:n], s[:n], idx[:n], winner[:n], hit[:n], gen[:n])


def join_rows(
    store, scores: np.ndarray, idx: np.ndarray, rows: List[int], k: int
) -> dict:
    """Join only the listed row indices against the store's host entries
    (the fused path materializes winners and pool rows — not B x L rows)."""
    if not rows:
        return {}
    joined = store.join_candidates(scores[rows], idx[rows], touch=False)
    return {i: m[:k] for i, m in zip(rows, joined)}
