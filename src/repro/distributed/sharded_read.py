"""Sharded zero-host-hop read path: ONE collective device program for the
whole mesh.

``repro.core.read_path`` fuses embed -> search -> decide -> touch for a
single-host bank; this module is its ``shard_map`` twin for deployments
whose DB lanes are sharded over the mesh. One jitted dispatch covers:

    embed forward                       (replicated — every shard embeds)
    replicated hot lanes  [Lr, cap, D]  per-level top-k on every device
    sharded cold lanes    [n, capl, D]  local MXU dot + local top-k per
                                        mesh slice (make_banked_lookup's
                                        kernel body), then all_gather of
                                        only the tiny [B, k] candidate sets
                                        (hierarchical ICI-then-DCN schedule)
    device-side router mask             lane visibility per query — no
                                        per-shard host loop
    threshold + generative-rule masks   repro.core.read_path.make_decide —
    + L1 > L2 > peers winner walk       the SAME traced body as the
                                        single-host program
    recency/frequency touch scatters    replicated lanes update identically
                                        everywhere; sharded lanes apply an
                                        ownership-masked local scatter into
                                        their own device-resident counters

Only compact decision tensors ([B, L, K] scores/slots, winner, hit /
generative masks, and the embeddings) return to host: zero host hops
between embed and decide, exactly one dispatch including the touches.

Entry lifecycle (TTL expiry + staleness penalty) runs in-program too, but
— unlike the single-host program, which rescores only the top-K candidates
— the penalty applies to the full per-shard score matrix BEFORE the local
top-k. Pre-top-k rescoring is strictly more faithful (a stale high-raw
score can no longer crowd a fresher entry out of the candidate set) and
makes ``host_reference_read`` an exact numpy mirror.

The pre-PR host walk (device search, host-side staleness rescore +
threshold decide + separate touch scatter) survives as
``ShardedVectorStore.search_host``/``search_batch_host``/
``lookup_batch_host`` and as ``host_reference_read`` below — references
for parity tests and the benchmark baseline, not serving paths.
"""
from __future__ import annotations

import functools
from typing import List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.profiler import TraceAnnotation
from jax.sharding import PartitionSpec as P

from repro.core.read_path import (
    _INT32_MIN,
    _NEG_FINITE,
    LevelSpec,
    ReadDecision,
    make_decide,
)
from repro.core.similarity import SCORE_PRECISION
from repro.core.store_bank import (
    StoreBank,
    _lane_scores,
    _normalize_rows as _norm_rows,
    pad_to_bucket,
)
from repro.distributed.sharded_store import (
    _shard_axes,
    all_gather_merge_topk,
    shard_id,
)


def _pad_cols(ts, ti, K: int):
    """Pad merged candidate columns up to K with -inf/slot-0 sentinels (the
    decide/touch masks treat non-finite scores as absent, and a slot-0 index
    under a False touch mask is a no-op scatter)."""
    pad = K - ts.shape[-1]
    if pad <= 0:
        return ts, ti
    ts = jnp.concatenate(
        [ts, jnp.full((*ts.shape[:-1], pad), -jnp.inf, ts.dtype)], -1
    )
    ti = jnp.concatenate([ti, jnp.zeros((*ti.shape[:-1], pad), ti.dtype)], -1)
    return ts, ti


@functools.lru_cache(maxsize=32)
def _build_sharded_program(
    forward,
    mesh,
    layout: Tuple[Tuple[str, int], ...],  # per level: ("rep", lane) | ("sh", member)
    specs: Tuple[LevelSpec, ...],
    K: int,
    rep_meta: Optional[Tuple[Tuple[str, ...], Tuple[bool, ...]]],
    sh_meta: Tuple[Tuple[str, bool], ...],  # (metric, prenormalized) per member
    lifecycle: bool,
    touch: bool,
    hierarchical: bool = True,
):
    """Compile-cached sharded fused read program (same bounded-key scheme as
    ``read_path._build_program``: forward identity + level specs + bank
    layout + mesh; jax.jit adds shape bucketing). The decide stage is
    ``read_path.make_decide`` — literally the same traced body as the
    single-host program, so the two paths cannot drift."""
    axes = _shard_axes(mesh)
    ax = axes if len(axes) > 1 else axes[0]
    L = len(specs)
    decide = make_decide(specs, K)
    rep_levels = [(li, j) for li, (kind, j) in enumerate(layout) if kind == "rep"]
    sh_levels = [(li, j) for li, (kind, j) in enumerate(layout) if kind == "sh"]
    rep_metrics, rep_prenorm = rep_meta if rep_meta is not None else ((), ())
    tick_off = 1 if rep_levels else 0

    def body(embed_args, thr, qmask, router, rep_arrays, rep_life, sh_arrays,
             sh_life, now, counters, ticks, shard_ok):
        with jax.named_scope("encoder"):
            q = forward(*embed_args)  # replicated: embeds never leave the device
        with jax.named_scope("search"):
            level_s: List = [None] * L
            level_i: List = [None] * L
            if rep_levels:
                buf, valid = rep_arrays
                cap = buf.shape[1]
                if lifecycle:
                    created, expires, w = rep_life
                    # expiry mask + staleness penalty PRE-top-k (module docstring)
                    valid_eff = valid & (expires > now)
                    frac = jnp.clip(
                        (now - created) / jnp.maximum(expires - created, 1e-6),
                        0.0, 1.0,
                    )
                    pen = jnp.where(jnp.isfinite(expires), w[:, None] * frac, 0.0)
                else:
                    valid_eff, pen = valid, None
                # fused_search_body's scoring with the optional pre-top-k penalty
                if len(set(rep_metrics)) == 1:
                    s = _lane_scores(buf, q, rep_metrics[0], all(rep_prenorm))
                else:
                    s = jnp.stack([
                        _lane_scores(buf[r], q, rep_metrics[r], rep_prenorm[r])
                        for r in range(len(rep_metrics))
                    ])
                if pen is not None:
                    s = s - pen[:, None, :]
                s = jnp.where(valid_eff[:, None, :], s, -jnp.inf)  # [Lr, Q, cap]
                ts, ti = jax.lax.top_k(s, min(K, cap))
                ts, ti = ts.transpose(1, 0, 2), ti.transpose(1, 0, 2)
                ts, ti = _pad_cols(ts, ti, K)
                for li, j in rep_levels:
                    level_s[li], level_i[li] = ts[:, j], ti[:, j]
            for li, j in sh_levels:
                db_l, valid_l = sh_arrays[j]
                lanes_loc, cap_local, dim = db_l.shape
                cap_shard = lanes_loc * cap_local
                metric_j, prenorm_j = sh_meta[j]
                db2 = db_l.reshape(cap_shard, dim)
                v2 = valid_l.reshape(cap_shard)
                # make_banked_lookup's kernel body: per-shard MXU dot, local top-k
                dbn = db2 if (metric_j != "cosine" or prenorm_j) else _norm_rows(db2)
                qn = _norm_rows(q) if metric_j == "cosine" else q
                s = jnp.matmul(qn, dbn.T, precision=SCORE_PRECISION)  # [Q, cap_shard]
                if lifecycle:
                    created_l, expires_l, w_l = sh_life[j]
                    c2 = created_l.reshape(cap_shard)
                    e2 = expires_l.reshape(cap_shard)
                    w2 = jnp.repeat(w_l, cap_local)
                    v2 = v2 & (e2 > now)
                    frac = jnp.clip(
                        (now - c2) / jnp.maximum(e2 - c2, 1e-6), 0.0, 1.0
                    )
                    s = s - jnp.where(jnp.isfinite(e2), w2 * frac, 0.0)[None, :]
                s = jnp.where(v2[None, :], s, -jnp.inf)
                # shard-availability mask (resilience): a shard marked dead
                # contributes only -inf candidates, so after the merge the
                # surviving shards' winners serve the lookup instead of the
                # whole collective failing — degraded, not down
                s = jnp.where(shard_ok[shard_id(axes)], s, -jnp.inf)
                ts, ti = jax.lax.top_k(s, min(K, cap_shard))
                # shard-local flat idx -> store-global flat idx, then the tiny
                # [B, k] candidate exchange (ICI first, DCN last)
                ti = ti + shard_id(axes) * cap_shard
                ts, ti = all_gather_merge_topk(axes, ts, ti, K,
                                               hierarchical=hierarchical)
                level_s[li], level_i[li] = _pad_cols(ts, ti, K)
            s_all = jnp.stack(level_s, 1)  # [B, L, K]
            idx_all = jnp.stack(level_i, 1)
            # device-side router: an invisible lane's candidates can neither win
            # nor be touched (the decide masks key off finite scores)
            s_all = jnp.where(router[:, :, None], s_all, -jnp.inf)
        with jax.named_scope("decide"):
            winner, hit, generative, tmask = decide(s_all, thr, qmask)
            rep_c, sh_c = counters
            if touch and rep_levels:
                # replicated counters: every device applies the identical full
                # scatter, so the arrays stay replicated without a collective
                last, cnt = rep_c
                idx_r = jnp.stack([idx_all[:, li] for li, _ in rep_levels], 1)
                tm_r = jnp.stack([tmask[:, li] for li, _ in rep_levels], 1)
                lane_ids = jnp.asarray([j for _, j in rep_levels], jnp.int32)
                lanes3 = jnp.broadcast_to(lane_ids[None, :, None], idx_r.shape)
                cnt = cnt.at[lanes3, idx_r].add(tm_r.astype(jnp.int32))
                stamp = jnp.where(tm_r, ticks[0], jnp.int32(_INT32_MIN))
                last = last.at[lanes3, idx_r].max(stamp)
                rep_c = (last, cnt)
            if touch and sh_levels:
                out_sh = []
                for li, j in sh_levels:
                    # ownership-masked local scatter: each shard bumps only the
                    # slots it owns — no cross-device counter traffic at all
                    last, cnt = sh_c[j]
                    lanes_loc, cap_local = last.shape
                    idxg = idx_all[:, li]
                    within = idxg % cap_local
                    ll = idxg // cap_local - shard_id(axes) * lanes_loc
                    # a dead shard must not move its counters either (its -inf
                    # candidates never win, but tmask covers probed levels)
                    own = tmask[:, li] & (ll >= 0) & (ll < lanes_loc)
                    own = own & shard_ok[shard_id(axes)]
                    llc = jnp.clip(ll, 0, lanes_loc - 1)
                    cnt = cnt.at[llc, within].add(own.astype(jnp.int32))
                    stamp = jnp.where(own, ticks[tick_off + j], jnp.int32(_INT32_MIN))
                    last = last.at[llc, within].max(stamp)
                    out_sh.append((last, cnt))
                sh_c = tuple(out_sh)
        return q, s_all, idx_all, winner, hit, generative, (rep_c, sh_c)

    REP3, REP2, REP1 = P(None, None, None), P(None, None), P(None)
    SH3, SH2, SH1 = P(ax, None, None), P(ax, None), P(ax)
    rep_arr_spec = (REP3, REP2) if rep_levels else ()
    rep_life_spec = (REP2, REP2, REP1) if (rep_levels and lifecycle) else ()
    sh_arr_spec = tuple((SH3, SH2) for _ in sh_meta)
    sh_life_spec = tuple((SH2, SH2, SH1) for _ in sh_meta) if lifecycle else ()
    counters_spec = (
        (REP2, REP2) if (touch and rep_levels) else (),
        tuple((SH2, SH2) for _ in sh_meta) if touch else (),
    )
    fn = jax.shard_map(
        body,
        mesh=mesh,
        in_specs=(P(), P(), P(), P(), rep_arr_spec, rep_life_spec,
                  sh_arr_spec, sh_life_spec, P(), counters_spec, P(), P()),
        out_specs=(P(), P(), P(), P(), P(), P(), counters_spec),
        check_vma=False,
    )
    return jax.jit(fn, donate_argnums=(9,))


class ShardedReadBank:
    """Device-resident view of a sharded hierarchy behind ONE collective
    read program: hot levels backed by ``InMemoryVectorStore`` are adopted
    into a replicated ``StoreBank`` (their full lanes live on every device),
    levels backed by ``ShardedVectorStore`` stay sharded by key over the
    mesh. ``fused_read`` then serves the whole hierarchy — embed, per-level
    candidates, candidate exchange, router, decide, winner walk, and both
    banks' counter touches — in a single dispatch.

    ``members`` is the level list in L1 > L2 > peers order, each entry
    ``("rep", InMemoryVectorStore)`` or ``("sh", ShardedVectorStore)``."""

    def __init__(self, mesh, members: Sequence[Tuple[str, object]]):
        axes = _shard_axes(mesh)
        if not axes:
            raise ValueError("sharded read path needs a mesh with a pod/data axis")
        self.mesh = mesh
        self.axes = axes
        self.members = list(members)
        self.rep_stores = [s for kind, s in self.members if kind == "rep"]
        self.sh_stores = [s for kind, s in self.members if kind == "sh"]
        if not self.sh_stores:
            raise ValueError("no sharded member — use read_path.fused_read")
        for s in self.sh_stores:
            if s.mesh is not mesh:
                raise ValueError("sharded members must share the program mesh")
        self.rep_bank: Optional[StoreBank] = (
            StoreBank.adopt(self.rep_stores) if self.rep_stores else None
        )
        if self.rep_bank is not None:
            self._replicate(self.rep_bank)
        layout: List[Tuple[str, int]] = []
        ri = si = 0
        for kind, _ in self.members:
            if kind == "rep":
                layout.append(("rep", ri))
                ri += 1
            else:
                layout.append(("sh", si))
                si += 1
        self.layout = tuple(layout)
        self.dim = (self.rep_bank or self.sh_stores[0].bank).dim
        # dataflow counters (same contract as StoreBank's): the collective
        # program counts ONE dispatch however many mesh slices it spans
        self.dispatches = 0
        self.host_hops = 0
        self.counter_scatters = 0
        # resilience: reads served with >= 1 shard masked dead (survivors'
        # candidates answered instead of the collective failing)
        self.degraded_reads = 0

    @property
    def n_shards(self) -> int:
        return int(np.prod([self.mesh.shape[a] for a in self.axes]))

    @property
    def degraded(self) -> bool:
        """True once any read ran with a shard masked out."""
        return self.degraded_reads > 0

    def _replicate(self, bank: StoreBank) -> None:
        """Pin the hot bank's arrays to an every-device replicated layout so
        the per-dispatch shard_map never pays a broadcast."""
        rspec = jax.NamedSharding(self.mesh, P())
        bank.buf = jax.device_put(bank.buf, rspec)
        bank.valid = jax.device_put(bank.valid, rspec)
        bank.d_last_access = jax.device_put(bank.d_last_access, rspec)
        bank.d_access_count = jax.device_put(bank.d_access_count, rspec)
        bank.d_insert_seq = jax.device_put(bank.d_insert_seq, rspec)
        bank.d_created = jax.device_put(bank.d_created, rspec)
        bank.d_expires = jax.device_put(bank.d_expires, rspec)

    def banks(self) -> List[StoreBank]:
        head = [self.rep_bank] if self.rep_bank is not None else []
        return head + [s.bank for s in self.sh_stores]

    def intact(self, stores: Sequence) -> bool:
        """The given level stores (in order) still match this adoption —
        same objects, replicated members still pointing at our shared bank
        lanes (a swapped/re-adopted store forces a rebuild)."""
        if len(stores) != len(self.members):
            return False
        ri = 0
        for (kind, s0), s in zip(self.members, stores):
            if s is not s0:
                return False
            if kind == "rep":
                if s._bank is not self.rep_bank or s._lane != ri:
                    return False
                ri += 1
        return True

    def lifecycle_active(self) -> bool:
        return any(b.lifecycle_active() for b in self.banks())

    def fused_read(
        self,
        embedder,
        texts: Sequence[str],
        thresholds: np.ndarray,  # [n, L] per-query/per-level effective t_s
        specs: Sequence[LevelSpec],
        vecs: Optional[np.ndarray] = None,
        router: Optional[np.ndarray] = None,  # [n, L] lane visibility
        touch: bool = True,
        shard_mask: Optional[np.ndarray] = None,  # [n_shards] bool; False = dead
    ) -> ReadDecision:
        """One collective read over the whole sharded hierarchy. Returns the
        same ``ReadDecision`` contract as ``read_path.fused_read``; sharded
        levels report store-global flat slot indices (what their
        ``join_candidates`` expects), replicated levels lane-local ones.

        ``shard_mask`` marks shards unavailable (False): their candidates
        score -inf inside the program and their counters stay untouched, so
        a lookup degrades to the surviving shards' winners instead of the
        whole collective failing — the read-path leg of the resilience
        degradation ladder.

        Opens the profiler spans of ``read_path.fused_read``:
        ``read.tokenize``, ``read.dispatch`` and ``read.fetch``."""
        from repro.core.embeddings import _identity_forward

        n = len(texts)
        specs = tuple(specs)
        L = len(specs)
        K = max(sp.k for sp in specs)
        with TraceAnnotation("read.tokenize"):
            if vecs is not None:
                v, _ = pad_to_bucket(np.asarray(vecs, np.float32).reshape(n, self.dim))
                args, B, forward = (v,), v.shape[0], _identity_forward
            else:
                prepare, forward = embedder.fused_forward()
                args, n_prep, B = prepare(list(texts))
                assert n_prep == n
            qmask = np.arange(B) < n
            thr = np.full((B, L), np.inf, np.float32)
            thr[:n] = np.asarray(thresholds, np.float32).reshape(n, L)
            rmask = np.ones((B, L), bool)
            if router is not None:
                rmask[:n] = np.asarray(router, bool).reshape(n, L)
        with TraceAnnotation("read.dispatch"):
            banks = self.banks()
            for b in banks:
                b.flush_pending()
            lifecycle = self.lifecycle_active()
            rb = self.rep_bank
            rep_meta = (rb.metrics, rb.prenorm) if rb is not None else None
            sh_meta = tuple(
                (s.metric, s.bank.prenormalized) for s in self.sh_stores
            )
            program = _build_sharded_program(
                forward, self.mesh, self.layout, specs, K, rep_meta, sh_meta,
                lifecycle, touch,
            )
            rep_arrays = (rb.buf, rb.valid) if rb is not None else ()
            rep_life = (
                (rb.d_created, rb.d_expires, rb.d_staleness())
                if (rb is not None and lifecycle) else ()
            )
            sh_arrays = tuple((s.bank.buf, s.bank.valid) for s in self.sh_stores)
            sh_life = tuple(
                (s.bank.d_created, s.bank.d_expires, s.bank.d_staleness())
                for s in self.sh_stores
            ) if lifecycle else ()
            if touch:
                ticks = tuple(np.int32(b.next_tick()) for b in banks)
                counters = (
                    (rb.d_last_access, rb.d_access_count) if rb is not None else (),
                    tuple(
                        (s.bank.d_last_access, s.bank.d_access_count)
                        for s in self.sh_stores
                    ),
                )
            else:
                ticks = ()
                counters = ((), ())
            if shard_mask is None:
                shard_ok = np.ones(self.n_shards, bool)
            else:
                shard_ok = np.asarray(shard_mask, bool).reshape(self.n_shards)
                if not shard_ok.any():
                    raise ValueError("shard_mask marks every shard dead")
                if not shard_ok.all():
                    self.degraded_reads += 1
            self.dispatches += 1
            q, s, idx, winner, hit, gen, new_counters = program(
                args, thr, qmask, rmask, rep_arrays, rep_life, sh_arrays, sh_life,
                np.float32(StoreBank.rel_now()), counters, ticks, shard_ok,
            )
            if touch:
                rep_c, sh_c = new_counters
                if rb is not None:
                    rb.adopt_fused_counters(*rep_c)
                for store, (last, cnt) in zip(self.sh_stores, sh_c):
                    store.bank.adopt_fused_counters(last, cnt)
        # ONE host fetch for all decision tensors (counters stay on device;
        # vector-ingress callers already hold the embeddings, so the
        # replicated q never crosses back — identity forward means q == v)
        with TraceAnnotation("read.fetch"):
            if vecs is not None:
                s, idx, winner, hit, gen = jax.device_get((s, idx, winner, hit, gen))
                q = v
            else:
                q, s, idx, winner, hit, gen = jax.device_get(
                    (q, s, idx, winner, hit, gen)
                )
        return ReadDecision(q[:n], s[:n], idx[:n], winner[:n], hit[:n], gen[:n])


# -- host reference walk (parity tests + benchmark baseline only) --------------


def _np_scores(db: np.ndarray, q: np.ndarray, metric: str, prenormalized: bool):
    """Numpy float32 mirror of the program's scoring leg (cosine/dot)."""
    db = np.asarray(db, np.float32)
    q = np.asarray(q, np.float32)
    if metric == "cosine":
        if not prenormalized:
            db = db / np.maximum(
                np.linalg.norm(db, axis=-1, keepdims=True), np.float32(1e-9)
            )
        q = q / np.maximum(
            np.linalg.norm(q, axis=-1, keepdims=True), np.float32(1e-9)
        )
    return q @ db.T


def _np_decide(specs: Tuple[LevelSpec, ...], K: int, s: np.ndarray,
               thr: np.ndarray):
    """Numpy mirror of ``read_path.make_decide`` (no padding rows here, so
    qmask is implicit all-True)."""
    L = len(specs)
    t_single = np.asarray([sp.t_single for sp in specs], np.float32)
    t_comb = np.asarray(
        [sp.t_combined if sp.generative else np.inf for sp in specs], np.float32
    )
    msl = np.asarray([min(sp.max_sources, sp.k) for sp in specs], np.int32)
    ks = np.asarray([sp.k for sp in specs], np.int32)
    gen_l = np.asarray([sp.generative for sp in specs])
    sec_l = np.asarray([(not sp.generative) or sp.secondary for sp in specs])
    colK = np.arange(K)
    finite = s > np.float32(_NEG_FINITE)
    best = s[:, :, 0]
    sem_direct = sec_l[None, :] & (best > thr)
    in_x = (
        finite
        & (s > t_single[None, :, None])
        & (colK[None, None, :] < msl[None, :, None])
        & gen_l[None, :, None]
    )
    combined = np.sum(np.where(in_x, s, np.float32(0.0)), axis=-1,
                      dtype=np.float32)
    gen_ok = in_x.any(-1) & (combined > t_comb[None, :])
    semantic = sem_direct | (gen_ok & (best > thr))
    hit = semantic | gen_ok
    generative = gen_ok & ~semantic
    winner = np.where(hit.any(1), np.argmax(hit, axis=1), L).astype(np.int32)
    probed = np.arange(L)[None, :] <= winner[:, None]
    tmask = probed[:, :, None] & finite & (colK[None, None, :] < ks[None, :, None])
    return winner, hit, generative, tmask


def host_reference_read(
    srb: ShardedReadBank,
    vecs: np.ndarray,
    thresholds: np.ndarray,
    specs: Sequence[LevelSpec],
    router: Optional[np.ndarray] = None,
    now: Optional[float] = None,
    shard_mask: Optional[np.ndarray] = None,
) -> dict:
    """The host walk, kept as the parity reference: a pure-numpy mirror of
    the sharded fused program over device-fetched state. Computes the FULL
    per-level effective-score matrices (so the pre-top-k lifecycle semantics
    are reproduced exactly), per-level top-K with jax's tie order (stable,
    ascending slot), the router mask, the shared decide/winner walk, and the
    touch mask — without mutating any device state. Returns a dict with
    ``scores``/``idx``/``winner``/``hit``/``generative``/``tmask``."""
    specs = tuple(specs)
    L = len(specs)
    K = max(sp.k for sp in specs)
    q = np.atleast_2d(np.asarray(vecs, np.float32))
    n = q.shape[0]
    lifecycle = srb.lifecycle_active()
    now32 = np.float32(StoreBank.rel_now() if now is None else now)
    level_s: List[np.ndarray] = []
    level_i: List[np.ndarray] = []
    rb = srb.rep_bank
    ri = 0
    for kind, store in srb.members:
        if kind == "rep":
            buf = np.asarray(rb.buf[ri])
            valid = np.asarray(rb.valid[ri]).copy()
            s = _np_scores(buf, q, rb.metrics[ri], rb.prenorm[ri])
            if lifecycle:
                c = np.asarray(rb.d_created[ri])
                e = np.asarray(rb.d_expires[ri])
                w = np.float32(rb.staleness_w[ri])
                valid &= e > now32
                with np.errstate(invalid="ignore"):
                    frac = np.clip(
                        (now32 - c) / np.maximum(e - c, np.float32(1e-6)),
                        np.float32(0.0), np.float32(1.0),
                    )
                s = s - np.where(np.isfinite(e), w * frac, np.float32(0.0))[None, :]
            ri += 1
        else:
            bank = store.bank
            buf = np.asarray(bank.buf).reshape(store.capacity, store.dim)
            valid = np.asarray(bank.valid).reshape(store.capacity).copy()
            if shard_mask is not None:
                # shard sid owns the contiguous global flat slots
                # [sid*cap_shard, (sid+1)*cap_shard) — mirror the program's
                # availability mask by invalidating dead shards' slots
                m = np.asarray(shard_mask, bool).ravel()
                valid &= np.repeat(m, store.capacity // m.size)
            s = _np_scores(buf, q, store.metric, bank.prenormalized)
            if lifecycle:
                c = np.asarray(bank.d_created).reshape(-1)
                e = np.asarray(bank.d_expires).reshape(-1)
                w = np.repeat(
                    bank.staleness_w.astype(np.float32), store.cap_local
                )
                valid &= e > now32
                with np.errstate(invalid="ignore"):
                    frac = np.clip(
                        (now32 - c) / np.maximum(e - c, np.float32(1e-6)),
                        np.float32(0.0), np.float32(1.0),
                    )
                s = s - np.where(np.isfinite(e), w * frac, np.float32(0.0))[None, :]
        s = np.where(valid[None, :], s, -np.inf).astype(np.float32)
        order = np.argsort(-s, axis=-1, kind="stable")[:, : min(K, s.shape[1])]
        ts = np.take_along_axis(s, order, -1)
        ti = order.astype(np.int32)
        if ts.shape[1] < K:
            pad = K - ts.shape[1]
            ts = np.concatenate([ts, np.full((n, pad), -np.inf, np.float32)], 1)
            ti = np.concatenate([ti, np.zeros((n, pad), np.int32)], 1)
        level_s.append(ts)
        level_i.append(ti)
    s_all = np.stack(level_s, 1)
    idx_all = np.stack(level_i, 1)
    if router is not None:
        s_all = np.where(
            np.asarray(router, bool).reshape(n, L)[:, :, None], s_all, -np.inf
        ).astype(np.float32)
    thr = np.asarray(thresholds, np.float32).reshape(n, L)
    winner, hit, generative, tmask = _np_decide(specs, K, s_all, thr)
    return {
        "scores": s_all, "idx": idx_all, "winner": winner, "hit": hit,
        "generative": generative, "tmask": tmask,
    }
