"""Mesh-sharded vector store: the cache's distributed data path.

Since the StoreBank refactor the DB is a bank of *shard lanes*: one
[n_shards, cap_local, D] tensor whose lane axis is sharded over the mesh
`data` axis (and, multi-pod, over `pod` — each pod's lanes act as its L1,
cross-pod merge is the L2 exchange; DESIGN.md §3). Lookup runs under
shard_map:

    per shard: MXU dot [Q, cap_local] -> local top-k
    all_gather of the tiny [Q, k] candidate sets over (pod, data)
    global top-k merge (still inside the jit)

Only k candidates per shard cross the interconnect — never the [Q, N]
score matrix. This is the step the dry-run lowers on the production mesh
(`cache_lookup` rows in EXPERIMENTS.md §Dry-run).

The bank also holds per-lane recency/frequency counters, so the sharded DB
now has a real eviction *policy*: once every slot is live, adds evict by
lru/lfu/fifo using the same victim rule as ``InMemoryVectorStore``
(``search_batch(touch=...)`` and ``touch_keys`` feed the counters).
"""
from __future__ import annotations

from typing import Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from repro.core.similarity import SCORE_PRECISION
from repro.core.store_bank import (
    _TICK_COMPACT_AT,
    StoreBank,
    _normalize_rows as _norm_rows,
    pad_to_bucket,
    prepare_scatter,
    select_victim,
)
from repro.distributed.sharding import resolve_spec


def _shard_axes(mesh) -> Tuple[str, ...]:
    return tuple(a for a in ("pod", "data") if a in mesh.axis_names)


def shard_id(axes: Tuple[str, ...]):
    """This device's linear shard index over ``axes`` inside a shard_map body
    (row-major over the axis order; matches the lane-axis sharding layout)."""
    sid = jnp.zeros((), jnp.int32)
    mul = 1
    for a in reversed(axes):
        sid = sid + jax.lax.axis_index(a) * mul
        mul = mul * jax.lax.axis_size(a)
    return sid


def all_gather_merge_topk(axes, gs, gi, k: int, *, hierarchical: bool = True):
    """Merge per-shard [Q, k'] candidate (score, idx) sets into the global
    top-k inside a shard_map body — the ONE collective reduction shared by
    the flat lookup, the banked lookup, and the fused sharded read program.

    ``hierarchical=True`` gathers k candidates per shard over the in-pod
    (ICI) axis first, merges back down to k, THEN crosses the pod (DCN) axis
    with only Q*k candidates instead of n_data_shards*Q*k — the paper's L1
    (pod-local) / L2 (cross-pod) hierarchy expressed as a collective
    schedule (§Perf). ``hierarchical=False`` is the flat baseline: gather
    every shard's candidates everywhere, one merge."""
    q_n = gs.shape[0]
    if hierarchical:
        for a in reversed(axes):  # innermost (ICI) first, DCN last
            all_s = jax.lax.all_gather(gs, a, axis=0, tiled=False)
            all_i = jax.lax.all_gather(gi, a, axis=0, tiled=False)
            flat_s = jnp.moveaxis(all_s, 0, 1).reshape(q_n, -1)
            flat_i = jnp.moveaxis(all_i, 0, 1).reshape(q_n, -1)
            k_eff = min(k, flat_s.shape[1])
            gs, pos = jax.lax.top_k(flat_s, k_eff)
            gi = jnp.take_along_axis(flat_i, pos, axis=1)
        return gs, gi
    k_in = gs.shape[-1]
    for a in axes:
        gs = jax.lax.all_gather(gs, a, axis=0, tiled=False)
        gi = jax.lax.all_gather(gi, a, axis=0, tiled=False)
    flat_s = jnp.moveaxis(gs.reshape(-1, q_n, k_in), 0, 1).reshape(q_n, -1)
    flat_i = jnp.moveaxis(gi.reshape(-1, q_n, k_in), 0, 1).reshape(q_n, -1)
    gs, pos = jax.lax.top_k(flat_s, min(k, flat_s.shape[1]))
    gi = jnp.take_along_axis(flat_i, pos, axis=1)
    return gs, gi


def make_sharded_lookup(mesh, *, k: int, metric: str = "cosine", hierarchical: bool = True):
    """Builds the jitted sharded lookup: (db, valid, q) -> (scores, global idx).

    db: [N, D] sharded P(("pod","data"), None); valid: [N] likewise;
    q: [Q, D] replicated. (Flat-buffer variant, kept for the dry-run and the
    perf-iteration studies; the store itself uses ``make_banked_lookup``.)
    """
    axes = _shard_axes(mesh)
    if not axes:
        from repro.core.similarity import top_k_scores

        return jax.jit(lambda db, valid, q: top_k_scores(db, valid, q, k, metric))

    axis_tuple = axes if len(axes) > 1 else axes[0]

    def local_lookup(db_l, valid_l, q):
        # db_l: [cap_local, D] local shard
        cap_local = db_l.shape[0]
        dbn = db_l
        qn = q
        if metric == "cosine":
            dbn = _norm_rows(db_l)
            qn = _norm_rows(q)
        s = jnp.matmul(qn, dbn.T, precision=SCORE_PRECISION)  # [Q, cap_local]
        s = jnp.where(valid_l[None, :], s, -jnp.inf)
        k_eff = min(k, cap_local)
        top_s, top_i = jax.lax.top_k(s, k_eff)  # local indices
        # translate to global ids, then one shared collective merge
        top_i = top_i + shard_id(axes) * cap_local
        return all_gather_merge_topk(axes, top_s, top_i, k,
                                     hierarchical=hierarchical)

    db_spec = P(axis_tuple, None)
    valid_spec = P(axis_tuple)
    fn = jax.shard_map(
        local_lookup,
        mesh=mesh,
        in_specs=(db_spec, valid_spec, P()),
        out_specs=(P(), P()),
        check_vma=False,
    )
    return jax.jit(fn)


def make_banked_lookup(
    mesh, *, k: int, metric: str = "cosine", hierarchical: bool = True,
    prenormalized: bool = False,
):
    """Jitted lookup over a bank of shard lanes:
    (db [L, cap_local, D], valid [L, cap_local], q [Q, D]) ->
    (scores [Q, k], flat global idx [Q, k] where idx = lane*cap_local+within).

    The lane axis is sharded over the mesh, so each device flattens its
    local lanes into one [lanes_loc*cap_local, D] block and the collective
    schedule is identical to the flat-buffer lookup. ``prenormalized`` skips
    the db normalization (the bank keeps unit rows for cosine lanes).
    """
    axes = _shard_axes(mesh)
    if not axes:

        def flat(db, valid, q):
            L, capl, D = db.shape
            db2 = db.reshape(L * capl, D)
            v2 = valid.reshape(L * capl)
            dbn = db2 if (metric != "cosine" or prenormalized) else _norm_rows(db2)
            qn = _norm_rows(q) if metric == "cosine" else q
            s = jnp.where(
                v2[None, :], jnp.matmul(qn, dbn.T, precision=SCORE_PRECISION),
                -jnp.inf,
            )
            return jax.lax.top_k(s, min(k, L * capl))

        return jax.jit(flat)

    axis_tuple = axes if len(axes) > 1 else axes[0]

    def local_lookup(db_l, valid_l, q):
        # db_l: [lanes_loc, cap_local, D] — this device's lanes, flattened so
        # the per-shard math matches the flat-buffer path exactly
        lanes_loc, cap_local, D = db_l.shape
        cap_shard = lanes_loc * cap_local
        db2 = db_l.reshape(cap_shard, D)
        v2 = valid_l.reshape(cap_shard)
        dbn = db2 if (metric != "cosine" or prenormalized) else _norm_rows(db2)
        qn = _norm_rows(q) if metric == "cosine" else q
        s = jnp.where(
            v2[None, :], jnp.matmul(qn, dbn.T, precision=SCORE_PRECISION),
            -jnp.inf,
        )  # [Q, cap_shard]
        k_eff = min(k, cap_shard)
        top_s, top_i = jax.lax.top_k(s, k_eff)  # shard-local flat indices
        # shard-local flat idx -> bank-global flat idx (lane-major layout)
        top_i = top_i + shard_id(axes) * cap_shard
        return all_gather_merge_topk(axes, top_s, top_i, k,
                                     hierarchical=hierarchical)

    fn = jax.shard_map(
        local_lookup,
        mesh=mesh,
        in_specs=(P(axis_tuple, None, None), P(axis_tuple, None), P()),
        out_specs=(P(), P()),
        check_vma=False,
    )
    return jax.jit(fn)


class ShardedVectorStore:
    """Host-facing lane view over a mesh-sharded StoreBank (one lane per
    shard): functional adds, fused sharded lookup, and a real eviction
    policy backed by the bank's per-lane counters."""

    def __init__(
        self, mesh, dim: int, capacity: int, *, k: int = 4, metric: str = "cosine",
        eviction: str = "lru",  # lru | lfu | fifo
        default_ttl_s: Optional[float] = None,
        staleness_weight: float = 0.0,
        tier1=None,  # HostRamTier: eviction victims demote here, keyed by home shard
        fused: bool = True,  # serve reads via the collective fused program
    ):
        assert eviction in ("lru", "lfu", "fifo")
        self.mesh = mesh
        self.dim = dim
        axes = _shard_axes(mesh)
        n_shards = 1
        for a in axes:
            n_shards *= mesh.shape[a]
        self.capacity = capacity - (capacity % max(n_shards, 1)) or n_shards
        self.n_shards = n_shards
        self.cap_local = self.capacity // n_shards
        self.metric = metric
        self.eviction = eviction
        self.k = k
        lane_axes = axes if len(axes) > 1 else (axes[0] if axes else None)
        self._db_sharding = jax.NamedSharding(mesh, P(lane_axes, None, None))
        self._valid_sharding = jax.NamedSharding(mesh, P(lane_axes, None))
        buf = jax.device_put(
            jnp.zeros((n_shards, self.cap_local, dim), jnp.float32), self._db_sharding
        )
        valid = jax.device_put(
            jnp.zeros((n_shards, self.cap_local), bool), self._valid_sharding
        )
        # the bank owns rows/masks/counters; this store is its sharded lane view
        self.bank = StoreBank(dim, [self.cap_local] * n_shards, metric=metric,
                              buf=buf, valid=valid)
        # counters and lifecycle stamps shard with the lanes they describe —
        # the fused read program's touch scatters land on the owning shard's
        # device slice without any cross-device counter traffic
        for name in ("d_last_access", "d_access_count", "d_insert_seq",
                     "d_created", "d_expires"):
            setattr(self.bank, name,
                    jax.device_put(getattr(self.bank, name), self._valid_sharding))
        self._lookup = make_banked_lookup(
            mesh, k=k, metric=metric, prenormalized=self.bank.prenormalized
        )
        self.fused = bool(fused) and bool(axes)
        self._srb = None  # lazy single-member ShardedReadBank (fused reads)
        self.default_ttl_s = default_ttl_s
        self.staleness_weight = float(staleness_weight)
        for lane in range(n_shards):
            self.bank.set_staleness(lane, staleness_weight)
        normalize = self.bank.prenormalized

        def _scatter(buf, valid, last, cnt, seq, created, expires, lanes, withins,
                     rows, c_lanes, c_withins, c_ticks, c_seqs, c_cnts, c_created,
                     c_expires):
            # rows, masks, AND the insert-time counter/lifecycle resets in one
            # donated update — the bank's device counters stay co-located with
            # the sharded lanes' lifecycle (counter placement is left to XLA)
            if normalize:
                rows = _norm_rows(rows)
            return (
                buf.at[lanes, withins].set(rows),
                valid.at[lanes, withins].set(True),
                last.at[c_lanes, c_withins].set(c_ticks),
                cnt.at[c_lanes, c_withins].set(c_cnts),
                seq.at[c_lanes, c_withins].set(c_seqs),
                created.at[c_lanes, c_withins].set(c_created),
                expires.at[c_lanes, c_withins].set(c_expires),
            )

        vsh = self._valid_sharding
        self._add_many = jax.jit(
            _scatter,
            donate_argnums=(0, 1, 2, 3, 4, 5, 6),
            out_shardings=(self._db_sharding, vsh, vsh, vsh, vsh, vsh, vsh),
        )

        def _free(valid, last, cnt, seq, created, expires, lanes, withins):
            # freed-slot hygiene: the full metadata row resets with the mask
            # (same contract as the in-memory lane view's _bank_free)
            return (
                valid.at[lanes, withins].set(False),
                last.at[lanes, withins].set(0),
                cnt.at[lanes, withins].set(0),
                seq.at[lanes, withins].set(0),
                created.at[lanes, withins].set(0.0),
                expires.at[lanes, withins].set(jnp.inf),
            )

        # the bank's free path must re-shard the mask AND counters like ours
        self.bank._free_jit = jax.jit(
            _free,
            donate_argnums=(0, 1, 2, 3, 4, 5),
            out_shardings=(vsh, vsh, vsh, vsh, vsh, vsh),
        )
        self.size = 0
        self.payloads: List[Optional[tuple]] = [None] * self.capacity
        # per-slot meta dicts (hierarchy promotion flags etc.) — payloads stay
        # bare (query, response) tuples for the legacy search_batch contract
        self._metas: List[Optional[dict]] = [None] * self.capacity
        self._rr = 0  # round-robin placement cursor for the first fill
        self._seq = 0  # insertion counter feeding the fifo policy
        # key -> slot map + freed-slot reuse (shared scheme with
        # InMemoryVectorStore) so sharded caches can evict: remove() frees the
        # slot, the next add reclaims it before the round-robin cursor advances
        self._next_key = 0
        self._key_to_slot: Dict[int, int] = {}
        self._slot_key: List[Optional[int]] = [None] * self.capacity
        self._free: List[int] = []
        # tier-1 demotion target + raw-row host mirror (same contract as
        # InMemoryVectorStore: eviction victims demote instead of vanishing;
        # demoted entries remember their home shard lane in TierEntry.meta)
        self.tier1 = None
        self._host_rows: Optional[np.ndarray] = None
        if tier1 is not None:
            self.attach_tier1(tier1)

    # -- tiering -------------------------------------------------------------

    def attach_tier1(self, tier) -> None:
        """Attach a host-RAM demotion tier (``repro.core.tiers.HostRamTier``).
        Eviction victims demote into it instead of vanishing — matching the
        in-memory lane view — with their home shard lane recorded in
        ``TierEntry.meta['home_shard']`` so promotions can land back on the
        shard whose counters/lifecycle they rode. A raw-row host mirror makes
        demotion a numpy copy instead of a device pull on the eviction path."""
        self.tier1 = tier
        self._host_rows = np.array(
            np.asarray(self.bank.buf).reshape(self.capacity, self.dim), np.float32
        )

    def _demote(self, idx: int) -> None:
        """Hand the (still-live) entry in flat slot ``idx`` to tier 1."""
        if self.tier1 is None:
            return
        payload = self.payloads[idx]
        key = self._slot_key[idx]
        if payload is None or key is None:
            return
        lane, within = self._lane_within(idx)
        expires_rel = float(self.bank.h_expires[lane, within])
        if expires_rel <= self.bank.rel_now():
            return  # dead entries are dropped, never demoted
        from repro.core.tiers import TierEntry

        row = (
            self._host_rows[idx]
            if self._host_rows is not None
            else np.asarray(self._db[idx])
        )
        self.tier1.put(
            TierEntry(
                key=key,
                query=payload[0],
                response=payload[1],
                meta={**(self._metas[idx] or {}), "home_shard": lane},
                created_at=self.bank.to_abs(float(self.bank.h_created[lane, within])),
                expires_at=self.bank.to_abs(expires_rel),
                access_count=int(self.bank.access_count[lane, within]),
            ),
            np.array(row, np.float32),
        )

    def _free_slot_in_lane(self, lane) -> Optional[int]:
        """A reusable freed slot on the given lane, if any — the home-shard
        preference promotions use before falling back to global placement."""
        if not isinstance(lane, int) or not 0 <= lane < self.n_shards:
            return None
        lo = lane * self.cap_local
        hi = lo + self.cap_local
        for pos in range(len(self._free) - 1, -1, -1):
            if lo <= self._free[pos] < hi:
                return self._free.pop(pos)
        return None

    def _restore_batch(self, rows: np.ndarray, tier_entries: List) -> None:
        """Promote tier-1 entries back into the sharded bank through the SAME
        donated batched scatter inserts ride. Keys, created/expires stamps,
        and access counts are preserved (a promoted hit is byte-identical to
        its pre-demotion self); each entry prefers a freed slot on its home
        shard lane and falls back to the global cursor/eviction policy."""
        n = len(tier_entries)
        if n == 0:
            return
        rows = np.asarray(rows, np.float32).reshape(n, self.dim)
        idxs: List[int] = []
        for j, te in enumerate(tier_entries):
            if self._seq >= _TICK_COMPACT_AT:
                self._seq = self.bank.compact_seqs()
            home = te.meta.get("home_shard") if isinstance(te.meta, dict) else None
            idx = self._free_slot_in_lane(home)
            if idx is None:
                idx = self._next_index()
            old = self._slot_key[idx]
            if old is not None:  # promotion displaced a live entry: demote it
                self._demote(idx)
                self._key_to_slot.pop(old, None)
            else:
                self.size += 1
            self.payloads[idx] = (te.query, te.response)
            # home_shard is placement routing, not entry state — strip it so a
            # later demotion records the slot's CURRENT lane, not a stale one
            meta = {k: v for k, v in dict(te.meta or {}).items()
                    if k != "home_shard"}
            self._metas[idx] = meta or None
            self._slot_key[idx] = te.key
            self._key_to_slot[te.key] = idx
            self._next_key = max(self._next_key, te.key + 1)
            lane, within = self._lane_within(idx)
            self.bank.note_insert(
                lane, within, self._seq,
                created=self.bank.to_rel(te.created_at),
                expires=(
                    self.bank.to_rel(te.expires_at)
                    if np.isfinite(te.expires_at)
                    else None
                ),
                count=int(te.access_count),
            )
            self._seq += 1
            idxs.append(idx)
            if self._host_rows is not None:
                # mirror immediately (not after the loop): a later placement
                # in this same batch may evict this row and demote its vector
                self._host_rows[idx] = rows[j]
        # tier-1 promotions stage through pinned host memory when the backend
        # supports it: the restore scatter's H2D copy can then overlap the
        # read dispatch it rides alongside (pageable fallback on CPU)
        self._scatter_rows(idxs, rows, pinned=True)

    # flat views of the banked buffers (the pre-bank [N, D] layout; lane-major
    # flattening preserves the old global slot numbering)
    @property
    def _db(self) -> jax.Array:
        return self.bank.buf.reshape(self.capacity, self.dim)

    @property
    def _valid(self) -> jax.Array:
        return self.bank.valid.reshape(self.capacity)

    # flat slot idx <-> (lane, within); flat layout is lane-major, matching
    # the banked lookup's global index translation
    def _lane_within(self, idx: int) -> Tuple[int, int]:
        return idx // self.cap_local, idx % self.cap_local

    def _next_index(self) -> int:
        if self._free:
            return self._free.pop()
        if self._rr < self.capacity:
            # first fill: balanced round-robin placement across shard lanes
            shard = self._rr % self.n_shards
            within = (self._rr // self.n_shards) % self.cap_local
            self._rr += 1
            return shard * self.cap_local + within
        # every slot is live: already-expired entries are free capacity — the
        # most-expired slot goes first, before any live entry is evicted
        if self.bank.lifecycle_active():
            exp = self.bank.h_expires.reshape(-1)
            dead = exp <= self.bank.rel_now()
            if dead.any():
                return int(np.argmin(np.where(dead, exp, np.inf)))
        # evict per policy over the bank's flat counter view (host mirror of
        # the device arrays, synced on demand)
        last, cnt, seq = self.bank.counters_host()
        return select_victim(
            self.eviction, last.reshape(-1), cnt.reshape(-1), seq.reshape(-1)
        )

    def _claim_slot(
        self, idx: int, query: str, response: str,
        meta: Optional[dict] = None, ttl_s: Optional[float] = None,
    ) -> int:
        """Host-side bookkeeping for one placement (shared by add/add_batch)."""
        old = self._slot_key[idx]
        if old is not None:  # policy eviction overwrote a live entry
            self._demote(idx)  # still-live victims move to tier 1, not /dev/null
            self._key_to_slot.pop(old, None)
        else:
            self.size += 1
        key = self._next_key
        self._next_key += 1
        self.payloads[idx] = (query, response)
        self._metas[idx] = dict(meta) if meta else None
        self._slot_key[idx] = key
        self._key_to_slot[key] = idx
        lane, within = self._lane_within(idx)
        if self._seq >= _TICK_COMPACT_AT:  # int32 insertion clock: rank-rebase
            self._seq = self.bank.compact_seqs()
        ttl_s = self.default_ttl_s if ttl_s is None else ttl_s
        created = self.bank.rel_now()
        expires = created + ttl_s if ttl_s is not None else None
        self.bank.note_insert(lane, within, self._seq, created=created,
                              expires=expires)
        self._seq += 1
        return key

    def _scatter_rows(self, idxs: List[int], rows: np.ndarray,
                      pinned: bool = False) -> None:
        sel_rows, sel_idx = prepare_scatter(idxs, rows)
        if pinned:
            from repro.kernels.backend import stage_pinned

            sel_rows = stage_pinned(sel_rows, self.bank.buf)
        lanes = (sel_idx // self.cap_local).astype(np.int32)
        withins = (sel_idx % self.cap_local).astype(np.int32)
        # the claims' counter + lifecycle resets ride the same donated update
        cl, ci, ct, cs, cc, ccr, cex = self.bank._drain_pending()
        bank = self.bank
        (
            bank.buf, bank.valid,
            bank.d_last_access, bank.d_access_count, bank.d_insert_seq,
            bank.d_created, bank.d_expires,
        ) = self._add_many(
            bank.buf, bank.valid,
            bank.d_last_access, bank.d_access_count, bank.d_insert_seq,
            bank.d_created, bank.d_expires,
            jnp.asarray(lanes), jnp.asarray(withins), jnp.asarray(sel_rows),
            jnp.asarray(cl), jnp.asarray(ci), jnp.asarray(ct), jnp.asarray(cs),
            jnp.asarray(cc), jnp.asarray(ccr), jnp.asarray(cex),
        )

    def add(self, vec: np.ndarray, query: str, response: str,
            meta: Optional[dict] = None, ttl_s: Optional[float] = None) -> int:
        idx = self._next_index()
        key = self._claim_slot(idx, query, response, meta, ttl_s)
        row = np.asarray(vec, np.float32).reshape(1, self.dim)
        if self._host_rows is not None:
            self._host_rows[idx] = row[0]
        self._scatter_rows([idx], row)
        return key

    def add_batch(self, vecs: np.ndarray, queries, responses,
                  metas: Optional[List[Optional[dict]]] = None,
                  ttls: Optional[List[Optional[float]]] = None) -> List[int]:
        """N placements in ONE donated scatter into the sharded bank.

        Placement order (and therefore the shard lane each entry lands on)
        matches N sequential ``add`` calls, freed-slot reuse and policy
        eviction included; if the batch overwrites one slot twice, the last
        write wins — exactly what the sequential loop would leave behind.
        ``metas``/``ttls`` carry optional per-entry meta dicts and TTLs
        (None = no meta / default_ttl_s) — the ``InMemoryVectorStore``
        signature, so ``SemanticCache`` levels can sit on a sharded store.
        """
        n = len(queries)
        if n == 0:
            return []
        rows = np.asarray(vecs, np.float32).reshape(n, self.dim)
        metas = list(metas) if metas is not None else [None] * n
        ttls = list(ttls) if ttls is not None else [None] * n
        idxs: List[int] = []
        keys: List[int] = []
        for j in range(n):
            idx = self._next_index()
            keys.append(self._claim_slot(idx, queries[j], responses[j],
                                         metas[j], ttls[j]))
            idxs.append(idx)
            if self._host_rows is not None:
                # mirror immediately (not after the loop): a later claim in
                # this same batch may evict this row and demote its vector
                self._host_rows[idx] = rows[j]
        self._scatter_rows(idxs, rows)
        return keys

    def remove(self, key: int) -> bool:
        """Evict one entry: clears its validity lane AND the slot's
        counter/lifecycle metadata on-device, then frees the slot for reuse
        by the next add (before the cursor advances)."""
        idx = self._key_to_slot.pop(key, None)
        if idx is None:
            return False
        self.payloads[idx] = None
        self._metas[idx] = None
        self._slot_key[idx] = None
        lane, within = self._lane_within(idx)
        self.bank.free_slots([lane], [within])
        self._free.append(idx)
        self.size -= 1
        return True

    def clear(self, older_than: Optional[float] = None) -> int:
        """Drop entries older than ``older_than`` seconds (None = everything);
        already-expired entries always qualify. One batched free update."""
        cutoff = self.bank.rel_now() - (older_than if older_than is not None else 0)
        rel_now = self.bank.rel_now()
        lanes: List[int] = []
        withins: List[int] = []
        for idx, key in enumerate(self._slot_key):
            if key is None:
                continue
            lane, within = self._lane_within(idx)
            created = self.bank.h_created[lane, within]
            expired = self.bank.h_expires[lane, within] <= rel_now
            if older_than is None or created <= cutoff or expired:
                self._key_to_slot.pop(key, None)
                self.payloads[idx] = None
                self._metas[idx] = None
                self._slot_key[idx] = None
                self._free.append(idx)
                self.size -= 1
                lanes.append(lane)
                withins.append(within)
        if lanes:
            self.bank.free_slots(lanes, withins)
        dropped = len(lanes)
        if self.tier1 is not None:  # age-based clears prune the tiers together
            dropped += self.tier1.clear(older_than=older_than)
        return dropped

    def __len__(self) -> int:
        return self.size

    def touch_keys(self, keys) -> None:
        """Deferred recency/frequency bookkeeping (same contract as
        ``InMemoryVectorStore.touch_keys``): one bump per occurrence, one
        device scatter for the whole key list; keys overwritten since the
        search are skipped."""
        pairs = [
            self._lane_within(idx)
            for idx in (self._key_to_slot.get(key) for key in keys)
            if idx is not None
        ]
        if pairs:
            self.bank.touch_slots([p[0] for p in pairs], [p[1] for p in pairs])

    # -- fused collective read path (1 dispatch / 0 host hops) -----------------

    def _fused_decision(self, q: np.ndarray, thr, k_eff: int, touch: bool):
        """One collective fused read over this store's lanes via a
        single-member ``ShardedReadBank``: local top-k, candidate exchange,
        pre-top-k lifecycle, threshold decide, and the in-program counter
        touches — all in ONE dispatch with zero host hops in between."""
        from repro.core.read_path import LevelSpec
        from repro.distributed.sharded_read import ShardedReadBank

        if self._srb is None or not self._srb.intact([self]):
            self._srb = ShardedReadBank(self.mesh, [("sh", self)])
        spec = LevelSpec(False, True, 0.0, float("inf"), 0, int(k_eff))
        n = q.shape[0]
        if thr is None:
            thr_arr = np.full((n, 1), -np.inf, np.float32)
        else:
            thr_arr = np.broadcast_to(
                np.asarray(thr, np.float32), (n,)
            ).reshape(n, 1)
        self.bank.dispatches += 1  # this store's share of the ONE dispatch
        return self._srb.fused_read(None, [None] * n, thr_arr, (spec,),
                                    vecs=q, touch=touch)

    def search(self, q_vecs: np.ndarray) -> Tuple[np.ndarray, np.ndarray]:
        """Top-k over every shard: (scores [Q, k], global flat idx [Q, k]).
        Served by the collective fused program (lifecycle applied pre-top-k,
        on device); ``fused=False`` stores keep the pre-PR host walk."""
        if not self.fused:
            return self.search_host(q_vecs)
        q = np.atleast_2d(np.asarray(q_vecs, np.float32))
        dec = self._fused_decision(q, None, self.k, touch=False)
        return dec.scores[:, 0], dec.idx[:, 0]

    def search_host(self, q_vecs: np.ndarray) -> Tuple[np.ndarray, np.ndarray]:
        """The pre-fused read path — device search, HOST-side lifecycle
        rescore (2 host hops) — kept as the parity-test / benchmark
        reference and the ``fused=False`` escape hatch."""
        # Q padded to a power-of-two bucket so variable serving batch sizes
        # reuse O(log Q) compiled variants instead of retracing per size
        self.bank.flush_pending()
        q, n_q = pad_to_bucket(np.atleast_2d(np.asarray(q_vecs, np.float32)))
        self.bank.dispatches += 1
        self.bank.host_hops += 2
        s, i = self._lookup(self.bank.buf, self.bank.valid, jnp.asarray(q))
        s, i = np.asarray(s)[:n_q], np.asarray(i)[:n_q]
        # entry lifecycle: expired candidates drop out, TTL'd ones pay the
        # staleness penalty (host-side on the tiny [Q, k] candidate sets —
        # the global flat idx decomposes into the bank's (lane, within))
        s_eff = self.bank.lifecycle_rescore(
            s, np.asarray(i) // self.cap_local, np.asarray(i) % self.cap_local
        )
        if s_eff is not None:
            s, i = self.bank.resort_desc(s_eff, i)
        return s, i

    def _join_payloads(
        self, scores: np.ndarray, idx: np.ndarray, k_eff: int,
    ) -> List[List[Tuple[float, tuple]]]:
        out: List[List[Tuple[float, tuple]]] = []
        for srow, irow in zip(scores, idx):
            row = []
            for sc, i in zip(srow, irow):
                payload = (
                    self.payloads[int(i)] if 0 <= int(i) < self.capacity else None
                )
                if np.isfinite(sc) and payload is not None:
                    row.append((float(sc), payload))
            out.append(row[:k_eff])
        return out

    def search_batch(
        self, q_vecs: np.ndarray, k: Optional[int] = None, touch: bool = True
    ) -> List[List[Tuple[float, tuple]]]:
        """Batched payload-joined lookup for Q queries in ONE shard_map
        program — including, on the fused path, the LRU/LFU touch scatters
        (each shard bumps the counters of the slots it owns, inside the same
        dispatch). Returns, per query, the finite (score, (query, response))
        candidates in score order — the same join
        ``InMemoryVectorStore.search_batch`` performs. ``k`` caps the
        candidates per query (at most the configured search k);
        ``touch=False`` defers the counter bumps to ``touch_keys``."""
        q = np.atleast_2d(np.asarray(q_vecs, np.float32))
        k_eff = self.k if k is None else min(k, self.k)
        if not self.fused:
            return self.search_batch_host(q, k=k_eff, touch=touch)
        dec = self._fused_decision(q, None, k_eff, touch=touch)
        return self._join_payloads(dec.scores[:, 0], dec.idx[:, 0], k_eff)

    def search_batch_host(
        self, q_vecs: np.ndarray, k: Optional[int] = None, touch: bool = True
    ) -> List[List[Tuple[float, tuple]]]:
        """Host-walk reference twin of ``search_batch``: device search, then
        join + touch decided in host Python (one extra counter scatter)."""
        q = np.atleast_2d(np.asarray(q_vecs, np.float32))
        s, idx = self.search_host(q)
        k_eff = self.k if k is None else min(k, self.k)
        out: List[List[Tuple[float, tuple]]] = []
        touched: List[Tuple[int, int]] = []
        for srow, irow in zip(s, idx):
            row = []
            for sc, i in zip(srow, irow):
                payload = self.payloads[int(i)] if 0 <= int(i) < self.capacity else None
                if np.isfinite(sc) and payload is not None:
                    if len(row) < k_eff and touch:
                        touched.append(self._lane_within(int(i)))
                    row.append((float(sc), payload))
            out.append(row[:k_eff])
        if touched:
            # one scatter (one shared tick) for the whole batch's bumps
            self.bank.touch_slots([p[0] for p in touched], [p[1] for p in touched])
        return out

    def lookup_batch(
        self, q_vecs: np.ndarray, thresholds
    ) -> List[Optional[Tuple[float, tuple]]]:
        """Apply per-query thresholds vectorized over the batched search:
        returns the best (score, payload) when score > threshold, else None.
        On the fused path the threshold compare happens IN the device
        program (the decide stage's hit mask) — the host only joins
        payloads for the winning rows."""
        q = np.atleast_2d(np.asarray(q_vecs, np.float32))
        thr = np.broadcast_to(np.asarray(thresholds, np.float32), (q.shape[0],))
        if not self.fused:
            return self.lookup_batch_host(q, thr)
        dec = self._fused_decision(q, thr, self.k, touch=True)
        out: List[Optional[Tuple[float, tuple]]] = []
        for qi in range(q.shape[0]):
            if not dec.hit[qi, 0]:
                out.append(None)
                continue
            i = int(dec.idx[qi, 0, 0])
            payload = self.payloads[i] if 0 <= i < self.capacity else None
            out.append(
                (float(dec.scores[qi, 0, 0]), payload)
                if payload is not None else None
            )
        return out

    def lookup_batch_host(
        self, q_vecs: np.ndarray, thresholds
    ) -> List[Optional[Tuple[float, tuple]]]:
        """Host-walk reference twin of ``lookup_batch`` (threshold compare
        in host numpy over the host-joined candidate rows)."""
        q = np.atleast_2d(np.asarray(q_vecs, np.float32))
        thr = np.broadcast_to(np.asarray(thresholds, np.float32), (q.shape[0],))
        rows = self.search_batch_host(q)
        best = np.asarray([r[0][0] if r else -np.inf for r in rows])
        hit = best > thr
        return [rows[i][0] if hit[i] else None for i in range(q.shape[0])]

    def join_candidates(
        self, scores: np.ndarray, idx: np.ndarray, touch: bool = True
    ) -> List[List[Tuple[float, "object"]]]:
        """Join raw (scores [Q, k], GLOBAL flat idx [Q, k]) search output
        into (score, ``Entry``) rows — the hierarchy-facing twin of
        ``InMemoryVectorStore.join_candidates``, reconstructing Entries from
        the host payload/meta/lifecycle state the sharded store keeps.
        ``touch=True`` bumps the joined slots' counters in one scatter (the
        fused read path passes ``touch=False`` — its bumps already happened
        inside the read program)."""
        from repro.core.vector_store import Entry

        out: List[List[Tuple[float, Entry]]] = []
        touched: List[Tuple[int, int]] = []
        for srow, irow in zip(scores, idx):
            row = []
            for sc, i in zip(srow, irow):
                i = int(i)
                if not 0 <= i < self.capacity:
                    continue
                payload = self.payloads[i]
                key = self._slot_key[i]
                if not np.isfinite(sc) or payload is None or key is None:
                    continue
                lane, within = self._lane_within(i)
                if touch:
                    touched.append((lane, within))
                row.append((
                    float(sc),
                    Entry(
                        key, payload[0], payload[1],
                        dict(self._metas[i] or {}),
                        self.bank.to_abs(float(self.bank.h_created[lane, within])),
                        self.bank.to_abs(float(self.bank.h_expires[lane, within])),
                    ),
                ))
            out.append(row)
        if touched:
            self.bank.touch_slots([p[0] for p in touched], [p[1] for p in touched])
        return out
