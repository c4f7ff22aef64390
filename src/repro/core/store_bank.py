"""StoreBank: one device-resident [L, cap, D] buffer for many vector stores.

The cache's read path used to issue one ``search_batch`` dispatch per
hierarchy level (and the sharded DB kept a separate flat buffer). The bank
stacks every *lane* — a hierarchy level (private L1 / shared L2 / peers) or
a DB shard — into a single [L, cap, D] embedding tensor with a [L, cap]
validity mask, so a B-query lookup across the whole hierarchy is ONE fused
top-k dispatch:

    [L, cap, D] x [B, D] -> scores [B, L, k], lane-local idx [B, L, k]

``InMemoryVectorStore`` and ``ShardedVectorStore`` are thin lane views over
a bank: each keeps its public add/search/remove API and host-side entry
metadata, while the device tensors, the per-lane recency/frequency counters
(LRU/LFU over any lane, sharded included), and the search dispatch live
here. A standalone store is just a 1-lane bank; ``StoreBank.adopt`` stacks
live stores into a shared bank (repointing each store's lane view) so a
hierarchy's levels become rows of one tensor.

Eviction counters are DEVICE-RESIDENT since the zero-host-hop read path:
``last_access`` (a logical event tick — ordering-equivalent to the old
``time.monotonic()`` stamps, including the tie semantics of one shared
stamp per touch event), ``access_count`` and ``insert_seq`` are [L, cap]
int32 ``jnp`` arrays. Touches are scatter-adds fused into the read dispatch
(or one small scatter for the legacy host-join paths); insert-time counter
resets ride the same donated scatter as the row write. Host code
(``select_victim``, save/load, tests) reads them through a lazily-synced
numpy mirror — the ``last_access``/``access_count``/``insert_seq``
properties — which only pays a device->host copy after a fused read touched
counters on device.

For cosine lanes the bank keeps rows unit-normalized at insert time (dot ==
cosine on unit vectors), so searches skip the per-call [cap, D]
re-normalization entirely. Lanes may carry *mixed metrics* (per-lane metric
tags: cosine/dot/euclidean) — the fused jnp search scores each lane under
its own metric in one program, and the Pallas kernel covers cosine+dot
mixes by scoring raw dots against unit rows and rescaling cosine lanes by
1/|q| (rank-preserving). Search backends: a jitted jnp einsum+top_k path,
or the ``similarity_topk`` Pallas kernel with its batched-lanes grid
(``use_pallas=True``); the kernel backend (interpret vs compiled) is
auto-selected per JAX backend via ``repro.kernels.backend``.
"""
from __future__ import annotations

import functools
import time
from typing import Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.similarity import SCORE_PRECISION

_KERNEL_METRICS = ("cosine", "dot")  # metrics the Pallas kernel path covers
_INT32_MIN = np.iinfo(np.int32).min
# renumber the logical event clock well before int32 saturates (headroom for
# one batch worth of ticks past the check)
_TICK_COMPACT_AT = np.iinfo(np.int32).max - (1 << 20)
# lifecycle epoch: created/expires stamps are float seconds RELATIVE to this
# process-wide origin, so every bank in the process shares one time base
# (adoption copies stamps verbatim) and the device float32 copies keep
# sub-second precision over any realistic process lifetime. Snapshots persist
# absolute times and re-base on load.
_EPOCH = time.time()


def bucket_len(n: int) -> int:
    """THE bucketing policy: the next power-of-two length >= n (>= 1).
    Every padded host->device block (rows, scatter indices, touch lists)
    uses this so jits compile O(log N) variants, not one per size."""
    return 1 << (n - 1).bit_length() if n > 1 else 1


def pad_to_bucket(rows: np.ndarray) -> Tuple[np.ndarray, int]:
    """Zero-pad a [N, D] block to the next power-of-two row bucket.

    Serving drains variable-size micro-batches; an unbucketed jit would
    recompile per distinct N (stalling the lookup scheduler for hundreds of
    ms at each new size). Returns the padded block and the original N so the
    caller can slice the result back down. Shared by the in-memory and
    sharded search paths.
    """
    n = rows.shape[0]
    bucket = bucket_len(n)
    if bucket > n:
        rows = np.concatenate(
            [rows, np.zeros((bucket - n, *rows.shape[1:]), rows.dtype)]
        )
    return rows, n


def prepare_scatter(
    idxs: List[int], rows: np.ndarray, *extras: np.ndarray
) -> Tuple[np.ndarray, ...]:
    """Build the (rows, idxs, *extras) update for a multi-row
    ``buf.at[idxs].set``.

    Deduplicates repeated slots last-write-wins (a batch that wraps capacity
    may pick the same victim twice; XLA scatter order for conflicting updates
    is implementation-defined, the sequential loop's is not) and pads to the
    next power-of-two bucket by repeating the final update (identical
    duplicate writes are order-independent) so the scatter jit compiles per
    bucket, not per batch size. ``extras`` are per-row arrays (insert ticks,
    sequence numbers) deduped and padded in lockstep. Shared by the
    in-memory and sharded stores.
    """
    slot_to_row: Dict[int, int] = {}
    for j, idx in enumerate(idxs):
        slot_to_row[idx] = j
    out_idx = np.fromiter(slot_to_row.keys(), np.int32, len(slot_to_row))
    keep = np.fromiter(slot_to_row.values(), np.int64, len(slot_to_row))
    out_rows = rows[keep]
    out_extras = [np.asarray(e)[keep] for e in extras]
    bucket = bucket_len(len(out_idx))
    if bucket > len(out_idx):
        pad = bucket - len(out_idx)
        out_idx = np.concatenate([out_idx, np.repeat(out_idx[-1:], pad)])
        out_rows = np.concatenate([out_rows, np.repeat(out_rows[-1:], pad, axis=0)])
        out_extras = [
            np.concatenate([e, np.repeat(e[-1:], pad, axis=0)]) for e in out_extras
        ]
    return (out_rows, out_idx, *out_extras)


def select_victim(
    eviction: str,
    last_access: np.ndarray,
    access_count: np.ndarray,
    insert_seq: np.ndarray,
) -> int:
    """Pick the slot an lru/lfu/fifo policy evicts (flat index into the
    given counter views). One victim rule for every lane view — the
    in-memory store and the sharded DB evict identically."""
    if eviction == "fifo":
        return int(np.argmin(insert_seq))
    if eviction == "lfu":
        return int(np.argmin(access_count))
    return int(np.argmin(last_access))


def _normalize_rows(rows: jax.Array) -> jax.Array:
    return rows / jnp.maximum(jnp.linalg.norm(rows, axis=-1, keepdims=True), 1e-9)


# -- module-level jits: compiled once per shape and shared by every bank ------


@functools.partial(jax.jit, donate_argnums=(0, 1, 2, 3, 4, 5, 6),
                   static_argnames=("normalize",))
def _bank_scatter(buf, valid, last, cnt, seq, created, expires, lane, idxs, rows,
                  c_lanes, c_idxs, c_ticks, c_seqs, c_cnts, c_created, c_expires,
                  *, normalize: bool):
    """Row scatter with the insert-time counter AND lifecycle resets fused in:
    one donated device update covers rows, masks,
    last_access/access_count/insert_seq, and created/expires stamps for the
    claimed slots (slots deduped host-side; padding repeats the final update
    with identical values, so conflicting-order scatter is moot).
    ``c_cnts`` is 0 for a fresh insert and the preserved count for a tier-1
    promotion restoring a demoted entry."""
    if normalize:
        rows = _normalize_rows(rows)
    return (
        buf.at[lane, idxs].set(rows),
        valid.at[lane, idxs].set(True),
        last.at[c_lanes, c_idxs].set(c_ticks),
        cnt.at[c_lanes, c_idxs].set(c_cnts),
        seq.at[c_lanes, c_idxs].set(c_seqs),
        created.at[c_lanes, c_idxs].set(c_created),
        expires.at[c_lanes, c_idxs].set(c_expires),
    )


@functools.partial(jax.jit, donate_argnums=(0, 1, 2, 3, 4))
def _bank_counter_set(last, cnt, seq, created, expires,
                      c_lanes, c_idxs, c_ticks, c_seqs, c_cnts, c_created, c_expires):
    return (
        last.at[c_lanes, c_idxs].set(c_ticks),
        cnt.at[c_lanes, c_idxs].set(c_cnts),
        seq.at[c_lanes, c_idxs].set(c_seqs),
        created.at[c_lanes, c_idxs].set(c_created),
        expires.at[c_lanes, c_idxs].set(c_expires),
    )


@functools.partial(jax.jit, donate_argnums=(0, 1, 2, 3, 4, 5))
def _bank_free(valid, last, cnt, seq, created, expires, lanes, idxs):
    """Freed-slot hygiene in ONE donated update: clearing validity alone
    would leave stale recency/frequency/TTL metadata attached to the slot
    (visible to snapshots, counter mirrors, and any future policy that scans
    invalid slots) — a remove resets the slot's whole metadata row."""
    return (
        valid.at[lanes, idxs].set(False),
        last.at[lanes, idxs].set(0),
        cnt.at[lanes, idxs].set(0),
        seq.at[lanes, idxs].set(0),
        created.at[lanes, idxs].set(0.0),
        expires.at[lanes, idxs].set(jnp.inf),
    )


@functools.partial(jax.jit, donate_argnums=(0, 1))
def _bank_touch(last, cnt, lanes, idxs, weights, tick):
    """Batched recency/frequency bump: one scatter for N (lane, idx) touches.
    ``weights`` is 1 per real touch and 0 for bucket padding — duplicate
    (lane, idx) pairs accumulate in ``access_count`` (add commutes) and share
    one tick in ``last_access`` (max of equal values), exactly matching the
    sequential host loop's one-stamp-per-event semantics."""
    stamp = jnp.where(weights > 0, tick, jnp.int32(_INT32_MIN))
    return last.at[lanes, idxs].max(stamp), cnt.at[lanes, idxs].add(weights)


def _lane_scores(db, q, metric: str, prenormalized: bool):
    """db [.., N, D] x q [Q, D] -> scores [.., Q, N] (higher = more similar)."""
    q = q.astype(jnp.float32)
    db = db.astype(jnp.float32)
    dots = functools.partial(
        jnp.einsum, "qd,...nd->...qn", precision=SCORE_PRECISION
    )
    if metric == "cosine":
        if not prenormalized:
            db = _normalize_rows(db)
        q = _normalize_rows(q)
        return dots(q, db)
    if metric == "dot":
        return dots(q, db)
    if metric == "euclidean":
        d2 = (
            jnp.sum(q * q, -1)[:, None]
            - 2 * dots(q, db)
            + jnp.sum(db * db, -1)[..., None, :]
        )
        return -jnp.sqrt(jnp.maximum(d2, 0.0))
    raise ValueError(f"unknown metric {metric!r}")


def fused_search_body(buf, valid, q, k: int, metrics: tuple, prenorm: tuple):
    """Traced body of the fused all-lanes search, shared by the standalone
    jit below and the zero-host-hop read program (repro.core.read_path):
    buf [L, cap, D], valid [L, cap], q [Q, D] -> ([Q, L, k], [Q, L, k]).
    Uniform-metric banks score all lanes in one einsum; mixed-metric banks
    score each lane under its own per-lane metric tag — still one program,
    one dispatch."""
    if len(set(metrics)) == 1:
        s = _lane_scores(buf, q, metrics[0], all(prenorm))  # [L, Q, cap]
    else:
        s = jnp.stack([
            _lane_scores(buf[li], q, metrics[li], prenorm[li])
            for li in range(len(metrics))
        ])
    s = jnp.where(valid[:, None, :], s, -jnp.inf)
    ts, ti = jax.lax.top_k(s, k)  # [L, Q, k]
    return ts.transpose(1, 0, 2), ti.transpose(1, 0, 2)


@functools.lru_cache(maxsize=None)
def _fused_search_jnp(k: int, metrics: tuple, prenorm: tuple):
    return jax.jit(functools.partial(fused_search_body, k=k, metrics=metrics,
                                     prenorm=prenorm))


@functools.lru_cache(maxsize=None)
def _lane_search_jnp(k: int, metric: str, prenormalized: bool):
    def fn(buf, valid, lane, q):  # one lane, sliced inside the jit (no copy hop)
        s = _lane_scores(buf[lane], q, metric, prenormalized)  # [Q, cap]
        s = jnp.where(valid[lane][None, :], s, -jnp.inf)
        return jax.lax.top_k(s, k)

    return jax.jit(fn)


@functools.lru_cache(maxsize=None)
def _lane_search_pallas(k: int, metric: str, interpret: bool, prenormalized: bool):
    from repro.kernels.similarity_topk.ops import _similarity_topk_lanes

    def fn(buf, valid, lane, q):
        s, i = _similarity_topk_lanes(
            buf[lane][None], valid[lane][None], q, k=k, metric=(metric,),
            block_n=None, interpret=interpret, prenormalized=prenormalized,
        )
        return s[:, 0], i[:, 0]

    return jax.jit(fn)


class StoreBank:
    """Device-resident multi-lane store: stacked [L, cap, D] rows + masks +
    per-lane device eviction counters + the fused search dispatch."""

    def __init__(
        self,
        dim: int,
        capacities: Sequence[int],
        *,
        metric="cosine",  # one metric for every lane, or a per-lane sequence
        use_pallas: bool = False,
        interpret: Optional[bool] = None,
        buf: Optional[jax.Array] = None,
        valid: Optional[jax.Array] = None,
    ):
        self.dim = dim
        self.use_pallas = use_pallas
        self.interpret = interpret  # None = auto (repro.kernels.backend)
        self.capacities = list(capacities)
        self.L = len(self.capacities)
        self.cap = max(self.capacities)
        if isinstance(metric, str):
            self.metrics: Tuple[str, ...] = (metric,) * self.L
        else:
            self.metrics = tuple(metric)
            assert len(self.metrics) == self.L
        # cosine lanes hold unit rows: normalize once at insert, never at search
        self.prenorm: Tuple[bool, ...] = tuple(m == "cosine" for m in self.metrics)
        self.buf = (
            buf if buf is not None else jnp.zeros((self.L, self.cap, dim), jnp.float32)
        )
        self.valid = (
            valid if valid is not None else jnp.zeros((self.L, self.cap), bool)
        )
        # per-lane recency/frequency/insertion counters: DEVICE arrays, shared
        # by every lane view's eviction policy (LRU/LFU over sharded lanes
        # too). last_access holds logical event ticks — order-equivalent to
        # wall-clock stamps, and exactly one tick per touch event so argmin
        # tie-breaking matches the old host loop.
        self.d_last_access = jnp.zeros((self.L, self.cap), jnp.int32)
        self.d_access_count = jnp.zeros((self.L, self.cap), jnp.int32)
        self.d_insert_seq = jnp.zeros((self.L, self.cap), jnp.int32)
        self._mirror: Optional[Tuple[np.ndarray, np.ndarray, np.ndarray]] = (
            np.zeros((self.L, self.cap), np.int32),
            np.zeros((self.L, self.cap), np.int32),
            np.zeros((self.L, self.cap), np.int32),
        )
        # entry lifecycle: created/expires stamps (seconds relative to the
        # process _EPOCH). The device float32 copies feed the fused read
        # program's expiry mask + staleness penalty; the float64 host arrays
        # are the source of truth — lifecycle only changes on host-initiated
        # paths (insert/remove/clear), so unlike the eviction counters they
        # never go stale and need no mirror-sync machinery.
        self.d_created = jnp.zeros((self.L, self.cap), jnp.float32)
        self.d_expires = jnp.full((self.L, self.cap), jnp.inf, jnp.float32)
        self.h_created = np.zeros((self.L, self.cap), np.float64)
        self.h_expires = np.full((self.L, self.cap), np.inf, np.float64)
        # per-lane staleness weight: an aging entry's effective score drops by
        # w * age_fraction (0 at insert -> w at expiry), so it must beat a
        # correspondingly higher threshold. 0 = scoring unchanged.
        self.staleness_w = np.zeros(self.L, np.float32)
        self._d_stale: Optional[jax.Array] = None  # device cache of staleness_w
        self._ttl_live = False  # any finite expiry ever installed
        self._tick = 1  # 0 = never touched/inserted
        # insert-time counter updates awaiting the next row scatter (claims
        # run host-side first; the device catches up in the same donated
        # update that writes the rows)
        self._pending: List[Tuple[int, int, int, int, int, float, float]] = []
        self._free_jit = _bank_free  # sharded lane views swap in a sharded jit
        self.dispatches = 0  # fused/device search dispatches issued by this bank
        self.counter_scatters = 0  # standalone counter scatters (non-fused paths)
        self.free_scatters = 0  # slot-free updates (remove/clear; off the read path)
        self.host_hops = 0  # host<->device data hops on the search path

    # -- metric helpers --------------------------------------------------------

    @property
    def metric(self) -> str:
        """Uniform metric name, or "mixed" for per-lane-tagged banks."""
        return self.metrics[0] if len(set(self.metrics)) == 1 else "mixed"

    @property
    def prenormalized(self) -> bool:
        return all(self.prenorm)

    def _kernel_ok(self) -> bool:
        return all(m in _KERNEL_METRICS for m in self.metrics)

    # -- entry lifecycle (TTL/expiry + staleness) ------------------------------

    @staticmethod
    def rel_now() -> float:
        """Current time on the bank's relative clock (seconds since _EPOCH)."""
        return time.time() - _EPOCH

    @staticmethod
    def to_rel(abs_time: float) -> float:
        return abs_time - _EPOCH if np.isfinite(abs_time) else float("inf")

    @staticmethod
    def to_abs(rel_time: float) -> float:
        return rel_time + _EPOCH if np.isfinite(rel_time) else float("inf")

    def lifecycle_active(self) -> bool:
        """True once any entry carries a finite TTL or any lane scores with a
        staleness penalty — the read paths skip all lifecycle math until then,
        so TTL-free deployments pay nothing."""
        return self._ttl_live or bool((self.staleness_w != 0).any())

    def set_staleness(self, lane: int, weight: float) -> None:
        self.staleness_w[lane] = np.float32(weight)
        self._d_stale = None

    def d_staleness(self) -> jax.Array:
        if self._d_stale is None:
            self._d_stale = jnp.asarray(self.staleness_w)
        return self._d_stale

    def set_lifecycle(self, created_rel: np.ndarray, expires_rel: np.ndarray) -> None:
        """Install full lifecycle arrays (adoption / snapshot load), in the
        relative-seconds representation."""
        self.h_created = np.asarray(created_rel, np.float64).copy()
        self.h_expires = np.asarray(expires_rel, np.float64).copy()
        self.d_created = jnp.asarray(self.h_created.astype(np.float32))
        self.d_expires = jnp.asarray(self.h_expires.astype(np.float32))
        if np.isfinite(self.h_expires).any():
            self._ttl_live = True

    def lifecycle_rescore(
        self, scores: np.ndarray, lanes, idx: np.ndarray, now: Optional[float] = None
    ) -> Optional[np.ndarray]:
        """Host-side expiry mask + staleness penalty for the legacy search
        paths (the fused read program applies the same rule in-program):
        expired candidates drop to -inf (never served; ``join_candidates``'
        finite filter discards them), live TTL'd candidates lose
        ``w[lane] * clip(age / ttl, 0, 1)``. Returns the effective scores
        (same shape as ``scores``; the caller re-sorts), or None when no
        lifecycle state is active — pure numpy, zero extra dispatches."""
        if not self.lifecycle_active():
            return None
        now = self.rel_now() if now is None else now
        lanes = np.broadcast_to(np.asarray(lanes, np.int64), idx.shape)
        c = self.h_created[lanes, idx]
        e = self.h_expires[lanes, idx]
        s = np.asarray(scores, np.float32).copy()
        finite = np.isfinite(s)
        expired = finite & (e <= now)
        aging = finite & ~expired & np.isfinite(e)
        if aging.any():
            frac = np.clip(
                (now - c[aging]) / np.maximum(e[aging] - c[aging], 1e-6), 0.0, 1.0
            )
            s[aging] -= (self.staleness_w[lanes[aging]] * frac).astype(np.float32)
        s[expired] = -np.inf
        return s

    @staticmethod
    def resort_desc(s: np.ndarray, idx: np.ndarray) -> Tuple[np.ndarray, np.ndarray]:
        """Re-establish descending score order after lifecycle rescoring
        (decide rules assume candidates arrive best-first); stable, so
        untouched rows keep their original top-k order exactly."""
        order = np.argsort(-s, axis=-1, kind="stable")
        return np.take_along_axis(s, order, -1), np.take_along_axis(idx, order, -1)

    # -- counters: device truth + lazily-synced host mirror --------------------

    def next_tick(self) -> int:
        if self._tick >= _TICK_COMPACT_AT:
            self._compact_ticks()
        t = self._tick
        self._tick += 1
        return t

    def _compact_ticks(self) -> None:
        """Renumber last_access ticks densely (order- and tie-preserving
        rank transform) before the int32 event clock saturates: at most
        L*cap distinct stamps survive, so the clock restarts near zero.
        Runs once every ~2B touch events — one host sync + one upload."""
        self.flush_pending()  # pre-compaction ticks must not resurface later
        last, cnt, seq = self.counters_host()
        ranks = np.unique(last, return_inverse=True)[1]
        self.set_counters(ranks.reshape(last.shape).astype(np.int32), cnt, seq)
        self._tick = int(ranks.max(initial=0)) + 1

    def counters_host(self) -> Tuple[np.ndarray, np.ndarray, np.ndarray]:
        """Host view of the device counters (synced on demand; only a fused
        read invalidates it, so eviction-time syncs cost one copy per dirty
        epoch, not one per insert). A clean mirror already reflects pending
        insert claims (note_insert writes it in place), so no flush happens
        here — victim selection between claims in one add_batch stays free;
        only a dirty mirror forces the pending flush + device copy."""
        if self._mirror is None:
            self.flush_pending()
            # np.array (not asarray): jax arrays view as read-only, and the
            # mirror takes in-place updates from note_insert/touch_slots
            self._mirror = (
                np.array(self.d_last_access),
                np.array(self.d_access_count),
                np.array(self.d_insert_seq),
            )
        return self._mirror

    @property
    def last_access(self) -> np.ndarray:
        return self.counters_host()[0]

    @property
    def access_count(self) -> np.ndarray:
        return self.counters_host()[1]

    @property
    def insert_seq(self) -> np.ndarray:
        return self.counters_host()[2]

    def adopt_fused_counters(self, new_last: jax.Array, new_cnt: jax.Array) -> None:
        """Install counters returned by a fused read program (the donated
        scatter-add already applied on device); the host mirror goes stale."""
        self.d_last_access = new_last
        self.d_access_count = new_cnt
        self._mirror = None

    def set_counters(self, last: np.ndarray, cnt: np.ndarray, seq: np.ndarray) -> None:
        """Install full counter arrays (adoption / snapshot load)."""
        last = np.asarray(last, np.int32)
        cnt = np.asarray(cnt, np.int32)
        seq = np.asarray(seq, np.int32)
        self.d_last_access = jnp.asarray(last)
        self.d_access_count = jnp.asarray(cnt)
        self.d_insert_seq = jnp.asarray(seq)
        self._mirror = (last.copy(), cnt.copy(), seq.copy())
        self._tick = max(self._tick, int(last.max(initial=0)) + 1)

    def note_insert(
        self,
        lane: int,
        idx: int,
        seq: int,
        *,
        created: Optional[float] = None,
        expires: Optional[float] = None,
        count: int = 0,
    ) -> None:
        """Counter + lifecycle bookkeeping for one claimed slot. The device
        update is deferred into the next row scatter; the host mirror (when
        clean) and the host lifecycle arrays are updated immediately so
        victim selection inside the same add_batch sees earlier claims.
        ``created``/``expires`` are relative-clock stamps (defaults: now /
        never); ``count`` restores a promoted entry's access_count."""
        tick = self.next_tick()
        created = self.rel_now() if created is None else float(created)
        expires = float("inf") if expires is None else float(expires)
        if np.isfinite(expires):
            self._ttl_live = True
        if self._mirror is not None:
            ml, mc, ms = self._mirror
            ml[lane, idx] = tick
            mc[lane, idx] = count
            ms[lane, idx] = seq
        self.h_created[lane, idx] = created
        self.h_expires[lane, idx] = expires
        self._pending.append((lane, idx, tick, seq, count, created, expires))

    def _drain_pending(self):
        """Pending insert-counter updates as bucketed scatter arrays
        (last-wins dedupe per slot, padding repeats the final update)."""
        last_wins: Dict[Tuple[int, int], Tuple[int, int, int, float, float]] = {}
        for lane, idx, tick, seq, count, created, expires in self._pending:
            last_wins[(lane, idx)] = (tick, seq, count, created, expires)
        self._pending.clear()
        n = len(last_wins)
        lanes = np.fromiter((k[0] for k in last_wins), np.int32, n)
        idxs = np.fromiter((k[1] for k in last_wins), np.int32, n)
        ticks = np.fromiter((v[0] for v in last_wins.values()), np.int32, n)
        seqs = np.fromiter((v[1] for v in last_wins.values()), np.int32, n)
        cnts = np.fromiter((v[2] for v in last_wins.values()), np.int32, n)
        created = np.fromiter((v[3] for v in last_wins.values()), np.float32, n)
        expires = np.fromiter((v[4] for v in last_wins.values()), np.float32, n)
        cols = [lanes, idxs, ticks, seqs, cnts, created, expires]
        bucket = bucket_len(n)
        if bucket > n:
            pad = bucket - n
            cols = [np.concatenate([c, np.repeat(c[-1:], pad)]) for c in cols]
        return tuple(cols)

    def flush_pending(self) -> None:
        """Push deferred insert-counter updates to device (normally they ride
        the row scatter; this standalone path is a safety net for callers
        that read counters between a claim and its ``set_rows``)."""
        if not self._pending:
            return
        cl, ci, ct, cs, cc, ccr, cex = self._drain_pending()
        self.counter_scatters += 1
        (
            self.d_last_access, self.d_access_count, self.d_insert_seq,
            self.d_created, self.d_expires,
        ) = _bank_counter_set(
            self.d_last_access, self.d_access_count, self.d_insert_seq,
            self.d_created, self.d_expires,
            jnp.asarray(cl), jnp.asarray(ci), jnp.asarray(ct), jnp.asarray(cs),
            jnp.asarray(cc), jnp.asarray(ccr), jnp.asarray(cex),
        )

    def touch_slots(self, lanes, idxs) -> None:
        """Bump recency/frequency for N (lane, idx) pairs in ONE device
        scatter (one shared tick per call — the old one-``now``-per-event
        semantics). Duplicate pairs accumulate one count each. Keeps the
        host mirror in sync when it is clean."""
        lanes = np.asarray(lanes, np.int32).reshape(-1)
        idxs = np.asarray(idxs, np.int32).reshape(-1)
        if lanes.size == 0:
            return
        tick = self.next_tick()
        if self._mirror is not None:
            ml, mc, _ = self._mirror
            ml[lanes, idxs] = tick
            np.add.at(mc, (lanes, idxs), 1)
        n = lanes.size
        bucket = bucket_len(n)
        w = np.ones(n, np.int32)
        if bucket > n:
            pad = bucket - n
            lanes = np.concatenate([lanes, np.repeat(lanes[-1:], pad)])
            idxs = np.concatenate([idxs, np.repeat(idxs[-1:], pad)])
            w = np.concatenate([w, np.zeros(pad, np.int32)])
        self.counter_scatters += 1
        self.d_last_access, self.d_access_count = _bank_touch(
            self.d_last_access, self.d_access_count,
            jnp.asarray(lanes), jnp.asarray(idxs), jnp.asarray(w), np.int32(tick),
        )

    # -- device updates --------------------------------------------------------

    def set_rows(self, lane: int, idxs: List[int], rows: np.ndarray,
                 *, pinned: bool = False) -> None:
        """Scatter N raw rows into one lane (ONE donated device update that
        also applies the pending insert-counter/lifecycle resets; rows are
        unit-normalized in-jit for cosine lanes). ``pinned=True`` stages the
        row block through pinned host memory on the bank's own devices when the
        platform has it (tier-1 promotions overlap their H2D copy with the
        read dispatch they ride alongside); pageable numpy on CPU."""
        sel, scatter_idx = prepare_scatter(idxs, np.asarray(rows, np.float32))
        if pinned:
            from repro.kernels.backend import stage_pinned

            sel = stage_pinned(sel, self.buf)
        cl, ci, ct, cs, cc, ccr, cex = self._drain_pending()
        (
            self.buf, self.valid,
            self.d_last_access, self.d_access_count, self.d_insert_seq,
            self.d_created, self.d_expires,
        ) = _bank_scatter(
            self.buf, self.valid,
            self.d_last_access, self.d_access_count, self.d_insert_seq,
            self.d_created, self.d_expires,
            lane, jnp.asarray(scatter_idx), jnp.asarray(sel),
            jnp.asarray(cl), jnp.asarray(ci), jnp.asarray(ct), jnp.asarray(cs),
            jnp.asarray(cc), jnp.asarray(ccr), jnp.asarray(cex),
            normalize=self.prenorm[lane],
        )

    def invalidate(self, lane: int, idx: int) -> None:
        self.free_slots([lane], [idx])

    def free_slots(self, lanes, idxs) -> None:
        """Free N (lane, idx) slots in ONE donated update, resetting the
        whole metadata row (validity, recency/frequency/insertion counters,
        created/expires) — a recycled slot must be indistinguishable from a
        never-used one. Shared by remove() and clear(older_than) on both
        lane-view stores (the sharded view swaps in a jit with its output
        shardings via ``_free_jit``)."""
        lanes = np.asarray(lanes, np.int32).reshape(-1)
        idxs = np.asarray(idxs, np.int32).reshape(-1)
        if lanes.size == 0:
            return
        # drop any pending insert for a slot freed before its row scatter
        if self._pending:
            freed = set(zip(lanes.tolist(), idxs.tolist()))
            self._pending = [p for p in self._pending if (p[0], p[1]) not in freed]
        if self._mirror is not None:
            ml, mc, ms = self._mirror
            ml[lanes, idxs] = 0
            mc[lanes, idxs] = 0
            ms[lanes, idxs] = 0
        self.h_created[lanes, idxs] = 0.0
        self.h_expires[lanes, idxs] = np.inf
        n = lanes.size
        bucket = bucket_len(n)
        if bucket > n:  # pad repeats the final pair — the free is idempotent
            pad = bucket - n
            lanes = np.concatenate([lanes, np.repeat(lanes[-1:], pad)])
            idxs = np.concatenate([idxs, np.repeat(idxs[-1:], pad)])
        self.free_scatters += 1
        (
            self.valid,
            self.d_last_access, self.d_access_count, self.d_insert_seq,
            self.d_created, self.d_expires,
        ) = self._free_jit(
            self.valid,
            self.d_last_access, self.d_access_count, self.d_insert_seq,
            self.d_created, self.d_expires,
            jnp.asarray(lanes), jnp.asarray(idxs),
        )

    def compact_seqs(self) -> int:
        """Rank-rebase the insert_seq counters before the int32 insertion
        clock saturates — the insert-side twin of ``_compact_ticks`` (same
        order- and tie-preserving rank transform as the legacy-snapshot
        loader). At most L*cap distinct sequence numbers survive, so the
        clock restarts near zero; per-lane fifo victim ordering is unchanged
        (a rank transform is monotone, and it is applied bank-wide so every
        lane view's future inserts stay above every surviving rank). Returns
        the next free sequence number for the calling store."""
        self.flush_pending()
        last, cnt, seq = self.counters_host()
        ranks = np.unique(seq, return_inverse=True)[1].reshape(seq.shape)
        self.set_counters(last, cnt, ranks.astype(np.int32))
        return int(ranks.max(initial=0)) + 1

    # -- search ----------------------------------------------------------------

    def _resolved_interpret(self) -> bool:
        from repro.kernels.backend import resolve_interpret

        return resolve_interpret(self.interpret)

    def search_lane(
        self, lane: int, q_vecs: np.ndarray, k: int
    ) -> Tuple[np.ndarray, np.ndarray]:
        """Top-k of ONE lane for Q queries in one device dispatch ->
        (scores [Q, k], lane-local idx [Q, k])."""
        self.flush_pending()
        q, n_q = pad_to_bucket(np.atleast_2d(np.asarray(q_vecs, np.float32)))
        self.dispatches += 1
        self.host_hops += 2  # query upload + score download around the dispatch
        metric = self.metrics[lane]
        if self.use_pallas and metric in _KERNEL_METRICS:
            from repro.kernels.similarity_topk import ops as st_ops

            st_ops.record_dispatch()
            fn = _lane_search_pallas(
                k, metric, self._resolved_interpret(), self.prenorm[lane]
            )
        else:
            fn = _lane_search_jnp(k, metric, self.prenorm[lane])
        s, i = fn(self.buf, self.valid, lane, jnp.asarray(q))
        s, i = np.asarray(s)[:n_q], np.asarray(i)[:n_q]
        s_eff = self.lifecycle_rescore(s, lane, i)
        if s_eff is not None:
            s, i = self.resort_desc(s_eff, i)
        return s, i

    def search_lanes(
        self, q_vecs: np.ndarray, k: int
    ) -> Tuple[np.ndarray, np.ndarray]:
        """Fused all-lanes top-k for Q queries in ONE device dispatch ->
        (scores [Q, L, k], lane-local idx [Q, L, k]). Candidates are never
        merged across lanes — cross-lane policy (hierarchy walk order,
        shard merge) stays with the caller, host-side, on these scores."""
        self.flush_pending()
        q, n_q = pad_to_bucket(np.atleast_2d(np.asarray(q_vecs, np.float32)))
        self.dispatches += 1
        self.host_hops += 2
        if self.use_pallas and self._kernel_ok():
            from repro.kernels.similarity_topk.ops import similarity_topk_lanes

            # mixed cosine/dot banks satisfy the kernel's unit-cosine-rows
            # requirement by construction (insert normalizes cosine lanes)
            mixed = len(set(self.metrics)) > 1
            s, i = similarity_topk_lanes(
                self.buf, self.valid, jnp.asarray(q), k=k, metric=self.metrics,
                interpret=self.interpret,
                prenormalized=True if mixed else self.prenormalized,
            )
        else:
            fn = _fused_search_jnp(k, self.metrics, self.prenorm)
            s, i = fn(self.buf, self.valid, jnp.asarray(q))
        s, i = np.asarray(s)[:n_q], np.asarray(i)[:n_q]
        s_eff = self.lifecycle_rescore(s, np.arange(self.L)[None, :, None], i)
        if s_eff is not None:
            s, i = self.resort_desc(s_eff, i)
        return s, i

    # -- lane views ------------------------------------------------------------

    def lane_buf(self, lane: int, capacity: Optional[int] = None) -> jax.Array:
        cap = self.capacities[lane] if capacity is None else capacity
        return self.buf[lane, :cap]

    def lane_valid(self, lane: int, capacity: Optional[int] = None) -> jax.Array:
        cap = self.capacities[lane] if capacity is None else capacity
        return self.valid[lane, :cap]

    # -- composition -----------------------------------------------------------

    @classmethod
    def adopt(cls, stores: Sequence) -> "StoreBank":
        """Stack live lane-view stores into ONE shared bank and repoint each
        store at its row. Contents (rows, masks, counters) are copied from
        each store's current bank lane, so adoption is transparent to the
        stores' own add/search/remove paths — they just start resolving
        against the shared tensor. Per-lane metric tags let mixed-metric
        stores share a bank; mixed dims cannot."""
        dims = {s.dim for s in stores}
        if len(dims) != 1:
            raise ValueError(f"cannot stack stores with mixed dim: {dims}")
        for s in stores:
            s._bank.flush_pending()
        interps = {s._bank.interpret for s in stores}
        bank = cls(
            dims.pop(),
            [s.capacity for s in stores],
            metric=[s.metric for s in stores],
            # conservative: the compiled-kernel path only when every lane opted in
            use_pallas=all(getattr(s, "use_pallas", False) for s in stores),
            # an explicit interpret override shared by every source lane
            # survives adoption (like use_pallas); disagreement falls back
            # to auto-selection
            interpret=interps.pop() if len(interps) == 1 else None,
        )
        buf = np.zeros((bank.L, bank.cap, bank.dim), np.float32)
        valid = np.zeros((bank.L, bank.cap), bool)
        last = np.zeros((bank.L, bank.cap), np.int32)
        cnt = np.zeros((bank.L, bank.cap), np.int32)
        seq = np.zeros((bank.L, bank.cap), np.int32)
        created = np.zeros((bank.L, bank.cap), np.float64)
        expires = np.full((bank.L, bank.cap), np.inf, np.float64)
        for li, s in enumerate(stores):
            ob, ol, cap = s._bank, s._lane, s.capacity
            src_last, src_cnt, src_seq = ob.counters_host()
            buf[li, :cap] = np.asarray(ob.buf[ol, :cap])
            valid[li, :cap] = np.asarray(ob.valid[ol, :cap])
            last[li, :cap] = src_last[ol, :cap]
            cnt[li, :cap] = src_cnt[ol, :cap]
            seq[li, :cap] = src_seq[ol, :cap]
            # lifecycle stamps share the process-wide epoch, so they copy
            # verbatim across banks; per-lane staleness follows the store
            created[li, :cap] = ob.h_created[ol, :cap]
            expires[li, :cap] = ob.h_expires[ol, :cap]
            bank.staleness_w[li] = ob.staleness_w[ol]
        bank.buf = jnp.asarray(buf)
        bank.valid = jnp.asarray(valid)
        bank.set_counters(last, cnt, seq)
        bank.set_lifecycle(created, expires)
        bank._d_stale = None
        bank._tick = max(bank._tick, *(s._bank._tick for s in stores))
        for li, s in enumerate(stores):
            s._bank = bank
            s._lane = li
        return bank
