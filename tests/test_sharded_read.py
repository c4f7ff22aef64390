"""Sharded zero-host-hop read path (repro.distributed.sharded_read).

Parity: the collective ``shard_map`` program must be BYTE-IDENTICAL to the
pure-numpy ``host_reference_read`` walk — winners, hit/generative classes,
candidate scores/slots, and the LRU/LFU counter deltas. Entries and queries
use dyadic coordinates (0.25/0.5/0.75/1.0) under the dot metric so numpy and
XLA f32 arithmetic cannot diverge by rounding.

Budget: one hierarchy lookup = ONE collective dispatch, ZERO host hops, ZERO
host-side counter scatters — asserted on the dataflow counters.

The in-process tests run on a mesh over however many devices this process
has (tier-1: usually 1 — a shard_map axis of size 1 still runs the
collective program). ``test_eight_device_collective`` re-executes the whole
file in a subprocess with ``--xla_force_host_platform_device_count=8`` so
the same assertions cover a real 8-shard mesh with cross-shard candidate
exchange and ownership-masked counter scatters.
"""
import os
import subprocess
import sys

import numpy as np
import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402

from repro.core import GenerativeCache, HierarchicalCache  # noqa: E402
from repro.core.embeddings import NgramHashEmbedder  # noqa: E402
from repro.core.read_path import LevelSpec  # noqa: E402
from repro.core.store_bank import StoreBank  # noqa: E402
from repro.core.vector_store import InMemoryVectorStore  # noqa: E402
from repro.distributed.sharded_read import (  # noqa: E402
    ShardedReadBank,
    host_reference_read,
)
from repro.distributed.sharded_store import ShardedVectorStore  # noqa: E402
from repro.launch.mesh import make_cache_mesh  # noqa: E402

DIM = 16
INF = float("inf")


def unit(i, scale=1.0):
    v = np.zeros(DIM, np.float32)
    v[i] = np.float32(scale)
    return v


def _mixed_bank(sh_ttl=None, staleness=0.0):
    """Replicated hot L1 (InMemory) + key-sharded L2 over the device mesh,
    adopted into one ShardedReadBank. Dyadic dot-metric fixtures:

        L1:  unit(0), unit(1), unit(2)
        L2:  unit(10), unit(11), unit(12), unit(1)
    """
    mesh = make_cache_mesh()
    rep = InMemoryVectorStore(DIM, 4, "dot", "lru")
    sh = ShardedVectorStore(
        mesh, dim=DIM, capacity=8, k=5, metric="dot",
        default_ttl_s=sh_ttl, staleness_weight=staleness,
    )
    for i in range(3):
        rep.add(unit(i), f"l1-q{i}", f"l1-a{i}")
    for i in (10, 11, 12, 1):
        sh.add(unit(i), f"l2-q{i}", f"l2-a{i}")
    srb = ShardedReadBank(mesh, [("rep", rep), ("sh", sh)])
    return mesh, rep, sh, srb


# L1 semantic (threshold-only), L2 generative (the §3 rule applies)
SPECS = (
    LevelSpec(False, True, 0.0, INF, 0, 4),
    LevelSpec(True, True, 0.3, 1.0, 4, 5),
)


def _queries():
    q = np.stack([
        unit(0),                               # L1 exact hit
        unit(10),                              # L2 exact hit
        unit(11, 0.75) + unit(12, 0.75),       # L2 generative (1.5 > t_comb)
        unit(13),                              # miss everywhere
        unit(0, 0.5),                          # below both thresholds: miss
        unit(1),                               # both levels score 1.0: L1 wins
    ])
    thr = np.full((len(q), 2), 0.9, np.float32)
    return q, thr


def _counters(srb):
    out = []
    for b in srb.banks():
        out.append((
            np.asarray(b.d_last_access).copy(),
            np.asarray(b.d_access_count).copy(),
        ))
    return out


def _expected_count_delta(srb, ref):
    """Counter model from the reference walk: +1 on every (query, level,
    col) cell the touch mask selects, landed at that level's bank slot."""
    deltas = [np.zeros(c.shape, np.int64) for _, c in _counters(srb)]
    bank_of = {}  # level -> (bank index in srb.banks(), lane or None)
    ri = 0
    for li, (kind, store) in enumerate(srb.members):
        if kind == "rep":
            bank_of[li] = (0, ri)
            ri += 1
        else:
            bank_of[li] = (1 + srb.sh_stores.index(store), None)
    tmask, idx = ref["tmask"], ref["idx"]
    for qi in range(tmask.shape[0]):
        for li in range(tmask.shape[1]):
            bi, lane = bank_of[li]
            flat = deltas[bi] if lane is None else None
            for col in range(tmask.shape[2]):
                if not tmask[qi, li, col]:
                    continue
                slot = int(idx[qi, li, col])
                if lane is not None:
                    deltas[bi][lane, slot] += 1
                else:
                    flat.reshape(-1)[slot] += 1
    return deltas


def test_fused_matches_host_reference_bitwise():
    _, rep, sh, srb = _mixed_bank()
    assert sh.n_shards == len(jax.devices())
    q, thr = _queries()
    ref = host_reference_read(srb, q, thr, SPECS)
    before = _counters(srb)
    dec = srb.fused_read(None, [None] * len(q), thr, SPECS, vecs=q)
    after = _counters(srb)

    np.testing.assert_array_equal(dec.winner, ref["winner"])
    np.testing.assert_array_equal(dec.hit, ref["hit"])
    np.testing.assert_array_equal(dec.generative, ref["generative"])
    np.testing.assert_array_equal(dec.scores, ref["scores"])
    np.testing.assert_array_equal(dec.idx, ref["idx"])
    # the walk itself: L1 beats L2 on the tie, generative classed correctly
    np.testing.assert_array_equal(ref["winner"], [0, 1, 1, 2, 2, 0])
    assert bool(dec.generative[2, 1]) and not bool(dec.generative[1, 1])

    # LRU/LFU counter deltas: exactly the reference touch mask, nothing else
    expected = _expected_count_delta(srb, ref)
    for (l0, c0), (l1, c1), exp in zip(before, after, expected):
        np.testing.assert_array_equal(
            c1.astype(np.int64) - c0.astype(np.int64), exp
        )
        touched = exp > 0
        assert (l1[touched] > l0[touched]).all()
        np.testing.assert_array_equal(l1[~touched], l0[~touched])

    # sharded levels report store-global flat slots join_candidates resolves
    win_slot = int(dec.idx[1, 1, 0])
    assert sh.payloads[win_slot] == ("l2-q10", "l2-a10")


def test_touch_false_leaves_counters():
    _, _, _, srb = _mixed_bank()
    q, thr = _queries()
    before = _counters(srb)
    srb.fused_read(None, [None] * len(q), thr, SPECS, vecs=q, touch=False)
    for (l0, c0), (l1, c1) in zip(before, _counters(srb)):
        np.testing.assert_array_equal(c0, c1)
        np.testing.assert_array_equal(l0, l1)


def test_router_masks_lane_visibility():
    _, _, _, srb = _mixed_bank()
    q, thr = _queries()
    router = np.ones((len(q), 2), bool)
    router[1, 1] = False  # hide L2 from the L2-exact-hit query
    router[5, 0] = False  # hide L1 from the tie query -> L2 must win it
    ref = host_reference_read(srb, q, thr, SPECS, router=router)
    dec = srb.fused_read(
        None, [None] * len(q), thr, SPECS, vecs=q, router=router, touch=False
    )
    np.testing.assert_array_equal(dec.winner, ref["winner"])
    np.testing.assert_array_equal(dec.scores, ref["scores"])
    assert int(dec.winner[1]) == 2  # routed-away lane cannot serve the hit
    assert int(dec.winner[5]) == 1  # ...and the walk falls through to L2


def test_lifecycle_pre_topk_parity(monkeypatch):
    _, _, sh, srb = _mixed_bank(sh_ttl=30.0, staleness=0.5)
    sh.add(unit(14), "l2-q14", "l2-a14", ttl_s=5.0)  # dead at now+15
    assert srb.lifecycle_active()
    now = StoreBank.rel_now() + 15.0
    monkeypatch.setattr(StoreBank, "rel_now", staticmethod(lambda: now))
    q, thr = _queries()
    q = np.concatenate([q, unit(14)[None]])
    thr = np.concatenate([thr, np.full((1, 2), 0.9, np.float32)])
    ref = host_reference_read(srb, q, thr, SPECS, now=now)
    dec = srb.fused_read(None, [None] * len(q), thr, SPECS, vecs=q, touch=False)
    np.testing.assert_array_equal(dec.scores, ref["scores"])
    np.testing.assert_array_equal(dec.winner, ref["winner"])
    # staleness penalty applied pre-top-k: ~1.0 - 0.5 * (15/30) = 0.75 < 0.9
    # (a hair more — the entry aged a few ms between insert and now-capture)
    assert abs(float(dec.scores[1, 1, 0]) - 0.75) < 0.01
    assert int(dec.winner[1]) == 2
    # the expired row is invisible, not merely penalized: its ~0.75
    # penalized dot can never surface (the best survivor is a live zero-dot
    # entry minus its staleness penalty)
    assert float(dec.scores[6, 1, 0]) < 0.0
    assert int(dec.winner[6]) == 2


def test_store_fused_matches_host_paths():
    mesh = make_cache_mesh()
    s = ShardedVectorStore(mesh, dim=DIM, capacity=8, k=3, metric="dot")
    for i in range(5):
        s.add(unit(i), f"q{i}", f"a{i}")
    q = np.stack([unit(0), unit(4), unit(2, 0.5), unit(7)])

    fs, fi = s.search(q)
    hs, hi = s.search_host(q)
    np.testing.assert_array_equal(fs, hs)
    np.testing.assert_array_equal(fi, hi)

    fb = s.search_batch(q, k=3, touch=False)
    hb = s.search_batch_host(q, k=3, touch=False)
    assert fb == hb

    fl = s.lookup_batch(q, np.full(len(q), 0.9))
    hl = s.lookup_batch_host(q, np.full(len(q), 0.9))
    assert fl == hl
    assert fl[0] == (1.0, ("q0", "a0")) and fl[3] is None


def test_dispatch_and_host_hop_budget():
    _, _, _, srb = _mixed_bank()
    q, thr = _queries()
    srb.fused_read(None, [None] * len(q), thr, SPECS, vecs=q)  # warm/flush
    banks = srb.banks()
    d0 = [b.dispatches for b in banks]
    h0 = [b.host_hops for b in banks]
    c0 = [b.counter_scatters for b in banks]
    sd0, sh0, sc0 = srb.dispatches, srb.host_hops, srb.counter_scatters
    srb.fused_read(None, [None] * len(q), thr, SPECS, vecs=q)
    assert srb.dispatches - sd0 == 1  # ONE collective dispatch
    assert srb.host_hops - sh0 == 0 and srb.counter_scatters - sc0 == 0
    for b, d, h, c in zip(banks, d0, h0, c0):
        assert b.dispatches == d  # member banks never dispatch on their own
        assert b.host_hops == h  # zero host hops anywhere in the read
        assert b.counter_scatters == c  # touches ride the collective program


def _hier():
    emb = NgramHashEmbedder(dim=DIM)
    mesh = make_cache_mesh()
    l1 = GenerativeCache(emb, threshold=0.6, t_single=0.45, t_combined=1.0,
                         capacity=16)
    l2 = GenerativeCache(
        emb, threshold=0.6, t_single=0.45, t_combined=1.0,
        store=ShardedVectorStore(mesh, dim=emb.dim, capacity=16, k=4),
    )
    return l1, l2, HierarchicalCache(l1, l2)


def test_hierarchy_serves_through_sharded_bank():
    l1, l2, h = _hier()
    srb = h.ensure_sharded_bank()
    assert srb is not None and h.ensure_sharded_bank() is srb  # cached
    l1.insert("what is the capital of france", "Paris")
    l2.insert("how tall is the eiffel tower", "330 m")
    h.lookup_batch(["warm"])  # adoption + compile + pending flush
    d0 = srb.dispatches
    res = h.lookup_batch([
        "what is the capital of france",
        "how tall is the eiffel tower",
        "unrelated quantum chromodynamics question",
    ])
    assert srb.dispatches - d0 == 1
    assert srb.host_hops == 0
    assert [r.hit for r in res] == [True, True, False]
    assert res[0].level.startswith("L1:")
    assert res[1].level.startswith("L2:")
    # the L2 winner was promoted into L1 by the deferred writeback
    d1 = srb.dispatches
    res2 = h.lookup_batch(["how tall is the eiffel tower"])
    assert res2[0].level.startswith("L1:") and srb.dispatches - d1 == 1


def test_hierarchy_router_knob():
    l1, l2, h0 = _hier()
    l2.insert("who wrote les miserables", "Victor Hugo")
    h = HierarchicalCache(
        l1, l2, router=lambda qs, cs: np.array([[True, False]] * len(qs))
    )
    assert h.ensure_sharded_bank() is not None
    res = h.lookup_batch(["who wrote les miserables"])
    assert not res[0].hit  # L2 is routed away for every query
    h_open = HierarchicalCache(l1, l2)
    assert h_open.lookup_batch(["who wrote les miserables"])[0].hit


def test_ineligible_levels_return_none():
    emb = NgramHashEmbedder(dim=DIM)
    l1 = GenerativeCache(emb, capacity=16)
    l2 = GenerativeCache(emb, capacity=16)
    # no sharded level: the single-host bank path owns this hierarchy
    assert HierarchicalCache(l1, l2).ensure_sharded_bank() is None

    mesh = make_cache_mesh()
    l2s = GenerativeCache(
        emb, store=ShardedVectorStore(mesh, dim=emb.dim, capacity=16)
    )
    hc = HierarchicalCache(l1, l2s)
    assert hc.ensure_sharded_bank() is not None

    class CustomStore(InMemoryVectorStore):
        def search_batch(self, q_vecs, k=4, touch=True):
            return super().search_batch(q_vecs, k=k, touch=touch)

    l1c = GenerativeCache(emb, store=CustomStore(emb.dim, 16))
    assert HierarchicalCache(l1c, l2s).ensure_sharded_bank() is None


def test_pinned_staging_cpu_fallback():
    from repro.kernels.backend import pinned_host_supported, stage_pinned

    rows = np.arange(2 * DIM, dtype=np.float32).reshape(2, DIM)
    dest = jnp.zeros((4, DIM), jnp.float32)
    staged = stage_pinned(rows, dest)
    np.testing.assert_array_equal(np.asarray(staged), rows)
    # chosen from the platform: TPU/GPU stage through pinned pages, CPU not
    assert pinned_host_supported("tpu") and pinned_host_supported("gpu")
    assert not pinned_host_supported("cpu")
    assert staged is rows  # CPU: the pageable block passes through


def test_shard_mask_degrades_to_survivors():
    """Resilience leg: a dead shard's candidates score -inf inside the
    collective program and its counters stay untouched, so lookups degrade
    to the surviving shards' winners — verified against the masked host
    reference walk. (On the 1-device mesh the only shard can't be masked;
    the 8-device subprocess rerun covers the real degradation.)"""
    _, _, sh, srb = _mixed_bank()
    q, thr = _queries()
    n_shards = srb.n_shards
    if n_shards == 1:
        with pytest.raises(ValueError):
            srb.fused_read(None, [None] * len(q), thr, SPECS, vecs=q,
                           shard_mask=np.zeros(1, bool))
        ref = host_reference_read(srb, q, thr, SPECS)
        dec = srb.fused_read(None, [None] * len(q), thr, SPECS, vecs=q,
                             touch=False, shard_mask=np.ones(1, bool))
        np.testing.assert_array_equal(dec.winner, ref["winner"])
        np.testing.assert_array_equal(dec.scores, ref["scores"])
        assert not srb.degraded  # an all-alive mask is not a degraded read
        return

    # kill the shard owning unit(10)'s L2 entry (the row-1 exact hit)
    clean = host_reference_read(srb, q, thr, SPECS)
    cap_shard = sh.capacity // n_shards
    dead = int(clean["idx"][1, 1, 0]) // cap_shard
    mask = np.ones(n_shards, bool)
    mask[dead] = False

    ref = host_reference_read(srb, q, thr, SPECS, shard_mask=mask)
    before = _counters(srb)
    dec = srb.fused_read(None, [None] * len(q), thr, SPECS, vecs=q,
                         shard_mask=mask)
    after = _counters(srb)

    assert srb.degraded and srb.degraded_reads == 1
    np.testing.assert_array_equal(dec.winner, ref["winner"])
    np.testing.assert_array_equal(dec.hit, ref["hit"])
    np.testing.assert_array_equal(dec.generative, ref["generative"])
    finite = np.isfinite(ref["scores"])
    np.testing.assert_array_equal(dec.scores[finite], ref["scores"][finite])
    np.testing.assert_array_equal(dec.idx[finite], ref["idx"][finite])
    # row 1 lost its exact L2 hit with the shard; row 0's L1 hit survives
    assert bool(clean["hit"][1, 1]) and not bool(dec.hit[1, 1])
    assert bool(dec.hit[0, 0])
    # counters: exactly the masked reference's touch mask, nothing on the
    # dead shard's slots
    expected = _expected_count_delta(srb, ref)
    for (l0, c0), (l1, c1), exp in zip(before, after, expected):
        np.testing.assert_array_equal(
            c1.astype(np.int64) - c0.astype(np.int64), exp
        )


def test_eight_device_collective():
    """The whole file again on a forced 8-virtual-device mesh: real
    cross-shard candidate exchange, ownership-masked counter scatters."""
    env = dict(os.environ)
    env["XLA_FLAGS"] = (
        env.get("XLA_FLAGS", "") + " --xla_force_host_platform_device_count=8"
    ).strip()
    env["JAX_PLATFORMS"] = "cpu"
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env["PYTHONPATH"] = os.path.join(root, "src")
    r = subprocess.run(
        [sys.executable, "-m", "pytest", "-x", "-q", os.path.abspath(__file__),
         "-k", "not eight_device", "-p", "no:cacheprovider"],
        env=env, cwd=root, capture_output=True, text=True, timeout=900,
    )
    assert r.returncode == 0, r.stdout[-4000:] + r.stderr[-4000:]
