"""The program's profiler spans on the lookup path: ``CacheService`` driven
over the CPU stack under ``jax.profiler``, the trace reduced by the
benchmark's own ``xtrace.load``. The span names asserted here are the ones
the benchmark's readers (``bench/spans.py``, ``bench/metrics``) look up, so a
rename fails this test instead of silencing a metric."""
import glob
import sys
from pathlib import Path

import jax
import pytest

from repro.core import (
    CacheRequest,
    EnhancedClient,
    GenerativeCache,
    MockLLM,
    NgramHashEmbedder,
)
from repro.core.request import GENERATED, HIT
from repro.serving.service import CacheService

# appended, not prepended: the benchmark's module names must not shadow
# anything the other tests import
sys.path.append(str(Path(__file__).resolve().parents[1] / "bench"))
import xtrace  # noqa: E402

HANDLE = "sched.lookup.handle"
EVERY_BATCH = ("lookup.lock_wait", "read.thresholds", "read.tokenize",
               "read.dispatch", "read.fetch", "read.join", "read.materialize",
               "lookup.respond", "lookup.resolve")
CACHED = ["alpha bravo charlie delta echo", "foxtrot golf hotel india juliet"]
# shares each cached prompt's n-grams: two partial matches that sum past
# t_combined, neither past t_s — a generative hit, whose answer is inserted
GENERATIVE = " ".join(CACHED)


def _inside(e, outer):
    return outer.start <= e.start and e.end <= outer.end


@pytest.fixture(scope="module")
def traced(tmp_path_factory):
    cache = GenerativeCache(
        NgramHashEmbedder(), threshold=0.85, t_single=0.45, t_combined=1.0
    )
    client = EnhancedClient(cache=cache)
    client.register_backend(MockLLM("backend"))
    cache.insert_batch(CACHED, ["answer A", "answer B"])
    for b in (1, 2, 4):  # compile every batch bucket outside the trace
        cache.lookup_batch([f"warm-up miss {i}" for i in range(b)])
    logdir = str(tmp_path_factory.mktemp("trace"))
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(logdir, profiler_options=opts)
    try:
        with CacheService(client, max_batch=8, max_wait_ms=200.0) as svc:
            first = [svc.submit(CacheRequest(p))
                     for p in (CACHED[0], GENERATIVE, "zz unrelated quokka words")]
            first = [f.result(timeout=30) for f in first]
            second = [svc.submit(CacheRequest(p)) for p in CACHED]
            second = [f.result(timeout=30) for f in second]
    finally:
        jax.profiler.stop_trace()
    assert [r.status for r in first] == [HIT, HIT, GENERATED]
    assert [r.status for r in second] == [HIT, HIT]
    assert cache.stats.generative_hits == 1
    (path,) = glob.glob(f"{logdir}/**/*.xplane.pb", recursive=True)
    return sorted((e for evs in xtrace.load(path).host.values() for e in evs),
                  key=lambda e: e.start)


def test_every_lookup_batch_carries_every_step(traced):
    handles = [e for e in traced if e.name == HANDLE]
    assert len(handles) == 2  # the three-request batch, then the two hits
    steps = [e for e in traced if e.name.startswith(("read.", "lookup."))]
    for e in steps:  # each step nests in exactly one lookup batch
        assert sum(_inside(e, h) for h in handles) == 1, e
    for h in handles:
        inner = [e for e in steps if _inside(e, h)]
        names = [e.name for e in inner]
        for name in EVERY_BATCH:
            assert names.count(name) == 1, (name, names)
        start = {e.name: e.start for e in inner}
        assert (start["read.tokenize"] < start["read.dispatch"]
                < start["read.fetch"] < start["read.join"])
    first, second = ([e.name for e in steps if _inside(e, h)] for h in handles)
    # the tier-1 consult runs only for a batch with misses, the insert only
    # for one with a generative hit
    assert "read.tier1" in first and "read.insert" in first
    assert "read.tier1" not in second and "read.insert" not in second


def test_collector_threads_are_tiled_by_scheduler_spans(traced):
    names = {e.name for e in traced}
    for sched in ("lookup", "dispatch"):
        for part in ("empty", "ride", "handle"):
            assert f"sched.{sched}.{part}" in names
    lookup = [e for e in traced if e.name.startswith("sched.lookup.")]
    for a, b in zip(lookup, lookup[1:]):  # one thread: they never overlap
        assert a.end <= b.start + 1e-9
