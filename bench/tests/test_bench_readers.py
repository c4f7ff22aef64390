"""Metric readers: latency from due times with failures counted late, the
scheduler counter and the collector's pauses, the FLOP and byte arithmetic of
``read_mfu`` and ``topk_roofline`` from known shapes, and the join of the
window's reads to the cache's entries."""
import json
from pathlib import Path

import pytest

import numpy as np

import flops
import run as harness
import xtrace
from check import Served
from deploy import Capture, Level, ReadRecorder
from readings import percentile

ROOT = Path(__file__).resolve().parents[2]
PEAK = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
HIT = ("hit", "generative", "tier1", "stale")
ENC = {"hidden_size": 768, "intermediate_size": 3072, "num_hidden_layers": 12}


def _reader(name):
    return harness.load_reader(ROOT, name)


def _run(served, trace=None, calls=(), c0=None, c1=None, live=1 << 20, gc_pauses=()):
    c = {"lookup_batches": 0, "lookup_items": 0}
    return harness.Run({"embedder": ENC}, {}, PEAK, 100.0, 10.0, 42.0, served,
                       c0 or c, c1 or c, 0, live, 768, list(calls), trace, giveup=200.0,
                       gc_pauses=list(gc_pauses))


def _served(kind, due, done, status, submit=None):
    s = Served("p", kind, 16, due if submit is None else submit, due)
    if status is not None:
        s.t_done, s.status = done, status
    return s


def test_latency_runs_from_due_and_failures_count_late():
    served = [_served("repeat", 100.0 + i, 100.0 + i + 0.001 * (i + 1), "hit")
              for i in range(19)]
    # submitted late, answered 1 ms after submit: 51 ms after it was due
    served.append(_served("repeat", 130.0, 130.051, "hit", submit=130.050))
    run = _run(served)
    assert _reader("hit_p50_ms").read(run) == pytest.approx(10.0)
    assert 1e3 * percentile(run.latencies(HIT), 95) == pytest.approx(19.0)
    served.append(_served("repeat", 140.0, None, None))  # failed repeat
    run = _run(served)
    assert 1e3 * percentile(run.latencies(HIT), 95) == pytest.approx(51.0)
    assert max(run.latencies(("hit",))) == pytest.approx(60.0)  # giveup - due
    assert run.latencies(("miss",)) == []
    served.append(_served("novel", 150.0, None, None))
    assert _run(served).latencies(("miss",)) == pytest.approx([50.0])
    assert _reader("arrival_lag_p95_ms").read(_run(served)) == pytest.approx(0.0)


def test_counter_and_collector_readers():
    c0 = {"lookup_batches": 10, "lookup_items": 30}
    c1 = {"lookup_batches": 20, "lookup_items": 90}
    run = _run([], c0=c0, c1=c1, gc_pauses=[(0, 0.001), (2, 0.75), (1, 0.002)])
    assert _reader("lookup_batch_mean").read(run) == pytest.approx(6.0)
    assert _reader("gc_pause_max_ms").read(run) == pytest.approx(750.0)
    assert _reader("lookup_batch_mean").read(_run([])) is None
    assert _reader("gc_pause_max_ms").read(_run([])) is None


def _trace(modules, ops, window=(0.0, 1.0), reads=()):
    host = {"python": [xtrace.Ev(xtrace.WINDOW, window[0], window[1] - window[0])]
            + [xtrace.Ev(f"{xtrace.READ}{s}", t, 0.001) for s, t in reads]}
    return xtrace.Trace(modules, ops, host, "/device:TPU:0")


def test_read_mfu_and_topk_roofline_from_shapes():
    rows, dim = 1 << 20, 768
    texts = ["a b c d e f g", "h i j"]  # 8 and 4 tokens with the CLS
    want_flops = (flops.encoder_flops(8, ENC) + flops.encoder_flops(4, ENC)
                  + 2 * 2.0 * rows * dim)
    assert flops.encoder_flops(8, ENC) == 12 * (2 * 8 * (4 * 768**2 + 2 * 768 * 3072)
                                               + 4 * 64 * 768)
    calls = [Capture(0, 0.1, 0.11, texts, None, [], None, None),
             Capture(1, 0.5, 0.51, texts, None, [], None, None)]
    mods = [xtrace.Ev("jit_program(7)", 0.2, 0.004), xtrace.Ev("jit_program(7)", 0.6, 0.006),
            xtrace.Ev("jit__lambda_", 0.7, 0.002)]
    kern = [xtrace.Ev("%similarity_topk_lanes_blocks.1 = (f32[1,2048,8,4]) custom-call()", 0.201, 0.003),
            xtrace.Ev("%similarity_topk_lanes_blocks.1 = (f32[1,2048,8,4]) custom-call()", 0.601, 0.005)]
    tr = _trace(mods, kern + [xtrace.Ev("%reduce.2 = f32[8,4] reduce(f32[1,2048,8,4] %similarity_topk_lanes_blocks.1)", 0.7, 0.002)],
                reads=[(0, 0.1), (1, 0.5)])
    run = _run([], trace=tr, calls=calls, live=rows)
    assert _reader("read_device_ms").read(run) == pytest.approx(5.0)
    assert _reader("read_mfu").read(run) == pytest.approx(
        100 * want_flops / (0.005 * 197e12))
    t_min = rows * (4 * dim + 1) / 819e9  # bytes bind at batch 2
    assert t_min > 2 * 2 * rows * dim / 197e12
    assert _reader("topk_roofline").read(run) == pytest.approx(100 * 2 * t_min / 0.008)
    assert xtrace.busy(tr) == pytest.approx(0.010)
    no_trace = _run([], calls=calls)
    for name in ("read_device_ms", "read_mfu", "topk_roofline"):
        assert _reader(name).read(no_trace) is None


def test_trace_reduction_on_a_recorded_trace():
    """A cut of a trace recorded on one v5e chip (mixed-bursty.64k)."""
    tr = xtrace.Trace.from_json((Path(__file__).parent / "fixtures" /
                                 "trace_v5e_small.json").read_text())
    w0, w1 = tr.window()
    b = xtrace.busy(tr)
    assert 0 < b <= w1 - w0
    ops = xtrace.device_ops(tr)
    assert ops and all(v > 0 for _, v in ops) and len(ops) <= 10
    assert sum(v for _, v in ops) <= b * (1 + 1e-9) + 1e-9 or len(tr.ops) == 0
    gaps = xtrace.idle_gaps(tr)
    assert gaps and len(gaps) <= 10
    assert sum(g for _, g in gaps) <= (w1 - w0) - b + 1e-9
    assert xtrace.matching(tr.modules, r"^jit_program", tr)


class _Entry:
    def __init__(self, query):
        self.query = query


class _Bank:
    def __init__(self, rows):
        self.buf = rows[None]

    def note_insert(self, lane, idx, *a, **k):
        pass

    def free_slots(self, lanes, idxs):
        pass


class _Store:
    def __init__(self, n):
        self.capacity = n
        self._lane = 0
        self._entries = [_Entry(f"q{i}") for i in range(n)]
        self._bank = _Bank(np.arange(n * 2, dtype=np.float32).reshape(n, 2))


class _Decision:
    def __init__(self, scores, idx):
        self.scores = np.asarray(scores, np.float32)[:, None]
        self.idx = np.asarray(idx)[:, None]
        self.vecs = np.zeros((len(scores), 2), np.float32)
        self.hit = self.generative = np.zeros((len(scores), 1), bool)
        self.winner = np.ones((len(scores),), np.int32)


def test_join_leaves_out_slots_written_since_the_read(monkeypatch):
    """A slot written again after the read keeps the entry it held at the
    read, and is left out of the rows read back; a slot that another thread
    wrote just before the read is not known."""
    import threading

    import repro.core.read_path as read_path

    store = _Store(8)
    dec = _Decision([[0.9, 0.8, 0.7, -np.inf]], [[1, 2, 4, 3]])
    monkeypatch.setattr(read_path, "fused_read", lambda *a, **k: dec)
    lv = Level("L1", store, False)
    rec = ReadRecorder([lv]).install()
    try:
        rec.on = True
        other = threading.Thread(target=lambda: (
            store._entries.__setitem__(4, _Entry("racing")),
            store._bank.note_insert(0, 4)))
        other.start()
        other.join(timeout=10)
        assert read_path.fused_read(None, None, ["a"], None, None) is dec
        store._entries[2] = _Entry("later")  # slot 2 rewritten after the read
        store._bank.note_insert(0, 2)
    finally:
        rec.uninstall()
    assert "note_insert" not in vars(store._bank)
    (c,) = rec.join()
    assert c.texts == ["a"] and c.cls == ["miss"] and c.level == [1]
    # slot 2 held q2 at the read, but its row is no longer the one read; the
    # empty candidate (-inf) is left out
    assert c.cands == [[[(pytest.approx(0.9), "q1", 1, True),
                         (pytest.approx(0.8), "q2", 2, False),
                         (pytest.approx(0.7), None, 4, False)]]]
    assert rec.present(0, c.t0, c.t1, c.thread) == (
        [f"q{i}" for i in range(8) if i != 4], False)
    got = lv.rows([2, 1, 2])
    assert sorted(got) == [1, 2] and got[2].tolist() == [4.0, 5.0]
