"""The readers of the program's own spans on the lookup thread
(``queue_wait_p50_ms``, ``read_prep_ms``, ``read_post_ms``) on a synthetic
trace, and their silence on a trace whose program opened no spans."""
from pathlib import Path

import pytest

import run as harness
import xtrace
from check import Served
from deploy import Capture

ROOT = Path(__file__).resolve().parents[2]
NEW = ("queue_wait_p50_ms", "read_prep_ms", "read_post_ms")
OFFSET = 1000.0  # trace clock minus the host's perf_counter


def _reader(name):
    return harness.load_reader(ROOT, name)


def _run(served, calls, trace):
    c = {"lookup_batches": 0, "lookup_items": 0}
    return harness.Run({}, {}, {}, 0.0, 10.0, 42.0, served, c, c, 0, 1, 768,
                       list(calls), trace)


def _ev(name, start, dur):
    return xtrace.Ev(name, start, dur)


def _batch(seq, t, prep, fetch, post):
    """One lookup batch at trace time ``t``: handler, read span, and the
    read's steps; ``prep`` ends with the dispatch, ``post`` runs from the end
    of the fetch to the end of the handler."""
    dispatch_end = t + prep
    fetch_end = dispatch_end + fetch
    end = fetch_end + post
    return [
        _ev("sched.lookup.handle", t, end - t),
        _ev("lookup.lock_wait", t + 0.0001, 0.0001),
        _ev("read.thresholds", t + 0.0002, 0.0001),
        _ev(f"{xtrace.READ}{seq}", t + 0.0004, fetch_end - t - 0.0004 + 0.0001),
        _ev("read.tokenize", t + 0.0005, prep - 0.0006),
        _ev("read.dispatch", dispatch_end - 0.0001, 0.0001),
        _ev("read.fetch", dispatch_end, fetch),
        _ev("read.join", fetch_end + 0.0002, 0.0001),
        _ev("lookup.resolve", end - 0.0002, 0.0001),
    ]


def _cell():
    """Three batches in a 1 s window (the third ends past it), two requests
    each, each request submitted ``wait`` before its batch's handler."""
    spans = [_ev(xtrace.WINDOW, 0.0 + OFFSET, 1.0),
             _ev("sched.lookup.empty", OFFSET + 0.05, 0.04),
             _ev("sched.lookup.ride", OFFSET + 0.09, 0.01)]
    served, calls = [], []
    plan = [(0, 0.1, 0.002, 0.008, 0.004, (0.003, 0.005)),
            (1, 0.4, 0.004, 0.008, 0.006, (0.001, 0.009)),
            (2, 0.99, 0.002, 0.008, 0.004, (0.002, 0.002))]
    for seq, t, prep, fetch, post, waits in plan:
        spans += _batch(seq, OFFSET + t, prep, fetch, post)
        texts = [f"p{seq}.{k}" for k in range(len(waits))]
        for text, w in zip(texts, waits):
            s = Served(text, "repeat", 16, t - w)
            s.sent, s.status, s.t_done = True, "hit", t + 0.02
            served.append(s)
        # the harness's host clock at the read: its span start less the offset
        calls.append(Capture(seq, t + 0.0004, t + prep + fetch, texts, None, [],
                             [True] * len(texts), [False] * len(texts)))
    tr = xtrace.Trace([], [], {"python3": spans}, "/device:TPU:0")
    return served, calls, tr


def test_span_readers_on_a_synthetic_trace():
    served, calls, tr = _cell()
    run = _run(served, calls, tr)
    # the batch past the window's end is left out
    assert _reader("read_prep_ms").read(run) == pytest.approx(3.0)
    assert _reader("read_post_ms").read(run) == pytest.approx(5.0)
    # waits 3, 5, 1, 9 ms: nearest-rank median
    assert _reader("queue_wait_p50_ms").read(run) == pytest.approx(3.0)


def test_span_readers_are_silent_without_program_spans():
    served, calls, tr = _cell()
    keep = (xtrace.WINDOW, xtrace.READ)
    bare = xtrace.Trace([], [], {"python3": [e for e in tr.host["python3"]
                                             if e.name.startswith(keep)]}, tr.device)
    for name in NEW:
        assert _reader(name).read(_run(served, calls, bare)) is None
        assert _reader(name).read(_run(served, calls, None)) is None
    recorded = xtrace.Trace.from_json((Path(__file__).parent / "fixtures" /
                                       "trace_v5e_small.json").read_text())
    for name in NEW:
        assert _reader(name).read(_run([], [], recorded)) is None
