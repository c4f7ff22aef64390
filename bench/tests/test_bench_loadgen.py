"""The seeded generator: same seed, same bytes; another seed, the same work
in another order."""
import json
from collections import Counter
from pathlib import Path

import loadgen

# bursts and novel prompts: the generator's every branch
MIX = Path(__file__).resolve().parent / "fixtures" / "tiny.mixed.json"


def _mix():
    m = loadgen.load_mix(MIX)
    m["cached_prompts"] = 512
    return m


def _dump(wl):
    return json.dumps([wl.corpus, wl.answers,
                       [vars(r) for r in wl.warmup], [vars(r) for r in wl.window]])


def test_one_seed_gives_identical_bytes():
    a = loadgen.make_workload(_mix(), 2**31 + 12345, 10.0)
    b = loadgen.make_workload(_mix(), 2**31 + 12345, 10.0)
    assert _dump(a) == _dump(b)


def test_seeds_share_sizes_and_arrivals():
    a = loadgen.make_workload(_mix(), 1, 10.0)
    b = loadgen.make_workload(_mix(), 2, 10.0)
    assert _dump(a) != _dump(b)

    def sizes(wl):
        return (Counter(len(r.prompt.split()) for r in wl.window),
                Counter(r.max_tokens for r in wl.window),
                Counter(r.kind for r in wl.window),
                Counter(r.rank for r in wl.window),
                Counter(len(p.split()) for p in wl.corpus))

    assert sizes(a) == sizes(b)
    ga = sorted(round(y.t_due - x.t_due, 9) for x, y in zip(a.window, a.window[1:]))
    gb = sorted(round(y.t_due - x.t_due, 9) for x, y in zip(b.window, b.window[1:]))
    # the same gaps, less the one that the reordering moves to the end
    assert len(set(ga) ^ set(gb)) <= 4


def test_window_is_open_loop_over_the_seconds():
    wl = loadgen.make_workload(_mix(), 3, 20.0)
    t = [r.t_due for r in wl.window]
    assert t == sorted(t) and t[0] == 0.0 and t[-1] < 20.0
    assert len(t) == round(_mix()["rate"] * 20.0)
    novel = [r.prompt for r in wl.window if r.kind == "novel"]
    assert len(set(novel)) == len(novel)
    assert not set(novel) & set(wl.corpus)
    assert all(r.prompt == wl.corpus[r.rank] for r in wl.window if r.kind == "repeat")


def test_fixed_order_queues_the_same_work_for_every_seed():
    """With ``"order": "fixed"`` two seeds send the same kinds, lengths and
    output lengths at the same times; only the words differ."""
    mix = dict(_mix(), order="fixed")
    a = loadgen.make_workload(mix, 2**31 + 1, 10.0)
    b = loadgen.make_workload(mix, 2**31 + 2, 10.0)
    shape = lambda w: [(r.t_due, r.kind, r.max_tokens, r.rank, len(r.prompt.split()))  # noqa: E731
                       for r in w.window]
    assert shape(a) == shape(b)
    assert [r.prompt for r in a.window] != [r.prompt for r in b.window]
    kinds = [r.kind for r in a.window]
    assert kinds != sorted(kinds)  # repeats and novel prompts interleave
    seeded = loadgen.make_workload(dict(mix, order="seeded"), 2**31 + 2, 10.0)
    assert shape(seeded) != shape(b)


def test_a_mix_without_a_rate_needs_one_given():
    """A mix whose knee has not been swept states no rate: it runs only at a
    rate given on the command line (a sweep), never at a guessed one."""
    import pytest

    m = _mix()
    del m["rate"]
    with pytest.raises(ValueError, match="no rate"):
        loadgen.make_workload(m, 3, 5.0)
    assert len(loadgen.make_workload(m, 3, 5.0, rate=4.0).window) == 20
