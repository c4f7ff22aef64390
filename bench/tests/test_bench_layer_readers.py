"""The readers of the engine and of the collective read program on
synthetic traces: ``decode_step_ms`` (also on the recorded one-chip
trace), ``decode_occupancy``, ``l2_win_share``, and on a four-chip trace
``l2_read_device_ms``, ``allgather_ms``, ``l2_topk_roofline`` (each chip's
own rows over its own time, under 100%) and ``l2_read_mfu``."""
from pathlib import Path

import pytest

import flops
import run as harness
import xtrace
from check import Served
from deploy import Capture

ROOT = Path(__file__).resolve().parents[2]
FIX = Path(__file__).resolve().parent / "fixtures"
PEAK = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
ENC = {"hidden_size": 768, "intermediate_size": 3072, "num_hidden_layers": 12}
CAP, DIM = 1 << 20, 768


def _reader(name):
    return harness.load_reader(ROOT, name)


def _run(trace=None, calls=(), served=(), c0=None, c1=None, shard_rows=(), cfg=None):
    c = {"lookup_batches": 0, "lookup_items": 0}
    return harness.Run(cfg or {"embedder": ENC}, {}, PEAK, 0.0, 10.0, 42.0, list(served),
                       c0 or c, c1 or c, 0, sum(shard_rows) or 1, DIM, list(calls),
                       trace, shard_rows=list(shard_rows))


def _cap(seq, texts, cls, level):
    return Capture(seq, 0.0, 0.0, texts, None, [], cls, level)


def test_decode_step_ms_finds_the_decode_module_by_its_spans():
    tr = xtrace.Trace.from_json((FIX / "trace_v5e_small.json").read_text())
    assert _reader("decode_step_ms").read(_run(tr)) is None  # no decode spans
    w0, w1 = tr.window()
    dec = [e for e in tr.modules if e.name.startswith("jit__lambda")
           and w0 <= e.start and e.end <= w1]
    assert dec
    spans = [xtrace.Ev(f"{xtrace.DECODE}{k}", e.start - 2e-4, 1e-4)
             for k, e in enumerate(dec)]
    tr.host["engine"] = spans
    want = 1e3 * sum(e.dur for e in dec) / len(dec)
    assert _reader("decode_step_ms").read(_run(tr)) == pytest.approx(want)


def test_decode_occupancy_from_the_engine_counters():
    served = []
    for n, status, cost in [(5, "miss", 0.1), (9, "miss", 0.1), (4, "miss", 0.0),
                            (7, "hit", 0.0)]:
        s = Served("p", "novel", n, 0.0)
        s.status, s.cost, s.text = status, cost, " ".join(f"t{i}" for i in range(n))
        served.append(s)
    c0 = {"engine": {"decode_steps": 100}, "engine_slots": 4}
    c1 = {"engine": {"decode_steps": 110}, "engine_slots": 4}
    # (5 - 1) + (9 - 1) decode tokens over 10 steps of 4 slots; a follower
    # (no cost) and a hit generated nothing
    got = _reader("decode_occupancy").read(_run(served=served, c0=c0, c1=c1))
    assert got == pytest.approx(100.0 * 12 / 40)
    assert _reader("decode_occupancy").read(_run(served=served, c0=c0, c1=c0)) is None


def test_gen_tokens_per_s_counts_generations_completed_in_the_window():
    """t0 = 0, a 10 s window: tokens of backend answers done by the close;
    one done after it, a follower (no cost) and a hit count nothing."""
    served = []
    for n, status, cost, done in [(5, "miss", 0.1, 2.0), (9, "miss", 0.1, 10.0),
                                  (30, "miss", 0.1, 10.5), (4, "miss", 0.0, 3.0),
                                  (7, "hit", 0.0, 1.0)]:
        s = Served("p", "novel", n, 0.0)
        s.status, s.cost, s.t_done = status, cost, done
        s.text = " ".join(f"t{i}" for i in range(n))
        served.append(s)
    assert _reader("gen_tokens_per_s").read(_run(served=served)) == pytest.approx(1.4)
    assert _reader("gen_tokens_per_s").read(_run(served=served[3:])) is None


def test_a_split_metric_shares_its_reader():
    """``<reader>.<part>``, the same quantity for cells that report another
    end-to-end metric, is read by ``<reader>.py``."""
    whole, part = _reader("compiles_in_window"), _reader("compiles_in_window.gen")
    assert part.UNIT == whole.UNIT and part.read(_run()) == whole.read(_run())
    with pytest.raises(harness.BenchError):
        _reader("no_such_reader.gen")


def test_l2_win_share_counts_cache_answers_by_level():
    calls = [_cap(0, ["a", "b", "c"], ["hit", "hit", "miss"], [0, 1, 2]),
             _cap(1, ["d", "e"], ["generative", "hit"], [1, 1])]
    assert _reader("l2_win_share").read(_run(calls=calls)) == pytest.approx(75.0)
    assert _reader("l2_win_share").read(
        _run(calls=[_cap(0, ["a"], ["miss"], [2])])) is None


def _four_chips(share, execs=3):
    """A four-chip trace: each chip runs ``jit_body`` ``execs`` times; inside,
    a score op and a top-k op on its shard's [*, CAP] arrays that take
    1/share of the least time its own rows need, an all-gather, and an
    encoder op that the roofline leaves out. Chip d's ops take (1 + d/10)
    longer."""
    rows = CAP
    t_min = flops.roofline_s(flops.topk_flops(8, rows, DIM),
                             flops.topk_bytes(rows, DIM), PEAK)
    host = [xtrace.Ev(xtrace.WINDOW, 0.0, 1.0)]
    devs = []
    for d in range(4):
        slow = 1 + d / 10
        mods, ops = [], []
        for x in range(execs):
            t = 0.1 + 0.25 * x
            score, topk = 0.7 * t_min / share * slow, 0.3 * t_min / share * slow
            enc, gather = 0.002, 0.0004 * slow
            dur = enc + score + topk + gather + 0.0001
            mods.append(xtrace.Ev("jit_body(77)", t, dur))
            ops += [
                xtrace.Ev("%fusion.3 = bf16[8,32,768]{2,1,0} fusion(f32[8,32])", t, enc),
                xtrace.Ev(f"%fusion.9 = f32[8,{CAP}]{{1,0}} fusion(f32[1,{CAP},768])",
                          t + enc, score),
                xtrace.Ev(f"%sort.2 = (f32[8,4]) custom-call(f32[8,{CAP}])",
                          t + enc + score, topk),
                xtrace.Ev("%all-gather.1 = f32[4,8,4]{2,1,0} all-gather(f32[8,4])",
                          t + enc + score + topk, gather),
            ]
            if d == 0:
                host.append(xtrace.Ev(f"{xtrace.READ}{x}", t - 0.001, dur + 0.002))
        devs.append(xtrace.Device(f"/device:TPU:{d}", mods, ops))
    tr = xtrace.Trace(devs[0].modules, devs[0].ops, {"python": host}, devs[0].name,
                      devs[1:])
    calls = [_cap(x, ["a b c", "d e f g h i j"], ["hit", "hit"], [1, 1])
             for x in range(execs)]
    cfg = {"embedder": ENC, "l2_rows": 4 * CAP, "shards": 4}
    return _run(tr, calls, shard_rows=[rows] * 4, cfg=cfg), t_min


def test_collective_readers_on_a_four_chip_trace():
    run, t_min = _four_chips(share=0.8)
    # the slowest chip (1.3 x) sets both times
    slow = 0.002 + t_min / 0.8 * 1.3 + 0.0004 * 1.3 + 0.0001
    assert _reader("l2_read_device_ms").read(run) == pytest.approx(1e3 * slow)
    assert _reader("allgather_ms").read(run) == pytest.approx(0.4 * 1.3)
    # each chip's own rows over its own time: the slowest chip's 80% / 1.3
    share = _reader("l2_topk_roofline").read(run)
    assert share == pytest.approx(80.0 / 1.3) and share < 100.0
    # four chips' rows over one chip's time would read over 100%
    assert 4 * share > 100.0
    fl = (flops.encoder_flops(4, ENC) + flops.encoder_flops(8, ENC)
          + 2 * flops.search_flops(4 * CAP, DIM))
    want = 100.0 * fl / (slow * 4 * PEAK["bf16_flops_per_s"])
    assert _reader("l2_read_mfu").read(run) == pytest.approx(want)
    assert 0 < want < 100


def test_collective_readers_are_silent_without_the_program():
    run, _ = _four_chips(share=0.8)
    for d in run.trace.devices():
        d.modules[:] = []
    run.trace.modules = []
    for name in ("l2_read_device_ms", "allgather_ms", "l2_topk_roofline", "l2_read_mfu"):
        assert _reader(name).read(run) is None
        assert _reader(name).read(_run()) is None


def test_busy_is_averaged_over_the_chips():
    run, _ = _four_chips(share=0.5)
    per = [xtrace._busy(d.ops, 0.0, 1.0) for d in run.trace.devices()]
    assert xtrace.busy(run.trace) == pytest.approx(sum(per) / 4)
    assert len(set(round(p, 9) for p in per)) == 4
    again = xtrace.Trace.from_json(run.trace.to_json())
    assert [d.name for d in again.devices()] == [f"/device:TPU:{d}" for d in range(4)]
    assert xtrace.busy(again) == pytest.approx(xtrace.busy(run.trace))
