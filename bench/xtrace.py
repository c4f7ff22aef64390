"""Reduction of a JAX profiler trace to the numbers the metric readers need.

``load`` turns an ``.xplane.pb`` into plain events (seconds on the trace's
own clock): the device's XLA modules and ops, and every host thread's spans.
``Trace`` can be written to and read from JSON, so the reduction is checked
on a small recorded trace in the tests. The traced window is the host span
named ``WINDOW`` that the harness opens after the profiler starts and closes
before it stops.
"""
from __future__ import annotations

import bisect
import json
import re
from dataclasses import asdict, dataclass, field
from typing import Dict, List, Optional, Tuple

WINDOW = "bench_trace_window"
READ = "bench_read#"  # + the read's sequence number; the harness's own span
DECODE = "bench_decode#"  # + the step's number; the harness's span around a decode step


@dataclass
class Ev:
    name: str
    start: float  # seconds
    dur: float

    @property
    def end(self) -> float:
        return self.start + self.dur


@dataclass
class Device:
    name: str
    modules: List[Ev] = field(default_factory=list)  # XLA modules
    ops: List[Ev] = field(default_factory=list)  # XLA ops


@dataclass
class Trace:
    modules: List[Ev] = field(default_factory=list)  # device 0: XLA modules
    ops: List[Ev] = field(default_factory=list)  # device 0: XLA ops
    host: Dict[str, List[Ev]] = field(default_factory=dict)  # thread -> spans
    device: str = ""
    others: List[Device] = field(default_factory=list)  # devices after the first

    def devices(self) -> List[Device]:
        """Every traced device, the first one first."""
        return [Device(self.device, self.modules, self.ops)] + self.others

    def window(self) -> Tuple[float, float]:
        for evs in self.host.values():
            for e in evs:
                if e.name == WINDOW:
                    return e.start, e.end
        raise ValueError("trace has no window span")

    def to_json(self) -> str:
        return json.dumps({
            "device": self.device,
            "modules": [asdict(e) for e in self.modules],
            "ops": [asdict(e) for e in self.ops],
            "host": {k: [asdict(e) for e in v] for k, v in self.host.items()},
            "others": [asdict(d) for d in self.others],
        })

    @staticmethod
    def from_json(s: str) -> "Trace":
        d = json.loads(s)
        evs = lambda xs: [Ev(**e) for e in xs]  # noqa: E731
        return Trace(evs(d["modules"]), evs(d["ops"]),
                     {k: evs(v) for k, v in d["host"].items()}, d["device"],
                     [Device(o["name"], evs(o["modules"]), evs(o["ops"]))
                      for o in d.get("others", [])])


def load(path: str, device_prefix: str = "/device:TPU:") -> Trace:
    """Events of every device plane whose name starts with ``device_prefix``
    (the first in ``modules`` and ``ops``, the others in ``others``), and of
    every host thread."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    tr = Trace()
    dev_planes = sorted((p for p in pd.planes if p.name.startswith(device_prefix)),
                        key=lambda p: int(re.sub(r"\D", "", p.name[len(device_prefix):]) or 0))
    for k, p in enumerate(dev_planes):
        dev = Device(p.name)
        for line in p.lines:
            evs = [Ev(e.name, e.start_ns * 1e-9, e.duration_ns * 1e-9) for e in line.events]
            if line.name == "XLA Modules":
                dev.modules += evs
            elif line.name == "XLA Ops":
                dev.ops += evs
        if k == 0:
            tr.device, tr.modules, tr.ops = dev.name, dev.modules, dev.ops
        else:
            tr.others.append(dev)
    for p in pd.planes:
        if p.name.startswith("/host:CPU"):
            for line in p.lines:
                tr.host.setdefault(line.name, []).extend(
                    Ev(e.name, e.start_ns * 1e-9, e.duration_ns * 1e-9) for e in line.events)
    return tr


def _clip(evs: List[Ev], t0: float, t1: float) -> List[Tuple[float, float]]:
    return sorted((max(e.start, t0), min(e.end, t1)) for e in evs
                  if e.end > t0 and e.start < t1)


def busy(tr: Trace) -> float:
    """Seconds of the window in which some op ran on a device, averaged over
    the traced devices."""
    devs = tr.devices()
    return sum(_busy(d.ops or d.modules, *tr.window()) for d in devs) / len(devs)


def _busy(evs: List[Ev], t0: float, t1: float) -> float:
    total, cur_s, cur_e = 0.0, None, None
    for s, e in _clip(evs, t0, t1):
        if cur_e is None or s > cur_e:
            if cur_e is not None:
                total += cur_e - cur_s
            cur_s, cur_e = s, e
        else:
            cur_e = max(cur_e, e)
    if cur_e is not None:
        total += cur_e - cur_s
    return total


def matching(evs: List[Ev], pattern: str, tr: Trace, ops: bool = False) -> List[Ev]:
    """Events inside the window whose name (``ops``: whose instruction
    name, see ``op_name``) matches ``pattern``."""
    t0, t1 = tr.window()
    rx = re.compile(pattern)
    key = op_name if ops else (lambda n: n)
    return [e for e in evs if rx.search(key(e.name)) and e.start >= t0 and e.end <= t1]


def op_name(name: str) -> str:
    """An XLA op event's instruction name, without its HLO text and its
    instance number: ``%fusion.12 = f32[..] fusion(..)`` -> ``fusion``."""
    head = name.split(" = ", 1)[0].lstrip("%")
    return re.sub(r"\.\d+$", "", head)


def self_times(evs: List[Ev]) -> List[Tuple[Ev, float]]:
    """Each event with its self time: its duration less the events nested in
    it (a ``while`` op holds its body's ops, which the trace lists too)."""
    out: List[List] = []
    stack: List[List] = []
    for e in sorted(evs, key=lambda e: (e.start, -e.dur)):
        while stack and stack[-1][0].end <= e.start:
            stack.pop()
        rec = [e, e.dur]
        if stack and e.end <= stack[-1][0].end + 1e-12:
            stack[-1][1] -= e.dur
        out.append(rec)
        stack.append(rec)
    return [(e, max(t, 0.0)) for e, t in out]


def device_ops(tr: Trace, top: int = 10) -> List[List]:
    """The ops that took most device time in the window, by self time,
    summed by name (numbered instances of one op name together)."""
    t0, t1 = tr.window()
    acc: Dict[str, float] = {}
    for e, t in self_times([e for e in tr.ops if e.start >= t0 and e.end <= t1]):
        k = op_name(e.name)
        acc[k] = acc.get(k, 0.0) + t
    return [[k, v] for k, v in sorted(acc.items(), key=lambda kv: -kv[1])[:top]]


def idle_gaps(tr: Trace, top: int = 10) -> List[List]:
    """The longest device-idle gaps in the window, each named by what the
    host was doing at its middle: the innermost span open on each thread,
    the longest of those."""
    t0, t1 = tr.window()
    iv = _clip(tr.ops or tr.modules, t0, t1)
    gaps, cur = [], t0
    for s, e in iv:
        if s > cur:
            gaps.append((cur, s))
        cur = max(cur, e)
    if t1 > cur:
        gaps.append((cur, t1))
    gaps.sort(key=lambda g: -(g[1] - g[0]))
    out = []
    for g0, g1 in gaps[:top]:
        out.append([host_at(tr, (g0 + g1) / 2), g1 - g0])
    return out


def host_at(tr: Trace, t: float) -> str:
    """The longest of the innermost host spans open at ``t``, one per
    thread; where none is open, the span that ended last before ``t``."""
    best: Optional[Tuple[float, str]] = None
    last: Optional[Tuple[float, str]] = None
    for thread, evs in tr.host.items():
        inner = None
        for e in evs:
            if e.name == WINDOW or e.name.startswith((READ, DECODE)):
                continue
            if e.start <= t <= e.end and (inner is None or e.dur < inner.dur):
                inner = e
            elif e.end < t and (last is None or e.end > last[0]):
                last = (e.end, f"{thread}: {e.name}")
        if inner is not None and (best is None or inner.dur > best[0]):
            best = (inner.dur, f"{thread}: {inner.name}")
    if best:
        return best[1]
    return f"no host span open; last ended: {last[1]}" if last else "no host span"


def reads_in_window(tr: Trace) -> List[int]:
    """Sequence numbers of the harness's read spans inside the window."""
    t0, t1 = tr.window()
    out = []
    for evs in tr.host.values():
        for e in evs:
            if e.name.startswith(READ) and e.start >= t0 and e.end <= t1:
                out.append(int(e.name[len(READ):]))
    return sorted(out)


def executions(tr: Trace, pattern: str) -> List[Tuple[Device, List[Ev]]]:
    """Per traced device, the executions inside the window of the XLA
    modules whose name matches ``pattern``; devices with none are left
    out."""
    out = []
    for d in tr.devices():
        evs = matching(d.modules, pattern, tr)
        if evs:
            out.append((d, evs))
    return out


def ops_within(ops: List[Ev], mods: List[Ev]) -> List[Tuple[Ev, float]]:
    """The ops (with their self times) that ran inside one of ``mods``."""
    mods = sorted(mods, key=lambda e: e.start)
    starts = [m.start for m in mods]
    out = []
    for e, t in self_times(ops):
        i = bisect.bisect_right(starts, e.start) - 1
        if i >= 0 and e.end <= mods[i].end + 1e-9:
            out.append((e, t))
    return out
