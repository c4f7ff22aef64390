"""The program's own profiler spans on the lookup thread, as the span
readers see them: each lookup batch is one ``sched.lookup.handle`` span, and
the steps of the read (``read.*``, ``lookup.*``) nest inside it. A trace
without these spans (a program that opens none) yields no batches."""
from __future__ import annotations

import bisect
from typing import Dict, List, Optional

from xtrace import READ, Ev, Trace

HANDLE = "sched.lookup.handle"


def named(tr: Trace, name: str) -> List[Ev]:
    """Every host span called ``name``, on any thread, by start."""
    return sorted((e for evs in tr.host.values() for e in evs if e.name == name),
                  key=lambda e: e.start)


def handles(tr: Trace) -> List[Ev]:
    """The lookup batches whose handler span lies inside the window."""
    t0, t1 = tr.window()
    return [e for e in named(tr, HANDLE) if e.start >= t0 and e.end <= t1]


def within(outer: Ev, evs: List[Ev]) -> List[Ev]:
    """The spans of ``evs`` (sorted by start) that lie inside ``outer``."""
    lo = bisect.bisect_left([e.start for e in evs], outer.start)
    out = []
    for e in evs[lo:]:
        if e.start > outer.end:
            break
        if e.end <= outer.end:
            out.append(e)
    return out


def enclosing(inner: Ev, outers: List[Ev], starts: List[float]) -> Optional[Ev]:
    """The span of ``outers`` (sorted by start, not overlapping; ``starts``
    their starts) that holds ``inner``, or None."""
    i = bisect.bisect_right(starts, inner.start) - 1
    if i >= 0 and outers[i].end >= inner.end:
        return outers[i]
    return None


def read_spans(tr: Trace) -> Dict[int, Ev]:
    """The harness's read spans inside the window, by sequence number."""
    t0, t1 = tr.window()
    return {int(e.name[len(READ):]): e for evs in tr.host.values() for e in evs
            if e.name.startswith(READ) and e.start >= t0 and e.end <= t1}


def per_batch(tr: Trace, child: str, last: bool) -> List[tuple]:
    """(handle, its first or last ``child`` span) for each window batch that
    has one."""
    kids = named(tr, child)
    out = []
    for h in handles(tr):
        got = within(h, kids)
        if got:
            out.append((h, got[-1] if last else got[0]))
    return out
