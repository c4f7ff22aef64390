"""Serving engine: mean device time of one execution of the jitted decode
step's XLA module. The module is found by the harness's decode spans
(``bench_decode#``, one around each call of the engine's decode): near each
span, the longest module execution is taken, and the name most of them
share is the decode step's; every execution of that module inside the
window counts."""
UNIT = "ms"
NEAR_S = (0.002, 0.02)  # looked at: 2 ms before a span's start to 20 ms after its end


def read(run):
    from collections import Counter

    from xtrace import DECODE

    if run.trace is None:
        return None
    tr = run.trace
    w0, w1 = tr.window()
    spans = [e for evs in tr.host.values() for e in evs
             if e.name.startswith(DECODE) and w0 <= e.start <= w1]
    mods = [e for e in tr.modules if w0 <= e.start and e.end <= w1]
    if not spans or not mods:
        return None
    votes = Counter()
    for sp in spans:
        near = [m for m in mods if sp.start - NEAR_S[0] <= m.start <= sp.end + NEAR_S[1]]
        if near:
            votes[max(near, key=lambda m: m.dur).name] += 1
    if not votes:
        return None
    name = votes.most_common(1)[0][0]
    evs = [e for e in mods if e.name == name]
    return 1e3 * sum(e.dur for e in evs) / len(evs)
