"""Fused read program: the FLOPs the traced window's lookups require (the
encoder over each request's real tokens, plus one score per live row),
over the read program's device time there times the chip's bf16 peak. The
whole step's share of peak: it still bounds a gain once a kernel is gone."""
UNIT = "%"
MODULE = r"^jit_program(_lc)?(\(|$)"


def read(run):
    from flops import encoder_flops, search_flops
    from reference import n_tokens
    from xtrace import matching, reads_in_window

    if run.trace is None:
        return None
    evs = matching(run.trace.modules, MODULE, run.trace)
    seqs = set(reads_in_window(run.trace))
    if not evs or not seqs:
        return None
    enc = run.cfg["embedder"]
    fl = [sum(encoder_flops(n_tokens(t), enc) + search_flops(run.live_rows, run.dim)
              for t in c.texts) for c in run.calls if c.seq in seqs]
    # mean FLOPs of a read whose span lies in the window, over the mean device
    # time of an execution there (the two counts differ by the window's edges)
    per_read = sum(fl) / len(fl)
    per_exec = sum(e.dur for e in evs) / len(evs)
    return 100.0 * per_read / (per_exec * run.peak["bf16_flops_per_s"])
