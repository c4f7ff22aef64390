"""Lookup host: mean time from the start of a lookup batch's handler
(``sched.lookup.handle``) to the end of its read program's enqueue
(``read.dispatch``): lock wait, thresholds, tokenize and dispatch, the host
work in front of the device (program spans in the profiler trace)."""
UNIT = "ms"


def read(run):
    from spans import per_batch

    if run.trace is None:
        return None
    got = per_batch(run.trace, "read.dispatch", last=False)
    if not got:
        return None
    return 1e3 * sum(d.end - h.start for h, d in got) / len(got)
