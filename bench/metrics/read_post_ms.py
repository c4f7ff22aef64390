"""Lookup host: mean time from the end of a lookup batch's device fetch
(``read.fetch``) to the end of its handler (``sched.lookup.handle``): join,
materialize, tier-1 consult, inserts, responses and resolving the futures,
the host work behind the device (program spans in the profiler trace)."""
UNIT = "ms"


def read(run):
    from spans import per_batch

    if run.trace is None:
        return None
    got = per_batch(run.trace, "read.fetch", last=True)
    if not got:
        return None
    return 1e3 * sum(h.end - f.end for h, f in got) / len(got)
