"""Scheduler: median time from a request's submit to the start of the
handler of the lookup batch that read it (``sched.lookup.handle``), over the
window requests whose read lies in the traced span. The submit time comes
onto the trace's clock through that read's own offset (its span's start
minus the host clock at the read)."""
UNIT = "ms"


def read(run):
    from check import match_reads
    from readings import percentile
    from spans import HANDLE, enclosing, named, read_spans

    if run.trace is None:
        return None
    reads = read_spans(run.trace)
    batches = named(run.trace, HANDLE)
    if not reads or not batches:
        return None
    starts = [h.start for h in batches]
    caps, _ = match_reads(run.served, run.calls)
    waits = []
    for i, (c, *_rest) in caps.items():
        span = reads.get(c.seq)
        h = enclosing(span, batches, starts) if span is not None else None
        if h is None:
            continue
        submit = run.served[i].t_submit + (span.start - c.t0)
        waits.append(h.start - submit)
    v = percentile(waits, 50)
    return None if v is None else v * 1e3
