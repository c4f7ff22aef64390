"""Load generator: 95th percentile of how late each window request was
submitted after its due time. A starved generator shows here, not as a fast
server."""
UNIT = "ms"


def read(run):
    from readings import percentile

    v = percentile([s.t_submit - s.t_due for s in run.served], 95)
    return None if v is None else v * 1e3
