"""Median latency of requests the backend answered (a miss of every level,
generated by the engine), from each request's due time to its resolved
future; a novel request that failed counts, later than any answer."""
UNIT = "ms"


def read(run):
    from readings import percentile

    v = percentile(run.latencies(("miss",)), 50)
    return None if v is None else v * 1e3
