"""Median latency of requests answered from the cache (hit, generative,
tier1, stale), from each request's due time to its resolved future."""
UNIT = "ms"
HIT = ("hit", "generative", "tier1", "stale")


def read(run):
    from readings import percentile

    v = percentile(run.latencies(HIT), 50)
    return None if v is None else v * 1e3
