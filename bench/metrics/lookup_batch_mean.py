"""Scheduler: mean requests per lookup batch over the window, from the
lookup coalescer's counters (batched items / batches)."""
UNIT = "req"


def read(run):
    b = run.counters1["lookup_batches"] - run.counters0["lookup_batches"]
    n = run.counters1["lookup_items"] - run.counters0["lookup_items"]
    return n / b if b else None
