"""Collective read program (``distributed/sharded_read``, the jitted
``shard_map`` of its ``body``: module ``jit_body``): mean device time of one
execution on each chip, from the device trace; the slowest chip's."""
UNIT = "ms"
MODULE = r"^jit_body(\(|$)"


def read(run):
    from xtrace import executions

    if run.trace is None:
        return None
    per = [sum(e.dur for e in evs) / len(evs) for _, evs in executions(run.trace, MODULE)]
    return 1e3 * max(per) if per else None
