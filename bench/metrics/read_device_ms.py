"""Fused read program: mean device time of one execution of its XLA module
(``jit_program``, or ``jit_program_lc`` when entries carry TTLs), from the
device trace."""
UNIT = "ms"
MODULE = r"^jit_program(_lc)?(\(|$)"


def read(run):
    from xtrace import matching

    if run.trace is None:
        return None
    evs = matching(run.trace.modules, MODULE, run.trace)
    if not evs:
        return None
    return 1e3 * sum(e.dur for e in evs) / len(evs)
