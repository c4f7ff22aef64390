"""Python runtime: the longest collection of the Python heap in the window
(``gc.callbacks``). A full collection walks every object the process holds,
the store's entries among them, and holds the interpreter lock meanwhile."""
UNIT = "ms"


def read(run):
    if not run.gc_pauses:
        return None
    return 1e3 * max(d for _, d in run.gc_pauses)
