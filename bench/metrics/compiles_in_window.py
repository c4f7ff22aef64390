"""JAX runtime: XLA backend compilations (``backend_compile_duration``
events) from the window's first due arrival to the last answer. Set-up
warms every shape, so this should read 0."""
UNIT = "count"


def read(run):
    return run.compiles_in_window
