"""Process start to the window's first due arrival: weights, build, fill,
warm-up and, in a run that compiles, compilation."""
UNIT = "s"


def read(run):
    return run.setup_s
