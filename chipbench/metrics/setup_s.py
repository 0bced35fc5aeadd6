"""Process start to the window's opening: loading, weights, compiling or
reading the compile cache, and warm-up."""


def read(run, name):
    """The metric's value in ``run``, or None where it has nothing to read."""
    return run.setup_s
