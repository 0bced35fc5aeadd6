"""Engine slot loop: host microseconds inside ``DualCoreEngine.advance()``
over the exec-group dispatches it made in the window (the engine's
``record``).  Serves every metric named ``host_us_per_group.<cells>``."""


def read(run, name):
    """The metric's value in ``run``, or None where it has nothing to read."""
    if not run.dispatches:
        return None
    return 1e6 * run.host_advance_s / run.dispatches
