"""Device: the share of the traced window in which no operation ran on the
chip, the mean over the chips used (each chip's share is logged).  Serves
every metric named ``device_idle.<cells>``."""


def read(run, name):
    """The metric's value in ``run``, or None where it has nothing to read."""
    if run.trace is None:
        return None
    return 100.0 * run.trace.idle_share()
