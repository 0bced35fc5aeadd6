"""Images whose request completed ok inside the window, over the
window's seconds: all the work over all the time."""


def read(run, name):
    """The metric's value in ``run``, or None where it has nothing to read."""
    return sum(r.images for r in run.ok_in_window()) / run.window_s
