"""Load generator: p95 of how late each request was submitted after it was
due.  A starved generator shows here, not as a fast server."""

from chipbench.harness import percentile


def read(run, name):
    """The metric's value in ``run``, or None where it has nothing to read."""
    if not run.open_loop:
        return None
    return percentile([(r.submitted - r.due) * 1e3 for r in run.records
                       if r.submitted is not None], 95)
