"""Admission queue: p95 of due time to admission into the engine
(``RequestMetrics.started_at``)."""

from chipbench.harness import percentile


def read(run, name):
    """The metric's value in ``run``, or None where it has nothing to read."""
    if not run.open_loop:
        return None
    return percentile([(r.started - r.due) * 1e3 for r in run.records
                       if r.started is not None], 95)
