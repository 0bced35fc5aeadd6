"""Median latency, due time to output materialized, over every request due
in the window (those in flight at the close are drained and count)."""

from chipbench.harness import percentile


def read(run, name):
    """The metric's value in ``run``, or None where it has nothing to read."""
    return percentile(run.latencies_ms(), 50) if run.open_loop else None
