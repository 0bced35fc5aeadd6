"""Unified telemetry: replay-deterministic metrics + exposition.

``repro.obs`` is dependency-free (stdlib only) and safe to import from
every layer — the executor, router, control loop, serving API, and the
§14 wire stack all instrument through one :class:`Registry` per
top-level engine.  See ``docs/observability.md`` for the metric table
and the slot/wall domain contract.
"""
from repro.obs.export import to_json, to_prometheus, write_metrics
from repro.obs.registry import (DEFAULT_COUNT_BOUNDS,
                                DEFAULT_SECONDS_BOUNDS, Counter, Gauge,
                                Histogram, Registry, parse_label_key,
                                track_gc)

__all__ = ["Counter", "Gauge", "Histogram", "Registry",
           "DEFAULT_COUNT_BOUNDS", "DEFAULT_SECONDS_BOUNDS",
           "parse_label_key", "to_json", "to_prometheus", "track_gc",
           "write_metrics"]
