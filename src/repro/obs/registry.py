"""Zero-dependency metrics registry: counters, gauges, histograms.

One :class:`Registry` instance is owned per top-level engine — the
``PoolExecutor`` creates its own and a ``MultiPoolRouter`` re-homes every
pool executor onto one shared registry, the same move it makes with the
seq counter — so two runs in one process (a live run and its replay)
never bleed into each other.

Every metric lives in one of two **domains**, the contract that keeps
replay honest (DESIGN.md §11-§12 extended to telemetry):

  * ``"slot"`` — a pure function of the instruction stream.  Incremented
    only on paths both live execution and ``router.replay`` pass through
    (``PoolExecutor.execute``, ``_submit_to``, the recovery-event log),
    from values the stream signature already pins (op, core, advances,
    slot).  ``registry.snapshot(domain="slot")`` of a replay is
    dict-equal to the live run's (tested, including crash recovery).
  * ``"wall"`` — observational: wall-clock durations, injector retries,
    envelope bytes, RTTs, heartbeat misses, controller decisions.  Never
    compared across replay; confined to its own channel so it cannot
    contaminate the deterministic one.

Labels are frozen ``(key, value)`` tuples internally and canonical
``"k=v,k2=v2"`` strings in snapshots (keys sorted); label values must
not contain ``','`` or ``'='``.  Snapshots are plain JSON-able dicts —
what ships over the wire (§14 ``telemetry_snap`` envelopes), merges
across processes (:meth:`Registry.absorb`), and exports
(:mod:`repro.obs.export`).

Spans (:meth:`Registry.span`) are ``wall`` data kept apart from the
metrics: an in-memory list of ``(t0_ns, t1_ns, name, parent, rid,
group, model)`` on ``time.perf_counter_ns``, read by
:meth:`Registry.spans` and never part of a snapshot.  :func:`track_gc`
adds every garbage collection to them as a ``gc`` span.
"""
from __future__ import annotations

import gc
import time
from typing import Mapping

# seconds-scaled bounds: instruction execution on this stack spans
# ~0.1 ms (stub slots) to seconds (cold-jit CNN slots)
DEFAULT_SECONDS_BOUNDS = (1e-4, 3e-4, 1e-3, 3e-3, 1e-2, 3e-2,
                          0.1, 0.3, 1.0, 3.0, 10.0)
# count-scaled bounds (advances per RUN, payloads per SEND)
DEFAULT_COUNT_BOUNDS = (0.0, 1.0, 2.0, 4.0, 8.0, 16.0, 32.0)

DOMAINS = ("slot", "wall")

#: spans a registry keeps; later ones are counted in
#: ``obs_spans_dropped_total`` and not kept
MAX_SPANS = 1_000_000


def _label_key(labels: Mapping[str, str] | None) -> str:
    if not labels:
        return ""
    parts = []
    for k in sorted(labels):
        v = str(labels[k])
        if "," in v or "=" in v:
            raise ValueError(f"label value {v!r} for {k!r} may not "
                             f"contain ',' or '='")
        parts.append(f"{k}={v}")
    return ",".join(parts)


def parse_label_key(key: str) -> dict[str, str]:
    """Invert :func:`_label_key`: ``"a=1,b=2"`` -> ``{"a": "1", "b": "2"}``."""
    if not key:
        return {}
    return dict(p.split("=", 1) for p in key.split(","))


class Counter:
    """Monotonic counter; one value per label set."""

    kind = "counter"

    def __init__(self, registry: "Registry", name: str, help: str,
                 domain: str):
        self.registry = registry
        self.name = name
        self.help = help
        self.domain = domain
        self.series: dict[str, float] = {}

    def inc(self, n: float = 1,
            labels: Mapping[str, str] | None = None) -> None:
        """Add ``n`` (default 1) to the series named by ``labels``."""
        if not self.registry.enabled or n == 0:
            return
        key = _label_key(labels)
        self.series[key] = self.series.get(key, 0) + n


class Gauge:
    """Last-write-wins instantaneous value; one per label set."""

    kind = "gauge"

    def __init__(self, registry: "Registry", name: str, help: str,
                 domain: str):
        self.registry = registry
        self.name = name
        self.help = help
        self.domain = domain
        self.series: dict[str, float] = {}

    def set(self, value: float,
            labels: Mapping[str, str] | None = None) -> None:
        """Set the series named by ``labels`` to ``value``."""
        if not self.registry.enabled:
            return
        self.series[_label_key(labels)] = value


class Histogram:
    """Fixed-bound histogram: per-bucket counts (bucket i counts
    observations ``<= bounds[i]``, non-cumulative internally; the last
    implicit bucket is +Inf), plus sum and count."""

    kind = "histogram"

    def __init__(self, registry: "Registry", name: str, help: str,
                 domain: str, bounds: tuple[float, ...]):
        if list(bounds) != sorted(bounds) or len(set(bounds)) != len(bounds):
            raise ValueError(f"histogram bounds must be strictly "
                             f"increasing (got {bounds})")
        self.registry = registry
        self.name = name
        self.help = help
        self.domain = domain
        self.bounds = tuple(float(b) for b in bounds)
        self.series: dict[str, dict] = {}

    def observe(self, value: float,
                labels: Mapping[str, str] | None = None) -> None:
        """File ``value`` into its bucket for the ``labels`` series."""
        if not self.registry.enabled:
            return
        key = _label_key(labels)
        s = self.series.get(key)
        if s is None:
            s = self.series[key] = {
                "counts": [0] * (len(self.bounds) + 1), "sum": 0.0, "n": 0}
        i = len(self.bounds)                  # +Inf bucket by default
        for j, b in enumerate(self.bounds):
            if value <= b:
                i = j
                break
        s["counts"][i] += 1
        s["sum"] += value
        s["n"] += 1


class _Span:
    """One open span of :meth:`Registry.span`; ``rid`` and ``group`` may
    be set inside the ``with`` block, once they are known."""

    __slots__ = ("registry", "name", "rid", "group", "model", "parent",
                 "index", "t0")

    def __init__(self, registry: "Registry", name: str, rid, group, model):
        self.registry, self.name = registry, name
        self.rid, self.group, self.model = rid, group, model

    def __enter__(self) -> "_Span":
        self.registry._open_span(self)
        return self

    def __exit__(self, *exc) -> None:
        self.registry._close_span(self)


class _NoSpan:
    """What :meth:`Registry.span` hands out when it records nothing."""

    __slots__ = ()
    rid = group = model = None

    def __enter__(self) -> "_NoSpan":
        return self

    def __exit__(self, *exc) -> None:
        return None

    def __setattr__(self, name, value) -> None:
        return None


_NO_SPAN = _NoSpan()


class Registry:
    """A process-local metric namespace (module docstring).

    ``enabled=False`` turns every ``inc``/``set``/``observe`` and every
    :meth:`span` into a no-op — the bare leg of
    ``benchmarks/obs_bench.py`` measures the instrumentation overhead
    against exactly this switch.
    """

    def __init__(self, enabled: bool = True):
        self.enabled = enabled
        self._metrics: dict[str, Counter | Gauge | Histogram] = {}
        self._absorbed: dict[str, dict] = {}     # source -> last snapshot
        self._spans: list[tuple] = []
        self._open: list[int] = []               # indices of open spans

    # ------------------------------------------------------------------
    def span(self, name: str, *, rid: int | None = None,
             group: int | None = None, model: str | None = None):
        """A context manager that records one span ``(t0_ns, t1_ns, name,
        parent, rid, group, model)``: ``parent`` is the index of the
        innermost span open when it began (None at the top); ``model``
        names the network a fleet member serves.  Records nothing when
        the registry is disabled, and counts what the bound
        :data:`MAX_SPANS` turns away in ``obs_spans_dropped_total``."""
        if not self.enabled or self._full():
            return _NO_SPAN
        return _Span(self, name, rid, group, model)

    def _full(self) -> bool:
        """True (and one more span counted as dropped) at the bound."""
        if len(self._spans) < MAX_SPANS:
            return False
        self.counter("obs_spans_dropped_total",
                     "spans past the registry's bound, not kept",
                     domain="wall").inc()
        return True

    def _open_span(self, s: _Span) -> None:
        s.parent = self._open[-1] if self._open else None
        s.t0 = time.perf_counter_ns()
        entry = (s.t0, None, s.name, s.parent, s.rid, s.group, s.model)
        # the index is read after the append: building the entry may run
        # a collection whose ``gc`` span lands first
        self._spans.append(entry)
        s.index = len(self._spans) - 1
        self._open.append(s.index)

    def _close_span(self, s: _Span) -> None:
        t1 = time.perf_counter_ns()
        if s.index not in self._open:          # cleared while open
            return
        self._open.remove(s.index)
        self._spans[s.index] = (s.t0, t1, s.name, s.parent, s.rid, s.group,
                                s.model)

    def _add_span(self, t0: int, t1: int, name: str) -> None:
        """Record a span that was timed elsewhere (a collection)."""
        if not self.enabled or self._full():
            return
        self._spans.append((t0, t1, name,
                            self._open[-1] if self._open else None,
                            None, None, None))

    def spans(self) -> list[tuple]:
        """Every span recorded so far, in the order each began (``t1_ns``
        is None while a span is open)."""
        return list(self._spans)

    def clear_spans(self) -> None:
        """Forget the spans recorded so far; call it with no span open."""
        self._spans.clear()
        self._open.clear()

    # ------------------------------------------------------------------
    def _get(self, cls, name: str, help: str, domain: str, **kw):
        if domain not in DOMAINS:
            raise ValueError(f"unknown metric domain {domain!r}; "
                             f"one of {DOMAINS}")
        m = self._metrics.get(name)
        if m is None:
            m = self._metrics[name] = cls(self, name, help, domain, **kw)
            return m
        if not isinstance(m, cls) or m.domain != domain:
            raise ValueError(
                f"metric {name!r} re-registered as {cls.__name__.lower()}/"
                f"{domain}, but it is a {m.kind}/{m.domain}")
        return m

    def counter(self, name: str, help: str = "",
                domain: str = "slot") -> Counter:
        """Get or create the counter ``name``."""
        return self._get(Counter, name, help, domain)

    def gauge(self, name: str, help: str = "",
              domain: str = "slot") -> Gauge:
        """Get or create the gauge ``name``."""
        return self._get(Gauge, name, help, domain)

    def histogram(self, name: str, help: str = "", domain: str = "wall",
                  bounds: tuple[float, ...] = DEFAULT_SECONDS_BOUNDS
                  ) -> Histogram:
        """Get or create the histogram ``name`` (fixed ``bounds``)."""
        return self._get(Histogram, name, help, domain, bounds=bounds)

    # ------------------------------------------------------------------
    def snapshot(self, domain: str | None = None, *,
                 sources: bool = True) -> dict:
        """Plain-dict view of every metric (optionally one ``domain``),
        merged with the latest absorbed per-source snapshots (cumulative,
        so counters add and histograms sum; ``sources=False`` restricts
        to this process).  Deterministically ordered: dict-equality of
        two snapshots is the replay-determinism acceptance check."""
        out = {"counters": {}, "gauges": {}, "histograms": {}}
        for name in sorted(self._metrics):
            m = self._metrics[name]
            if domain is not None and m.domain != domain:
                continue
            entry = {"help": m.help, "domain": m.domain,
                     "series": {k: m.series[k] for k in sorted(m.series)}}
            if isinstance(m, Histogram):
                entry["bounds"] = list(m.bounds)
                entry["series"] = {
                    k: {"counts": list(s["counts"]), "sum": s["sum"],
                        "n": s["n"]}
                    for k, s in sorted(m.series.items())}
                out["histograms"][name] = entry
            elif isinstance(m, Gauge):
                out["gauges"][name] = entry
            else:
                out["counters"][name] = entry
        if sources:
            for source in sorted(self._absorbed):
                _merge_into(out, self._absorbed[source], domain)
        return out

    def absorb(self, snapshot: dict, *, source: str) -> None:
        """Adopt a remote registry's cumulative ``snapshot`` (a §14
        ``telemetry_snap`` payload).  The latest snapshot per ``source``
        *replaces* its predecessor — each ships cumulative totals, so a
        killed worker loses at most the window since its last ship,
        never double-counts."""
        self._absorbed[source] = snapshot

    @property
    def sources(self) -> list[str]:
        """Names of remote registries absorbed so far."""
        return sorted(self._absorbed)


def track_gc(registry: Registry):
    """Record every garbage collection into ``registry``: a ``gc`` span
    and an observation of the ``wall`` histogram
    ``gc_pause_seconds{generation}``.  Installs one ``gc.callbacks``
    hook; returns a function that removes it."""
    started: list[int] = []

    def on_gc(phase: str, info: dict) -> None:
        if phase == "start":
            started.append(time.perf_counter_ns())
            return
        if not started:                  # installed mid-collection
            return
        t0, t1 = started.pop(), time.perf_counter_ns()
        registry._add_span(t0, t1, "gc")
        registry.histogram("gc_pause_seconds",
                           "garbage-collection pauses",
                           domain="wall").observe(
            (t1 - t0) * 1e-9, {"generation": info["generation"]})

    gc.callbacks.append(on_gc)

    def uninstall() -> None:
        if on_gc in gc.callbacks:
            gc.callbacks.remove(on_gc)

    return uninstall


def _merge_into(out: dict, snap: dict, domain: str | None) -> None:
    """Merge one absorbed snapshot into ``out`` (counters/histograms add,
    gauges last-write-wins, absent metrics adopted whole)."""
    for name, entry in snap.get("counters", {}).items():
        if domain is not None and entry.get("domain") != domain:
            continue
        dst = out["counters"].setdefault(
            name, {"help": entry.get("help", ""),
                   "domain": entry.get("domain", "wall"), "series": {}})
        for k, v in entry.get("series", {}).items():
            dst["series"][k] = dst["series"].get(k, 0) + v
        dst["series"] = {k: dst["series"][k]
                         for k in sorted(dst["series"])}
    for name, entry in snap.get("gauges", {}).items():
        if domain is not None and entry.get("domain") != domain:
            continue
        dst = out["gauges"].setdefault(
            name, {"help": entry.get("help", ""),
                   "domain": entry.get("domain", "wall"), "series": {}})
        dst["series"].update(entry.get("series", {}))
        dst["series"] = {k: dst["series"][k]
                         for k in sorted(dst["series"])}
    for name, entry in snap.get("histograms", {}).items():
        if domain is not None and entry.get("domain") != domain:
            continue
        dst = out["histograms"].setdefault(
            name, {"help": entry.get("help", ""),
                   "domain": entry.get("domain", "wall"),
                   "bounds": list(entry.get("bounds", [])), "series": {}})
        for k, s in entry.get("series", {}).items():
            d = dst["series"].get(k)
            if d is None:
                dst["series"][k] = {"counts": list(s["counts"]),
                                    "sum": s["sum"], "n": s["n"]}
            else:
                d["counts"] = [a + b
                               for a, b in zip(d["counts"], s["counts"])]
                d["sum"] += s["sum"]
                d["n"] += s["n"]
        dst["series"] = {k: dst["series"][k]
                         for k in sorted(dst["series"])}
