"""Reduce a profiler trace (``.xplane.pb``) to what the metrics read.

A traced run records the device alone: the host tracer is off, since the
runtime's own host events slow the driving loop several times over.  The
run marks its measured window on the device by running one tiny program,
:data:`MARK`, at its opening and at its close (two programs alike would
share one executable, and so one name in the trace), and keeps its own
host spans
(:class:`HostLog`, one of :data:`LABELS` per phase of its driving loop) on
``time.perf_counter_ns``, with the host time around each mark's call; the
marks map those spans onto the trace's clock.  From the trace this module
takes, per chip used:

* busy time: the union of the intervals of the device's ``XLA Ops``
  events inside the window (from the end of the opening mark to the start
  of the closing one);
* per op name (the HLO instruction's name without its numeric suffix: a
  Pallas kernel appears under the name of the jitted function that calls
  it, ``depthwise_conv2d``): the number of events that start in the
  window and their summed device time;
* idle time by what the host was doing: each gap in the busy union is
  split over the host spans that cover it (``other`` where none does).

It reads the trace with ``jax.profiler.ProfileData`` alone.
"""
from __future__ import annotations

import dataclasses
import gzip
import json
import pathlib
import re
import time

MARK = "chipbench_window_mark"
MARK_OPEN, MARK_CLOSE = "open", "close"     # the host log's two marks
LABELS = ("gen.sleep", "submit", "engine.advance", "engine.retire",
          "drain")
DEVICE_PLANE = re.compile(r"/device:TPU:(\d+)\Z")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
_HLO = re.compile(r"%([^\s=]+) = ")
_SUFFIX = re.compile(r"(\.(\d+|clone))+\Z")


def op_name(event_name: str) -> str:
    """``%depthwise_conv2d.3 = f32[...] custom-call(...)`` ->
    ``depthwise_conv2d``; a name that is not HLO text stays as it is."""
    m = _HLO.match(event_name)
    return _SUFFIX.sub("", m.group(1)) if m else event_name


class _Span:
    __slots__ = ("log", "label", "t0")

    def __init__(self, log, label):
        self.log, self.label = log, label

    def __enter__(self):
        self.t0 = time.perf_counter_ns()

    def __exit__(self, *exc):
        self.log.spans.append((self.t0, time.perf_counter_ns(), self.label))


@dataclasses.dataclass
class HostLog:
    """A traced run's own host record on ``time.perf_counter_ns``: its
    spans ``(start, end, label)`` and, per window mark, the host time just
    before its call and just after its result came back."""

    spans: list = dataclasses.field(default_factory=list)
    marks: dict = dataclasses.field(default_factory=dict)

    def span(self, label: str) -> _Span:
        """A context manager that logs one span of ``label``."""
        return _Span(self, label)

    def dump(self, path) -> None:
        """Write the log as JSON."""
        pathlib.Path(path).write_text(json.dumps(
            {"spans": self.spans, "marks": self.marks}))

    @classmethod
    def read(cls, path) -> "HostLog":
        """A log written by :meth:`dump`."""
        d = json.loads(pathlib.Path(path).read_text())
        return cls([tuple(s) for s in d["spans"]],
                   {k: tuple(v) for k, v in d["marks"].items()})


@dataclasses.dataclass
class Chip:
    """One device's reading over the window."""

    id: int
    busy_ns: float
    ops: dict[str, list[float]]      # name -> [events, device ns]
    idle_by_label: dict[str, float]  # host label -> idle ns


@dataclasses.dataclass
class Summary:
    """A traced window, reduced."""

    window_ns: float
    chips: list[Chip]

    @property
    def window_s(self) -> float:
        """The traced window's length in seconds."""
        return self.window_ns * 1e-9

    @property
    def busy_s(self) -> float:
        """Busy seconds, the mean over the chips."""
        return sum(c.busy_ns for c in self.chips) / len(self.chips) * 1e-9

    def idle_share(self, chip: Chip | None = None) -> float:
        """Idle share of one chip, or of the mean over chips."""
        busy = self.busy_s * 1e9 if chip is None else chip.busy_ns
        return 1.0 - busy / self.window_ns

    def kernel(self, names) -> tuple[int, float]:
        """Events and device seconds of the ops named ``names``, summed
        over the chips."""
        n, ns = 0, 0.0
        for c in self.chips:
            for name in names:
                if name in c.ops:
                    n += int(c.ops[name][0])
                    ns += c.ops[name][1]
        return n, ns * 1e-9

    def top_ops(self, k: int = 10) -> list[list]:
        """The ``k`` ops with most device time, summed over chips."""
        tot: dict[str, float] = {}
        for c in self.chips:
            for name, (_, ns) in c.ops.items():
                tot[name] = tot.get(name, 0.0) + ns
        top = sorted(tot.items(), key=lambda kv: -kv[1])[:k]
        return [[name, ns * 1e-9] for name, ns in top]

    def idle_gaps(self, k: int = 10) -> list[list]:
        """Idle seconds by the host annotation that covered them, the
        mean over the chips, longest first."""
        tot: dict[str, float] = {}
        for c in self.chips:
            for label, ns in c.idle_by_label.items():
                tot[label] = tot.get(label, 0.0) + ns / len(self.chips)
        top = sorted(tot.items(), key=lambda kv: -kv[1])[:k]
        return [[label, ns * 1e-9] for label, ns in top]


def load(path: str | pathlib.Path):
    """A ``ProfileData`` from an ``.xplane.pb`` file, gzipped or not."""
    from jax.profiler import ProfileData

    raw = pathlib.Path(path).read_bytes()
    if raw[:2] == b"\x1f\x8b":
        raw = gzip.decompress(raw)
    return ProfileData.from_serialized_xspace(raw)


def find(log_dir: str | pathlib.Path) -> pathlib.Path:
    """The one ``.xplane.pb`` a ``jax.profiler.trace`` left in
    ``log_dir``."""
    found = sorted(pathlib.Path(log_dir).glob("plugins/profile/*/*.xplane.pb"))
    if len(found) != 1:
        raise RuntimeError(f"expected one .xplane.pb under {log_dir}, "
                           f"found {len(found)}")
    return found[0]


def _union(intervals: list[tuple[float, float]]) -> list[list[float]]:
    out: list[list[float]] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return out


def _marks(pd) -> dict[str, tuple[float, float]]:
    """Device interval of the window's opening mark (the first run of
    :data:`MARK` in any TPU plane's ``XLA Modules`` line) and of its
    closing one (the last)."""
    found = []
    for plane in pd.planes:
        if not DEVICE_PLANE.match(plane.name):
            continue
        for line in plane.lines:
            if line.name != MODULES_LINE:
                continue
            found += [(e.start_ns, e.end_ns) for e in line.events
                      if e.name.startswith(f"jit_{MARK}")]
    if len(found) < 2:
        raise RuntimeError(f"the trace holds {len(found)} window mark(s) "
                           f"({MARK}), not an opening and a closing one")
    return {MARK_OPEN: min(found), MARK_CLOSE: max(found)}


def _to_device_clock(host: HostLog, marks) -> list[tuple]:
    """The host spans on the trace's clock: the mid-points of each mark's
    host call and of its device program, matched at both ends of the
    window, give a linear map."""
    (h0, d0), (h1, d1) = [
        (sum(host.marks[m]) / 2, sum(marks[m]) / 2)
        for m in (MARK_OPEN, MARK_CLOSE)]
    rate = (d1 - d0) / (h1 - h0) if h1 > h0 else 1.0
    return sorted((d0 + (a - h0) * rate, d0 + (b - h0) * rate, label)
                  for a, b, label in host.spans)


def _idle_by_label(gaps, spans) -> dict[str, float]:
    """Split each gap over the host spans that overlap it (spans of one
    thread do not overlap each other)."""
    out: dict[str, float] = {}
    j = 0
    for a, b in gaps:
        while j < len(spans) and spans[j][1] <= a:
            j += 1
        covered, k = 0.0, j
        while k < len(spans) and spans[k][0] < b:
            s, e, label = spans[k]
            ov = min(b, e) - max(a, s)
            if ov > 0:
                out[label] = out.get(label, 0.0) + ov
                covered += ov
            k += 1
        if b - a - covered > 0:
            out["other"] = out.get("other", 0.0) + (b - a - covered)
    return out


def reduce(pd, host: HostLog, device_ids=None) -> Summary:
    """Reduce a ``ProfileData`` and the run's :class:`HostLog` to a
    :class:`Summary` over the chips ``device_ids`` (every TPU in the trace
    when None)."""
    marks = _marks(pd)
    w0, w1 = marks[MARK_OPEN][1], marks[MARK_CLOSE][0]
    spans = _to_device_clock(host, marks)
    chips = []
    for plane in pd.planes:
        m = DEVICE_PLANE.match(plane.name)
        if not m or (device_ids is not None
                     and int(m.group(1)) not in device_ids):
            continue
        ivals, ops = [], {}
        for line in plane.lines:
            if line.name != OPS_LINE:
                continue
            for e in line.events:
                a, b = e.start_ns, e.end_ns
                if b <= w0 or a >= w1:
                    continue
                ivals.append((max(a, w0), min(b, w1)))
                if a >= w0:
                    rec = ops.setdefault(op_name(e.name), [0, 0.0])
                    rec[0] += 1
                    rec[1] += e.duration_ns
        busy = _union(ivals)
        gaps, t = [], w0
        for a, b in busy:
            if a > t:
                gaps.append((t, a))
            t = b
        if t < w1:
            gaps.append((t, w1))
        chips.append(Chip(id=int(m.group(1)),
                          busy_ns=sum(b - a for a, b in busy), ops=ops,
                          idle_by_label=_idle_by_label(gaps, spans)))
    if not chips:
        raise RuntimeError("the trace holds no TPU device plane")
    chips.sort(key=lambda c: c.id)
    return Summary(window_ns=w1 - w0, chips=chips)
