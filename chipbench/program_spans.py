#!/usr/bin/env python3
"""The program's own spans in one run of a cell: where the engine's host
time goes, and what each exec group costs on the device.

Run from the root of a checkout, on the chip:

    python3 chipbench/program_spans.py --workload mbv2-poisson \\
        --seed 12345 --seconds 30 --trace 1 [--out DIR]

The run is ``chipbench/run.py``'s (``harness.run_cell``), with the
engine recording its spans into a ``repro.obs.Registry``
(``DualCoreEngine.obs``: ``slot.dispatch``, ``group.call``,
``request.admit``, ``slot.retire``, ``request.materialize``) and every
garbage collection with ``repro.obs.track_gc`` (a ``gc`` span and
``gc_pause_seconds{generation}``).  The last line of standard output is
the run's result line with one more key, ``program``:

* ``spans``: per span name, the spans that start in the window, their
  seconds and their self seconds (duration less what their child spans
  cover; ``slot.dispatch``'s is the engine's bookkeeping);
* ``dispatch_call_us``: mean host µs of a ``group.call`` span;
* ``stall_s``: summed seconds of ``slot.dispatch`` and ``slot.retire``
  spans longer than :data:`STALL_S`;
* ``gc_pause_ms``: summed ms of the collections of the window, every
  generation (``gc_pause_seconds`` at the close less at the opening),
  and ``gc``, the collections per generation;
* with ``--trace 1``: ``idle_gaps``, the device's idle time split over
  the innermost span that covered it, a program span before the
  harness's span around it (``other`` where none did), and ``groups``,
  one row per exec-group program (``jit_dualcore_g<NN>_<core>``): its
  runs in the window, device ms, device ms of ops that are not kernels,
  and the FPGA model's prediction of the group (``exec_schedule``).

The per-group table is also logged on standard error.  With ``--trace
1 --out DIR`` the trace stays in ``DIR``, and the program's spans are
written beside the run's ``host.json`` as ``program.json``, in the same
form.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import bisect  # noqa: E402
import contextlib  # noqa: E402
import heapq  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import pathlib  # noqa: E402
import re  # noqa: E402
import sys  # noqa: E402

ROOT = pathlib.Path(__file__).resolve().parent.parent
if __name__ == "__main__":
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from chipbench import trace_reduce  # noqa: E402
from chipbench.metrics.roofline import KERNELS  # noqa: E402

STALL_S = 0.1                # a slot span longer than this is a stall
SLOT_SPANS = ("slot.dispatch", "slot.retire")
GROUP_MODULE = re.compile(r"jit_dualcore_g\d+_[cp]\b")
# the Pallas kernels, by the device-op names the roofline readers match
KERNEL_OPS = frozenset(op for ops in KERNELS.values() for op in ops)


# --------------------------------------------------------------------------
# host side: the program's spans over the window
# --------------------------------------------------------------------------
def in_window(spans, w0: int, w1: int) -> list[int]:
    """Indices of the closed spans that start in ``[w0, w1)``."""
    return [i for i, s in enumerate(spans)
            if s[1] is not None and w0 <= s[0] < w1]


def self_ns(spans) -> list[float]:
    """Each span's duration less what its child spans cover (children of
    one span do not overlap each other)."""
    out = [0.0 if s[1] is None else float(s[1] - s[0]) for s in spans]
    for s in spans:
        parent = s[3]
        if parent is not None and s[1] is not None:
            out[parent] -= s[1] - s[0]
    return out


def span_table(spans, w0: int, w1: int) -> dict:
    """Per span name: count, seconds and self seconds of the spans that
    start in the window."""
    own = self_ns(spans)
    out: dict[str, dict] = {}
    for i in in_window(spans, w0, w1):
        t0, t1, name = spans[i][:3]
        row = out.setdefault(name, {"n": 0, "s": 0.0, "self_s": 0.0})
        row["n"] += 1
        row["s"] += (t1 - t0) * 1e-9
        row["self_s"] += own[i] * 1e-9
    return out


def dispatch_call_us(spans, w0: int, w1: int) -> float | None:
    """Mean host µs of a ``group.call`` span starting in the window."""
    calls = [spans[i] for i in in_window(spans, w0, w1)
             if spans[i][2] == "group.call"]
    if not calls:
        return None
    return sum(s[1] - s[0] for s in calls) / len(calls) * 1e-3


def stall_s(spans, w0: int, w1: int) -> float | None:
    """Summed seconds of the slot spans longer than :data:`STALL_S`
    that start in the window (None where the window has no slot span)."""
    slots = [spans[i][1] - spans[i][0] for i in in_window(spans, w0, w1)
             if spans[i][2] in SLOT_SPANS]
    if not slots:
        return None
    return sum(d for d in slots if d > STALL_S * 1e9) * 1e-9


def gc_pause(before: dict, after: dict) -> tuple[float, dict] | None:
    """Milliseconds of collection between two registry snapshots, and the
    collections per generation (None without ``gc_pause_seconds``)."""
    h1 = after["histograms"].get("gc_pause_seconds")
    if h1 is None:
        return None
    h0 = before["histograms"].get("gc_pause_seconds", {"series": {}})
    ms, by_gen = 0.0, {}
    for key, s in h1["series"].items():
        s0 = h0["series"].get(key, {"sum": 0.0, "n": 0})
        ms += (s["sum"] - s0["sum"]) * 1e3
        by_gen[key] = s["n"] - s0["n"]
    return ms, by_gen


# --------------------------------------------------------------------------
# device side: idle time by innermost span, device time by exec group
# --------------------------------------------------------------------------
def idle_by_span(gaps, host_spans, program_spans) -> dict[str, float]:
    """Split each idle gap over the innermost span that covers it: of the
    spans that cover an instant, a program span before a harness span,
    then the one that began last; ``other`` where none covers it.  Gaps
    do not overlap; spans are ``(start, end, label)`` on the trace's
    clock."""
    events = [(a, 0, None, None) for a, _ in gaps]
    events += [(b, 1, None, None) for _, b in gaps]
    for prio, spans in ((0, host_spans), (1, program_spans)):
        for k, (a, b, label) in enumerate(spans):
            if b > a:
                key = (prio, a, k)
                events += [(a, 2, key, label), (b, 3, key, label)]
    events.sort(key=lambda e: (e[0], e[1]))
    out: dict[str, float] = {}
    covering: list = []          # heap: the innermost covering span first
    ended: set = set()
    in_gap, t = False, None
    for x, kind, key, label in events:
        if in_gap and x > t:
            while covering and covering[0][1] in ended:
                heapq.heappop(covering)
            top = covering[0][2] if covering else "other"
            out[top] = out.get(top, 0.0) + (x - t)
        t = x
        if kind < 2:
            in_gap = kind == 0
        elif kind == 2:
            heapq.heappush(covering, ((-key[0], -key[1], -key[2]), key,
                                      label))
        else:
            ended.add(key)
    return out


def _gaps(busy, w0: float, w1: float) -> list[tuple[float, float]]:
    gaps, t = [], w0
    for a, b in busy:
        if a > t:
            gaps.append((t, a))
        t = max(t, b)
    if t < w1:
        gaps.append((t, w1))
    return gaps


def reduce_program(pd, host: trace_reduce.HostLog, program_spans,
                   device_ids=None) -> dict:
    """The device's idle time by innermost span (the mean over the
    chips) and the device time of each exec-group program, from a trace,
    the run's host log and the program's spans (on the host's clock)."""
    marks = trace_reduce._marks(pd)
    w0 = marks[trace_reduce.MARK_OPEN][1]
    w1 = marks[trace_reduce.MARK_CLOSE][0]
    host_dev = trace_reduce._to_device_clock(host, marks)
    prog_dev = trace_reduce._to_device_clock(
        trace_reduce.HostLog([s[:3] for s in program_spans
                              if s[1] is not None], host.marks), marks)
    idle: dict[str, float] = {}
    groups: dict[str, dict] = {}
    chips = 0
    for plane in pd.planes:
        m = trace_reduce.DEVICE_PLANE.match(plane.name)
        if not m or (device_ids is not None
                     and int(m.group(1)) not in device_ids):
            continue
        chips += 1
        ops, modules = [], []
        for line in plane.lines:
            if line.name == trace_reduce.OPS_LINE:
                ops = [(e.start_ns, e.end_ns, trace_reduce.op_name(e.name))
                       for e in line.events
                       if e.end_ns > w0 and e.start_ns < w1]
            elif line.name == trace_reduce.MODULES_LINE:
                modules = [(e.start_ns, e.end_ns, mm.group(0))
                           for e in line.events
                           if (mm := GROUP_MODULE.match(e.name))
                           and w0 <= e.start_ns < w1]
        busy = trace_reduce._union([(max(a, w0), min(b, w1))
                                    for a, b, _ in ops])
        for label, ns in idle_by_span(_gaps(busy, w0, w1), host_dev,
                                      prog_dev).items():
            idle[label] = idle.get(label, 0.0) + ns
        _group_times(groups, ops, modules)
    if not chips:
        raise RuntimeError("the trace holds no TPU device plane")
    return {"idle_gaps": sorted(([k, v / chips * 1e-9]
                                 for k, v in idle.items()),
                                key=lambda kv: -kv[1]),
            "groups": [dict(module=k, **v)
                       for k, v in sorted(groups.items())]}


def _group_times(groups: dict, ops, modules) -> None:
    """Add each module's runs, device ns and the device ns of the ops
    inside it that are not kernels to ``groups``."""
    ops = sorted(ops)
    starts = [a for a, _, _ in ops]
    for a, b, name in modules:
        row = groups.setdefault(name, {"runs": 0, "device_ns": 0.0,
                                       "non_kernel_ns": 0.0})
        row["runs"] += 1
        row["device_ns"] += b - a
        for k in range(bisect.bisect_left(starts, a), len(ops)):
            oa, ob, op = ops[k]
            if oa >= b:
                break
            if op not in KERNEL_OPS:
                row["non_kernel_ns"] += min(ob, b) - oa


# --------------------------------------------------------------------------
# one run with the program's spans on
# --------------------------------------------------------------------------
@contextlib.contextmanager
def _program_spans(harness):
    """Within the block, ``harness.run_cell`` builds its system with the
    engine recording into a fresh registry and collections tracked, and
    notes its window; yields what it records.  The harness itself has
    no hook for the program's spans (PERF.md §7: adding one is a change
    to the benchmark), so this wraps the three functions it calls."""
    from repro.obs import Registry, track_gc

    got: dict = {}
    build, drives = harness.build, (harness.drive_open,
                                    harness.drive_closed)

    def build_traced(*args, **kw):
        system, tables, calls = build(*args, **kw)
        got["registry"] = system.engine.obs = Registry()
        got["untrack"] = track_gc(got["registry"])
        got["runners"] = system.runners
        return system, tables, calls

    def timed(drive):
        def run(*args, **kw):
            got["before"] = got["registry"].snapshot(domain="wall")
            out = drive(*args, **kw)
            got["after"] = got["registry"].snapshot(domain="wall")
            t0, window_s = out[0], out[1]
            got["window"] = (int(t0 * 1e9), int((t0 + window_s) * 1e9))
            return out
        return run

    harness.build = build_traced
    harness.drive_open, harness.drive_closed = map(timed, drives)
    try:
        yield got
    finally:
        harness.build = build
        harness.drive_open, harness.drive_closed = drives
        if "untrack" in got:
            got["untrack"]()


def predicted_ms(runners) -> dict[str, float]:
    """The FPGA model's latency of each exec group, in ms, by the name of
    its program."""
    out = {}
    for r in runners.values():
        es = r.plan.exec_schedule
        for gi, (g, cyc) in enumerate(zip(r.groups, es.group_latencies)):
            out[f"jit_dualcore_g{gi:02d}_{g.core}"] = \
                es.board.cycles_to_seconds(cyc) * 1e3
    return out


def run(root, workload: str, seed: int, seconds: float, traced: bool, *,
        t_start: float, out_dir=None, **kw) -> dict:
    """One run of ``workload`` with the program's spans on; the result
    line with its ``program`` key."""
    from chipbench import harness, spec

    keep = traced and out_dir is not None
    with _program_spans(harness) as got:
        line = harness.run_cell(root, workload, seed, seconds, traced,
                                t_start=t_start,
                                trace_dir=str(out_dir) if keep else None,
                                **kw)
    spans = got["registry"].spans()
    w0, w1 = got["window"]
    prog = {"spans": span_table(spans, w0, w1),
            "dispatch_call_us": dispatch_call_us(spans, w0, w1),
            "stall_s": stall_s(spans, w0, w1)}
    gcs = gc_pause(got["before"], got["after"])
    prog["gc_pause_ms"], prog["gc"] = gcs if gcs else (None, None)
    if keep:
        tdir = max(pathlib.Path(out_dir).glob("chipbench-trace-*"),
                   key=os.path.getmtime)
        host = trace_reduce.HostLog.read(tdir / "host.json")
        (tdir / "program.json").write_text(json.dumps(
            {"spans": spans, "marks": host.marks}))
        import jax

        chips = spec.Bench(root, kw.get("home", spec.HOME)).cell(
            workload)["chips"]
        ids = {d.id for d in jax.devices()[:chips]}
        red = reduce_program(trace_reduce.load(trace_reduce.find(tdir)),
                             host, spans, ids)
        pred = predicted_ms(got["runners"])
        for row in red["groups"]:
            row["predicted_ms"] = pred.get(row["module"])
        prog.update(red)
        _log_groups(red["groups"], line)
    line["program"] = prog
    return line


def _log_groups(groups, line) -> None:
    images = line.get("attempted", 0)
    print(f"[program_spans] {'program':<22}{'runs':>7}{'device ms':>12}"
          f"{'non-kernel ms':>15}{'model ms':>10}", file=sys.stderr)
    for g in groups:
        print(f"[program_spans] {g['module']:<22}{g['runs']:>7}"
              f"{g['device_ns'] * 1e-6:>12.3f}"
              f"{g['non_kernel_ns'] * 1e-6:>15.3f}"
              f"{g['predicted_ms'] or 0:>10.4f}", file=sys.stderr)
    print(f"[program_spans] {len(groups)} programs, {images} requests",
          file=sys.stderr, flush=True)


def main(argv=None) -> int:
    """Parse the arguments, run the cell once, print the line."""
    ap = argparse.ArgumentParser(prog="chipbench/program_spans.py",
                                 description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--out", type=pathlib.Path, default=None,
                    help="with --trace 1: keep the trace here, with "
                         "host.json and program.json beside it")
    args = ap.parse_args(argv)
    cache = str(ROOT / ".jax_cache" / "chipbench")
    os.makedirs(cache, exist_ok=True)
    os.environ["JAX_COMPILATION_CACHE_DIR"] = cache
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from chipbench import harness, spec

    harness.use_compile_cache(cache)
    if args.out is not None:
        args.out.mkdir(parents=True, exist_ok=True)
    try:
        line = run(ROOT, args.workload, args.seed, args.seconds,
                   bool(args.trace), t_start=T_START, out_dir=args.out)
    except spec.Refused as e:
        print(f"program_spans: refused: {e}", file=sys.stderr)
        return 2
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
