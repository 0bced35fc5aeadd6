"""Chrome-tracing export of executed fleet instruction streams.

Converts :class:`~repro.fleet.instructions.ExecRecord` streams into the
Chrome trace-event JSON format (the ``chrome://tracing`` / Perfetto
timeline — same target format as the Helium repo's tarmac converter):
one *process* row per pool, one *thread* track per submesh within it
('c-submesh', 'p-submesh'), a 'retire' track for FREEs, a 'control'
track for SEND/RECV/REBALANCE/SET_PARAM, and a 'bubbles' track marking
every submesh idle gap of >= 1 slot inside the pool's active window —
labeled with what the idle submesh could have run next, so a pipeline
bubble is a named event, not something to squint for.

Only executed records carry wall-clock stamps; compiled-only records
(``t0 is None``) are skipped and *counted* — the skip count comes back
from :func:`write_chrome_trace` so callers can report rather than
silently thin the timeline.  Timestamps are re-based to the earliest
``t0`` across every stream so the trace starts at 0.
"""
from __future__ import annotations

import json
from typing import Mapping, Sequence

from repro.fleet.instructions import (ExecRecord, Free, Rebalance, Recv,
                                      Run, Send, SetParam)

# track (tid) layout within each pool's process row; lower sorts first
_TRACKS = ("c-submesh", "p-submesh", "retire", "control", "bubbles")


def _track(instr) -> str:
    if isinstance(instr, Run):
        return {"c": "c-submesh", "p": "p-submesh"}.get(instr.core,
                                                        "control")
    if isinstance(instr, Free):
        return "retire"
    return "control"


def _label(instr, advances: int) -> str:
    if isinstance(instr, Run):
        tag = " primary" if instr.primary else ""
        fused = " fused" if instr.fused else ""
        return f"RUN {instr.member} x{advances}{tag}{fused}"
    if isinstance(instr, Free):
        return f"FREE {instr.member}"
    if isinstance(instr, Send):
        whom = instr.member or "*"
        return f"SEND {whom} -> {instr.peer} x{advances}"
    if isinstance(instr, Recv):
        return f"RECV <- {instr.peer} x{advances}"
    if isinstance(instr, Rebalance):
        return f"REBALANCE theta={instr.theta:.2f}"
    if isinstance(instr, SetParam):
        return f"SET {instr.member}.{instr.param}={instr.value}"
    return type(instr).__name__


def _bubbles(records: Sequence[ExecRecord]) -> list[dict]:
    """Idle-gap descriptors for one pool: for each core, every maximal
    run of >= 1 slot inside the pool's active slot range where that
    submesh ran nothing, stamped onto the per-slot wall windows."""
    slots = [r.slot for r in records]
    if not slots:
        return []
    lo, hi = min(slots), max(slots)
    # per-slot wall window across the whole pool (min t0, max t1)
    win: dict[int, list[float]] = {}
    for r in records:
        if r.t0 is None or r.t1 is None:
            continue
        w = win.setdefault(r.slot, [r.t0, r.t1])
        w[0] = min(w[0], r.t0)
        w[1] = max(w[1], r.t1)
    if not win:
        return []       # compiled-only: no wall clock to draw gaps on
    out: list[dict] = []
    for core in ("c", "p"):
        busy = {r.slot for r in records
                if isinstance(r.instr, Run) and r.instr.core == core}
        runs = sorted((r.slot, r.instr.member) for r in records
                      if isinstance(r.instr, Run) and r.instr.core == core)
        gap_start = None
        for slot in range(lo, hi + 2):          # hi+1 flushes a tail gap
            idle = slot <= hi and slot not in busy
            if idle and gap_start is None:
                gap_start = slot
            elif not idle and gap_start is not None:
                g0, g1 = gap_start, slot - 1
                gap_start = None
                nxt = next((m for s, m in runs if s > g1), None)
                could = (nxt if nxt is not None
                         else f"no {core}-core work")
                t0s = [win[s][0] for s in range(g0, g1 + 1) if s in win]
                t1s = [win[s][1] for s in range(g0, g1 + 1) if s in win]
                if t0s:
                    ts, te = min(t0s), max(t1s)
                else:       # a fully recordless gap: pin to neighbors
                    prev = [win[s][1] for s in win if s < g0]
                    after = [win[s][0] for s in win if s > g1]
                    ts = max(prev) if prev else 0.0
                    te = min(after) if after else ts
                out.append({"core": core, "slots": [g0, g1],
                            "could_have_run": could, "t0": ts, "t1": te})
    return out


def chrome_trace(streams: Mapping[str, Sequence[ExecRecord]]) -> dict:
    """``{pool name: records}`` -> a Chrome trace-event document.

    Every executed record becomes one complete ('X') event: ``ts``/``dur``
    in microseconds from the records' wall-clock window, filed under its
    pool's process and its submesh's thread, with slot / seq / advances
    in ``args`` for the details pane.
    """
    stamped = [r for recs in streams.values() for r in recs
               if r.t0 is not None and r.t1 is not None]
    base = min((r.t0 for r in stamped), default=0.0)
    events: list[dict] = []
    for pid, (pool, records) in enumerate(sorted(streams.items())):
        events.append({"ph": "M", "pid": pid, "tid": 0,
                       "name": "process_name",
                       "args": {"name": pool}})
        for tid, track in enumerate(_TRACKS):
            events.append({"ph": "M", "pid": pid, "tid": tid,
                           "name": "thread_name",
                           "args": {"name": track}})
            events.append({"ph": "M", "pid": pid, "tid": tid,
                           "name": "thread_sort_index",
                           "args": {"sort_index": tid}})
        for r in records:
            if r.t0 is None or r.t1 is None:
                continue
            args = {"slot": r.slot, "seq": r.seq,
                    "advances": r.advances}
            events.append({
                "ph": "X",
                "pid": pid,
                "tid": _TRACKS.index(_track(r.instr)),
                "name": _label(r.instr, r.advances),
                "cat": r.instr.op,
                "ts": (r.t0 - base) * 1e6,
                # sub-resolution slices still need nonzero width to render
                "dur": max((r.t1 - r.t0) * 1e6, 0.05),
                "args": args,
            })
        for b in _bubbles(records):
            events.append({
                "ph": "X",
                "pid": pid,
                "tid": _TRACKS.index("bubbles"),
                "name": (f"bubble {b['core']}-submesh "
                         f"x{b['slots'][1] - b['slots'][0] + 1}"),
                "cat": "bubble",
                "ts": (b["t0"] - base) * 1e6,
                "dur": max((b["t1"] - b["t0"]) * 1e6, 0.05),
                "args": {"core": b["core"], "slots": b["slots"],
                         "could_have_run": b["could_have_run"]},
            })
    return {"traceEvents": events, "displayTimeUnit": "ms"}


def write_chrome_trace(streams: Mapping[str, Sequence[ExecRecord]],
                       path: str) -> tuple[int, int]:
    """Write :func:`chrome_trace` to ``path``; returns ``(events,
    skipped)`` — the event count and how many compiled-only (unstamped)
    records the export had to leave out."""
    doc = chrome_trace(streams)
    skipped = sum(1 for recs in streams.values() for r in recs
                  if r.t0 is None or r.t1 is None)
    with open(path, "w") as f:
        json.dump(doc, f)
    return len(doc["traceEvents"]), skipped
