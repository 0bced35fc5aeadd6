"""Streaming CNN engine: online admission for the dual-core pipeline.

``DualCoreRunner.run_pipelined`` took a static image list and never refilled
a drained slot — the pipeline wound down as streams finished even when more
work was waiting.  :class:`DualCoreEngine` closes that gap (the ROADMAP
"online admission loop" item): requests queue up (bounded, with
:class:`~repro.serving.api.QueueFull` backpressure), and every scheduler
slot the engine

  1. advances each in-flight stream by one exec group, oldest stream first
     (stream admitted at slot ``s`` runs group ``k - s`` at slot ``k`` — the
     paper's one-slot offset, so neighbouring streams always occupy
     different cores by the alternation invariant);
  2. admits at most one queued request into the freed group-0 slot (the
     structural per-step limit — two streams entering the same slot would
     double-book a core; the :class:`AdmissionPolicy` can only throttle
     below that);
  3. retires streams that cleared the last group, materializing their
     output (the per-request latency the metrics record) — only after every
     dispatch of the slot is in flight, so the block never serializes the
     cross-core overlap.

With every request available up front this reproduces the
``run_pipelined`` dispatch trace exactly (a test asserts it); under bursty
arrivals, empty-queue slots become pipeline bubbles that later admissions
refill.  Capacity equals the number of exec groups — the deepest the
one-slot-offset pipeline can be — so in-flight work is bounded by
construction and the queue bound covers the rest.
"""
from __future__ import annotations

import dataclasses
import time
from typing import TYPE_CHECKING

from repro.serving.api import (AdmissionPolicy, Completion, EngineBase,
                               FixedRateAdmission, Metrics, RequestMetrics,
                               ServeResult, Ticket)

if TYPE_CHECKING:
    from repro.dualcore.runtime import DualCoreRunner


@dataclasses.dataclass
class _Flight:
    """One in-flight stream: its env and the next group it will run."""

    rid: int
    env: dict
    next_group: int
    ticket: Ticket
    metrics: RequestMetrics


class DualCoreEngine(EngineBase):
    """Continuous-streaming front end over a :class:`DualCoreRunner`.

    ``record``, when given, receives ``(slot, rid, group, core)`` tuples in
    dispatch order — the same trace ``run_pipelined`` produced, now with
    admission slots determined online by arrivals instead of statically.
    The engine's spans carry the runner's network as ``model``.
    """

    def __init__(self, runner: "DualCoreRunner", *,
                 policy: AdmissionPolicy | None = None,
                 max_queue: int | None = None,
                 record: list | None = None):
        super().__init__(max_queue=max_queue)
        self.runner = runner
        self.model = runner.graph.name
        self.policy = policy or FixedRateAdmission(1)
        self.capacity = len(runner.groups)
        self._handles = runner.handles
        self._record = record
        self._flight: list[_Flight] = []      # admission order: oldest first
        self._slot = 0

    # ------------------------------------------------------------------
    @property
    def in_flight(self) -> int:
        """Streams currently in the pipeline."""
        return len(self._flight)

    @property
    def has_work(self) -> bool:
        """True while any queued or in-flight work remains."""
        return bool(self._pending or self._flight)

    def next_dispatch_cycles(self) -> tuple[float, float]:
        """Predicted (c-cycles, p-cycles) the *next* ``step`` will dispatch,
        from the per-group latency model the schedule carries
        (``core.scheduler.Schedule.group_latencies`` of the exec schedule):
        every in-flight stream contributes its next group's latency on
        that group's core, plus group 0 if an admission would land.  The
        fleet front-end reads this to co-dispatch a member whose slot is
        conv-heavy with one whose slot is dw-heavy."""
        lat = self.runner.plan.exec_schedule.group_latencies
        groups = self.runner.groups
        cyc = {"c": 0.0, "p": 0.0}
        for f in self._flight:
            cyc[groups[f.next_group].core] += lat[f.next_group]
        if self._pending and len(self._flight) < self.capacity:
            cyc[groups[0].core] += lat[0]
        return cyc["c"], cyc["p"]

    @property
    def next_core(self) -> str | None:
        """Core carrying the dominant share of the next step's dispatches
        (``None`` when the engine has no work)."""
        if not self.has_work:
            return None
        c, p = self.next_dispatch_cycles()
        return "c" if c >= p else "p"

    # ------------------------------------------------------------------
    def _dispatch(self, f: _Flight) -> None:
        """Run flight ``f``'s next group via the runner's group handle
        (cross-core env hop included)."""
        gi = f.next_group
        h = self._handles[gi]
        prev = self._handles[gi - 1].core if gi > 0 else None
        if self.obs is None:
            f.env = h(f.env, prev_core=prev)
        else:
            with self.obs.span("group.call", rid=f.rid, group=gi,
                               model=self.model):
                f.env = h(f.env, prev_core=prev)
        if self._record is not None:
            self._record.append((self._slot, f.rid, gi, h.core))
        f.next_group = gi + 1

    def relocate(self, dual) -> None:
        """Move the engine onto a re-split pool (REBALANCE): relocate the
        runner's params/shardings, then re-place every in-flight env on
        its next group's core — a stream mid-chain resumes on the new
        submeshes without losing its position."""
        self.runner.relocate(dual)
        self._handles = self.runner.handles
        for f in self._flight:
            f.env = self.runner._place(f.env,
                                       self._handles[f.next_group].core)

    def step(self) -> list[Completion]:
        """Advance the pipeline by one slot (see module docstring)."""
        return self.retire(self.advance())

    def advance(self) -> list["_Flight"]:
        """Dispatch phase of one slot: advance every in-flight stream and
        admit into the freed group-0 slot, returning the flights that
        cleared the last group WITHOUT materializing them.  Callers that
        own more dispatches for the same wall-clock window (the fleet's
        cross-engine co-dispatch) issue those first and call
        :meth:`retire` after — the same block-last rule ``step`` applies
        within one engine, extended across engines.  With :attr:`obs`
        set, the whole phase is a ``slot.dispatch`` span."""
        if self.obs is None:
            return self._advance()
        with self.obs.span("slot.dispatch", model=self.model):
            return self._advance()

    def _advance(self) -> list["_Flight"]:
        self._start_clock()
        # 0. shed past-deadline queue entries (ShedPolicy only) against
        #    the engine's own slot counter — unless an external clock
        #    (the fleet executor's slot) already swept this dispatch
        if self._ext_clock is None:
            self._shed_buf.extend(self.shed_expired())
        finished: list[_Flight] = []
        # 1. advance in-flight streams, oldest (deepest group) first
        kept: list[_Flight] = []
        for f in self._flight:
            self._dispatch(f)
            (finished if f.next_group >= self.capacity else kept).append(f)
        self._flight = kept
        # 2. admit into the freed group-0 slot — at most one per slot, or
        #    the one-slot offset (one group per core per slot) breaks
        n = self.policy.admit(queued=len(self._pending),
                              in_flight=len(self._flight),
                              capacity=self.capacity)
        n = max(0, min(n, 1, self.capacity - len(self._flight),
                       len(self._pending)))
        if n:
            if self.obs is None:
                f = self._admit()
            else:
                with self.obs.span("request.admit",
                                   model=self.model) as span:
                    f = self._admit()
                    span.rid = None if f is None else f.rid
            if f is not None:
                self._dispatch(f)
                if f.next_group >= self.capacity:   # single-group chain
                    finished.append(f)
                else:
                    self._flight.append(f)
        self._slot += 1
        return finished

    def _admit(self) -> "_Flight | None":
        """Pop the next request and place its input on group 0's core;
        None when shedding emptied the queue."""
        popped = self._pop_admission()
        if popped is None:
            return None
        req, ticket = popped
        self._metrics[req.rid].started_at = time.perf_counter()
        return _Flight(rid=req.rid,
                       env=self.runner.place_input(req.payload),
                       next_group=0, ticket=ticket,
                       metrics=self._metrics[req.rid])

    def retire(self, finished: list["_Flight"]) -> list[Completion]:
        """Materialize the outputs of flights returned by
        :meth:`advance` — only after every dispatch of the slot is in
        flight; blocking earlier would serialize the cross-core overlap.
        Shed completions buffered during the dispatch phase ride out
        here too.  With :attr:`obs` set, it is a ``slot.retire`` span."""
        if self.obs is None:
            return self._retire(finished)
        with self.obs.span("slot.retire", model=self.model):
            return self._retire(finished)

    def _retire(self, finished: list["_Flight"]) -> list[Completion]:
        out = self._take_shed()
        out.extend(self._finish(f.rid, f.env["out"]) for f in finished)
        return out

    # ------------------------------------------------------------------
    def _extra_stats(self, metrics: Metrics) -> dict:
        return {"engine": "dualcore", "slots": self._slot,
                "capacity": self.capacity,
                "exec_groups": self.capacity,
                "completed": metrics.completed,
                "queued": len(self._pending),
                "in_flight": len(self._flight),
                "fps": metrics.requests_per_s()}


def stream_images(runner: "DualCoreRunner", images, *,
                  policy: AdmissionPolicy | None = None,
                  max_queue: int | None = None,
                  record: list | None = None) -> ServeResult:
    """Serve a ready list of images through a fresh engine (the engine-API
    equivalent of the old ``run_pipelined`` call shape: everything arrives
    at slot 0, the admission loop staggers entry one slot apart)."""
    eng = DualCoreEngine(runner, policy=policy, max_queue=max_queue,
                         record=record)
    for x in images:
        eng.submit(x)
    return eng.drain()
