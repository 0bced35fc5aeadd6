"""Several networks behind one fleet: the objects ``serve fleet`` builds.

``build_cnn_fleet`` over a ``DevicePool`` of the cell's devices split at
``theta``: one ``DualCoreRunner`` and ``DualCoreEngine`` per model under the
configuration's scheme and fuse mode, with the seed's weights, behind one
``FleetEngine`` with the configuration's policy, weights, co-dispatch and
burst.  The harness drives ``FleetEngine.step`` whole; the host time of a
slot's dispatch phase (policy, lowering and the RUNs) is the step's time
less the FREE instructions' time, read from the executor's records;
``record``, shared by every member, counts the exec-group dispatches.
"""
from __future__ import annotations

import time


class System:
    """The serving surface the harness drives."""

    def __init__(self, config: dict, params: dict, devices, span):
        from repro.fleet import DevicePool, build_cnn_fleet, make_policy

        fleet = config["fleet"]
        self.record: list = []
        self.engine, _ = build_cnn_fleet(
            list(config["models"]),
            pool=DevicePool(devices, theta=config["theta"]),
            scheme=config["scheme"], fuse=config["fuse"],
            policy=make_policy(fleet["policy"]), weights=fleet["weights"],
            co_dispatch=fleet["co_dispatch"], burst=fleet["burst"],
            params=params, record=self.record)
        self.runners = {m.name: m.engine.runner
                        for m in self.engine.members}
        self.span = span
        self.host_advance_s = 0.0

    @property
    def has_work(self) -> bool:
        """True while anything is queued or in flight."""
        return self.engine.has_work

    @property
    def dispatches(self) -> int:
        """Exec-group dispatches so far, over every member."""
        return len(self.record)

    def submit(self, payload, model: str) -> int:
        """Route one request to its model's member; returns its id."""
        from repro.serving import Request

        return self.engine.submit(Request(payload, model=model)).rid

    def step(self) -> list:
        """One fleet slot: every member's dispatches, then what finished
        materialized."""
        from repro.fleet.instructions import Free

        records = self.engine.executor.records
        first = len(records)
        with self.span("engine.step"):
            t0 = time.perf_counter()
            done = self.engine.step()
            slot_s = time.perf_counter() - t0
        free_s = sum(r.t1 - r.t0 for r in records[first:]
                     if isinstance(r.instr, Free))
        self.host_advance_s += slot_s - free_s
        return done

    def warm(self, payload) -> None:
        """Compile every exec group of every model at this payload's shape,
        then fill each member's pipeline once through the fleet."""
        for r in self.runners.values():
            r.run_sequential([payload])
        for m in self.engine.members:
            for _ in range(m.engine.capacity):
                self.submit(payload, m.name)
        while self.has_work:
            self.step()


def build(config: dict, params: dict, devices, span) -> System:
    """The system for ``config`` on ``devices``."""
    return System(config, params, devices, span)
