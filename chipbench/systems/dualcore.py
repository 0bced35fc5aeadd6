"""One network on the dual-core runtime: the objects ``serve cnn`` builds.

A ``DualCoreRunner`` over the configuration's scheme and fuse mode, on the
cell's devices split at ``theta``, behind one ``DualCoreEngine``.  The
harness calls ``advance`` and ``retire`` apart, so the host time of the
dispatch phase is measured on its own; ``record`` counts the exec-group
dispatches.
"""
from __future__ import annotations

import time


def schedule(config: dict, model: str):
    """The model's dual-core schedule under the configuration's scheme."""
    from repro.core.arch import DUAL_BASELINE, BoardModel
    from repro.core.scheduler import build_schedule
    from repro.models.zoo import get_graph

    return build_schedule(get_graph(model), DUAL_BASELINE, BoardModel(),
                          config["scheme"])


def runner(config: dict, model: str, params: dict, devices):
    """A ``DualCoreRunner`` as ``serve cnn`` builds it, on ``devices``."""
    from repro.dualcore.runtime import DualCoreRunner

    return DualCoreRunner(model, params, schedule(config, model),
                          devices=devices, theta=config["theta"],
                          fuse=config["fuse"])


class System:
    """The serving surface the harness drives."""

    def __init__(self, config: dict, params: dict, devices, span):
        from repro.serving import DualCoreEngine

        (model,) = config["models"]
        self.model = model
        self.runners = {model: runner(config, model, params[model],
                                      devices)}
        self.record: list = []
        self.engine = DualCoreEngine(self.runners[model],
                                     record=self.record)
        self.span = span
        self.host_advance_s = 0.0

    @property
    def has_work(self) -> bool:
        """True while anything is queued or in flight."""
        return self.engine.has_work

    @property
    def dispatches(self) -> int:
        """Exec-group dispatches so far."""
        return len(self.record)

    def submit(self, payload, model: str) -> int:
        """Enqueue one request; returns its id."""
        from repro.serving import Request

        return self.engine.submit(Request(payload, model=model)).rid

    def step(self) -> list:
        """One engine slot: dispatch, then materialize what finished."""
        with self.span("engine.advance"):
            t0 = time.perf_counter()
            finished = self.engine.advance()
            self.host_advance_s += time.perf_counter() - t0
        with self.span("engine.retire"):
            return self.engine.retire(finished)

    def warm(self, payload) -> None:
        """Compile every exec group at this payload's shape, then fill the
        pipeline once through the engine."""
        r = self.runners[self.model]
        r.run_sequential([payload])
        for _ in range(self.engine.capacity):
            self.submit(payload, self.model)
        while self.has_work:
            self.step()


def build(config: dict, params: dict, devices, span) -> System:
    """The system for ``config`` on ``devices``."""
    return System(config, params, devices, span)
