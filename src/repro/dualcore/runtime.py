"""Pipelined dual-core CNN runtime: execute a Schedule for real (Fig.4b).

``core/scheduler.py`` builds the alternating c/p group chain and predicts the
two-batch latency T_b2; this module is the missing execution half.  The
device pool splits into a c-submesh and a p-submesh (``dualmesh.partition``,
the Eq.10 theta split); each schedule group compiles to one jitted step
placed on its core's submesh (c-groups dispatch the 1x1 GEMM kernel and
XLA's convolution, p-groups the depthwise / fused-block kernels); and N
input images stream through the group chain with the paper's one-slot
offset, so stream i runs group k while stream i+1 runs group k-1 on the
other core.  JAX
dispatch is asynchronous: both group calls of a slot are in flight together
and the per-submesh execution queues realise the overlap.

Mapping a :class:`~repro.core.scheduler.Schedule` (layer-level) onto an
executable step program (``dualcore.program``) happens in
:func:`build_exec_plan`: each step is assigned the core where the schedule
put the dominant share of its cycles, consecutive same-core steps merge into
exec groups (on the Pallas path their dw->pw chains then fuse within the
group), and the merged chain is itself re-expressed as a ``Schedule``
(``plan.exec_schedule``) so T_b2 / the instruction-level simulator stay
directly comparable with what actually runs.
"""
from __future__ import annotations

import dataclasses
import time

import jax
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.core.arch import BoardModel, DualCoreConfig
from repro.core.graph import LayerGraph
from repro.core.latency import layer_latency
from repro.core.scheduler import Group, Schedule
from repro.dualcore.program import (Env, Params, Program, Step,
                                    build_program, regroup_fused)
from repro.dualmesh.partition import DualMesh, split_mesh


@dataclasses.dataclass
class ExecGroup:
    """One pipeline stage: consecutive same-core steps."""

    core: str                    # 'c' | 'p'
    steps: list[Step]

    @property
    def layers(self) -> list[str]:
        return [n for s in self.steps for n in s.layers]


@dataclasses.dataclass
class ExecPlan:
    """Executable partition of a program + its analytical twin."""

    groups: list[ExecGroup]
    exec_schedule: Schedule      # the merged chain as a Schedule (T_b2 etc.)
    live_after: list[set[str]]   # env keys that must survive each boundary


def _layer_core_map(schedule: Schedule) -> dict[str, tuple[str, int]]:
    """Base layer name -> (core, height); the tallest split of a
    load-balanced layer wins (it carries the dominant share of the work)."""
    out: dict[str, tuple[str, int]] = {}
    for g in schedule.groups:
        for l in g.layers:
            base = l.name.split(".")[0]
            cur = out.get(base)
            if cur is None or l.H > cur[1]:
                out[base] = (g.core, l.H)
    return out


def _step_core(step: Step, lmap: dict[str, tuple[str, int]],
               graph: LayerGraph, cfg: DualCoreConfig,
               board: BoardModel) -> str:
    """Core carrying the dominant share of the step's cycles.  A fused step
    whose layers the schedule spread across both cores must still run on
    one device — the latency-weighted majority decides."""
    weight = {"c": 0, "p": 0}
    for name in step.layers:
        core = lmap[name][0]
        lat = layer_latency(graph.layer(name), cfg.core(core),
                            board).t_layer
        weight[core] += lat
    return "c" if weight["c"] >= weight["p"] else "p"


def build_exec_plan(program: Program, schedule: Schedule) -> ExecPlan:
    """Partition ``program`` into alternating-core exec groups per the
    schedule's allocation.  On the Pallas path the per-layer steps of each
    group are then fused (dw->pw chains the schedule kept on one core
    become single fused pallas_calls, :func:`regroup_fused`)."""
    graph = program.graph
    lmap = _layer_core_map(schedule)
    missing = [n for s in program.steps for n in s.layers if n not in lmap]
    if missing:
        raise ValueError(f"schedule does not cover layers {missing[:4]}; "
                         f"was it built from graph {graph.name!r}?")
    cores = [_step_core(s, lmap, graph, schedule.cfg, schedule.board)
             for s in program.steps]
    # merge consecutive same-core steps
    parts: list[list[Step]] = []
    part_cores: list[str] = []
    for step, core in zip(program.steps, cores):
        if part_cores and part_cores[-1] == core:
            parts[-1].append(step)
        else:
            parts.append([step])
            part_cores.append(core)
    parts = regroup_fused(program, parts)
    groups = [ExecGroup(core=c, steps=p)
              for c, p in zip(part_cores, parts)]
    exec_schedule = Schedule(
        groups=[Group(g.core, [graph.layer(n) for n in g.layers])
                for g in groups],
        cfg=schedule.cfg, board=schedule.board,
        scheme=schedule.scheme + "+exec")
    # liveness: buffers read after each boundary before being rewritten
    # (plus the final output) — the env a group must hand to the next
    live_after: list[set[str]] = []
    live = {"out"}
    for g in reversed(groups):
        live_after.append(set(live))
        for s in reversed(g.steps):
            live -= set(s.writes)
            live |= set(s.reads)
    live_after.reverse()
    return ExecPlan(groups=groups, exec_schedule=exec_schedule,
                    live_after=live_after)


# --------------------------------------------------------------------------
# runner
# --------------------------------------------------------------------------
@dataclasses.dataclass
class GroupHandle:
    """Executable handle on one exec group: what a RUN instruction needs
    to advance a stream one stage — the group's jitted fn bound to its
    core's resident params, with the cross-core env hop applied when the
    caller says which core the env currently sits on.  Handles stay valid
    across :meth:`DualCoreRunner.relocate` (they close over the runner,
    not over device buffers)."""

    runner: "DualCoreRunner"
    index: int
    core: str

    def __call__(self, env: Env, *, prev_core: str | None = None) -> Env:
        r = self.runner
        if prev_core is not None and prev_core != self.core:
            env = r._place(env, self.core)
        return r._fns[self.index](r._params[self.core], env)


class DualCoreRunner:
    """Executes one CNN's schedule on the c/p submeshes, images pipelined
    with the one-slot offset of Fig.4b.

    The per-layer program is partitioned into exec groups, and on the
    Pallas path dw->pw chains are re-fused *within* each group — fusion
    never crosses a core boundary, so the schedule's allocation is
    honoured exactly.  ``use_pallas=False`` runs XLA's reference ops, with
    every layer its own step.  Each group is one jitted program; on TPU
    and GPU every group but the first donates its env.

    ``fuse`` names that within-group fusion and has one legal value,
    ``"group"``; any other raises ``ValueError``.  It stays only because
    the benchmark's configurations (``chipbench/configs``) still pass it.
    """

    def __init__(self, graph: LayerGraph | str, params: Params,
                 schedule: Schedule, *, devices=None, theta: float = 0.5,
                 use_pallas: bool = True, fuse: str = "group"):
        if fuse != "group":
            raise ValueError(f"fuse={fuse!r}: exec groups fuse within a "
                             f"core group only (fuse='group')")
        self.program = build_program(graph, use_pallas=use_pallas)
        self.graph = self.program.graph
        self.schedule = schedule
        self.plan = build_exec_plan(self.program, schedule)
        self.groups = self.plan.groups
        # ``devices`` may be an already-split DualMesh — a fleet pool
        # leases one split to every member so they share the submeshes
        self.dual: DualMesh = (devices if isinstance(devices, DualMesh)
                               else split_mesh(devices, theta))
        self._distinct = self.dual.c_mesh is not self.dual.p_mesh
        self._shard = {"c": NamedSharding(self.dual.c_mesh, P()),
                       "p": NamedSharding(self.dual.p_mesh, P())}
        # each core gets exactly the params its groups consume
        self._params = {
            core: jax.device_put(
                {n: params[n] for g in self.groups if g.core == core
                 for n in g.layers},
                self._shard[core])
            for core in ("c", "p")}
        # donation is a no-op on CPU backends
        self._donate = jax.default_backend() in ("tpu", "gpu")
        self._fns = self._compile_all()

    def _compile_all(self) -> list:
        # group 0 must not donate: its env holds the caller's image array,
        # which re-runs (timed reps, warm-up + measure) reuse
        return [self._compile(i, self._donate and i > 0)
                for i in range(len(self.groups))]

    def _compile(self, gi: int, donate: bool):
        group = self.groups[gi]
        live = self.plan.live_after[gi]

        def group_fn(params: Params, env: Env) -> Env:
            env = dict(env)
            for s in group.steps:
                s.fn(params, env, None)
            return {k: v for k, v in env.items() if k in live}

        # a stable name per exec group: the device trace shows the
        # program as ``jit_dualcore_g07_p``
        group_fn.__name__ = group_fn.__qualname__ = \
            f"dualcore_g{gi:02d}_{group.core}"
        fn = group_fn
        mesh = self._shard[group.core].mesh
        if mesh.size > 1:
            # the TPU compiler cannot partition a Pallas kernel: on a
            # multi-chip submesh every chip runs the whole group on its
            # replica of params and env (P(): replicated, as placed)
            fn = jax.shard_map(group_fn, mesh=mesh, in_specs=(P(), P()),
                               out_specs=P(), check_vma=False)
        if donate:                   # inter-group buffer donation: the env
            #                          flows linearly through the chain
            return jax.jit(fn, donate_argnums=(1,))
        return jax.jit(fn)

    def _place(self, env: Env, core: str) -> Env:
        if not self._distinct:
            return env
        return jax.device_put(env, self._shard[core])

    # ------------------------------------------------------------------
    # executor-facing surface: what a RUN instruction needs
    # ------------------------------------------------------------------
    @property
    def handles(self) -> list[GroupHandle]:
        """One :class:`GroupHandle` per exec group, in chain order."""
        return [GroupHandle(runner=self, index=i, core=g.core)
                for i, g in enumerate(self.groups)]

    def place_input(self, x) -> Env:
        """Wrap a raw input into the env of a new stream, placed on the
        first group's core — the admission half of a RUN."""
        return self._place({"h": x}, self.groups[0].core)

    def relocate(self, dual: DualMesh) -> None:
        """Move this runner onto a re-split pool (the runner-side half of
        a REBALANCE): rebuild the shardings for the new c/p submeshes,
        re-place the resident params and re-wrap the group fns for the
        new submeshes (jit is lazy: recompilation happens only for groups
        that actually run again)."""
        self.dual = dual
        self._distinct = dual.c_mesh is not dual.p_mesh
        self._shard = {"c": NamedSharding(dual.c_mesh, P()),
                       "p": NamedSharding(dual.p_mesh, P())}
        self._params = {core: jax.device_put(self._params[core],
                                             self._shard[core])
                        for core in ("c", "p")}
        self._fns = self._compile_all()

    # ------------------------------------------------------------------
    def run_pipelined(self, images, record: list | None = None):
        """Stream every image through the exec-group chain, offset by one
        slot: at slot k, stream i executes group k-i (different cores for
        neighbouring streams by the alternation invariant).  All calls of a
        slot are dispatched before any is awaited (async overlap).

        Compatibility shim: the slot loop now lives in the streaming engine
        (``repro.serving.DualCoreEngine``) whose online admission refills
        drained slots from a live request queue — this method submits a
        ready image list and drains, which reproduces the original static
        dispatch schedule exactly.

        ``record``, when given, receives ``(slot, stream, group, core)``
        tuples in dispatch order — the execution trace the tests check
        against the analytical slot offsets.
        """
        from repro.serving.cnn import stream_images

        return stream_images(self, images, record=record).outputs

    def run_sequential(self, images):
        """Strictly serialized baseline: one image at a time through the
        whole chain, awaiting completion before the next image starts (only
        one core active at any moment — the denominator of the pipeline
        speedup)."""
        outs = []
        for x in images:
            env = self._place({"h": x}, self.groups[0].core)
            for g in range(len(self.groups)):
                if g > 0 and self.groups[g].core != self.groups[g - 1].core:
                    env = self._place(env, self.groups[g].core)
                env = self._fns[g](self._params[self.groups[g].core], env)
            jax.block_until_ready(env["out"])
            outs.append(env["out"])
        return outs

    def trace_groups(self, x) -> list[tuple]:
        """Run one input through the chain as :meth:`run_sequential`
        does, returning each exec group's compiled program (what a RUN
        dispatches; ``.as_text()`` is the HLO the device runs) with the
        env it produced — how a caller checks which kernels a group runs
        and which devices hold its output."""
        out = []
        env = self.place_input(x)
        for g, (fn, h) in enumerate(zip(self._fns, self.handles)):
            if g > 0 and h.core != self.groups[g - 1].core:
                env = self._place(env, h.core)
            params = self._params[h.core]
            compiled = fn.lower(params, env).compile()
            env = fn(params, env)
            out.append((compiled, env))
        return out

    # ------------------------------------------------------------------
    def timed(self, images, mode: str = "pipelined",
              reps: int = 1) -> tuple[list, float]:
        """Best-of-``reps`` wall-clock of a full run.  With reps > 1 the
        best rep excludes jit compilation (it lands in the first rep)."""
        run = (self.run_pipelined if mode == "pipelined"
               else self.run_sequential)
        outs, best = None, float("inf")
        for _ in range(max(1, reps)):
            t0 = time.perf_counter()
            outs = run(images)
            best = min(best, time.perf_counter() - t0)
        return outs, best
