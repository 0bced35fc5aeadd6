"""Serving launcher: both runtimes behind the shared streaming engine API.

Two subcommands, one engine interface (``repro.serving.Engine`` —
submit/step/drain with per-request latency metrics, bounded-queue
backpressure, and a pluggable admission policy):

LM (dual-mesh N-stream continuous batching):

  PYTHONPATH=src python -m repro.launch.serve lm --arch qwen2_0_5b --smoke \
      --requests 8 --prompt-len 16 --gen 8 [--streams 8] \
      [--theta 0.5 | --search] [--arrival-rate 1.0] [--max-queue 64]

  Requests are submitted to a ``DualMeshEngine`` on a fixed Poisson-ish
  arrival trace (``--arrival-rate``, in requests per scheduler slot;
  ``inf`` submits everything up front): chunked prefills on the c-submesh
  overlap fused decode batches on the p-submesh, the decode fusion width
  defaults to the makespan-aware admission plan (``--group-size``
  overrides), and with ``--search`` the §V-B design flow picks theta and
  the TP widths first.

CNN (dual-core pipeline with online slot-refill admission):

  PYTHONPATH=src python -m repro.launch.serve cnn mobilenet_v1 \
      --requests 4 --image-size 64 [--scheme balanced] [--no-pallas] \
      [--arrival-rate 1.0] [--max-queue 64]

  Builds the dual-core schedule, splits the local devices into c/p
  submeshes, and streams the requests through a ``DualCoreEngine``: each
  scheduler slot advances every in-flight image one exec group (the
  Fig.4b one-slot offset) and refills the drained group-0 slot from the
  request queue.  ``--requests 1`` is honored as the degenerate
  single-image run (no silent workload bump).  Prints steady-state fps and
  p50/p95 request latency next to the analytical/simulated two-batch
  latency.

Fleet (several CNNs multiplexed over one device pool, DESIGN.md §10):

  PYTHONPATH=src python -m repro.launch.serve fleet \
      --models mbv1,mbv2,squeezenet --mix 0.4,0.35,0.25 --requests 9 \
      [--policy weighted_fair] [--plan] [--scheme balanced] [--no-pallas] \
      [--no-interleave] [--image-size 64] [--arrival-rate] [--max-queue] \
      [--pools 2] [--trace trace.json]

  One ``DevicePool`` leases the shared c/p split to a ``DualCoreEngine``
  per model; requests tagged per the traffic mix stream through the
  ``FleetEngine``, whose scheduling policy picks which member's exec
  group dispatches first each slot, with up to ``--co-dispatch`` further
  members following core-complementary-first per the latency model —
  conv-heavy and dw-heavy groups from different networks overlap on the
  two submeshes.  ``--plan`` first
  runs the §V-B co-scheduling search over the mix and serves under the
  planned PE config, printing the predicted Table-VII-style throughput
  next to the measured one.  Prints aggregate fps and per-model p50/p95.

  ``--pools N`` stands up N process-local pools (one fleet each) behind a
  ``MultiPoolRouter`` — requests place onto the least outstanding pool,
  and the executed per-pool instruction streams interleave by router
  sequence number.  ``--trace PATH`` exports the executed stream as
  Chrome-tracing JSON (one track per submesh per pool).

  ``--slo-ms X`` serves every member under a ``ShedPolicy`` with an
  ``X``-millisecond wall-clock deadline per request — past-deadline queue
  entries are shed instead of served, and the summary reports goodput
  (served AND within SLO) next to raw throughput.  ``--faults PLAN.json``
  arms a seeded ``repro.fleet.FaultPlan`` on the executors: deterministic
  injected RUN errors / pool crashes / dropped SENDs / latency skew,
  retried and recovered per DESIGN.md §12 (crash recovery needs
  ``--pools >= 2``).  A malformed plan or a non-positive SLO is a usage
  error (exit 2).

  ``--adapt`` attaches a closed-loop controller (DESIGN.md §13,
  ``repro.fleet.ControlLoop``) to each pool's fleet: every
  ``--control-interval`` slots it observes the sliding completion window
  and injects SET_PARAM / REBALANCE instructions — re-weighting member
  shares toward the observed arrival mix, narrowing/widening retunable
  engines' fusion width on p95 SLO breaches (needs ``--slo-ms``), and
  re-leasing theta on sustained shedding.  The summary reports the
  decisions taken; the injected instructions land in the recorded
  stream, so ``--trace`` shows them on the control track and the run
  replays bitwise without the controller.
"""
from __future__ import annotations

import argparse
import dataclasses
import sys
from typing import Any

import jax
import numpy as np

from repro.configs.registry import ARCH_IDS, get_arch, get_smoke
from repro.dualmesh import (DualMeshRunner, TpuModel, plan_admission,
                            request_stages, search, split_mesh)
from repro.launch.compile_cache import enable_compile_cache
from repro.lm.model import init_params
from repro.serving import (DualCoreEngine, DualMeshEngine, Request,
                           ServeResult, poisson_arrivals, replay)

CNN_MODELS = ("mobilenet_v1", "mobilenet_v2", "squeezenet")
CNN_SCHEMES = ("layer_type", "greedy", "round_robin", "balanced", "best")
MODEL_ALIASES = {"mbv1": "mobilenet_v1", "mbv2": "mobilenet_v2",
                 "sqz": "squeezenet",
                 **{m: m for m in CNN_MODELS}}


@dataclasses.dataclass
class Served:
    """What one ``serve`` run handed back: the requests it submitted, the
    engine's result (completions and outputs in submission order), the
    dual-core runner of each CNN member, and the CLI exit code."""

    requests: list[Request]
    result: ServeResult | None
    runners: dict[str, Any] = dataclasses.field(default_factory=dict)
    rc: int = 0


def _print_devices(use_pallas: bool = True) -> None:
    """Header of every in-process run: platform, device kind and count as
    JAX reports them, and whether the Pallas kernels compile or run in
    interpret mode — so a CPU run can never read as a chip run."""
    from repro.kernels.util import default_interpret

    d = jax.devices()
    kernels = ("xla (--no-pallas)" if not use_pallas else
               "interpret" if default_interpret() else "compiled")
    print(f"[serve] devices: platform={d[0].platform} "
          f"kind={d[0].device_kind} count={len(d)} kernels={kernels}")


def images(n: int, batch: int, size: int,
           seed: int = 0) -> list[np.ndarray]:
    """``n`` seeded (batch, size, size, 3) f32 request payloads, made with
    numpy: building them never initialises a JAX backend, so a parent
    that spawns worker processes leaves every device to them."""
    rng = np.random.default_rng(seed)
    return [rng.standard_normal((batch, size, size, 3), dtype=np.float32)
            for _ in range(n)]


def _fail(msg: str) -> None:
    """CLI usage error: clear one-line message on stderr, exit code 2
    (argparse's convention for bad arguments) — never a raw traceback."""
    print(f"repro.launch.serve: error: {msg}", file=sys.stderr)
    raise SystemExit(2)


def _arrivals(n: int, rate: float) -> list[int]:
    """Arrival trace for n requests: Poisson-ish at ``rate`` per slot, or
    everything at slot 0 when the rate is infinite."""
    if rate == float("inf"):
        return [0] * n
    return poisson_arrivals(n, rate=rate, seed=0)


def _print_latency(metrics) -> None:
    print(f"[serve] latency: p50 {metrics.p50_ms():.1f} ms, "
          f"p95 {metrics.p95_ms():.1f} ms over "
          f"{metrics.completed} requests")


def serve_cnn(args) -> Served:
    """``cnn`` subcommand: streaming CNN serving on the c/p submeshes."""
    from repro.core.arch import BoardModel, DUAL_BASELINE
    from repro.core.scheduler import best_schedule, build_schedule
    from repro.core.simulator import simulate_dual_core
    from repro.dualcore.runtime import DualCoreRunner
    from repro.models.cnn import build_model

    board = BoardModel()
    params, _, graph = build_model(args.model)
    if args.scheme == "best":
        sched = best_schedule(graph, DUAL_BASELINE, board)
    else:
        sched = build_schedule(graph, DUAL_BASELINE, board, args.scheme)

    _print_devices(not args.no_pallas)
    runner = DualCoreRunner(args.model, params, sched,
                            use_pallas=not args.no_pallas)
    es = runner.plan.exec_schedule
    n = args.requests
    payloads = images(n, args.batch, args.image_size)
    runner.run_sequential(payloads[:1])         # warm the per-group jits

    engine = DualCoreEngine(runner, max_queue=args.max_queue)
    requests = [Request(x) for x in payloads]
    res = replay(engine, requests, _arrivals(n, args.arrival_rate))
    _, t_seq = runner.timed(payloads, "sequential", reps=2)

    degenerate = runner.dual.c_mesh is runner.dual.p_mesh
    sim = simulate_dual_core(es)
    print(f"[serve] cnn {args.model} scheme={sched.scheme}: "
          f"{len(es.groups)} exec groups on "
          f"{runner.dual.c_chips}c+{runner.dual.p_chips}p devices"
          + (" (degenerate: both submeshes alias one device, no real "
             "overlap)" if degenerate else ""))
    print(f"[serve] model-side: T_b2={es.t_b2():,} cyc "
          f"(sim {sim.cycles_two_images:,} cyc, "
          f"{board.cycles_to_seconds(sim.cycles_two_images)*1e3:.2f} ms "
          f"@{board.freq_mhz:.0f}MHz), "
          f"pipeline speedup {2*sum(es.group_latencies)/es.t_b2():.2f}x")
    s = res.stats
    print(f"[serve] streamed {n} request(s) x batch {args.batch} @ "
          f"{args.image_size}px in {s['slots']} slots: "
          f"{s['wall_s']*1e3:.0f} ms "
          f"({n*args.batch/s['wall_s']:.2f} img/s), "
          f"sequential {t_seq*1e3:.0f} ms "
          f"({t_seq/s['wall_s']:.2f}x)")
    _print_latency(res.metrics)
    return Served(requests, res, {args.model: runner})


class _MetricsSink:
    """``--metrics PATH`` / ``--metrics-every K`` plumbing shared by the
    three fleet paths.  Without ``--metrics-every`` the final registry
    snapshot is written once (``-`` = Prometheus text on stdout, ``.json``
    = JSON, else Prometheus text).  With it, one compact
    ``{"step": s, "snapshot": ...}`` JSON line is appended every K steps
    plus a final line — a replayable time series.  While a registry
    that is written out and enabled is attached, every garbage
    collection is recorded in it (``gc_pause_seconds``)."""

    def __init__(self, args):
        self.path = getattr(args, "metrics", None)
        self.every = getattr(args, "metrics_every", None)
        self.registry = None      # set once the engine/router exists
        self._started = False
        self._untrack_gc = None

    def attach(self, registry) -> None:
        """Write ``registry``; track collections into it when enabled."""
        from repro.obs import track_gc

        self.registry = registry
        if self.path is not None and registry.enabled:
            self._untrack_gc = track_gc(registry)

    def on_step(self, step: int) -> None:
        if self.registry is None or not self.every:
            return
        if (step + 1) % self.every == 0:
            self._append(step)

    def _append(self, step: int) -> None:
        import json

        line = json.dumps({"step": step,
                           "snapshot": self.registry.snapshot()},
                          sort_keys=True)
        if self.path == "-":
            print(line)
            return
        with open(self.path, "a" if self._started else "w") as f:
            f.write(line + "\n")
        self._started = True

    def finish(self, steps: int) -> None:
        if self._untrack_gc is not None:
            self._untrack_gc()
            self._untrack_gc = None
        if self.registry is None or self.path is None:
            return
        if self.every:
            self._append(steps)
            if self.path != "-":
                print(f"[serve] appended metric snapshots every "
                      f"{self.every} step(s) to {self.path}")
            return
        from repro.obs import write_metrics

        fmt = write_metrics(self.registry, self.path)
        if self.path != "-":
            print(f"[serve] wrote {fmt} metrics to {self.path}")


def _parse_fleet_mix(args) -> dict[str, float]:
    """--models/--mix -> normalized {model: share} (aliases expanded).
    Malformed values are usage errors: message + exit 2 via :func:`_fail`,
    not a traceback."""
    names = []
    for tok in args.models.split(","):
        tok = tok.strip()
        if tok not in MODEL_ALIASES:
            _fail(f"unknown model {tok!r} in --models; one of "
                  f"{sorted(MODEL_ALIASES)}")
        names.append(MODEL_ALIASES[tok])
    if len(set(names)) != len(names):
        _fail(f"duplicate models in --models: {names}")
    if args.mix is None:
        shares = [1.0] * len(names)
    else:
        try:
            shares = [float(t) for t in args.mix.split(",")]
        except ValueError:
            _fail(f"--mix must be comma-separated numbers "
                  f"(got {args.mix!r})")
        if len(shares) != len(names):
            _fail(f"{len(names)} models in --models but {len(shares)} "
                  f"shares in --mix")
    from repro.fleet import normalize_mix

    try:
        return normalize_mix(dict(zip(names, shares)))
    except ValueError as e:
        _fail(str(e))


def _serve_fleet_workers(args, mix, build, requests, arrivals) -> Served:
    """``fleet --workers N --transport socket``: each pool is a real
    worker process (``python -m repro.fleet.worker``) hosting the same
    CNN fleet; the coordinator drives them over ``SocketTransport``
    through the standard ``MultiPoolRouter`` placement / migration /
    crash-recovery logic (DESIGN.md §14)."""
    from repro.fleet import MultiPoolRouter, RecoveryConfig
    from repro.fleet.net.coordinator import (connect, start_workers,
                                             stop_workers)
    from repro.serving import QueueFull

    kill = None
    if args.kill_worker is not None:
        pool_name, sep, at = args.kill_worker.partition("@")
        if not sep or not at.isdigit():
            _fail(f"--kill-worker wants POOL@STEP (e.g. pool1@3), got "
                  f"{args.kill_worker!r}")
        kill = (pool_name, int(at))
    pools = [f"pool{p}" for p in range(args.workers)]
    if kill is not None and kill[0] not in pools:
        _fail(f"--kill-worker pool {kill[0]!r} is not one of {pools}")

    wargs = ["--models", ",".join(mix),
             "--image-size", str(args.image_size),
             "--scheme", args.scheme, "--policy", args.policy,
             "--burst", str(args.burst)]
    if args.no_pallas:
        wargs.append("--no-pallas")
    co = 0 if args.no_interleave else args.co_dispatch
    if co is not None:
        wargs += ["--co-dispatch", str(co)]
    if args.max_queue is not None:
        wargs += ["--max-queue", str(args.max_queue)]

    recovery = RecoveryConfig()
    print(f"[serve] spawning {args.workers} worker process(es): "
          f"python -m repro.fleet.worker --pool <name> {' '.join(wargs)}")
    procs = start_workers({p: list(wargs) for p in pools})
    fleets = {}
    try:
        fleets = connect(procs, heartbeat_s=recovery.heartbeat_s)
        router = MultiPoolRouter(fleets, recovery=recovery)
        sink = _MetricsSink(args)
        sink.attach(router.obs)

        def collect_telemetry():
            for ex in router.executors.values():
                handle = getattr(ex, "_handle", None)
                if handle is not None and handle.lost is None:
                    handle.collect(ex)

        addrs = ", ".join(f"{p}={procs[p].address}" for p in pools)
        print(f"[serve] fleet {'+'.join(mix)} x {args.workers} workers "
              f"over SocketTransport ({addrs})")
        # replay()'s open loop, plus the mid-run SIGKILL hook
        order = sorted(range(len(requests)), key=lambda i: arrivals[i])
        refused, nxt, step = [], 0, 0
        while nxt < len(order) or refused or router.has_work:
            if kill is not None and step >= kill[1]:
                print(f"[serve] SIGKILL worker {kill[0]} at router "
                      f"step {step}")
                procs[kill[0]].kill()
                kill = None
            due, refused = refused, []
            while nxt < len(order) and arrivals[order[nxt]] <= step:
                due.append(order[nxt])
                nxt += 1
            for i in due:
                try:
                    router.submit(requests[i])
                except QueueFull:
                    refused.append(i)
            router.step()
            if args.metrics:
                # pull each worker's cumulative snapshot every step so a
                # SIGKILL loses at most the last unshipped window
                collect_telemetry()
                sink.on_step(step)
            step += 1
        if args.metrics:
            collect_telemetry()
        res = router.result()
        st = res.stats
        streams = {name: list(ex.records)
                   for name, ex in router.executors.items()}
        placements = list(router.placements)
        events = list(router.events)
    finally:
        stop_workers(fleets, procs)

    n = len(requests)
    sink.finish(st["steps"])
    print(f"[serve] streamed {n} request(s) over {args.workers} workers "
          f"in {st['steps']} router steps: {st['wall_s']*1e3:.0f} ms, "
          f"aggregate {st['aggregate_fps']:.2f} fps")
    for pname, pp in st["pools"].items():
        served = ", ".join(f"{m}:{c}" for m, c in pp["served"].items())
        print(f"  {pname:<8} {pp['slots']} slots "
              f"{pp['dispatches']} dispatches  served {served or '-'}")
    for name, pm in st["per_model"].items():
        print(f"  {name:<14} {pm['completed']} done  "
              f"p50 {pm['p50_ms']:.1f} ms  p95 {pm['p95_ms']:.1f} ms  "
              f"{pm['requests_per_s']:.2f} fps")
    done = len(res.completions)
    print(f"[serve] exactly-once: {done}/{n} retired, "
          f"{st['duplicates_dropped']} duplicates dropped, "
          f"{st['failed']} failed, {st['recovered']} recovered, "
          f"dead workers {st['dead'] or '-'}")
    if done != n or st["duplicates_dropped"] or st["failed"]:
        print("repro.launch.serve: error: exactly-once retirement "
              "violated", file=sys.stderr)
        return Served(requests, res, rc=1)
    if args.verify_replay:
        from repro.fleet.compiler import stream_signature

        fresh = MultiPoolRouter({p: build()[0] for p in pools})
        fresh.replay(streams, placements, requests, events)
        for p, recs in streams.items():
            if stream_signature(recs) != stream_signature(
                    fresh.executors[p].records):
                print(f"repro.launch.serve: error: replay diverged on "
                      f"{p}", file=sys.stderr)
                return Served(requests, res, rc=1)
        print(f"[serve] replay verified: "
              f"{sum(len(r) for r in streams.values())} records across "
              f"{len(streams)} pool(s) replay bitwise on fresh "
              f"in-process fleets")
    if args.trace:
        import json

        from repro.fleet.trace import chrome_trace

        doc = chrome_trace(streams)
        with open(args.trace, "w") as f:
            json.dump(doc, f)
        print(f"[serve] wrote {len(doc['traceEvents'])} trace events to "
              f"{args.trace} (open in chrome://tracing)")
    return Served(requests, res)


def serve_fleet(args) -> Served:
    """``fleet`` subcommand: multi-network serving over one device pool —
    or over ``--pools N`` process-local pools (hosts stand-in) behind a
    ``MultiPoolRouter``, each pool replaying its own compiled instruction
    stream — or over ``--workers N`` real worker processes behind
    ``--transport socket`` (DESIGN.md §14)."""
    from repro.fleet import (FaultInjector, FaultPlan, MultiPoolRouter,
                             build_cnn_fleet, make_policy, mix_schedule,
                             plan_fleet, plan_rows)
    from repro.serving import ShedPolicy

    mix = _parse_fleet_mix(args)
    if args.pools < 1:
        _fail(f"--pools must be >= 1, got {args.pools}")
    if args.workers < 0:
        _fail(f"--workers must be >= 0, got {args.workers}")
    if args.workers:
        if args.transport != "socket":
            _fail(f"--workers {args.workers} puts each pool in its own "
                  f"process; only --transport socket crosses process "
                  f"boundaries ({args.transport!r} is an in-process "
                  f"mailbox binding — use --pools for it)")
        if args.pools != 1:
            _fail("--workers and --pools are mutually exclusive: "
                  "workers are real processes, pools are process-local")
        if args.faults is not None:
            _fail("--faults is in-process fault injection; with "
                  "--workers, kill a real process instead "
                  "(--kill-worker POOL@STEP)")
        if args.adapt:
            _fail("--adapt runs a per-pool in-process controller; it is "
                  "not supported over --workers")
        if args.slo_ms is not None:
            _fail("--slo-ms attaches in-process shed policies; it is "
                  "not supported over --workers")
        if args.plan:
            _fail("--plan is not supported over --workers (each worker "
                  "builds its own fleet from the model list)")
    elif args.transport == "socket":
        _fail("--transport socket needs --workers N (worker processes "
              "to talk to)")
    elif args.transport == "file" and args.pools < 2:
        _fail("--transport file is the multi-pool spool mailbox; it "
              "needs --pools >= 2")
    if args.spool is not None and args.transport != "file":
        _fail("--spool only applies to --transport file")
    if args.kill_worker is not None and not args.workers:
        _fail("--kill-worker needs --workers")
    if args.verify_replay and not args.workers:
        _fail("--verify-replay needs --workers (the in-process paths "
              "have replay tests of their own)")
    if args.slo_ms is not None and not args.slo_ms > 0:
        _fail(f"--slo-ms must be > 0, got {args.slo_ms}")
    if args.control_interval < 1:
        _fail(f"--control-interval must be >= 1, got "
              f"{args.control_interval}")
    if args.metrics_every is not None and not args.metrics:
        _fail("--metrics-every needs --metrics PATH (where would the "
              "snapshots go?)")
    if args.metrics_every is not None and args.metrics_every < 1:
        _fail(f"--metrics-every must be >= 1, got {args.metrics_every}")
    fault_plan = None
    if args.faults is not None:
        try:
            fault_plan = FaultPlan.load(args.faults)
        except (OSError, ValueError) as e:
            _fail(f"--faults {args.faults!r}: {e}")
    admission = None
    if args.slo_ms is not None:
        admission = {m: ShedPolicy(slo_s=args.slo_ms / 1e3, clock="wall")
                     for m in mix}
    plan = None
    if args.plan:
        plan = plan_fleet(mix, max_evals=args.plan_evals)
        print(f"[serve] fleet plan: config={plan.config} "
              f"theta={plan.theta:.2f} predicted aggregate "
              f"{plan.aggregate_fps:.1f} fps")

    def build():
        return build_cnn_fleet(
            list(mix), plan=plan, scheme=args.scheme,
            use_pallas=not args.no_pallas, policy=make_policy(args.policy),
            weights=mix, admission=admission, max_queue=args.max_queue,
            co_dispatch=0 if args.no_interleave else args.co_dispatch,
            burst=args.burst)

    n = args.requests
    tags = mix_schedule(mix, n)
    payloads = images(n, args.batch, args.image_size)
    requests = [Request(x, model=t) for x, t in zip(payloads, tags)]
    arrivals = _arrivals(n, args.arrival_rate)

    if args.workers:
        return _serve_fleet_workers(args, mix, build, requests, arrivals)
    _print_devices(not args.no_pallas)

    sink = _MetricsSink(args)

    def attach_controller(fleet_engine):
        if not args.adapt:
            return None
        from repro.fleet import ControlLoop

        return ControlLoop(fleet_engine, interval=args.control_interval,
                           slo_ms=args.slo_ms, plan_evals=args.plan_evals)

    if args.pools == 1:
        engine, pool = build()
        controller = attach_controller(engine)
        if fault_plan is not None:
            engine.executor.injector = FaultInjector(fault_plan)
        for m in engine.members:         # warm each member's per-group jits
            # any image warms a member — a skewed mix or --requests <
            # number of models can leave a member with no tagged request
            m.engine.runner.run_sequential(payloads[:1])
        s = pool.stats()
        print(f"[serve] fleet {'+'.join(mix)} policy={args.policy} "
              f"({s['c_chips']}c+{s['p_chips']}p devices"
              + (", degenerate: both submeshes alias one device"
                 if s["degenerate"] else "") + ")")
        sink.attach(engine.executor.obs)
        res = replay(engine, requests, arrivals, on_step=sink.on_step)
        st = res.stats
        print(f"[serve] streamed {n} request(s) in {st['slots']} fleet "
              f"slots ({st['dispatches']} member dispatches): "
              f"{st['wall_s']*1e3:.0f} ms, aggregate "
              f"{st['aggregate_fps']:.2f} fps")
        for name, pm in st["per_model"].items():
            d = st["per_member"][name]
            print(f"  {name:<14} {pm['completed']} done "
                  f"({d['dispatches']} dispatches)  "
                  f"p50 {pm['p50_ms']:.1f} ms  p95 {pm['p95_ms']:.1f} ms  "
                  f"{pm['requests_per_s']:.2f} fps")
        if plan is not None:
            measured = {m: v["requests_per_s"]
                        for m, v in st["per_model"].items()}
            print("[serve] predicted (Table-VII-style) vs measured fps:")
            for name, share, fps, pred, meas in plan_rows(
                    plan, measured, st["aggregate_fps"]):
                print(f"  {name:<14} share={share:.2f} "
                      f"model-side={fps:8.1f} predicted={pred:8.1f} "
                      f"measured="
                      + (f"{meas:8.2f}" if meas is not None else "     n/a"))
        if args.slo_ms is not None or fault_plan is not None:
            print(f"[serve] goodput {st['goodput_fps']:.2f} fps "
                  f"(shed {res.metrics.count('shed')}, "
                  f"retries {engine.executor.retries})")
        if controller is not None:
            cs = controller.stats()
            weights = ", ".join(f"{m.name}={m.weight:.2f}"
                                for m in engine.members)
            print(f"[serve] control: {cs['observations']} observations, "
                  f"{cs['decisions']} decisions {cs['by_kind'] or '{}'}; "
                  f"final weights {weights}")
        streams = {"pool0": engine.stream}
        steps_done = st["slots"]
        runners = {m.name: m.engine.runner for m in engine.members}
    else:
        fleets = {f"pool{p}": build()[0] for p in range(args.pools)}
        controllers = {name: attach_controller(fl)
                       for name, fl in fleets.items()} if args.adapt else {}
        transport = None
        if args.transport == "file":
            import tempfile

            from repro.fleet.net import FileTransport

            spool = args.spool or tempfile.mkdtemp(prefix="repro_spool_")
            transport = FileTransport(spool)
            print(f"[serve] inter-pool migration spooled through "
                  f"{spool} (FileTransport)")
        router = MultiPoolRouter(
            fleets, injector=(FaultInjector(fault_plan)
                              if fault_plan is not None else None),
            transport=transport)
        for fleet_engine in fleets.values():
            for m in fleet_engine.members:
                m.engine.runner.run_sequential(payloads[:1])
        print(f"[serve] fleet {'+'.join(mix)} x {args.pools} pools "
              f"policy={args.policy} (requests placed on the least "
              f"outstanding pool)")
        sink.attach(router.obs)
        res = replay(router, requests, arrivals, on_step=sink.on_step)
        st = res.stats
        print(f"[serve] streamed {n} request(s) over {args.pools} pools "
              f"in {st['steps']} router steps: {st['wall_s']*1e3:.0f} ms, "
              f"aggregate {st['aggregate_fps']:.2f} fps")
        for pname, pp in st["pools"].items():
            served = ", ".join(f"{m}:{c}" for m, c in pp["served"].items())
            print(f"  {pname:<8} {pp['slots']} slots "
                  f"{pp['dispatches']} dispatches  served {served or '-'}")
        for name, pm in st["per_model"].items():
            print(f"  {name:<14} {pm['completed']} done  "
                  f"p50 {pm['p50_ms']:.1f} ms  p95 {pm['p95_ms']:.1f} ms  "
                  f"{pm['requests_per_s']:.2f} fps")
        if args.slo_ms is not None or fault_plan is not None:
            print(f"[serve] goodput {st['goodput_fps']:.2f} fps "
                  f"(shed {st['shed']}, failed {st['failed']}, "
                  f"recovered {st['recovered']}, dead pools "
                  f"{st['dead'] or '-'}, duplicates dropped "
                  f"{st['duplicates_dropped']})")
        for pname, ctl in controllers.items():
            if ctl is not None:
                cs = ctl.stats()
                print(f"[serve] control {pname}: {cs['observations']} "
                      f"observations, {cs['decisions']} decisions "
                      f"{cs['by_kind'] or '{}'}")
        streams = {name: ex.records
                   for name, ex in router.executors.items()}
        steps_done = st["steps"]
        runners = {}
    sink.finish(steps_done)
    if args.trace:
        import json

        from repro.fleet.trace import chrome_trace

        doc = chrome_trace(streams)
        with open(args.trace, "w") as f:
            json.dump(doc, f)
        print(f"[serve] wrote {len(doc['traceEvents'])} trace events to "
              f"{args.trace} (open in chrome://tracing)")
    return Served(requests, res, runners)


def serve_lm(args) -> Served:
    """``lm`` subcommand: dual-mesh continuous batching."""
    cfg = get_smoke(args.arch) if args.smoke else get_arch(args.arch)
    n_streams = args.streams or max(1, args.requests)
    theta = args.theta
    if args.search:
        stages = request_stages(
            cfg, [(args.batch, args.prompt_len, args.gen)])
        res = search(stages, cfg, n_devices=args.plan_chips, max_evals=10,
                     n_streams=n_streams)
        theta = res.theta
        print(f"[serve] design flow: theta={theta:.2f} "
              f"tp=({res.tp_c},{res.tp_p}) n_streams={n_streams} "
              f"planned makespan={res.makespan*1e3:.1f} ms "
              f"tokens/s={res.tokens_per_s:.0f} on {args.plan_chips} chips")

    _print_devices(use_pallas=False)
    params = init_params(cfg, jax.random.PRNGKey(0))
    dual = split_mesh(jax.devices(), theta)
    plan = plan_admission(cfg, dual, TpuModel(), args.batch,
                          args.prompt_len, args.gen, n_streams,
                          max_group=args.group_size)
    group_size = args.group_size or plan.group_size
    print(f"[serve] admission plan: group_size={group_size} "
          f"(est {plan.est_tokens_per_s:.0f} tok/s model-side)")

    runner = DualMeshRunner(cfg, params, dual,
                            max_len=args.prompt_len + args.gen + 8)
    n = max(1, args.requests)
    keys = jax.random.split(jax.random.PRNGKey(1), n)
    prompts = [jax.random.randint(k, (args.batch, args.prompt_len), 0,
                                  cfg.vocab) for k in keys]
    engine = DualMeshEngine(runner, group_size=group_size,
                            prefill_chunk=args.prefill_chunk,
                            max_queue=args.max_queue)
    requests = [Request(p, gen_steps=args.gen) for p in prompts]
    res = replay(engine, requests, _arrivals(n, args.arrival_rate))
    s = res.stats
    print(f"[serve] {n} requests x {args.batch} batch: "
          f"{s['wall_s']*1e3:.0f} ms ({s['tokens_per_s']:.0f} tok/s, "
          f"{s['total_tokens']} tokens, fused decode batches "
          f"{s['fused_sizes']}, on {len(jax.devices())} local device(s))")
    _print_latency(res.metrics)
    for kind, mesh_name, t in res.trace:
        print(f"  {kind:<8} on {mesh_name}-mesh  {t*1e3:7.1f} ms")
    return Served(requests, res)


def _add_common(ap: argparse.ArgumentParser) -> None:
    ap.add_argument("--requests", type=int, default=2,
                    help="number of requests to serve (>= 1)")
    ap.add_argument("--batch", type=int, default=2)
    ap.add_argument("--arrival-rate", type=float, default=float("inf"),
                    help="Poisson-ish arrivals per scheduler slot "
                         "(default inf: everything at slot 0)")
    ap.add_argument("--max-queue", type=int, default=None,
                    help="bounded request queue (backpressure beyond it)")


def parse_args(argv=None) -> argparse.Namespace:
    """The ``serve`` command line, validated (usage errors exit 2)."""
    ap = argparse.ArgumentParser(
        prog="repro.launch.serve",
        description="Serve the LM or the CNN through the shared "
                    "repro.serving engine API.")
    sub = ap.add_subparsers(dest="cmd", required=True)

    lm = sub.add_parser("lm", help="dual-mesh LM continuous batching")
    lm.add_argument("--arch", choices=ARCH_IDS, required=True)
    lm.add_argument("--smoke", action="store_true")
    _add_common(lm)
    lm.add_argument("--prompt-len", type=int, default=16)
    lm.add_argument("--gen", type=int, default=8)
    lm.add_argument("--theta", type=float, default=0.5)
    lm.add_argument("--streams", type=int, default=None,
                    help="concurrent streams the planner optimizes for "
                         "(default: --requests)")
    lm.add_argument("--group-size", type=int, default=None,
                    help="decode fusion width (default: makespan-aware)")
    lm.add_argument("--prefill-chunk", type=int, default=None,
                    help="chunked-prefill slice in tokens")
    lm.add_argument("--search", action="store_true",
                    help="run the design-flow search for theta/tp first")
    lm.add_argument("--plan-chips", type=int, default=256,
                    help="pod size for the planning search")
    lm.set_defaults(func=serve_lm)

    cnn = sub.add_parser("cnn", help="dual-core CNN streaming pipeline")
    cnn.add_argument("model", choices=CNN_MODELS)
    cnn.add_argument("--scheme", choices=CNN_SCHEMES, default="balanced",
                     help="dual-core allocation scheme")
    cnn.add_argument("--image-size", type=int, default=64,
                     help="input H=W (224 = paper size)")
    cnn.add_argument("--no-pallas", action="store_true",
                     help="use the XLA reference ops")
    _add_common(cnn)
    cnn.set_defaults(func=serve_cnn)

    from repro.fleet import POLICY_NAMES

    fleet = sub.add_parser(
        "fleet", help="multi-CNN fleet over one device pool")
    fleet.add_argument("--models", default="mbv1,mbv2,squeezenet",
                       help="comma-separated member models "
                            "(aliases: mbv1, mbv2, sqz)")
    fleet.add_argument("--mix", default=None,
                       help="comma-separated qps shares aligned with "
                            "--models (default: equal)")
    fleet.add_argument("--policy", choices=POLICY_NAMES,
                       default="weighted_fair",
                       help="cross-engine step scheduling policy")
    fleet.add_argument("--scheme", choices=CNN_SCHEMES, default="balanced",
                       help="per-model allocation scheme (without --plan)")
    fleet.add_argument("--plan", action="store_true",
                       help="co-schedule the mix through the §V-B search "
                            "first and serve under the planned PE config")
    fleet.add_argument("--plan-evals", type=int, default=8,
                       help="search budget for --plan")
    fleet.add_argument("--image-size", type=int, default=64,
                       help="input H=W (224 = paper size)")
    fleet.add_argument("--no-pallas", action="store_true",
                       help="use the XLA reference ops")
    fleet.add_argument("--co-dispatch", type=int, default=None,
                       help="max members co-dispatched per slot beyond "
                            "the primary (default: all with work)")
    fleet.add_argument("--burst", type=int, default=4,
                       help="consecutive slots each batched member "
                            "advances per fleet step (locality "
                            "amortization; raises other members' "
                            "queueing by up to burst-1 slots; default 4 "
                            "matches the BENCH_fleet configuration — 1 "
                            "is strict slot-granular interleaving)")
    fleet.add_argument("--no-interleave", action="store_true",
                       help="disable co-dispatch entirely (same as "
                            "--co-dispatch 0): one policy-picked member "
                            "per slot")
    fleet.add_argument("--pools", type=int, default=1,
                       help="process-local device pools (hosts stand-in); "
                            "> 1 serves through a MultiPoolRouter that "
                            "places requests on the least outstanding "
                            "pool")
    fleet.add_argument("--workers", type=int, default=0, metavar="N",
                       help="serve over N real worker processes (python "
                            "-m repro.fleet.worker), one pool each, "
                            "behind --transport socket; mutually "
                            "exclusive with --pools > 1")
    fleet.add_argument("--transport", default="local",
                       choices=("local", "socket", "file"),
                       help="inter-pool request transport: 'local' "
                            "(in-memory mailbox, the --pools default), "
                            "'socket' (length-prefixed wire envelopes to "
                            "--workers processes), 'file' (spool-"
                            "directory mailbox between --pools, see "
                            "--spool)")
    fleet.add_argument("--spool", default=None, metavar="DIR",
                       help="spool directory for --transport file "
                            "(default: a fresh temp dir)")
    fleet.add_argument("--kill-worker", default=None, metavar="POOL@STEP",
                       help="SIGKILL the named worker process at the "
                            "given router step (crash-recovery demo; "
                            "needs --workers)")
    fleet.add_argument("--verify-replay", action="store_true",
                       help="after a --workers run, replay the collected "
                            "per-worker streams + placement log on fresh "
                            "in-process fleets and assert they match "
                            "bitwise")
    fleet.add_argument("--trace", default=None, metavar="PATH",
                       help="write the executed instruction stream as "
                            "Chrome-tracing JSON to PATH (one track per "
                            "submesh per pool, labeled bubble events; "
                            "open in chrome://tracing)")
    fleet.add_argument("--metrics", default=None, metavar="PATH",
                       help="write the telemetry registry at the end of "
                            "the run: '-' = Prometheus text on stdout, "
                            "*.json = JSON, else Prometheus text "
                            "(docs/observability.md)")
    fleet.add_argument("--metrics-every", type=int, default=None,
                       metavar="K",
                       help="with --metrics: append one JSON snapshot "
                            "line every K engine/router steps (a metric "
                            "time series) instead of one final "
                            "exposition")
    fleet.add_argument("--faults", default=None, metavar="PLAN.json",
                       help="arm a seeded FaultPlan (repro.fleet.faults) "
                            "on the executors: deterministic RUN errors, "
                            "pool crashes, dropped SENDs, latency skew")
    fleet.add_argument("--slo-ms", type=float, default=None,
                       help="per-request wall-clock SLO in ms: serve "
                            "every member under a ShedPolicy that drops "
                            "past-deadline queue entries and report "
                            "goodput (served AND within SLO)")
    fleet.add_argument("--adapt", action="store_true",
                       help="attach a closed-loop controller (DESIGN.md "
                            "§13) to each pool: observe the completion "
                            "window every --control-interval slots and "
                            "inject SET_PARAM/REBALANCE — reweight "
                            "members toward the observed mix, retune "
                            "fusion width on p95 breaches (with "
                            "--slo-ms), re-lease theta on sustained "
                            "shedding")
    fleet.add_argument("--control-interval", type=int, default=8,
                       metavar="K",
                       help="fleet slots between controller observations "
                            "(with --adapt; default 8)")
    _add_common(fleet)
    fleet.set_defaults(func=serve_fleet)

    args = ap.parse_args(argv)
    if args.requests < 1:
        ap.error(f"--requests must be >= 1, got {args.requests}")
    if args.max_queue is not None and args.max_queue < 1:
        ap.error(f"--max-queue must be >= 1, got {args.max_queue}")
    if not args.arrival_rate > 0:
        ap.error(f"--arrival-rate must be > 0, got {args.arrival_rate}")
    return args


def run(argv=None) -> Served:
    """Serve one command line and hand back what was served — the entry
    point the CLI and ``chip_smoke.py`` share."""
    args = parse_args(argv)
    enable_compile_cache()
    return args.func(args)


def main(argv=None) -> int:
    return run(argv).rc


if __name__ == "__main__":
    sys.exit(main())
