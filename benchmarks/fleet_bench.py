"""Fleet serving benchmark: the Table VII multi-network workload, measured.

Serves the mbv1+mbv2+squeezenet traffic mix through a ``FleetEngine``
(shared device pool, weighted-fair step scheduling, core-complementary
interleave) and compares against the best *sequential* way to serve the
same requests on the same host with the engines this repo already had:

  * ``engine_at_a_time`` — drain each model's requests through its own
    standalone ``DualCoreEngine``, one model after another (per-model
    pipelining intact, zero cross-network overlap);
  * ``run_sequential``   — strictly serialized single-image forwards.

The fleet's win condition (the ISSUE-5 acceptance) is aggregate fps >= the
best of those baselines: multiplexing several networks over one pool must
never cost throughput, and the cross-engine interleave should buy some.
Latency percentiles come from a separate fixed Poisson-arrival replay leg
(seeded, identical across runs), broken down per model via
``Metrics.by_model``.  The planner's model-side prediction
(``fleet.planner.plan_fleet`` — deterministic, cycle-domain) rides along
for the Table-VII-style predicted-vs-measured comparison in
``benchmarks/paper_tables.py``.

Writes ``BENCH_fleet.json`` — the committed baseline CI diffs against
(``aggregate_fps`` is gated as higher-is-better, the p50/p95 fields as
lower-is-better, in ``benchmarks/compare_bench.py``).

    PYTHONPATH=src python -m benchmarks.fleet_bench --smoke
"""
from __future__ import annotations

import argparse
import gc
import json
import os
import sys
import time

# A >=2-device mesh is the point of the exercise: force two host platform
# devices unless the caller already configured XLA (must happen pre-import).
if "jax" not in sys.modules and \
        "xla_force_host_platform_device_count" not in \
        os.environ.get("XLA_FLAGS", ""):
    os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                               + " --xla_force_host_platform_device_count=2")

MIX = {"mobilenet_v1": 0.4, "mobilenet_v2": 0.35, "squeezenet": 0.25}
ARRIVAL_RATE = 1.0          # requests per fleet slot (Poisson-ish)
ARRIVAL_SEED = 0


BURST = 4       # per-member slot burst: amortizes the cache locality the
#                 one-model-at-a-time baselines get for free; without it
#                 the fleet measures a few percent BEHIND them on this
#                 2-CPU host, with it par-or-slightly-ahead


def _fresh_fleet(runners, mix, co_dispatch=None, burst=BURST):
    """New engines over the already-jitted runners (cheap per rep)."""
    from repro.fleet import FleetEngine, WeightedFair
    from repro.serving import DualCoreEngine

    members = {m: DualCoreEngine(r) for m, r in runners.items()}
    return FleetEngine(members, policy=WeightedFair(), weights=mix,
                       co_dispatch=co_dispatch, burst=burst)


def bench_fleet(report: dict, image_size: int, requests: int,
                reps: int) -> None:
    import jax

    from repro.fleet import build_cnn_fleet, mix_schedule, plan_fleet
    from repro.serving import Request, poisson_arrivals, replay

    engine, pool = build_cnn_fleet(list(MIX), weights=MIX,
                                   use_pallas=True)
    runners = {m.name: m.engine.runner for m in engine.members}
    tags = mix_schedule(MIX, requests)
    keys = jax.random.split(jax.random.PRNGKey(0), requests)
    images = [jax.random.normal(k, (1, image_size, image_size, 3))
              for k in keys]
    by_model: dict[str, list] = {m: [] for m in MIX}
    for x, t in zip(images, tags):
        by_model[t].append(x)
    for m, r in runners.items():        # warm every member's per-group jits
        r.run_sequential(by_model[m][:1])

    print(f"\n## fleet serving ({'+'.join(MIX)}, {image_size}px, "
          f"{requests} requests, mix "
          f"{'/'.join(f'{s:.2f}' for s in MIX.values())}, "
          f"{len(jax.devices())} local device(s))")

    # steady state: everything at slot 0.  The three legs are interleaved
    # rep-by-rep (fleet, engine-at-a-time, run_sequential, repeat) with
    # best-of per leg: on this host the first-measured leg routinely loses
    # 5-10% to allocator/cache warm-in that later legs inherit for free,
    # so measuring all fleet reps before all baseline reps biases the
    # comparison either way the machine is drifting.  gc.collect keeps the
    # previous leg's deallocations out of the timed window (as in
    # serving_bench); rep 0 of each leg is an untimed warm-in.
    from repro.serving import stream_images

    # every leg is a full wall (perf_counter around engine construction +
    # submits + drain): summing the baselines' *internal* engine walls
    # would drop the inter-engine gaps the engine-at-a-time leg really
    # pays between models, while the fleet's single wall includes
    # everything — an asymmetry worth a percent
    def leg_fleet():
        t0 = time.perf_counter()
        eng = _fresh_fleet(runners, MIX)
        for x, t in zip(images, tags):
            eng.submit(Request(x, model=t))
        res = eng.drain()
        return time.perf_counter() - t0, res

    def leg_eaat():
        t0 = time.perf_counter()
        for m, r in runners.items():
            stream_images(r, by_model[m])
        return time.perf_counter() - t0

    def leg_seq():
        t0 = time.perf_counter()
        for m, r in runners.items():
            r.run_sequential(by_model[m])
        return time.perf_counter() - t0

    leg_fleet(), leg_eaat(), leg_seq()          # warm-in, untimed
    t_fleet = t_eaat = t_seq = float("inf")
    best_res = None
    for _ in range(max(2, reps)):
        gc.collect()
        wall, res = leg_fleet()
        if wall < t_fleet:
            t_fleet, best_res = wall, res
        gc.collect()
        t_eaat = min(t_eaat, leg_eaat())
        gc.collect()
        t_seq = min(t_seq, leg_seq())
    fleet_fps = requests / t_fleet
    baseline_fps = requests / min(t_eaat, t_seq)

    # latency leg: fixed Poisson-ish arrivals, best-of (a single replay's
    # p95 is one GC pause away from a phantom CI failure)
    arrivals = poisson_arrivals(requests, rate=ARRIVAL_RATE,
                                seed=ARRIVAL_SEED)
    lat: dict[str, dict[str, float]] = {}
    for _ in range(max(2, reps // 2)):
        gc.collect()
        res = replay(_fresh_fleet(runners, MIX),
                     [Request(x, model=t)
                      for x, t in zip(images, tags)], arrivals)
        for m, pm in res.metrics.by_model().items():
            cur = lat.setdefault(m, {"p50_ms": float("inf"),
                                     "p95_ms": float("inf")})
            cur["p50_ms"] = min(cur["p50_ms"], pm["p50_ms"])
            cur["p95_ms"] = min(cur["p95_ms"], pm["p95_ms"])
        agg = lat.setdefault("aggregate", {"p50_ms": float("inf"),
                                           "p95_ms": float("inf")})
        agg["p50_ms"] = min(agg["p50_ms"], res.metrics.p50_ms())
        agg["p95_ms"] = min(agg["p95_ms"], res.metrics.p95_ms())

    # deterministic model-side prediction for the Table-VII comparison
    plan = plan_fleet(MIX, max_evals=6)

    st = best_res.stats
    report["mix"] = MIX
    report["theta"] = pool.theta        # the c/p split the pool served on
    report["fleet"] = {
        "aggregate_fps": round(fleet_fps, 2),
        "policy": st["policy"],
        "co_dispatch": st["co_dispatch"],
        "burst": st["burst"],
        "slots": st["slots"],
        "dispatches": st["dispatches"],
        "per_model": {
            m: {"completed": pm["completed"],
                "requests_per_s": pm["requests_per_s"]}
            for m, pm in st["per_model"].items()},
        "latency": {m: {k: round(v, 2) for k, v in d.items()}
                    for m, d in lat.items()},
    }
    report["baseline"] = {
        "engine_at_a_time_fps": round(requests / t_eaat, 2),
        "run_sequential_fps": round(requests / t_seq, 2),
        "best_fps": round(baseline_fps, 2),
    }
    report["fleet_vs_baseline"] = round(fleet_fps / baseline_fps, 3)
    report["planner"] = plan.summary()

    print(f"{'leg':<22}{'fps':>8}")
    print(f"{'fleet (interleaved)':<22}{fleet_fps:>8.2f}")
    print(f"{'engine-at-a-time':<22}{requests / t_eaat:>8.2f}")
    print(f"{'run_sequential':<22}{requests / t_seq:>8.2f}")
    print(f"fleet vs best sequential baseline: "
          f"{report['fleet_vs_baseline']:.2f}x")
    for m, d in lat.items():
        print(f"  {m:<16} p50 {d['p50_ms']:7.1f} ms  "
              f"p95 {d['p95_ms']:7.1f} ms")
    print(f"planner predicted aggregate (model-side): "
          f"{plan.aggregate_fps:.1f} fps under {plan.config}")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--smoke", action="store_true",
                    help="CI shape: small images, few requests")
    ap.add_argument("--out", default="BENCH_fleet.json")
    ap.add_argument("--image-size", type=int, default=None,
                    help="input H=W (default: 64 smoke / 96 full)")
    ap.add_argument("--requests", type=int, default=None,
                    help="total requests across the mix "
                         "(default: 9 smoke / 18 full)")
    ap.add_argument("--reps", type=int, default=2)
    args = ap.parse_args(argv)

    image_size = args.image_size or (64 if args.smoke else 96)
    requests = args.requests or (9 if args.smoke else 18)

    import jax

    report: dict = {"devices": len(jax.devices()),
                    "backend": jax.default_backend(),
                    "image_size": image_size,
                    "requests": requests}
    bench_fleet(report, image_size, requests, args.reps)
    with open(args.out, "w") as f:
        json.dump(report, f, indent=1, sort_keys=True)
    print(f"wrote {args.out}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
