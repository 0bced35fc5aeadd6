"""Multi-pool serving benchmark: one pool vs N process-local pools.

Serves the same mbv1+squeezenet traffic mix two ways on the same host:

  * ``one_pool``  — a single ``FleetEngine`` over one ``DevicePool``
    (the PR-5 fleet path, now compiled to an instruction stream);
  * ``two_pool``  — two pools (each its own ``DevicePool`` + fleet)
    behind a ``MultiPoolRouter``: requests place onto the least
    outstanding pool and each pool executes its own instruction stream.

On this CPU host both pools share the physical cores, so two pools is a
*scheduling* experiment (placement + per-pool streams), not a capacity
one — the interesting check is that the router multiplexes at par rather
than collapsing.  A third leg measures migration under drain: mid-run,
``drain_pool`` evacuates pool1's queue through SEND/RECV instructions and
the run must still complete every admitted request.

Writes ``BENCH_multipool.json`` — the committed baseline CI diffs against
(the ``aggregate_fps`` leaves are gated higher-is-better in
``benchmarks/compare_bench.py``, same as BENCH_fleet.json).

    PYTHONPATH=src python -m benchmarks.multipool_bench --smoke
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

MIX = {"mobilenet_v1": 0.5, "squeezenet": 0.5}
BURST = 4           # same locality amortization as fleet_bench
POOLS = 2


def _fresh_fleet(runners, pool=None):
    from repro.fleet import FleetEngine, WeightedFair
    from repro.serving import DualCoreEngine

    members = {m: DualCoreEngine(r) for m, r in runners.items()}
    return FleetEngine(members, policy=WeightedFair(), weights=MIX,
                       burst=BURST, pool=pool)


def bench_multipool(report: dict, image_size: int, requests: int,
                    reps: int) -> None:
    import jax

    from repro.fleet import MultiPoolRouter, build_cnn_fleet, mix_schedule
    from repro.serving import Request

    # one runner set per pool (each pool leases its own DevicePool split),
    # plus the single-pool reference set
    def build():
        eng, pool = build_cnn_fleet(list(MIX), weights=MIX,
                                    use_pallas=True)
        return {m.name: m.engine.runner for m in eng.members}, pool

    single_runners, single_pool = build()
    pool_sets = [build() for _ in range(POOLS)]

    tags = mix_schedule(MIX, requests)
    keys = jax.random.split(jax.random.PRNGKey(0), requests)
    images = [jax.random.normal(k, (1, image_size, image_size, 3))
              for k in keys]
    by_model: dict[str, list] = {m: [] for m in MIX}
    for x, t in zip(images, tags):
        by_model[t].append(x)
    for runners in [single_runners] + [rs for rs, _ in pool_sets]:
        for m, r in runners.items():    # warm every member's per-group jits
            r.run_sequential(by_model[m][:1])

    print(f"\n## multi-pool serving ({'+'.join(MIX)}, {image_size}px, "
          f"{requests} requests, 1 vs {POOLS} pools, "
          f"{len(jax.devices())} local device(s))")

    def reqs():
        return [Request(x, model=t) for x, t in zip(images, tags)]

    def leg_one_pool():
        t0 = time.perf_counter()
        eng = _fresh_fleet(single_runners, single_pool)
        for r in reqs():
            eng.submit(r)
        res = eng.drain()
        return time.perf_counter() - t0, res

    def fresh_router():
        return MultiPoolRouter({
            f"pool{i}": _fresh_fleet(rs, pool)
            for i, (rs, pool) in enumerate(pool_sets)})

    def leg_two_pool():
        t0 = time.perf_counter()
        router = fresh_router()
        for r in reqs():
            router.submit(r)
        res = router.drain()
        return time.perf_counter() - t0, res

    def leg_migration():
        """Same workload, but pool1's queue is forcibly evacuated mid-run
        (SEND on pool1, RECV on pool0) — drain-for-maintenance."""
        t0 = time.perf_counter()
        router = fresh_router()
        for r in reqs():
            router.submit(r)
        # evacuate before pool1 admits anything: with burst=4 a single
        # step already admits this whole smoke-sized queue
        moved = router.drain_pool("pool1")
        res = router.drain()
        return time.perf_counter() - t0, res, moved

    # interleave the legs rep-by-rep with best-of per leg (the machine
    # drifts either way; see fleet_bench); rep 0 is an untimed warm-in
    leg_one_pool(), leg_two_pool(), leg_migration()
    t_one = t_two = t_mig = float("inf")
    res_two = res_mig = None
    moved = 0
    for _ in range(max(2, reps)):
        gc.collect()
        t_one = min(t_one, leg_one_pool()[0])
        gc.collect()
        wall, res = leg_two_pool()
        if wall < t_two:
            t_two, res_two = wall, res
        gc.collect()
        wall, res, mv = leg_migration()
        if wall < t_mig:
            t_mig, res_mig, moved = wall, res, mv

    one_fps = requests / t_one
    two_fps = requests / t_two
    mig_fps = requests / t_mig
    assert res_two.metrics.completed == requests
    assert res_mig.metrics.completed == requests    # nothing lost in
    #                                                 transit under drain

    st = res_two.stats
    report["mix"] = MIX
    report["theta"] = single_pool.theta
    report["pools"] = POOLS
    report["one_pool"] = {"aggregate_fps": round(one_fps, 2)}
    report["two_pool"] = {
        "aggregate_fps": round(two_fps, 2),
        "steps": st["steps"],
        "per_pool_served": {p: sum(d["served"].values())
                            for p, d in st["pools"].items()},
    }
    report["migration"] = {
        "aggregate_fps": round(mig_fps, 2),
        "moved": moved,
        "completed": res_mig.metrics.completed,
        "in_transit_after": res_mig.stats["in_transit"],
    }
    report["two_vs_one"] = round(two_fps / one_fps, 3)

    print(f"{'leg':<26}{'fps':>8}")
    print(f"{'one pool':<26}{one_fps:>8.2f}")
    print(f"{f'{POOLS} pools (router)':<26}{two_fps:>8.2f}")
    print(f"{'migration under drain':<26}{mig_fps:>8.2f}  "
          f"({moved} request(s) migrated)")
    print(f"{POOLS} pools vs one: {report['two_vs_one']:.2f}x")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--smoke", action="store_true",
                    help="CI shape: small images, few requests")
    ap.add_argument("--out", default="BENCH_multipool.json")
    ap.add_argument("--image-size", type=int, default=None,
                    help="input H=W (default: 64 smoke / 96 full)")
    ap.add_argument("--requests", type=int, default=None,
                    help="total requests across the mix "
                         "(default: 8 smoke / 16 full)")
    ap.add_argument("--reps", type=int, default=2)
    args = ap.parse_args(argv)

    image_size = args.image_size or (64 if args.smoke else 96)
    requests = args.requests or (8 if args.smoke else 16)

    import jax

    report: dict = {"devices": len(jax.devices()),
                    "backend": jax.default_backend(),
                    "image_size": image_size,
                    "requests": requests}
    bench_multipool(report, image_size, requests, args.reps)
    with open(args.out, "w") as f:
        json.dump(report, f, indent=1, sort_keys=True)
    print(f"wrote {args.out}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
