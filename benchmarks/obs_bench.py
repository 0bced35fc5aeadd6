"""Telemetry overhead benchmark: instrumented vs bare fleet serving.

Serves the same mbv1+squeezenet mix through a 2-pool
``MultiPoolRouter`` twice on the same host:

  * ``bare``         — the shared ``repro.obs`` registry disabled
    (``router.obs.enabled = False``): every ``inc``/``set``/``observe``
    is a guard-clause no-op, the PR-10 zero-cost-when-off claim;
  * ``instrumented`` — the registry live, counting every executed
    instruction, placement, retire, and wall-clock duration.

The committed contract is ``instrumented / bare >= 0.95`` — telemetry
may cost at most 5% of serving throughput — asserted here so the CI
smoke run fails loudly, and both legs' ``aggregate_fps`` leaves are
additionally gated higher-is-better against the committed baseline by
``benchmarks/compare_bench.py``.

A third leg exports the instrumented run's instruction streams as a
Chrome trace and asserts its shape: advancing RUN slices, and at least
one labeled pipeline-bubble event.

    PYTHONPATH=src python -m benchmarks.obs_bench --smoke
"""
from __future__ import annotations

import argparse
import gc
import json
import os
import sys
import time

# two host platform devices, one per pool (must happen pre-import)
if "jax" not in sys.modules and \
        "xla_force_host_platform_device_count" not in \
        os.environ.get("XLA_FLAGS", ""):
    os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                               + " --xla_force_host_platform_device_count=2")

MIX = {"mobilenet_v1": 0.5, "squeezenet": 0.5}
BURST = 4
POOLS = 2
MAX_OVERHEAD = 0.95     # instrumented must keep >= 95% of bare fps


def _fresh_fleet(runners, pool=None):
    from repro.fleet import FleetEngine, WeightedFair
    from repro.serving import DualCoreEngine

    members = {m: DualCoreEngine(r) for m, r in runners.items()}
    return FleetEngine(members, policy=WeightedFair(), weights=MIX,
                       burst=BURST, pool=pool)


def bench_obs(report: dict, image_size: int, requests: int,
              reps: int) -> None:
    import jax

    from repro.fleet import MultiPoolRouter, build_cnn_fleet
    from repro.fleet.trace import chrome_trace
    from repro.fleet import mix_schedule
    from repro.serving import Request

    def build():
        eng, pool = build_cnn_fleet(list(MIX), weights=MIX,
                                    use_pallas=True)
        return {m.name: m.engine.runner for m in eng.members}, pool

    pool_sets = [build() for _ in range(POOLS)]

    tags = mix_schedule(MIX, requests)
    keys = jax.random.split(jax.random.PRNGKey(0), requests)
    images = [jax.random.normal(k, (1, image_size, image_size, 3))
              for k in keys]
    by_model: dict[str, list] = {m: [] for m in MIX}
    for x, t in zip(images, tags):
        by_model[t].append(x)
    for runners, _ in pool_sets:
        for m, r in runners.items():    # warm every member's per-group jits
            r.run_sequential(by_model[m][:1])

    print(f"\n## telemetry overhead ({'+'.join(MIX)}, {image_size}px, "
          f"{requests} requests, {POOLS} pools, "
          f"{len(jax.devices())} local device(s))")

    def reqs():
        return [Request(x, model=t) for x, t in zip(images, tags)]

    def fresh_router():
        return MultiPoolRouter({
            f"pool{i}": _fresh_fleet(rs, pool)
            for i, (rs, pool) in enumerate(pool_sets)})

    def leg(enabled):
        t0 = time.perf_counter()
        router = fresh_router()
        router.obs.enabled = enabled
        for r in reqs():
            router.submit(r)
        res = router.drain()
        return time.perf_counter() - t0, router, res

    # interleave rep-by-rep with best-of per leg (same drift hedge as
    # multipool_bench); rep 0 is an untimed warm-in
    leg(False), leg(True)
    t_bare = t_inst = float("inf")
    router_inst = res_inst = None
    for _ in range(max(2, reps)):
        gc.collect()
        t_bare = min(t_bare, leg(False)[0])
        gc.collect()
        wall, router, res = leg(True)
        if wall < t_inst:
            t_inst, router_inst, res_inst = wall, router, res

    bare_fps = requests / t_bare
    inst_fps = requests / t_inst
    ratio = inst_fps / bare_fps
    assert res_inst.metrics.completed == requests

    # the instrumented run really counted: every pool shows executed
    # instructions in the slot domain
    instr = router_inst.obs.snapshot(domain="slot")["counters"][
        "fleet_instructions_total"]["series"]
    for i in range(POOLS):
        assert any(f"pool=pool{i}" in k for k in instr), instr

    # trace leg: the export carries RUN slices and labeled bubbles
    doc = chrome_trace(router_inst.streams())
    slices = [e for e in doc["traceEvents"]
              if e["ph"] == "X" and e["name"].startswith("RUN")
              and e["args"].get("advances", 0) > 0]
    assert slices, "no advancing RUN slices in the trace"
    bubbles = [e for e in doc["traceEvents"]
               if e.get("cat") == "bubble"]
    assert bubbles, "no pipeline-bubble events in the trace"

    assert ratio >= MAX_OVERHEAD, (
        f"telemetry overhead too high: instrumented/bare = {ratio:.3f} "
        f"< {MAX_OVERHEAD}")

    report["bare"] = {"aggregate_fps": round(bare_fps, 2)}
    report["instrumented"] = {
        "aggregate_fps": round(inst_fps, 2),
        "slot_series": sum(
            len(m["series"]) for part in
            router_inst.obs.snapshot(domain="slot").values()
            for m in part.values()),
    }
    report["overhead_ratio"] = round(ratio, 3)
    report["trace"] = {
        "events": len(doc["traceEvents"]),
        "run_slices": len(slices),
        "bubbles": len(bubbles),
    }

    print(f"{'leg':<26}{'fps':>8}")
    print(f"{'bare (obs off)':<26}{bare_fps:>8.2f}")
    print(f"{'instrumented':<26}{inst_fps:>8.2f}")
    print(f"instrumented vs bare: {ratio:.3f}x  "
          f"(gate: >= {MAX_OVERHEAD})")
    print(f"trace: {len(slices)} RUN slice(s), "
          f"{len(bubbles)} bubble(s)")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--smoke", action="store_true",
                    help="CI shape: small images, few requests")
    ap.add_argument("--out", default="BENCH_obs.json")
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
    bench_obs(report, image_size, requests, args.reps)
    with open(args.out, "w") as f:
        json.dump(report, f, indent=1, sort_keys=True)
    print(f"wrote {args.out}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
