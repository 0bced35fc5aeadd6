"""Chaos benchmark: goodput under SLO while a pool dies mid-run.

Replays the mbv1+squeezenet traffic mix as an *open-loop bursty Poisson*
trace (arrival rate well above capacity, so the SLO matters) three ways:

  * ``baseline`` — one fleet pool, no faults: the no-fault single-pool
    goodput reference.  Members run under a slot-clock :class:`ShedPolicy`
    with per-request slot deadlines, so late work is shed, not served.
  * ``chaos``    — two pools behind a :class:`MultiPoolRouter` with a
    seeded :class:`FaultPlan` that **kills pool1 mid-run**.  The router
    re-routes the dead pool's un-retired requests onto the survivor
    (status ``recovered``), re-leases the survivor's split (REBALANCE),
    and keeps shedding past-deadline work.  Invariants checked hard:
    every admitted request retires exactly once (none lost, none
    duplicated) and chaos goodput stays >= 0.9x the baseline's.
  * ``replay``   — the faulted run's recorded streams + placement log +
    recovery event log re-executed on fresh pools with **no injector
    attached**: stream signatures, shed set, recovered rids and the
    event log must all match bitwise.
  * ``process``  — a deterministic sim-member spike against real worker
    *processes* (``python -m repro.fleet.worker``) over SocketTransport,
    with one worker **SIGKILL'd** mid-drain (no injector — a real dead
    process): exactly-once retirement, slot-domain goodput
    (completions per router step) >= 0.9x the clean single-worker run,
    and bitwise replay of the killed run on fresh in-process pools.

Writes ``BENCH_chaos.json``; its ``goodput_fps`` leaves are gated
higher-is-better in ``benchmarks/compare_bench.py``.

    PYTHONPATH=src python -m benchmarks.chaos_bench --smoke
"""
from __future__ import annotations

import argparse
import gc
import json
import os
import sys
import time

# Two host platform devices unless the caller already configured XLA
# (must happen pre-import) — each pool leases its own 2-device split.
if "jax" not in sys.modules and \
        "xla_force_host_platform_device_count" not in \
        os.environ.get("XLA_FLAGS", ""):
    os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                               + " --xla_force_host_platform_device_count=2")

MIX = {"mobilenet_v1": 0.5, "squeezenet": 0.5}
BURST = 4
CRASH_SLOT = 1      # pool1 dies with admitted + queued work on board
RATE = 50.0         # arrivals per slot — the mix lands as one spike
SLACK = 3           # slot deadline = arrival + SLACK (+ per-rid jitter)


def _statuses(res):
    return {c.ticket.rid: c.metrics.status for c in res.completions}


def _drive(engine, reqs, arrivals):
    """Open-loop drive: submit each request at its arrival step, retry
    admission-refused (QueueFull) submissions next step, run to drain."""
    from repro.serving import QueueFull

    order = sorted(range(len(reqs)), key=lambda i: arrivals[i])
    nxt, step, refused = 0, 0, []
    while nxt < len(order) or refused or engine.has_work:
        due, refused = refused, []
        while nxt < len(order) and arrivals[order[nxt]] <= step:
            due.append(order[nxt])
            nxt += 1
        for i in due:
            try:
                engine.submit(reqs[i])
            except QueueFull:
                refused.append(i)
        if engine.has_work:
            engine.step()
        step += 1
    return engine.result()


def bench_chaos(report: dict, image_size: int, requests: int,
                reps: int) -> None:
    import jax

    from repro.fleet import (Fault, FaultInjector, FaultPlan, FleetEngine,
                             MultiPoolRouter, WeightedFair, build_cnn_fleet,
                             mix_schedule, stream_from_json,
                             stream_signature, stream_to_json)
    from repro.serving import Request, ShedPolicy, poisson_arrivals

    def build():
        eng, pool = build_cnn_fleet(list(MIX), weights=MIX,
                                    use_pallas=True)
        return {m.name: m.engine.runner for m in eng.members}, pool

    def fresh_fleet(runners, pool):
        from repro.serving import DualCoreEngine

        members = {m: DualCoreEngine(r) for m, r in runners.items()}
        eng = FleetEngine(members, policy=WeightedFair(), weights=MIX,
                          burst=BURST, pool=pool)
        for m in eng.members:   # slot-clock SLO shedding at admission
            m.engine.policy = ShedPolicy(inner=m.engine.policy)
        return eng

    single_runners, single_pool = build()
    pool_sets = [build() for _ in range(2)]

    # bursty overload: Poisson arrivals at ~3x the per-slot admit rate,
    # slot deadlines a fixed slack past arrival — late work must shed
    tags = mix_schedule(MIX, requests)
    arrivals = poisson_arrivals(requests, rate=RATE, seed=0)
    keys = jax.random.split(jax.random.PRNGKey(0), requests)
    images = [jax.random.normal(k, (1, image_size, image_size, 3))
              for k in keys]
    by_model: dict[str, list] = {m: [] for m in MIX}
    for x, t in zip(images, tags):
        by_model[t].append(x)
    for runners in [single_runners] + [rs for rs, _ in pool_sets]:
        for m, r in runners.items():    # warm every member's per-group jits
            r.run_sequential(by_model[m][:1])

    plan = FaultPlan(faults=(
        Fault(kind="pool_crash", pool="pool1", slot=CRASH_SLOT),), seed=0)

    print(f"\n## chaos serving ({'+'.join(MIX)}, {image_size}px, "
          f"{requests} requests, pool1 killed at slot {CRASH_SLOT}, "
          f"{len(jax.devices())} local device(s))")

    def reqs():
        return [Request(x, model=t, deadline=arrivals[i] + SLACK + i % 3)
                for i, (x, t) in enumerate(zip(images, tags))]

    def leg_baseline():
        t0 = time.perf_counter()
        eng = fresh_fleet(single_runners, single_pool)
        res = _drive(eng, reqs(), arrivals)
        return time.perf_counter() - t0, res

    def fresh_router(injector=None):
        return MultiPoolRouter({
            f"pool{i}": fresh_fleet(rs, pool)
            for i, (rs, pool) in enumerate(pool_sets)},
            injector=injector, plan_evals=2)

    def leg_chaos():
        t0 = time.perf_counter()
        router = fresh_router(injector=FaultInjector(plan))
        res = _drive(router, reqs(), arrivals)
        return time.perf_counter() - t0, res, router

    # rep 0 is an untimed warm-in; best-of per leg after that
    leg_baseline(), leg_chaos()
    best_base = best_chaos = None
    g_base = g_chaos = -1.0
    for _ in range(max(2, reps)):
        gc.collect()
        _w, res = leg_baseline()
        if res.metrics.goodput_fps() > g_base:
            g_base, best_base = res.metrics.goodput_fps(), res
        gc.collect()
        _w, res, router = leg_chaos()
        if res.metrics.goodput_fps() > g_chaos:
            g_chaos, best_chaos = res.metrics.goodput_fps(), (res, router)
    res_chaos, router = best_chaos

    # ---- invariants: exactly-once retirement, explicit accounting ----
    st = _statuses(res_chaos)
    assert sorted(st) == list(range(requests)), \
        "lost or duplicated request ids"
    assert set(st.values()) <= {"ok", "shed", "recovered", "failed"}
    assert router.duplicates_dropped == 0, "a request retired twice"
    assert list(router.dead) == ["pool1"], "the injected crash must land"
    assert "failed" not in st.values(), \
        "pool0 serves every model: crash recovery must re-route, not fail"
    ratio = g_chaos / g_base if g_base else float("inf")
    assert ratio >= 0.9, (
        f"chaos goodput {g_chaos:.2f} fps fell below 0.9x the no-fault "
        f"single-pool baseline {g_base:.2f} fps")

    # ---- replay: the faulted run, bitwise, with no injector ----------
    rt = {name: stream_from_json(stream_to_json(recs, pool=name))
          for name, recs in router.streams().items()}
    fresh = fresh_router()
    res_rep = fresh.replay(rt, router.placements, reqs(),
                           events=router.events)
    assert stream_signature(fresh.stream()) == \
        stream_signature(router.stream()), "replay diverged from recording"
    assert fresh.events == router.events
    st_rep = _statuses(res_rep)
    assert st_rep == st, "replayed shed/recovered sets differ"
    shed_set = sorted(r for r, s in st.items() if s == "shed")
    recovered = sorted(r for r, s in st.items() if s == "recovered")

    base_sum = best_base.metrics.summary()
    chaos_sum = res_chaos.metrics.summary()
    report["slo"] = {"clock": "slot", "slack_slots": SLACK}
    report["fault_plan"] = plan.to_json()
    report["baseline"] = {
        "goodput_fps": round(g_base, 2),
        "completed": best_base.metrics.completed,
        "shed": base_sum["shed"],
    }
    report["chaos"] = {
        "goodput_fps": round(g_chaos, 2),
        "completed": res_chaos.metrics.completed,
        "shed": chaos_sum["shed"],
        "recovered": chaos_sum["recovered"],
        "failed": chaos_sum["failed"],
        "dead": sorted(router.dead),
        "duplicates_dropped": router.duplicates_dropped,
        "recovery_events": len(router.events),
    }
    report["replay"] = {
        "bitwise": True,
        "records": len(router.stream()),
        "shed_rids": shed_set,
        "recovered_rids": recovered,
    }
    report["chaos_vs_baseline"] = round(ratio, 3)

    print(f"{'leg':<28}{'goodput fps':>12}{'shed':>6}{'recov':>7}")
    print(f"{'baseline (1 pool, clean)':<28}{g_base:>12.2f}"
          f"{base_sum['shed']:>6}{0:>7}")
    print(f"{'chaos (2 pools, 1 dies)':<28}{g_chaos:>12.2f}"
          f"{chaos_sum['shed']:>6}{chaos_sum['recovered']:>7}")
    print(f"chaos vs baseline: {ratio:.2f}x; replay bitwise over "
          f"{len(router.stream())} records, {len(router.events)} "
          f"recovery events")


def bench_process(report: dict, requests: int, reps: int) -> None:
    """Real-process chaos (DESIGN.md §14): the same spike against worker
    *processes* over SocketTransport, with one worker **SIGKILL'd**
    mid-drain — no injector, a genuinely dead process detected by
    connection loss.  Members are deterministic sim stubs with a modeled
    per-slot compute cost, so outcomes and step counts are bitwise
    reproducible.  Gated hard: exactly-once retirement, and chaos
    goodput — measured in the *slot domain* (in-SLO completions per
    router step, which is deterministic; wall-clock fps over ~100 ms
    walls is scheduler noise) — >= 0.9x the clean single-worker run."""
    from repro.fleet import MultiPoolRouter, stream_signature
    from repro.fleet.net.coordinator import (connect, start_workers,
                                             stop_workers)
    from repro.fleet.net.worker import build_sim_fleet
    from repro.serving import QueueFull, Request, poisson_arrivals

    spec = "cnn:c:2,lm:p:3:opaque"
    cost_us = 200                   # modeled compute per occupied slot
    kill_step = max(2, requests // 5)   # mid-drain: victim holds work
    arrivals = poisson_arrivals(requests, rate=RATE, seed=0)

    def reqs():
        return [Request(payload=i, model=("cnn" if i % 2 == 0 else "lm"))
                for i in range(requests)]

    def run(n_workers, kill_at=None):
        procs = start_workers({
            f"pool{i}": ["--sim", spec, "--sim-cost-us", str(cost_us)]
            for i in range(n_workers)})
        fleets = {}
        try:
            fleets = connect(procs, heartbeat_s=30.0)
            router = MultiPoolRouter(fleets)
            rs = reqs()
            order = sorted(range(requests), key=lambda i: arrivals[i])
            nxt, step, refused = 0, 0, []
            t0 = time.perf_counter()
            while nxt < len(order) or refused or router.has_work:
                if kill_at is not None and step >= kill_at:
                    procs[f"pool{n_workers - 1}"].kill()
                    kill_at = None
                due, refused = refused, []
                while nxt < len(order) and arrivals[order[nxt]] <= step:
                    due.append(order[nxt])
                    nxt += 1
                for i in due:
                    try:
                        router.submit(rs[i])
                    except QueueFull:
                        refused.append(i)
                router.step()
                step += 1
            res = router.result()
            wall = time.perf_counter() - t0
        finally:
            stop_workers(fleets, procs)
        return wall, res, router, rs, step

    print(f"\n## real-process chaos (sim members {spec!r}, {requests} "
          f"requests, SIGKILL worker at router step {kill_step})")

    best = {}
    for name, leg in (("clean", lambda: run(1)),
                      ("chaos", lambda: run(2, kill_at=kill_step))):
        for _ in range(max(1, reps)):
            gc.collect()
            out = leg()
            if name not in best or out[1].metrics.goodput_fps() > \
                    best[name][1].metrics.goodput_fps():
                best[name] = out

    _w, res_chaos, router, rs, steps_chaos = best["chaos"]
    steps_clean = best["clean"][4]
    g_clean = best["clean"][1].metrics.goodput_fps()
    g_chaos = res_chaos.metrics.goodput_fps()

    # ---- invariants: exactly-once under a real SIGKILL ---------------
    st = _statuses(res_chaos)
    assert sorted(st) == list(range(requests)), \
        "lost or duplicated request ids"
    assert router.duplicates_dropped == 0, "a request retired twice"
    assert list(router.dead) == ["pool1"], "the SIGKILL must land"
    assert "failed" not in st.values(), \
        "the survivor serves every model: recovery must re-route"
    # slot-domain goodput: deterministic (same placements, same recovery
    # path every run), so this gate cannot flake on machine load
    gps_clean = best["clean"][1].metrics.completed / steps_clean
    gps_chaos = res_chaos.metrics.completed / steps_chaos
    ratio = gps_chaos / gps_clean if gps_clean else float("inf")
    assert ratio >= 0.9, (
        f"process-chaos goodput {gps_chaos:.3f}/step fell below 0.9x "
        f"the clean single-worker run {gps_clean:.3f}/step")

    # ---- the killed run replays bitwise on fresh in-process pools ----
    streams = router.streams()
    fresh = MultiPoolRouter({p: build_sim_fleet(spec) for p in streams})
    fresh.replay(streams, list(router.placements), rs,
                 list(router.events))
    for pool, recs in streams.items():
        assert stream_signature(recs) == stream_signature(
            fresh.executors[pool].records), f"replay diverged on {pool}"
    st_rep = {rid: fresh._metrics[rid].status for rid in range(requests)}
    assert st_rep == st, "replayed recovered sets differ"

    summ = res_chaos.metrics.summary()
    report["process"] = {
        "sim_cost_us": cost_us,
        "kill_step": kill_step,
        "clean": {"goodput_fps": round(g_clean, 2),
                  "goodput_per_step": round(gps_clean, 4),
                  "steps": steps_clean,
                  "completed": best["clean"][1].metrics.completed},
        "chaos": {"goodput_fps": round(g_chaos, 2),
                  "goodput_per_step": round(gps_chaos, 4),
                  "steps": steps_chaos,
                  "completed": res_chaos.metrics.completed,
                  "recovered": summ["recovered"],
                  "dead": sorted(router.dead),
                  "duplicates_dropped": router.duplicates_dropped},
        "chaos_vs_clean_per_step": round(ratio, 3),
        "replay_records": sum(len(r) for r in streams.values()),
    }
    print(f"{'leg':<28}{'good/step':>10}{'steps':>7}{'fps':>10}"
          f"{'recov':>7}")
    print(f"{'clean (1 worker)':<28}{gps_clean:>10.3f}{steps_clean:>7}"
          f"{g_clean:>10.1f}{0:>7}")
    print(f"{'chaos (2 workers, SIGKILL)':<28}{gps_chaos:>10.3f}"
          f"{steps_chaos:>7}{g_chaos:>10.1f}{summ['recovered']:>7}")
    print(f"process chaos vs clean: {ratio:.2f}x per-step; replay "
          f"bitwise over {report['process']['replay_records']} records")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--smoke", action="store_true",
                    help="CI shape: small images, few requests")
    ap.add_argument("--out", default="BENCH_chaos.json")
    ap.add_argument("--image-size", type=int, default=None,
                    help="input H=W (default: 48 smoke / 96 full)")
    ap.add_argument("--requests", type=int, default=None,
                    help="total requests across the mix "
                         "(default: 10 smoke / 24 full)")
    ap.add_argument("--reps", type=int, default=4)
    args = ap.parse_args(argv)

    image_size = args.image_size or (48 if args.smoke else 96)
    requests = args.requests or (10 if args.smoke else 24)

    import jax

    report: dict = {"devices": len(jax.devices()),
                    "backend": jax.default_backend(),
                    "image_size": image_size,
                    "requests": requests}
    bench_chaos(report, image_size, requests, args.reps)
    bench_process(report, requests=200, reps=2)
    with open(args.out, "w") as f:
        json.dump(report, f, indent=1, sort_keys=True)
    print(f"wrote {args.out}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
