"""Fault-tolerant fleet serving (ISSUE-7): seeded fault injection at
instruction boundaries, executor retry/escalation, router crash recovery
and SLO shedding, and the property that a faulted live run replays
bitwise from its recorded streams + placement log + recovery event log."""
import json
import os
import sys

import jax
import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

from test_fleet import _stub_fleet  # noqa: E402

from repro.fleet import (Fault, FaultInjector, FaultPlan,  # noqa: E402
                         InjectedFault, MultiPoolRouter, PoolCrash,
                         RecoveryConfig, Run, WeightedFair, build_cnn_fleet,
                         stream_from_json, stream_signature, stream_to_json)
from repro.serving import (QueueFull, Request, ShedPolicy,  # noqa: E402
                           poisson_arrivals, replay)


# --------------------------------------------------------------------------
# plan schema + generation
# --------------------------------------------------------------------------
def test_fault_plan_json_round_trip(tmp_path):
    plan = FaultPlan(faults=(
        Fault(kind="run_error", pool="p0", slot=2, member="a", times=2),
        Fault(kind="pool_crash", pool="p1", slot=3),
        Fault(kind="send_drop", pool="p0", slot=1),
        Fault(kind="latency", pool="p1", skew_s=0.002)), seed=7)
    path = tmp_path / "plan.json"
    plan.dump(str(path))
    loaded = FaultPlan.load(str(path))
    assert loaded == plan
    assert json.loads(json.dumps(plan.to_json())) == plan.to_json()


def test_fault_plan_rejects_malformed(tmp_path):
    with pytest.raises(ValueError, match="unknown fault kind"):
        Fault(kind="meteor")
    with pytest.raises(ValueError, match="skew_s"):
        Fault(kind="latency", skew_s=0.0)
    with pytest.raises(ValueError, match="JSON object"):
        FaultPlan.from_json([1, 2])
    with pytest.raises(ValueError, match="version"):
        FaultPlan.from_json({"version": 9, "faults": []})
    with pytest.raises(ValueError, match="'faults' list"):
        FaultPlan.from_json({"version": 1})
    with pytest.raises(ValueError, match="unknown fields"):
        FaultPlan.from_json({"version": 1,
                             "faults": [{"kind": "pool_crash", "gpu": 3}]})
    bad = tmp_path / "bad.json"
    bad.write_text("{not json")
    with pytest.raises(ValueError, match="not valid JSON"):
        FaultPlan.load(str(bad))


def test_fault_plan_generate_is_seeded_and_caps_crashes():
    pools = ["p0", "p1"]
    for seed in range(20):
        plan = FaultPlan.generate(seed, pools=pools, members=["a", "b"],
                                  n=4, max_slot=8)
        again = FaultPlan.generate(seed, pools=pools, members=["a", "b"],
                                   n=4, max_slot=8)
        assert plan == again                    # same seed, same plan
        crashes = [f for f in plan.faults if f.kind == "pool_crash"]
        assert len(crashes) <= len(pools) - 1   # a survivor always remains
        assert plan.seed == seed
    assert any(FaultPlan.generate(s, pools=pools, n=4) !=
               FaultPlan.generate(s + 1, pools=pools, n=4)
               for s in range(5))


def test_recovery_config_validation():
    RecoveryConfig()                            # defaults are valid
    with pytest.raises(ValueError, match="max_retries"):
        RecoveryConfig(max_retries=-1)
    with pytest.raises(ValueError, match="backoff_s"):
        RecoveryConfig(backoff_s=-0.1)
    with pytest.raises(ValueError, match="run_timeout_s"):
        RecoveryConfig(run_timeout_s=0.0)
    with pytest.raises(ValueError, match="timeout_strikes"):
        RecoveryConfig(timeout_strikes=0)


# --------------------------------------------------------------------------
# executor: retry, escalation, record stamping
# --------------------------------------------------------------------------
def _one_pool(**kw):
    return _stub_fleet(cores=("c", "p"), names=["a", "b"],
                       policy=WeightedFair(), service_steps=2, **kw)


def test_run_error_retried_and_clean_replay_matches():
    plan = FaultPlan(faults=(
        Fault(kind="run_error", pool="pool0", slot=1, member="a", times=2),))
    fleet = _one_pool()
    fleet.executor.injector = FaultInjector(plan)
    reqs = [Request(i, model="ab"[i % 2]) for i in range(6)]
    res = replay(fleet, reqs, [0] * 6)
    assert res.metrics.completed == 6           # retry absorbed the fault
    assert fleet.executor.retries == 2
    assert max(r.retries for r in fleet.stream) == 2
    # retries ride the JSON schema but stay out of the signature: a clean
    # (injector-free) replay of the faulted recording matches bitwise
    rt = stream_from_json(stream_to_json(fleet.stream, pool="pool0"))
    assert [r.retries for r in rt] == [r.retries for r in fleet.stream]
    fresh = _one_pool()
    res_rep = fresh.executor.replay(
        rt, [Request(i, model="ab"[i % 2]) for i in range(6)], [0] * 6)
    assert res_rep.outputs == res.outputs
    assert stream_signature(fresh.stream) == stream_signature(fleet.stream)
    assert all(r.retries == 0 for r in fresh.stream)


def test_retries_exhausted_escalate_to_pool_crash():
    plan = FaultPlan(faults=(
        Fault(kind="run_error", pool="pool0", slot=0, times=5),))
    fleet = _one_pool()
    fleet.executor.injector = FaultInjector(plan)
    fleet.executor.recovery = RecoveryConfig(max_retries=1)
    fleet.submit(Request(0, model="a"))
    with pytest.raises(PoolCrash, match="still failing after 2 attempts"):
        fleet.step()
    assert fleet.executor.retries == 2


def test_injector_fires_deterministically():
    plan = FaultPlan(faults=(
        Fault(kind="run_error", pool="p0", slot=0, times=1),))
    inj = FaultInjector(plan)
    with pytest.raises(InjectedFault):
        inj.before("p0", Run(member="a"), 0)
    inj.before("p0", Run(member="a"), 0)        # times=1: fires once
    inj.before("p1", Run(member="a"), 0)        # wrong pool: never
    assert inj.summary()["faults"][0]["fired"] == 1


# --------------------------------------------------------------------------
# router: crash recovery, dropped SENDs, degradation
# --------------------------------------------------------------------------
def _mk_router(injector=None, shed=False, **kw):
    def pool():
        f = _stub_fleet(cores=("c", "p"), names=["a", "b"],
                        policy=WeightedFair(), service_steps=2,
                        max_queue=16)
        if shed:                    # member admission -> SLO shedding
            for m in f.members:
                m.engine.policy = ShedPolicy()
        return f
    return MultiPoolRouter({"p0": pool(), "p1": pool()},
                           injector=injector, **kw)


def _statuses(res):
    return {c.ticket.rid: c.metrics.status for c in res.completions}


def test_pool_crash_recovers_unretired_requests_on_survivor():
    plan = FaultPlan(faults=(Fault(kind="pool_crash", pool="p0", slot=2),))
    router = _mk_router(injector=FaultInjector(plan))
    reqs = [Request(i, model="ab"[i % 2]) for i in range(8)]
    for r in reqs:
        router.submit(r)
    res = router.drain()
    # exactly-once: every request retired exactly once, none lost
    assert sorted(c.ticket.rid for c in res.completions) == list(range(8))
    assert router.duplicates_dropped == 0
    assert router.dead == {"p0": router.dead["p0"]}
    assert "injected crash" in router.dead["p0"]
    st = _statuses(res)
    assert set(st.values()) <= {"ok", "recovered"}
    assert "recovered" in st.values()           # p0 held work when it died
    assert any(e[0] == "fail" and e[2] == "p0" for e in router.events)
    assert any(e[0] == "recover" for e in router.events)
    # post-crash submissions avoid the dead pool
    t = router.submit(Request(99, model="a"))
    assert router.placements[-1][1] == "p1"
    assert router.drain().completions[-1].ticket.rid == t.rid


def test_faulted_crash_run_replays_bitwise():
    plan = FaultPlan(faults=(Fault(kind="pool_crash", pool="p0", slot=2),))
    live = _mk_router(injector=FaultInjector(plan))
    reqs = [Request(i, model="ab"[i % 2]) for i in range(8)]
    for r in reqs:
        live.submit(r)
    res_live = live.drain()

    rt = {name: stream_from_json(stream_to_json(recs, pool=name))
          for name, recs in live.streams().items()}
    fresh = _mk_router()                        # no injector attached
    res_rep = fresh.replay(rt, live.placements,
                           [Request(i, model="ab"[i % 2]) for i in range(8)],
                           events=live.events)
    assert stream_signature(fresh.stream()) == \
        stream_signature(live.stream())
    assert fresh.events == live.events
    assert _statuses(res_rep) == _statuses(res_live)
    assert res_rep.outputs == res_live.outputs
    assert fresh.dead.keys() == live.dead.keys()


def test_dropped_send_rerouted_and_replays():
    plan = FaultPlan(faults=(Fault(kind="send_drop", pool="p1", slot=0),))
    live = _mk_router(injector=FaultInjector(plan))
    reqs = [Request(i, model="a") for i in range(6)]
    for r in reqs:
        live.submit(r)
    queued_p1 = live.executors["p1"].fleet.queued
    assert queued_p1 >= 1
    moved = live.migrate("p1", "p0")
    assert moved == 0                           # lost in transit
    assert any(e[0] == "drop" for e in live.events)
    res_live = live.drain()
    st = _statuses(res_live)
    assert sorted(st) == list(range(6))         # nothing lost
    assert list(st.values()).count("recovered") == queued_p1
    assert live.dead == {} and live.duplicates_dropped == 0

    rt = {name: stream_from_json(stream_to_json(recs, pool=name))
          for name, recs in live.streams().items()}
    fresh = _mk_router()
    res_rep = fresh.replay(rt, live.placements,
                           [Request(i, model="a") for i in range(6)],
                           events=live.events)
    assert stream_signature(fresh.stream()) == \
        stream_signature(live.stream())
    assert fresh.events == live.events
    assert _statuses(res_rep) == st
    assert res_rep.outputs == res_live.outputs


def test_timeout_strikes_degrade_pool():
    recovery = RecoveryConfig(run_timeout_s=1e-12, timeout_strikes=2)
    router = _mk_router(recovery=recovery)
    for i in range(8):
        router.submit(Request(i, model="ab"[i % 2]))
    res = router.drain()
    assert res.metrics.completed == 8
    # every RUN beats a 1ps timeout: the first pool over the strike
    # threshold degrades (drained, no longer placed on); its sibling
    # keeps serving because degradation requires a placeable survivor
    assert router.degraded == {"p0"}
    assert router.executors["p0"].timeouts >= 2
    t = router.submit(Request(99, model="a"))
    assert router.placements[-1][1] == "p1"
    assert router.drain().completions[-1].ticket.rid == t.rid


def test_crash_of_sole_server_fails_requests_explicitly():
    # p1 cannot serve model "only0": requests stranded by p0's crash
    # complete as status="failed", never silently vanish
    def pool(names):
        return _stub_fleet(cores=("c", "p")[:len(names)], names=names,
                           policy=WeightedFair(), service_steps=3)
    plan = FaultPlan(faults=(Fault(kind="pool_crash", pool="p0", slot=1),))
    router = MultiPoolRouter({"p0": pool(["only0", "b"]),
                              "p1": pool(["b"])},
                             injector=FaultInjector(plan))
    for i in range(4):
        router.submit(Request(i, model="only0"))
    res = router.drain()
    st = _statuses(res)
    assert sorted(st) == list(range(4))
    assert "failed" in st.values()
    assert all(c.output is None for c in res.completions
               if c.metrics.status == "failed")
    with pytest.raises(KeyError, match="no pool serves"):
        router.submit(Request(9, model="only0"))


def test_replay_reports_pointed_mismatch_not_keyerror():
    # a recovery log claiming p0 died at seq 0 contradicts p0's recorded
    # stream (which keeps retiring work): the offending rid is named in
    # a ValueError, not surfaced as a bare KeyError lookup failure
    live = _mk_router()
    for i in range(4):
        live.submit(Request(i, model="ab"[i % 2]))
    live.drain()
    rt = {name: stream_from_json(stream_to_json(recs, pool=name))
          for name, recs in live.streams().items()}
    fresh = _mk_router()
    with pytest.raises(ValueError, match=r"placement log .*disagree"):
        fresh.replay(rt, live.placements,
                     [Request(i, model="ab"[i % 2]) for i in range(4)],
                     events=[("fail", 0, "p0")])
    fresh2 = _mk_router()
    with pytest.raises(ValueError, match="unknown recovery event kind"):
        fresh2.replay(rt, live.placements,
                      [Request(i, model="ab"[i % 2]) for i in range(4)],
                      events=[("meteor", 0, "p0")])


# --------------------------------------------------------------------------
# SLO shedding + status metrics
# --------------------------------------------------------------------------
def test_shed_policy_validation():
    with pytest.raises(ValueError, match="clock"):
        ShedPolicy(clock="sundial")
    with pytest.raises(ValueError, match="slo_s"):
        ShedPolicy(slo_s=0.0, clock="wall")
    with pytest.raises(ValueError, match="wall-clock"):
        ShedPolicy(slo_s=1.0, clock="slot")


def test_slot_deadline_requests_shed_not_lost():
    from test_fleet import StubEngine

    eng = StubEngine(capacity=1, service_steps=1, policy=ShedPolicy())
    eng._slot = 1                   # StubEngine has no scheduler loop of
    for i, dl in enumerate([None, 0, 99]):      # its own; pin the clock
        eng.submit(Request(i, deadline=dl))
    res = eng.drain()
    st = {c.ticket.rid: c.metrics.status for c in res.completions}
    # capacity 1 admits rid 0 first; rid 1 (deadline slot 0 < clock 1)
    # expires in queue and sheds at admission; rid 2's slack survives
    assert st == {0: "ok", 1: "shed", 2: "ok"}
    assert [c.output for c in res.completions
            if c.metrics.status == "shed"] == [None]
    m = res.metrics
    assert (m.count("shed"), m.count("ok")) == (1, 2)
    assert m.goodput() == 2
    assert res.stats                            # result() stays intact


def test_everything_shed_stays_json_safe():
    from test_fleet import StubEngine

    eng = StubEngine(capacity=1, service_steps=1, policy=ShedPolicy())
    eng.submit(Request(0, model="a", deadline=0))
    eng.submit(Request(1, model="a", deadline=0))
    eng._slot = 5                               # every deadline is past
    res = eng.drain()
    s = res.metrics.summary()
    assert s["shed"] == 2 and s["completed"] == 2   # retired, not lost
    assert s["requests_per_s"] == 0.0               # but zero served
    assert s["goodput_fps"] == 0.0
    assert s["p50_ms"] is None and s["p95_ms"] is None
    assert s["per_model"]["a"]["shed"] == 2
    json.dumps(s)                               # lands in BENCH JSONs


def test_fleet_slot_clock_sheds_deterministically():
    # the fleet executor clocks members with the *fleet* slot before each
    # RUN — live, compiled and replayed runs shed the identical set
    from repro.fleet import compile_fleet, validate_stream

    # member admission policy = ShedPolicy, fleet scheduling policy =
    # WeightedFair
    def build():
        f = _stub_fleet(cores=("c", "p"), names=["a", "b"],
                        policy=WeightedFair(), service_steps=2,
                        capacity=1)
        for m in f.members:
            m.engine.policy = ShedPolicy()
        return f

    reqs = [Request(i, model="a", deadline=3) for i in range(6)]
    arr = [0] * 6
    compiled = compile_fleet(build(), reqs, arr)
    validate_stream(compiled)
    live = build()
    res_live = replay(live, [Request(i, model="a", deadline=3)
                             for i in range(6)], arr)
    st = {c.ticket.rid: c.metrics.status for c in res_live.completions}
    assert sorted(st) == list(range(6))
    assert "shed" in st.values()                # capacity 1, deadline 3
    assert stream_signature(compiled) == stream_signature(live.stream)
    fresh = build()
    res_rep = fresh.executor.replay(
        live.stream, [Request(i, model="a", deadline=3) for i in range(6)],
        arr)
    assert {c.ticket.rid: c.metrics.status
            for c in res_rep.completions} == st


# --------------------------------------------------------------------------
# the property: faulted runs replay bitwise, across seeded plans
# --------------------------------------------------------------------------
def _drive(router, reqs, arrivals, migrate_at=3):
    """Open-loop drive with a forced mid-run migration attempt (so SEND
    faults have a boundary to fire at)."""
    order = sorted(range(len(reqs)), key=lambda i: arrivals[i])
    nxt, step, refused = 0, 0, []
    while nxt < len(order) or refused or router.has_work:
        due, refused = refused, []
        while nxt < len(order) and arrivals[order[nxt]] <= step:
            due.append(order[nxt])
            nxt += 1
        for i in due:
            try:
                router.submit(reqs[i])
            except QueueFull:
                refused.append(i)
        if (step == migrate_at and not router.dead
                and router.executors["p1"].fleet.queued):
            router.migrate("p1", "p0")
        if router.has_work:
            router.step()
        step += 1
    return router.result()


@pytest.mark.parametrize("seed", range(25))
def test_seeded_fault_plans_replay_bitwise(seed):
    """The acceptance property, swept over 25 seeded plans: a faulted
    live run (crashes, injected RUN errors, dropped SENDs, latency skew,
    under slot-deadline shedding) replays bitwise from its recorded
    streams + placements + recovery events — same stream signatures,
    same shed set, same recovered/failed rids, same outputs — with no
    injector attached."""
    n = 12
    plan = FaultPlan.generate(seed, pools=["p0", "p1"],
                              members=["a", "b"], n=3, max_slot=6)
    arrivals = poisson_arrivals(n, rate=2.0, seed=seed)

    def reqs():
        return [Request(i, model="ab"[i % 2],
                        deadline=arrivals[i] + 5 + (i % 3))
                for i in range(n)]

    live = _mk_router(injector=FaultInjector(plan), shed=True)
    res_live = _drive(live, reqs(), arrivals)
    st_live = _statuses(res_live)
    assert sorted(st_live) == list(range(n))    # exactly once, none lost
    assert live.duplicates_dropped == 0

    rt = {name: stream_from_json(stream_to_json(recs, pool=name))
          for name, recs in live.streams().items()}
    fresh = _mk_router(shed=True)
    res_rep = fresh.replay(rt, live.placements, reqs(),
                           events=live.events)
    assert stream_signature(fresh.stream()) == \
        stream_signature(live.stream())
    assert fresh.events == live.events
    assert _statuses(res_rep) == st_live
    assert res_rep.outputs == res_live.outputs
    assert fresh.dead.keys() == live.dead.keys()


@pytest.mark.parametrize("seed", [1, 4])
def test_cnn_pool_crash_recovers_and_replays_bitwise(seed):
    """Real pipeline members: killing one of two single-model CNN pools
    mid-run re-routes its in-flight work to the survivor (with a crash
    REBALANCE re-leasing the survivor's split) and the faulted run
    replays bitwise — output arrays included."""
    def pools():
        e0, _ = build_cnn_fleet(["squeezenet"], use_pallas=False)
        e1, _ = build_cnn_fleet(["squeezenet"], use_pallas=False)
        return {"p0": e0, "p1": e1}

    def reqs():
        keys = jax.random.split(jax.random.PRNGKey(seed), 6)
        return [Request(jax.random.normal(k, (1, 32, 32, 3)),
                        model="squeezenet") for k in keys]

    plan = FaultPlan(faults=(Fault(kind="pool_crash", pool="p0",
                                   slot=1 + seed % 2),), seed=seed)
    live = MultiPoolRouter(pools(), injector=FaultInjector(plan),
                           plan_evals=1)
    for r in reqs():
        live.submit(r)
    res_live = live.drain()
    st = _statuses(res_live)
    assert sorted(st) == list(range(6))
    assert set(st.values()) <= {"ok", "recovered"}
    assert "recovered" in st.values()
    assert list(live.dead) == ["p0"]
    # graceful degradation re-leased theta on the survivor
    assert any(p == "p1" for p, _t in live.rebalances)

    rt = {name: stream_from_json(stream_to_json(recs, pool=name))
          for name, recs in live.streams().items()}
    fresh = MultiPoolRouter(pools(), plan_evals=1)
    res_rep = fresh.replay(rt, live.placements, reqs(),
                           events=live.events)
    assert stream_signature(fresh.stream()) == \
        stream_signature(live.stream())
    assert _statuses(res_rep) == st
    for a, b in zip(res_rep.outputs, res_live.outputs):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


# --------------------------------------------------------------------------
# CLI: --faults / --slo-ms validation (exit 2, never a traceback)
# --------------------------------------------------------------------------
def test_serve_fleet_rejects_bad_slo_and_plans(tmp_path):
    from repro.launch import serve

    with pytest.raises(SystemExit) as ei:
        serve.main(["fleet", "--slo-ms", "-5"])
    assert ei.value.code == 2
    with pytest.raises(SystemExit) as ei:
        serve.main(["fleet", "--slo-ms", "0"])
    assert ei.value.code == 2
    bad = tmp_path / "bad.json"
    bad.write_text("{not json")
    with pytest.raises(SystemExit) as ei:
        serve.main(["fleet", "--faults", str(bad)])
    assert ei.value.code == 2
    stale = tmp_path / "stale.json"
    stale.write_text(json.dumps({"version": 99, "faults": []}))
    with pytest.raises(SystemExit) as ei:
        serve.main(["fleet", "--faults", str(stale)])
    assert ei.value.code == 2
    with pytest.raises(SystemExit) as ei:
        serve.main(["fleet", "--faults", str(tmp_path / "missing.json")])
    assert ei.value.code == 2
