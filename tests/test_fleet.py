"""Fleet serving (ISSUE-5): per-model outputs bitwise-equal to standalone
engines, router fairness under a skewed Poisson mix, per-member QueueFull
isolation, deadline-EDF / priority admission ordering, the planner /
Table-VII cross-check, and the committed BENCH_fleet.json acceptance."""
import json
import os
import sys
import time

import jax
import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))            # repo root -> benchmarks pkg

from repro.core.arch import DUAL_MULTI
from repro.core.search import harmonic_mean
from repro.fleet import (DeadlineEDF, DevicePool, FleetEngine, RoundRobin,
                         Router, ShortestQueue, WeightedFair,
                         build_cnn_fleet, make_policy, mix_schedule,
                         normalize_mix, plan_fleet, plan_rows)
from repro.serving import (DeadlineAdmission, Engine, EngineBase,
                           FixedRateAdmission, PriorityAdmission,
                           QueueFull, Request, poisson_arrivals, replay)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


# --------------------------------------------------------------------------
# a minimal member engine: the fleet's cross-engine logic under test needs
# queues and slots, not a real network
# --------------------------------------------------------------------------
class StubEngine(EngineBase):
    """Serves any payload in ``service_steps`` slots; declares a fixed
    dominant core so co-dispatch ordering is controllable.  Mirrors the
    CNN engine's two-phase ``advance``/``retire`` split and can record
    its dispatch order into a shared ``trace`` list."""

    def __init__(self, *, capacity=2, service_steps=1, core="c",
                 max_queue=None, policy=None, name=None, trace=None):
        super().__init__(max_queue=max_queue)
        self.policy = policy or FixedRateAdmission(1)
        self.capacity = capacity
        self.service_steps = service_steps
        self._core = core
        self._name = name
        self._trace = trace
        self._flight: list[list] = []       # [remaining, rid, payload]

    @property
    def in_flight(self):
        return len(self._flight)

    @property
    def has_work(self):
        return bool(self._pending or self._flight)

    @property
    def next_core(self):
        return self._core if self.has_work else None

    def advance(self):
        self._start_clock()
        if self._trace is not None:
            self._trace.append(self._name)
        for f in self._flight:
            f[0] -= 1
        finished = [f for f in self._flight if f[0] <= 0]
        self._flight = [f for f in self._flight if f[0] > 0]
        n = self.policy.admit(queued=len(self._pending),
                              in_flight=len(self._flight),
                              capacity=self.capacity)
        for _ in range(max(0, min(n, len(self._pending),
                                  self.capacity - len(self._flight)))):
            popped = self._pop_admission()      # None: the rest was shed
            if popped is None:
                break
            req, _t = popped
            self._metrics[req.rid].started_at = time.perf_counter()
            self._flight.append([self.service_steps, req.rid, req.payload])
        return finished

    def retire(self, finished):
        out = self._take_shed()
        out.extend(self._finish(rid, payload)
                   for _, rid, payload in finished)
        return out

    def step(self):
        return self.retire(self.advance())


def _stub_fleet(cores=("c", "p"), names=None, weights=None, policy=None,
                co_dispatch=None, trace=None, **stub_kw):
    names = names or [f"m{i}" for i in range(len(cores))]
    members = {n: StubEngine(core=c, name=n, trace=trace, **stub_kw)
               for n, c in zip(names, cores)}
    return FleetEngine(members, weights=weights, policy=policy,
                       co_dispatch=co_dispatch)


# --------------------------------------------------------------------------
# pool + router basics
# --------------------------------------------------------------------------
def test_pool_lease_exclusive_and_release():
    pool = DevicePool(jax.devices())
    dual = pool.lease("mobilenet_v1")
    assert dual is pool.dual                 # one shared split, no copies
    assert pool.lease("squeezenet") is dual
    with pytest.raises(ValueError, match="already held"):
        pool.lease("mobilenet_v1")
    pool.release("mobilenet_v1")
    assert pool.lease("mobilenet_v1") is dual
    with pytest.raises(KeyError):
        pool.release("never_leased")
    assert set(pool.stats()["leases"]) == {"mobilenet_v1", "squeezenet"}


def test_router_routes_and_rejects():
    r = Router(["a", "b"])
    assert r.route(Request(0, model="b")) == "b"
    with pytest.raises(KeyError, match="no member serves"):
        r.route(Request(0, model="zzz"))
    with pytest.raises(KeyError, match="untagged"):
        r.route(Request(0))                  # ambiguous in a 2-member fleet
    assert Router(["solo"]).route(Request(0)) == "solo"
    with pytest.raises(ValueError, match="duplicate"):
        Router(["a", "a"])
    with pytest.raises(ValueError, match="unknown scheduling policy"):
        make_policy("nope")


def test_mix_schedule_realizes_shares_deterministically():
    mix = {"a": 0.5, "b": 0.3, "c": 0.2}
    tags = mix_schedule(mix, 10)
    assert tags == mix_schedule(mix, 10)
    assert {t: tags.count(t) for t in mix} == {"a": 5, "b": 3, "c": 2}
    # interleaved, not model-sized bursts: 'a' never waits 3 slots
    assert all("a" in tags[i:i + 3] for i in range(0, 8))
    with pytest.raises(ValueError, match="> 0"):
        normalize_mix({"a": 1.0, "b": 0.0})


# --------------------------------------------------------------------------
# fleet engine mechanics (stub members)
# --------------------------------------------------------------------------
def test_fleet_satisfies_engine_protocol():
    assert isinstance(_stub_fleet(), Engine)


def test_fleet_routes_and_completes_tagged_requests():
    eng = _stub_fleet(cores=("c", "p"), names=["a", "b"])
    for i, m in enumerate(["a", "b", "a"]):
        t = eng.submit(Request(100 + i, model=m))
        assert t.rid == i
    res = eng.drain()
    assert res.outputs == [100, 101, 102]    # fleet submission order
    assert [m.model for m in res.metrics.requests] == ["a", "b", "a"]
    assert res.stats["per_member"]["a"]["completed"] == 2
    assert res.metrics.by_model()["a"]["completed"] == 2
    assert "per_model" in res.metrics.summary()


def test_fleet_co_dispatch_orders_complementary_core_first():
    """A fleet slot dispatches the policy's primary first, then the
    remaining members with the core-complementary one ahead — the
    cross-network Fig.4b ordering — and ``co_dispatch`` bounds the
    slot width (0 = strict policy-only stepping)."""
    trace = []
    eng = _stub_fleet(cores=("c", "c", "p"), names=["a", "b", "p1"],
                      trace=trace)
    for name in ("a", "b", "p1"):
        eng.submit(Request(0, model=name))
    eng.step()
    # primary a (round-robin), then p1 (opposite core), then b
    assert trace == ["a", "p1", "b"]
    assert [m.dispatches for m in eng.members] == [1, 1, 1]
    # bounded width: only the primary + one complementary co-dispatch
    trace2 = []
    eng2 = _stub_fleet(cores=("c", "c", "p"), names=["a", "b", "p1"],
                       trace=trace2, co_dispatch=1)
    for name in ("a", "b", "p1"):
        eng2.submit(Request(0, model=name))
    eng2.step()
    assert trace2 == ["a", "p1"]
    # co_dispatch=0: one member per slot, the policy's pick only
    solo = _stub_fleet(cores=("c", "p"), names=["a", "b"], co_dispatch=0)
    solo.submit(Request(1, model="a"))
    solo.submit(Request(2, model="b"))
    solo.step()
    assert sorted(m.dispatches for m in solo.members) == [0, 1]
    with pytest.raises(ValueError, match="co_dispatch"):
        _stub_fleet(co_dispatch=-1)


def test_burst_advances_consecutive_slots_before_retiring():
    """burst=k advances each batched member k slots back-to-back (the
    locality amortization), retiring once at the end; completions and
    accounting stay exact."""
    trace = []
    eng = _stub_fleet(cores=("c", "p"), names=["a", "b"], trace=trace,
                      capacity=2, service_steps=2)
    eng.burst = 3
    for name in ("a", "a", "b"):
        eng.submit(Request(0, model=name))
    eng.step()
    assert trace == ["a", "a", "a", "b", "b", "b"]
    assert eng._by_name["a"].dispatches == 3
    res = eng.drain()
    assert res.metrics.completed == 3
    with pytest.raises(ValueError, match="burst"):
        FleetEngine({"m": StubEngine()}, burst=0)


def test_backpressure_isolated_per_member_queue():
    """A full member queue raises QueueFull for that model's traffic only,
    and the failed submit leaves no trace in the fleet accounting."""
    eng = _stub_fleet(cores=("c", "p"), names=["a", "b"],
                      capacity=1, service_steps=3, max_queue=1)
    eng.submit(Request(0, model="a"))
    with pytest.raises(QueueFull):
        eng.submit(Request(1, model="a"))    # a's queue is full...
    eng.submit(Request(2, model="b"))        # ...b's is not
    with pytest.raises(QueueFull):
        eng.submit(Request(3, model="b"))    # now b's is full as well
    eng.step()      # c/p-complementary co-dispatch admits both queues
    eng.submit(Request(1, model="a"))        # freed: accepted now
    eng.submit(Request(3, model="b"))
    res = eng.drain()
    assert res.metrics.completed == 4        # only successful submits exist
    assert [c.ticket.rid for c in res.completions] == [0, 1, 2, 3]
    assert res.outputs == [0, 2, 1, 3]       # fleet submission order


def test_replay_retries_through_member_backpressure():
    eng = _stub_fleet(cores=("c", "p"), names=["a", "b"],
                      capacity=1, service_steps=2, max_queue=1)
    reqs = [Request(i, model=("a" if i % 2 == 0 else "b"))
            for i in range(6)]
    res = replay(eng, reqs, [0] * 6)
    assert res.metrics.completed == 6
    assert res.outputs == list(range(6))


def test_replay_queuefull_does_not_block_other_members():
    """A refused submit (member queue full) must not head-of-line block
    same-step traffic for other members: replay retries the refused
    request later but keeps submitting past it."""
    eng = _stub_fleet(cores=("c", "p"), names=["a", "b"],
                      capacity=1, service_steps=4, max_queue=1)
    # two a-requests due at step 0 — the second is refused (a's queue
    # holds one) — then a b-request also due at step 0
    reqs = [Request(0, model="a"), Request(1, model="a"),
            Request(2, model="b")]
    res = replay(eng, reqs, [0, 0, 0])
    assert res.metrics.completed == 3
    # b was admitted at slot 0 alongside a's first request, not behind
    # a's retry: their start stamps precede the refused request's
    m = {r.model: [] for r in res.metrics.requests}
    for r in res.metrics.requests:
        m[r.model].append(r.started_at)
    assert min(m["b"]) < max(m["a"])


def test_weighted_fair_tracks_skewed_mix():
    """Dispatch counts stay within one slot of the weighted entitlement
    while every member has backlog (deficit round-robin), and a skewed
    Poisson trace drains fully."""
    weights = {"a": 0.6, "b": 0.3, "c": 0.1}
    eng = _stub_fleet(cores=("c", "p", "c"), names=list(weights),
                      weights=weights, policy=WeightedFair(),
                      co_dispatch=0, capacity=1, service_steps=2)
    for name in mix_schedule(weights, 30):
        eng.submit(Request(0, model=name))
    steps = 20
    for _ in range(steps):
        eng.step()
    for m in eng.members:
        assert abs(m.dispatches - weights[m.name] * steps) <= 1.0, \
            (m.name, m.dispatches)
    # skewed Poisson arrivals: everything still completes, mix preserved
    eng2 = _stub_fleet(cores=("c", "p", "c"), names=list(weights),
                       weights=weights, policy=WeightedFair(),
                       capacity=2, service_steps=1)
    tags = mix_schedule(weights, 20)
    res = replay(eng2, [Request(i, model=t) for i, t in enumerate(tags)],
                 poisson_arrivals(20, rate=2.0, seed=3))
    assert res.metrics.completed == 20
    assert res.metrics.by_model()["a"]["completed"] == tags.count("a")


def test_weighted_fair_zero_weights_degrade_to_equal_share():
    """All-zero weights must fall back to equal entitlement (alternating
    picks), not collapse to lowest-index-first."""
    from repro.fleet import MemberView

    def view(i, dispatches):
        return MemberView(index=i, name=f"m{i}", queued=1, in_flight=0,
                          weight=0.0, dispatches=dispatches,
                          head_deadline=None, next_core="c", has_work=True)

    wf = WeightedFair()
    picks = []
    counts = [0, 0]
    for t in range(6):
        i = wf.pick([view(0, counts[0]), view(1, counts[1])], t)
        counts[i] += 1
        picks.append(i)
    assert counts == [3, 3]              # equal share, not always m0


def test_round_robin_and_shortest_queue_policies():
    eng = _stub_fleet(cores=("c", "c", "c"), names=["a", "b", "c"],
                      policy=RoundRobin(), co_dispatch=0,
                      capacity=1, service_steps=1)
    for name in ("a", "b", "c"):
        eng.submit(Request(0, model=name))
        eng.submit(Request(1, model=name))
    for _ in range(6):
        eng.step()
    assert [m.dispatches for m in eng.members] == [2, 2, 2]
    sq = _stub_fleet(cores=("c", "c"), names=["big", "small"],
                     policy=ShortestQueue(), co_dispatch=0,
                     capacity=1, service_steps=1)
    for _ in range(4):
        sq.submit(Request(0, model="big"))
    sq.submit(Request(0, model="small"))
    sq.step()                               # least outstanding work first
    assert sq._by_name["small"].dispatches == 1
    assert sq._by_name["big"].dispatches == 0


def test_deadline_edf_orders_admissions_and_members():
    """Member-level DeadlineAdmission admits the earliest deadline first
    (completion order follows deadlines, not submission); fleet-level
    DeadlineEDF steps the member holding the most urgent queued request."""
    m = StubEngine(core="c", capacity=1, service_steps=1,
                   policy=DeadlineAdmission())
    eng = FleetEngine({"m": m}, co_dispatch=0)
    # deadlines deliberately out of submission order; None sorts last
    for payload, dl in [(0, 30.0), (1, 10.0), (2, 20.0), (3, None),
                        (4, 5.0)]:
        eng.submit(Request(payload, deadline=dl))
    finished = []
    while eng.has_work:
        finished.extend(c.output for c in eng.step())
    assert finished == [4, 1, 2, 0, 3]      # EDF admission order
    assert eng.result().outputs == [0, 1, 2, 3, 4]   # submit order kept
    # fleet-level: the member whose head deadline is earliest goes first
    fleet = FleetEngine({"a": StubEngine(core="c"),
                         "b": StubEngine(core="c")},
                        policy=DeadlineEDF(), co_dispatch=0)
    fleet.submit(Request(0, model="a", deadline=20.0))
    fleet.submit(Request(1, model="b", deadline=5.0))
    fleet.step()
    assert fleet._by_name["b"].dispatches == 1
    assert fleet._by_name["a"].dispatches == 0


def test_priority_admission_orders_queue():
    m = StubEngine(core="c", capacity=1, service_steps=1,
                   policy=PriorityAdmission())
    eng = FleetEngine({"m": m}, co_dispatch=0)
    for payload, prio in [(0, 0), (1, 5), (2, 1)]:
        eng.submit(Request(payload, priority=prio))   # untagged: solo member
    finished = []
    while eng.has_work:
        finished.extend(c.output for c in eng.step())
    assert finished == [1, 2, 0]            # high priority first, then FIFO


def test_fleet_admission_map_installs_member_policies():
    members = {"a": StubEngine(core="c"), "b": StubEngine(core="p")}
    edf = DeadlineAdmission()
    FleetEngine(members, admission={"a": edf})
    assert members["a"].policy is edf
    assert isinstance(members["b"].policy, FixedRateAdmission)
    with pytest.raises(KeyError, match="unknown member"):
        FleetEngine({"a": StubEngine()}, admission={"zzz": edf})


# --------------------------------------------------------------------------
# real engines: bitwise parity + shared pool
# --------------------------------------------------------------------------
def test_fleet_outputs_bitwise_equal_standalone_engines():
    """Per-model outputs through the fleet are bitwise-identical to each
    model's standalone engine (same params seed, same step program): the
    fleet multiplexes, it never touches the math."""
    from repro.models.cnn import build_model
    from repro.serving import stream_images

    models = ["mobilenet_v1", "squeezenet"]
    eng, pool = build_cnn_fleet(models, use_pallas=False, fuse=False)
    assert set(pool.stats()["leases"]) == set(models)
    tags = mix_schedule({m: 0.5 for m in models}, 4)
    imgs = [jax.random.normal(k, (1, 32, 32, 3))
            for k in jax.random.split(jax.random.PRNGKey(0), 4)]
    res = replay(eng, [Request(x, model=t) for x, t in zip(imgs, tags)],
                 poisson_arrivals(4, rate=1.0, seed=0))
    assert res.metrics.completed == 4
    by_model: dict[str, list] = {m: [] for m in models}
    for t, x in zip(tags, imgs):
        by_model[t].append(x)
    standalone = {}
    for m in models:
        params, _, graph = build_model(m)
        from repro.core.arch import BoardModel, DUAL_BASELINE
        from repro.core.scheduler import build_schedule
        from repro.dualcore.runtime import DualCoreRunner

        sched = build_schedule(graph, DUAL_BASELINE, BoardModel(),
                               "balanced")
        runner = DualCoreRunner(m, params, sched, use_pallas=False,
                                fuse=False)
        standalone[m] = iter(stream_images(runner, by_model[m]).outputs)
    for t, out in zip(tags, res.outputs):
        np.testing.assert_array_equal(np.asarray(out),
                                      np.asarray(next(standalone[t])))
    # per-model latency breakdown present for every member
    assert set(res.metrics.by_model()) == set(models)


def test_real_engines_co_dispatch_on_shared_pool():
    """With real runners the interleaved fleet issues more member
    dispatches than fleet slots — cross-network groups share slots."""
    eng, _ = build_cnn_fleet(["mobilenet_v1", "squeezenet"],
                             use_pallas=False, fuse=False)
    imgs = [jax.random.normal(k, (1, 32, 32, 3))
            for k in jax.random.split(jax.random.PRNGKey(1), 4)]
    tags = mix_schedule({"mobilenet_v1": 0.5, "squeezenet": 0.5}, 4)
    for x, t in zip(imgs, tags):
        eng.submit(Request(x, model=t))
    res = eng.drain()
    assert res.stats["dispatches"] > res.stats["slots"]
    assert res.metrics.completed == 4


def test_build_cnn_fleet_serves_the_given_params():
    """``params=`` replaces ``build_model``'s weights for every member:
    each output is bitwise what a standalone engine computes with those
    weights (and not what the default weights give), and a map that
    leaves a member out is refused."""
    from repro.core.arch import BoardModel, DUAL_BASELINE
    from repro.core.scheduler import build_schedule
    from repro.dualcore.runtime import DualCoreRunner
    from repro.models.cnn import init_params
    from repro.models.zoo import get_graph
    from repro.serving import stream_images

    models = ["mobilenet_v1", "squeezenet"]
    params = {m: jax.tree.map(lambda a: a + 0.01, init_params(
        get_graph(m), jax.random.PRNGKey(10 + i)))
        for i, m in enumerate(models)}
    tags = mix_schedule({m: 0.5 for m in models}, 4)
    imgs = [jax.random.normal(k, (1, 32, 32, 3))
            for k in jax.random.split(jax.random.PRNGKey(2), 4)]

    def serve(**kw):
        eng, _ = build_cnn_fleet(models, use_pallas=False, fuse=False, **kw)
        for x, t in zip(imgs, tags):
            eng.submit(Request(x, model=t))
        return [np.asarray(y) for y in eng.drain().outputs]

    given, default = serve(params=params), serve()
    for m in models:
        sched = build_schedule(get_graph(m), DUAL_BASELINE, BoardModel(),
                               "balanced")
        runner = DualCoreRunner(m, params[m], sched, use_pallas=False,
                                fuse=False)
        mine = [x for x, t in zip(imgs, tags) if t == m]
        want = stream_images(runner, mine).outputs
        got = [y for y, t in zip(given, tags) if t == m]
        other = [y for y, t in zip(default, tags) if t == m]
        for g, w, o in zip(got, want, other):
            np.testing.assert_array_equal(g, np.asarray(w))
            assert not np.array_equal(g, o)
    with pytest.raises(ValueError, match="every member"):
        build_cnn_fleet(models, use_pallas=False, fuse=False,
                        params={"squeezenet": params["squeezenet"]})


@pytest.mark.slow
def test_fleet_with_lm_member():
    """LM + CNN mix: a DualMeshEngine rides alongside a DualCoreEngine
    behind the same fleet front end."""
    from repro.configs.registry import get_smoke
    from repro.core.arch import BoardModel, DUAL_BASELINE
    from repro.core.scheduler import build_schedule
    from repro.dualcore.runtime import DualCoreRunner
    from repro.dualmesh import DualMeshRunner, split_mesh
    from repro.lm.model import init_params
    from repro.models.cnn import build_model
    from repro.serving import DualCoreEngine, DualMeshEngine

    cfg = get_smoke("qwen2_0_5b")
    lm = DualMeshEngine(DualMeshRunner(cfg, init_params(
        cfg, jax.random.PRNGKey(0)), split_mesh(jax.devices(), 0.5),
        max_len=16), group_size=1)
    params, _, graph = build_model("squeezenet")
    sched = build_schedule(graph, DUAL_BASELINE, BoardModel(), "balanced")
    cnn = DualCoreEngine(DualCoreRunner("squeezenet", params, sched,
                                        use_pallas=False, fuse=False))
    eng = FleetEngine({"lm": lm, "squeezenet": cnn})
    prompt = jax.random.randint(jax.random.PRNGKey(1), (1, 4), 0,
                                cfg.vocab)
    img = jax.random.normal(jax.random.PRNGKey(2), (1, 32, 32, 3))
    eng.submit(Request(prompt, gen_steps=2, model="lm"))
    eng.submit(Request(img, model="squeezenet"))
    res = eng.drain()
    assert res.metrics.completed == 2
    assert res.outputs[0].shape == (1, 6)      # prompt + 2 generated
    assert res.outputs[1].shape == (1, 1000)
    assert set(res.metrics.by_model()) == {"lm", "squeezenet"}


# --------------------------------------------------------------------------
# planner + Table VII cross-check + committed bench acceptance
# --------------------------------------------------------------------------
def test_weighted_harmonic_mean_is_mix_aggregate():
    fps = [100.0, 400.0]
    # 50/50 mix: each unit of work is 0.5/100 + 0.5/400 seconds
    assert harmonic_mean(fps, [0.5, 0.5]) == pytest.approx(160.0)
    assert harmonic_mean(fps) == pytest.approx(160.0)      # unweighted ==
    assert harmonic_mean(fps, [1.0, 0.0]) == pytest.approx(100.0)
    with pytest.raises(ValueError, match="weights"):
        harmonic_mean(fps, [0.5])
    with pytest.raises(ValueError, match="weights"):
        harmonic_mean(fps, [-1.0, 2.0])


def test_plan_fleet_fixed_config_predictions():
    mix = {"mobilenet_v1": 0.5, "squeezenet": 0.5}
    plan = plan_fleet(mix, config=DUAL_MULTI)
    assert plan.config is DUAL_MULTI
    assert sum(plan.mix.values()) == pytest.approx(1.0)
    agg = harmonic_mean([plan.fps[m] for m in plan.mix],
                        [plan.mix[m] for m in plan.mix])
    assert plan.aggregate_fps == pytest.approx(agg)
    # served shares realize the mix exactly
    for m, s in plan.mix.items():
        assert plan.predicted[m] == pytest.approx(s * plan.aggregate_fps)
    assert sum(plan.predicted.values()) == \
        pytest.approx(plan.aggregate_fps)


def test_build_cnn_fleet_realises_plan_theta():
    """The pool split must use the planned Eq.10 theta, not the default —
    on a multi-device mesh the c/p chip ratio IS the planned config."""
    plan = plan_fleet({"squeezenet": 1.0}, config=DUAL_MULTI)
    eng, pool = build_cnn_fleet(["squeezenet"], plan=plan,
                                use_pallas=False, fuse=False)
    assert pool.theta == plan.theta
    assert eng.members[0].engine.runner.schedule is \
        plan.schedules["squeezenet"]


def test_paper_table_vii_fleet_matches_planner():
    """The Table-VII-style rows printed by benchmarks/paper_tables.py are
    exactly fleet.planner.plan_rows of a live plan (ISSUE-5 satellite)."""
    from benchmarks.paper_tables import FLEET_MIX, table_vii_fleet

    rows = table_vii_fleet(config=DUAL_MULTI,
                           measured_path="/nonexistent.json")
    plan = plan_fleet(FLEET_MIX, config=DUAL_MULTI)
    assert rows == plan_rows(plan)
    assert rows[-1][0] == "aggregate"
    assert rows[-1][3] == pytest.approx(plan.aggregate_fps)


def test_committed_fleet_bench_meets_acceptance():
    """The committed BENCH_fleet.json must show the ISSUE-5 acceptance:
    fleet aggregate fps >= the best sequential one-engine-at-a-time
    baseline on the same host (and the gated fields must be present)."""
    with open(os.path.join(REPO, "BENCH_fleet.json")) as f:
        rep = json.load(f)
    fleet, base = rep["fleet"], rep["baseline"]
    assert base["best_fps"] == pytest.approx(
        max(base["engine_at_a_time_fps"], base["run_sequential_fps"]))
    assert fleet["aggregate_fps"] >= base["best_fps"]
    assert rep["fleet_vs_baseline"] >= 1.0
    assert set(rep["mix"]) == set(fleet["per_model"])
    for m in rep["mix"]:
        assert {"p50_ms", "p95_ms"} <= set(fleet["latency"][m])
