"""Instruction-stream fleet execution (ISSUE-6): schema round-trips,
compile-vs-live bitwise parity, PoolExecutor replay, cross-pool
migration + REBALANCE through the MultiPoolRouter, and the Chrome-tracing
export."""
import json
import os
import sys
import time

import jax
import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))            # repo root -> benchmarks pkg

from test_fleet import StubEngine, _stub_fleet  # noqa: E402

from repro.fleet import (DevicePool, ExecRecord, FleetEngine,  # noqa: E402
                         Free,
                         MultiPoolRouter, Rebalance, Recv, RoundRobin, Run,
                         SCHEMA_VERSION, Send, WeightedFair, build_cnn_fleet,
                         compile_fleet, dump_stream, load_stream,
                         mix_schedule, stream_from_json, stream_signature,
                         stream_to_json, validate_stream)
from repro.fleet.compiler import CompileError  # noqa: E402
from repro.fleet.trace import chrome_trace  # noqa: E402
from repro.serving import (EngineBase, Request, poisson_arrivals,  # noqa: E402
                           replay)


# --------------------------------------------------------------------------
# instruction schema
# --------------------------------------------------------------------------
def test_instruction_json_round_trip():
    from repro.fleet.instructions import instr_from_dict, instr_to_dict

    for instr in (Run(member="a", slots=3, core="c", primary=True),
                  Run(member="lm", fused=True),
                  Free(member="a"),
                  Send(peer="pool1", member="a", count=2),
                  Send(peer="pool1"),              # member/count wildcards
                  Recv(peer="pool0", count=3),
                  Rebalance(theta=0.25)):
        wire = json.loads(json.dumps(instr_to_dict(instr)))
        assert instr_from_dict(wire) == instr


def test_instruction_schema_rejects_drift():
    from repro.fleet.instructions import instr_from_dict

    with pytest.raises(ValueError, match="unknown fleet instruction op"):
        instr_from_dict({"op": "HALT"})
    with pytest.raises(ValueError, match="schema drift"):
        instr_from_dict({"op": "RUN", "member": "a", "gpu": 1})
    with pytest.raises(ValueError, match="schema version"):
        stream_from_json({"version": SCHEMA_VERSION + 1, "records": []})


def test_stream_dump_load_round_trip(tmp_path):
    records = [ExecRecord(instr=Run(member="a", slots=2, core="c",
                                    primary=True),
                          slot=0, seq=0, advances=2, t0=1.0, t1=1.5),
               ExecRecord(instr=Free(member="a"), slot=0, seq=1,
                          advances=0, t0=1.5, t1=1.6),
               # compiled-only records carry no wall-clock stamps
               ExecRecord(instr=Rebalance(theta=0.4), slot=1, seq=2)]
    path = tmp_path / "stream.json"
    dump_stream(records, str(path), pool="pool7")
    with open(path) as f:
        doc = json.load(f)
    assert doc["version"] == SCHEMA_VERSION
    assert doc["pool"] == "pool7"
    loaded = load_stream(str(path))
    assert stream_signature(loaded) == stream_signature(records)
    assert [(r.t0, r.t1) for r in loaded] == \
        [(r.t0, r.t1) for r in records]


def test_validate_stream_invariants():
    ok = [ExecRecord(instr=Run(member="a"), slot=0, seq=0),
          ExecRecord(instr=Free(member="a"), slot=0, seq=1),
          ExecRecord(instr=Run(member="a"), slot=1, seq=2)]
    validate_stream(ok)                      # FREE then next-slot RUN: fine
    with pytest.raises(ValueError, match="slot went backwards"):
        validate_stream([ExecRecord(instr=Run(member="a"), slot=1, seq=0),
                         ExecRecord(instr=Run(member="a"), slot=0, seq=1)])
    with pytest.raises(ValueError, match="seq not strictly increasing"):
        validate_stream([ExecRecord(instr=Run(member="a"), slot=0, seq=0),
                         ExecRecord(instr=Run(member="a"), slot=0, seq=0)])
    with pytest.raises(ValueError, match="dispatch must precede"):
        validate_stream([ExecRecord(instr=Free(member="a"), slot=0, seq=0),
                         ExecRecord(instr=Run(member="b"), slot=0, seq=1)])


# --------------------------------------------------------------------------
# compile-vs-live parity + replay (stub members)
# --------------------------------------------------------------------------
_WEIGHTS = {"a": 0.5, "b": 0.3, "c": 0.2}


def _mk(trace=None):
    return _stub_fleet(cores=("c", "p", "c"), names=list(_WEIGHTS),
                       weights=_WEIGHTS, policy=WeightedFair(),
                       trace=trace, capacity=2, service_steps=2,
                       max_queue=2)


def _reqs(n=12):
    return [Request(i, model=t)
            for i, t in enumerate(mix_schedule(_WEIGHTS, n))]


def test_compiled_stream_matches_live_and_replays_bitwise():
    """The tentpole property: compile_fleet's ahead-of-time stream equals
    the live fleet's recorded stream decision-for-decision, and replaying
    it through a fresh fleet's PoolExecutor reproduces the dispatch trace
    and outputs bitwise."""
    arr = poisson_arrivals(12, rate=1.5, seed=1)   # exercises QueueFull
    compiled = compile_fleet(_mk(), _reqs(), arr)  # retries mid-stream
    validate_stream(compiled)

    trace_live = []
    live = _mk(trace_live)
    res_live = replay(live, _reqs(), arr)
    assert res_live.metrics.completed == 12
    assert stream_signature(compiled) == stream_signature(live.stream)

    # serialize -> deserialize -> replay on a fresh fleet
    rt = stream_from_json(stream_to_json(compiled, pool="pool0"))
    trace_rep = []
    fresh = _mk(trace_rep)
    res_rep = fresh.executor.replay(rt, _reqs(), arr)
    assert trace_rep == trace_live
    assert res_rep.outputs == res_live.outputs
    assert stream_signature(fresh.stream) == stream_signature(live.stream)
    assert [c.ticket.rid for c in res_rep.completions] == \
        [c.ticket.rid for c in res_live.completions]


def test_compile_does_not_consume_live_policy_state():
    """Stateful policies (RoundRobin's cursor) are deep-copied by the
    compiler: compiling must not perturb the live fleet's subsequent
    decisions."""
    fleet = _stub_fleet(cores=("c", "p"), names=["a", "b"],
                        policy=RoundRobin(), co_dispatch=0,
                        capacity=1, service_steps=1)
    reqs = [Request(i, model="ab"[i % 2]) for i in range(6)]
    compiled = compile_fleet(fleet, reqs)
    again = compile_fleet(fleet, reqs)
    assert stream_signature(compiled) == stream_signature(again)
    res = replay(fleet, reqs, [0] * 6)       # live run after compiling
    assert res.metrics.completed == 6
    assert stream_signature(fleet.stream) == stream_signature(compiled)


def test_replay_rejects_streams_for_other_traces():
    compiled = compile_fleet(_mk(), _reqs(4))
    fresh = _mk()
    with pytest.raises(ValueError, match="instruction stream exhausted"):
        fresh.executor.replay(compiled, _reqs(8))   # twice the traffic


# --------------------------------------------------------------------------
# opaque members: fused RUN, and the AOT compile refusal
# --------------------------------------------------------------------------
class OpaqueStub(EngineBase):
    """A bare ``step()`` engine (no advance/retire split): serves one
    queued request per step — the shape of the LM ``DualMeshEngine``."""

    @property
    def in_flight(self):
        return 0

    @property
    def has_work(self):
        return bool(self._pending)

    def step(self):
        self._start_clock()
        if not self._pending:
            return []
        req, _t = self._pop_admission()
        self._metrics[req.rid].started_at = time.perf_counter()
        return [self._finish(req.rid, req.payload)]


def test_opaque_member_runs_fused_and_rejects_aot_compile():
    def mk():
        return FleetEngine({"op": OpaqueStub(),
                            "b": StubEngine(core="p", name="b")})

    fleet = mk()
    with pytest.raises(CompileError, match="opaque"):
        compile_fleet(fleet, [Request(0, model="op")])
    fleet.submit(Request(10, model="op"))
    fleet.submit(Request(11, model="b"))
    res = fleet.drain()
    assert res.outputs == [10, 11]
    # the slot lowered to: pure RUN b, fused RUN op, FREE b — the fused
    # dispatch lands after every pure dispatch, before the deferrable FREE
    kinds = [(r.instr.op, getattr(r.instr, "fused", None), r.instr.member)
             for r in fleet.stream if r.slot == 0]
    assert kinds == [("RUN", False, "b"), ("RUN", True, "op"),
                     ("FREE", None, "b")]
    # ...and the recorded stream (the CompileError's pointer) replays
    fresh = mk()
    res2 = fresh.executor.replay(fleet.stream,
                                 [Request(10, model="op"),
                                  Request(11, model="b")])
    assert res2.outputs == res.outputs
    assert stream_signature(fresh.stream) == stream_signature(fleet.stream)


# --------------------------------------------------------------------------
# withdraw_pending (the SEND half of migration)
# --------------------------------------------------------------------------
def test_engine_withdraw_pending_takes_newest_first():
    eng = StubEngine(capacity=1)
    for p in (10, 11, 12):
        eng.submit(Request(p))
    taken = eng.withdraw_pending(2)
    # newest two leave (oldest stays closest to admission), order kept
    assert [req.payload for _, req in taken] == [11, 12]
    assert eng.queued == 1
    rids = [rid for rid, _ in taken]
    assert all(rid not in eng._metrics for rid in rids)
    assert eng.drain().outputs == [10]       # withdrawn leave no trace


def test_fleet_withdraw_pending_unaccounts_and_restores_route():
    fleet = _stub_fleet(cores=("c", "p"), names=["a", "b"], capacity=1,
                        service_steps=3)
    for i, m in enumerate(["a", "a", "a", "b"]):
        fleet.submit(Request(i, model=m))
    pairs = fleet.withdraw_pending(member="a")
    assert [req.payload for _, req in pairs] == [0, 1, 2]
    for frid, req in pairs:
        assert req.rid is None               # fleet identity stripped...
        assert req.model == "a"              # ...route preserved
        assert frid not in fleet._metrics
    with pytest.raises(KeyError, match="no member"):
        fleet.withdraw_pending(member="zzz")
    res = fleet.drain()                      # only b's request remains
    assert res.metrics.completed == 1
    # the withdrawn requests re-submit cleanly elsewhere (the RECV half)
    other = _stub_fleet(cores=("c", "p"), names=["a", "b"])
    for _, req in pairs:
        other.submit(req)
    assert other.drain().outputs == [0, 1, 2]


def test_pool_revoke_all_and_resplit():
    pool = DevicePool(jax.devices())
    pool.lease("mobilenet_v1")
    pool.lease("squeezenet")
    with pytest.raises(RuntimeError, match="leases held"):
        pool.resplit(0.25)
    assert pool.revoke_all() == ["mobilenet_v1", "squeezenet"]
    assert pool.stats()["leases"] == []
    dual = pool.resplit(0.25)
    assert pool.theta == 0.25
    assert pool.lease("squeezenet") is dual   # leasing works again


def test_metrics_zero_completions_stay_json_safe():
    eng = StubEngine(service_steps=5)
    eng.submit(Request(0, model="a"))
    eng.step()                               # started, nothing completes
    m = eng.result().metrics
    s = m.summary()
    assert s["completed"] == 0
    assert s["p50_ms"] is None and s["p95_ms"] is None
    assert s["requests_per_s"] == 0.0
    assert m.by_model() == {}                # nothing completed, no rows
    json.dumps(s)                            # lands in BENCH JSONs as-is
    # and with the clock never started at all
    s0 = StubEngine().result().metrics.summary()
    assert (s0["requests_per_s"], s0["p50_ms"]) == (0.0, None)
    json.dumps(s0)


# --------------------------------------------------------------------------
# multi-pool router: placement, migration, replay
# --------------------------------------------------------------------------
def _mk_router(**kw):
    def pool():
        return _stub_fleet(cores=("c", "p"), names=["a", "b"],
                           policy=WeightedFair(), service_steps=2)
    return MultiPoolRouter({"p0": pool(), "p1": pool()}, **kw)


def test_multipool_places_serves_and_drains_a_pool():
    router = _mk_router()
    reqs = [Request(i, model="ab"[i % 2]) for i in range(8)]
    for r in reqs[:6]:
        router.submit(r)
    router.step()
    moved = router.drain_pool("p1")          # evacuate p1's queue
    assert moved >= 1
    for r in reqs[6:]:
        router.submit(r)
    res = router.drain()
    assert res.metrics.completed == 8
    assert res.outputs == list(range(8))     # router submission order
    st = res.stats
    assert st["engine"] == "multipool"
    assert set(st["pools"]) == {"p0", "p1"}
    assert st["in_transit"] == 0
    assert sum(sum(p["served"].values())
               for p in st["pools"].values()) == 8
    with pytest.raises(KeyError, match="no pool serves"):
        router.submit(Request(0, model="zzz"))
    with pytest.raises(ValueError, match="itself"):
        router.migrate("p0", "p0")


def test_multipool_replay_round_trip_bitwise():
    """The multi-pool acceptance round-trip: record a 2-pool run with a
    forced mid-run migration, serialize the per-pool streams, and re-run
    the (streams, placements) recipe on a fresh router — the re-executed
    streams and every output must come back bitwise-identical."""
    def run_live():
        router = _mk_router()
        reqs = [Request(i, model="ab"[i % 2]) for i in range(10)]
        for r in reqs[:6]:
            router.submit(r)
        router.step()
        router.step()
        router.migrate("p1", "p0")
        for r in reqs[6:]:
            router.submit(r)
        return router, router.drain()

    live, res_live = run_live()
    assert res_live.metrics.completed == 10
    sig_live = stream_signature(live.stream())

    rt = {name: stream_from_json(stream_to_json(recs, pool=name))
          for name, recs in live.streams().items()}
    fresh = _mk_router()
    res_rep = fresh.replay(rt, live.placements,
                           [Request(i, model="ab"[i % 2])
                            for i in range(10)])
    assert res_rep.metrics.completed == 10
    assert stream_signature(fresh.stream()) == sig_live
    assert res_rep.outputs == res_live.outputs
    assert [c.ticket.rid for c in res_rep.completions] == \
        [c.ticket.rid for c in res_live.completions]


def test_multipool_replay_rejects_mismatched_recipe():
    router = _mk_router()
    with pytest.raises(KeyError, match="unknown pools"):
        router.replay({"nope": []}, [], [])
    with pytest.raises(ValueError, match="placements"):
        router.replay({"p0": []}, [(0, "p0")], [])


def test_multipool_drift_check_skips_poolless_fleets():
    # stub fleets hold no DevicePool: the drift detector must pass over
    # them instead of attempting a REBALANCE they cannot execute
    router = _mk_router(rebalance_drift=0.0, rebalance_every=1)
    for i in range(4):
        router.submit(Request(i, model="ab"[i % 2]))
    res = router.drain()
    assert res.metrics.completed == 4
    assert router.rebalances == []


# --------------------------------------------------------------------------
# real CNN engines: compile / replay / rebalance, bitwise
# --------------------------------------------------------------------------
_MODELS = ["mobilenet_v1", "squeezenet"]


def _cnn_fleet():
    return build_cnn_fleet(_MODELS, use_pallas=False, fuse=False)


def _cnn_requests(n=4, seed=0):
    tags = mix_schedule({m: 0.5 for m in _MODELS}, n)
    keys = jax.random.split(jax.random.PRNGKey(seed), n)
    return [Request(jax.random.normal(k, (1, 32, 32, 3)), model=t)
            for k, t in zip(keys, tags)]


def test_cnn_fleet_compile_and_replay_bitwise():
    """Real pipeline members: the AOT-compiled stream matches the live
    run's, and replaying its JSON round-trip on a fresh fleet reproduces
    every output array bitwise (the single-pool acceptance)."""
    arr = poisson_arrivals(4, rate=1.0, seed=0)
    live, _ = _cnn_fleet()
    compiled = compile_fleet(live, _cnn_requests(), arr)
    validate_stream(compiled)
    res_live = replay(live, _cnn_requests(), arr)
    assert res_live.metrics.completed == 4
    assert stream_signature(compiled) == stream_signature(live.stream)

    rt = stream_from_json(stream_to_json(compiled, pool="pool0"))
    fresh, _ = _cnn_fleet()
    res_rep = fresh.executor.replay(rt, _cnn_requests(), arr)
    assert res_rep.metrics.completed == 4
    for a, b in zip(res_rep.outputs, res_live.outputs):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_cnn_fleet_rebalance_mid_run_replays_bitwise():
    """A REBALANCE recorded mid-run (revoke -> resplit -> re-lease ->
    relocate params and in-flight envs) must replay like any other
    instruction: same completions, same output arrays."""
    def run(fleet):
        for r in _cnn_requests(4, seed=2):
            fleet.submit(r)
        fleet.step()
        fleet.step()                         # work now in flight
        fleet.executor.inject(Rebalance(theta=0.7))
        return fleet.drain()

    live, pool = _cnn_fleet()
    res_live = run(live)
    assert res_live.metrics.completed == 4
    assert pool.theta == 0.7
    assert set(pool.stats()["leases"]) == set(_MODELS)  # re-leased
    assert any(isinstance(r.instr, Rebalance) for r in live.stream)

    rt = stream_from_json(stream_to_json(live.stream))
    fresh, fresh_pool = _cnn_fleet()
    res_rep = fresh.executor.replay(rt, _cnn_requests(4, seed=2))
    assert res_rep.metrics.completed == 4
    assert fresh_pool.theta == 0.7
    for a, b in zip(res_rep.outputs, res_live.outputs):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_multipool_cnn_migration_rebalance_parity_vs_standalone():
    """The 2-pool acceptance: a run with a forced migration and one
    REBALANCE completes every admitted request, and each request's output
    is bitwise what its model's standalone engine computes."""
    from repro.core.arch import BoardModel, DUAL_BASELINE
    from repro.core.scheduler import build_schedule
    from repro.dualcore.runtime import DualCoreRunner
    from repro.models.cnn import build_model
    from repro.serving import stream_images

    e0, _ = _cnn_fleet()
    e1, _ = build_cnn_fleet(["squeezenet"], use_pallas=False, fuse=False)
    router = MultiPoolRouter({"p0": e0, "p1": e1})
    reqs = _cnn_requests(6, seed=3)
    for r in reqs:
        router.submit(r)
    assert router.queued == 6
    moved = router.drain_pool("p1")          # force the migration leg
    assert moved >= 1
    theta = router.rebalance(
        "p0", mix={m: 0.5 for m in _MODELS}, theta=0.6)
    assert theta == 0.6
    res = router.drain()
    assert res.metrics.completed == 6
    assert res.stats["rebalances"] == [{"pool": "p0", "theta": 0.6}]
    assert any(isinstance(r.instr, Send) for r in router.stream())
    assert any(isinstance(r.instr, Recv) for r in router.stream())

    by_model = {m: [] for m in _MODELS}
    for r in reqs:
        by_model[r.model].append(r.payload)
    standalone = {}
    for m in _MODELS:
        params, _, graph = build_model(m)
        sched = build_schedule(graph, DUAL_BASELINE, BoardModel(),
                               "balanced")
        runner = DualCoreRunner(m, params, sched, use_pallas=False,
                                fuse=False)
        standalone[m] = iter(stream_images(runner, by_model[m]).outputs)
    for r, out in zip(reqs, res.outputs):    # router submission order
        np.testing.assert_array_equal(
            np.asarray(out), np.asarray(next(standalone[r.model])))


@pytest.mark.slow
def test_multipool_lm_cnn_round_trip_bitwise():
    """The mixed-modality acceptance round-trip: a 2-pool fleet with an
    LM member (opaque -> fused RUNs) next to CNN members, with a forced
    cross-pool migration — record, serialize, replay on fresh pools,
    outputs bitwise."""
    from repro.configs.registry import get_smoke
    from repro.core.arch import BoardModel, DUAL_BASELINE
    from repro.core.scheduler import build_schedule
    from repro.dualcore.runtime import DualCoreRunner
    from repro.dualmesh import DualMeshRunner, split_mesh
    from repro.lm.model import init_params
    from repro.models.cnn import build_model
    from repro.serving import DualCoreEngine, DualMeshEngine

    cfg = get_smoke("qwen2_0_5b")

    def cnn():
        params, _, graph = build_model("squeezenet")
        sched = build_schedule(graph, DUAL_BASELINE, BoardModel(),
                               "balanced")
        return DualCoreEngine(DualCoreRunner(
            "squeezenet", params, sched, use_pallas=False, fuse=False))

    def pools():
        lm = DualMeshEngine(DualMeshRunner(
            cfg, init_params(cfg, jax.random.PRNGKey(0)),
            split_mesh(jax.devices(), 0.5), max_len=16), group_size=1)
        return {"p0": FleetEngine({"lm": lm, "squeezenet": cnn()}),
                "p1": FleetEngine({"squeezenet": cnn()})}

    prompt = jax.random.randint(jax.random.PRNGKey(1), (1, 4), 0,
                                cfg.vocab)
    imgs = [jax.random.normal(k, (1, 32, 32, 3))
            for k in jax.random.split(jax.random.PRNGKey(2), 4)]

    def reqs():
        return [Request(prompt, gen_steps=2, model="lm")] + \
            [Request(x, model="squeezenet") for x in imgs]

    def run_live():
        router = MultiPoolRouter(pools())
        for r in reqs():
            router.submit(r)
        moved = router.drain_pool("p1")      # force SEND/RECV mid-run
        assert moved >= 1
        return router, router.drain()

    live, res_live = run_live()
    assert res_live.metrics.completed == 5
    assert res_live.outputs[0].shape == (1, 6)   # prompt + 2 generated
    fused = [r for r in live.stream()
             if isinstance(r.instr, Run) and r.instr.fused]
    assert fused and all(r.instr.member == "lm" for r in fused)

    rt = {name: stream_from_json(stream_to_json(recs, pool=name))
          for name, recs in live.streams().items()}
    fresh = MultiPoolRouter(pools())
    res_rep = fresh.replay(rt, live.placements, reqs())
    assert res_rep.metrics.completed == 5
    assert stream_signature(fresh.stream()) == \
        stream_signature(live.stream())
    for a, b in zip(res_rep.outputs, res_live.outputs):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


# --------------------------------------------------------------------------
# Chrome-tracing export
# --------------------------------------------------------------------------
def _executed_stub_stream():
    trace = []
    fleet = _mk(trace)
    replay(fleet, _reqs(6), [0] * 6)
    return fleet.stream


def test_chrome_trace_tracks_and_events():
    records = _executed_stub_stream()
    doc = chrome_trace({"poolA": records})
    events = doc["traceEvents"]
    meta = [e for e in events if e["ph"] == "M"]
    assert {"name": "poolA"} in [e["args"] for e in meta
                                 if e["name"] == "process_name"]
    tracks = [e["args"]["name"] for e in meta if e["name"] == "thread_name"]
    assert tracks == ["c-submesh", "p-submesh", "retire", "control",
                      "bubbles"]
    slices = [e for e in events if e["ph"] == "X"
              and e["cat"] != "bubble"]
    assert len(slices) == len(records)       # every record is stamped
    assert all(e["ts"] >= 0 and e["dur"] > 0 for e in slices)
    # a RUN on a c-dominant member files under the c-submesh track (0),
    # FREEs under retire (2)
    by_cat = {e["cat"] for e in slices}
    assert {"RUN", "FREE"} <= by_cat
    for r, e in zip(records, slices):
        if isinstance(r.instr, Free):
            assert e["tid"] == 2
    json.dumps(doc)
    # compiled-only records (no stamps) are skipped, not exported at 0
    compiled = compile_fleet(_mk(), _reqs(4))
    assert chrome_trace({"p": compiled})["traceEvents"] == \
        [e for e in chrome_trace({"p": compiled})["traceEvents"]
         if e["ph"] == "M"]


def test_trace_export_cli(tmp_path, capsys):
    from benchmarks import trace_export

    p0 = tmp_path / "s0.json"
    p1 = tmp_path / "s1.json"
    dump_stream(_executed_stub_stream(), str(p0), pool="pool0")
    dump_stream(_executed_stub_stream(), str(p1), pool="pool1")
    out = tmp_path / "trace.json"
    rc = trace_export.main([str(p0), str(p1), "-o", str(out)])
    assert rc == 0
    assert "2 pool(s)" in capsys.readouterr().out
    with open(out) as f:
        doc = json.load(f)
    names = {e["args"]["name"] for e in doc["traceEvents"]
             if e.get("name") == "process_name"}
    assert names == {"pool0", "pool1"}
    # colliding pool names: usage error, exit 2
    dup = tmp_path / "dup.json"
    dump_stream(_executed_stub_stream(), str(dup), pool="pool0")
    with pytest.raises(SystemExit) as ei:
        trace_export.main([str(p0), str(dup), "-o", str(out)])
    assert ei.value.code == 2
    # a compiled-only stream has no wall clock to draw: usage error
    cold = tmp_path / "cold.json"
    dump_stream(compile_fleet(_mk(), _reqs(4)), str(cold), pool="aot")
    with pytest.raises(SystemExit) as ei:
        trace_export.main([str(cold), "-o", str(out)])
    assert ei.value.code == 2


def test_trace_export_reports_partial_skips(tmp_path, capsys):
    """A stream mixing stamped and compiled-only records exports the
    stamped ones and *reports* the skip count instead of silently
    thinning the timeline."""
    from benchmarks import trace_export

    records = _executed_stub_stream() + compile_fleet(_mk(), _reqs(4))
    n_cold = sum(1 for r in records if r.t0 is None)
    assert n_cold > 0
    p = tmp_path / "mixed.json"
    dump_stream(records, str(p), pool="pool0")
    out = tmp_path / "trace.json"
    assert trace_export.main([str(p), "-o", str(out)]) == 0
    text = capsys.readouterr().out
    assert f"skipped {n_cold} compiled-only" in text


def test_chrome_trace_empty_and_recordless_streams():
    doc = chrome_trace({})
    assert doc["traceEvents"] == []
    doc = chrome_trace({"p0": []})
    assert all(e["ph"] == "M" for e in doc["traceEvents"])
    json.dumps(doc)


def test_chrome_trace_control_track_and_pool_row_order():
    from repro.fleet.instructions import SetParam

    mk = [ExecRecord(instr=SetParam(member="a", param="weight", value=2.0),
                     slot=0, seq=0, advances=0, t0=1.0, t1=1.1),
          ExecRecord(instr=Rebalance(theta=0.3), slot=1, seq=1,
                     advances=0, t0=1.1, t1=1.2)]
    # pools are assigned process rows in sorted-name order regardless of
    # dict insertion order
    doc = chrome_trace({"pZ": list(mk), "pA": list(mk)})
    rows = {e["pid"]: e["args"]["name"] for e in doc["traceEvents"]
            if e["name"] == "process_name"}
    assert rows == {0: "pA", 1: "pZ"}
    control = [e for e in doc["traceEvents"]
               if e["ph"] == "X" and e["cat"] != "bubble"]
    assert control and all(e["tid"] == 3 for e in control)
    assert {e["cat"] for e in control} == {"SET_PARAM", "REBALANCE"}


def test_chrome_trace_run_slices_carry_advances_and_wall_window():
    recs = [
        ExecRecord(instr=Run(member="a", slots=1, core="c"), slot=0,
                   seq=0, advances=4, t0=10.0, t1=10.002),
        ExecRecord(instr=Run(member="b", slots=1, core="p"), slot=1,
                   seq=1, advances=1, t0=10.002, t1=10.003),
    ]
    doc = chrome_trace({"p0": recs})
    runs = [e for e in doc["traceEvents"]
            if e["ph"] == "X" and e["cat"] == "RUN"]
    assert [e["args"] for e in runs] == [
        {"slot": 0, "seq": 0, "advances": 4},
        {"slot": 1, "seq": 1, "advances": 1}]
    # the wall window, in µs from the earliest stamp
    assert runs[0]["ts"] == 0.0
    assert runs[0]["dur"] == pytest.approx(2000.0)
    assert runs[1]["ts"] == pytest.approx(2000.0)
    assert runs[1]["dur"] == pytest.approx(1000.0)
    assert [e["tid"] for e in runs] == [0, 1]


def test_chrome_trace_bubble_events():
    mk = lambda m, c, s, q: ExecRecord(  # noqa: E731
        instr=Run(member=m, slots=1, core=c), slot=s, seq=q,
        advances=1, t0=0.01 * s, t1=0.01 * s + 0.005)
    recs = [mk("a", "c", 0, 0), mk("b", "p", 0, 1),
            mk("a", "c", 1, 2), mk("a", "c", 2, 3),
            mk("b", "p", 3, 4), mk("a", "c", 3, 5)]
    doc = chrome_trace({"p0": recs})
    bubbles = [e for e in doc["traceEvents"]
               if e["ph"] == "X" and e["cat"] == "bubble"]
    # the p submesh is idle over slots 1-2 while c runs: one bubble,
    # labeled with the member that next RUNs on p
    assert len(bubbles) == 1
    b = bubbles[0]
    assert b["tid"] == 4
    assert b["name"] == "bubble p-submesh x2"
    assert b["args"] == {"core": "p", "slots": [1, 2],
                         "could_have_run": "b"}
    assert b["dur"] > 0
    # fully-busy streams produce no bubbles
    busy = [mk("a", "c", s, s) for s in range(3)] + \
           [mk("b", "p", s, 10 + s) for s in range(3)]
    doc2 = chrome_trace({"p0": busy})
    assert not [e for e in doc2["traceEvents"]
                if e.get("cat") == "bubble"]
