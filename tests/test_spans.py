"""Host spans in ``repro.obs`` and inside the dual-core engine: nesting,
the bound, the off paths (no span and no clock read), one ``group.call``
per exec-group dispatch, garbage-collection tracking, the stable program
name of each exec group, and the fleet's slot spans around its members'."""
import gc
import re
import time

import jax
import numpy as np
import pytest

from repro.core.arch import BoardModel, DUAL_BASELINE
from repro.core.scheduler import build_schedule
from repro.dualcore.runtime import DualCoreRunner
from repro.models.cnn import build_model
from repro.launch import serve
from repro.obs import Registry, registry, track_gc
from repro.fleet import build_cnn_fleet
from repro.fleet.compiler import compile_fleet, stream_signature
from repro.fleet.instructions import Run
from repro.serving import DualCoreEngine, Request, poisson_arrivals, replay


@pytest.fixture(scope="module")
def runner():
    """mobilenet_v2 on the XLA path, the balanced schedule."""
    params, _, g = build_model("mobilenet_v2")
    sched = build_schedule(g, DUAL_BASELINE, BoardModel(), "balanced")
    return DualCoreRunner("mobilenet_v2", params, sched, use_pallas=False)


def _images(n, size=32):
    return [jax.random.normal(k, (1, size, size, 3))
            for k in jax.random.split(jax.random.PRNGKey(0), n)]


def _serve(runner, n, obs):
    rec = []
    eng = DualCoreEngine(runner, record=rec)
    eng.obs = obs
    for x in _images(n):
        eng.submit(Request(x))
    res = eng.drain()
    assert res.metrics.completed == n
    return rec


@pytest.fixture
def clock_calls(monkeypatch):
    """Counts calls of ``time.perf_counter_ns`` from now on."""
    calls = []
    real = time.perf_counter_ns

    def counted():
        calls.append(1)
        return real()

    monkeypatch.setattr(time, "perf_counter_ns", counted)
    return calls


# --------------------------------------------------------------------------
# Registry.span
# --------------------------------------------------------------------------
def test_span_nesting_sets_parent():
    reg = Registry()
    with reg.span("outer"):
        with reg.span("a", rid=3, group=1):
            pass
        with reg.span("b") as s:
            s.rid = 7                    # known only inside the block
    with reg.span("next"):
        pass
    spans = reg.spans()
    assert [(name, parent, rid, group, model)
            for _, _, name, parent, rid, group, model in spans] == [
        ("outer", None, None, None, None), ("a", 0, 3, 1, None),
        ("b", 0, 7, None, None), ("next", None, None, None, None)]
    for t0, t1, *_ in spans:
        assert t0 <= t1
    assert spans[0][0] <= spans[1][0] and spans[2][1] <= spans[0][1]


def test_spans_stay_out_of_snapshots_and_clear():
    reg = Registry()
    before = reg.snapshot()
    with reg.span("x"):
        pass
    assert reg.snapshot() == before
    reg.clear_spans()
    assert reg.spans() == []


def test_span_bound_counts_what_it_drops(monkeypatch):
    monkeypatch.setattr(registry, "MAX_SPANS", 2)
    reg = Registry()
    for _ in range(5):
        with reg.span("x"):
            pass
    assert len(reg.spans()) == 2
    snap = reg.snapshot(domain="wall")
    assert snap["counters"]["obs_spans_dropped_total"]["series"] == {"": 3}
    assert reg.snapshot(domain="slot")["counters"] == {}


def test_disabled_registry_records_no_span_and_reads_no_clock(clock_calls):
    reg = Registry(enabled=False)
    with reg.span("x", rid=1) as s:
        s.rid = 2
    assert reg.spans() == [] and clock_calls == []


# --------------------------------------------------------------------------
# the engine's spans
# --------------------------------------------------------------------------
def test_engine_without_obs_reads_no_clock_for_spans(runner, clock_calls):
    _serve(runner, 1, None)              # compile outside the count
    del clock_calls[:]
    _serve(runner, 3, None)
    assert clock_calls == []
    disabled = Registry(enabled=False)
    _serve(runner, 3, disabled)
    assert clock_calls == [] and disabled.spans() == []


def test_engine_emits_one_group_call_per_dispatch(runner):
    reg = Registry()
    rec = _serve(runner, 3, reg)
    spans = reg.spans()
    calls = [s for s in spans if s[2] == "group.call"]
    assert [(rid, group) for *_, rid, group, _ in calls] == \
        [(rid, group) for _, rid, group, _ in rec]
    assert {s[6] for s in spans if s[2] != "gc"} == {"mobilenet_v2"}
    # every group call runs inside a slot's dispatch phase
    assert {spans[s[3]][2] for s in calls} == {"slot.dispatch"}
    names = [s[2] for s in spans]
    slots = names.count("slot.dispatch")
    assert names.count("slot.retire") == slots == max(s for s, *_ in rec) + 1
    admits = [s for s in spans if s[2] == "request.admit"]
    assert [s[4] for s in admits] == [0, 1, 2]
    assert all(spans[s[3]][2] == "slot.dispatch" for s in admits)
    done = [s for s in spans if s[2] == "request.materialize"]
    assert sorted(s[4] for s in done) == [0, 1, 2]
    assert all(spans[s[3]][2] == "slot.retire" for s in done)
    assert all(t1 is not None for _, t1, *_ in spans)


# --------------------------------------------------------------------------
# garbage collection
# --------------------------------------------------------------------------
def test_track_gc_records_a_collection_and_uninstalls():
    reg = Registry()
    hooks = len(gc.callbacks)
    untrack = track_gc(reg)
    assert len(gc.callbacks) == hooks + 1
    with reg.span("work"):
        gc.collect()
    untrack()
    assert len(gc.callbacks) == hooks
    spans = reg.spans()
    collected = [s for s in spans if s[2] == "gc"]
    assert collected and all(spans[s[3]][2] == "work" for s in collected)
    hist = reg.snapshot(domain="wall")["histograms"]["gc_pause_seconds"]
    assert hist["series"]["generation=2"]["n"] >= 1
    n = len(reg.spans())
    gc.collect()
    assert len(reg.spans()) == n
    untrack()                            # a second call is harmless


def test_serve_metrics_sink_tracks_gc_while_attached(tmp_path):
    """``serve fleet --metrics`` tracks collections into the registry it
    writes, from attaching it to the end of the run."""
    import argparse
    import json

    hooks = len(gc.callbacks)
    out = tmp_path / "metrics.json"
    sink = serve._MetricsSink(argparse.Namespace(metrics=str(out),
                                                 metrics_every=None))
    sink.attach(Registry())
    assert len(gc.callbacks) == hooks + 1
    gc.collect()
    sink.finish(0)
    assert len(gc.callbacks) == hooks
    hist = json.loads(out.read_text())["histograms"]["gc_pause_seconds"]
    assert hist["domain"] == "wall"
    assert hist["series"]["generation=2"]["n"] >= 1
    quiet = serve._MetricsSink(argparse.Namespace(metrics=None,
                                                  metrics_every=None))
    quiet.attach(Registry())
    assert len(gc.callbacks) == hooks


# --------------------------------------------------------------------------
# a stable program name per exec group
# --------------------------------------------------------------------------
def test_trace_groups_names_each_program(runner):
    compiled = [c for c, _ in runner.trace_groups(_images(1)[0])]
    assert len(compiled) == len(runner.groups)
    for gi, (c, g) in enumerate(zip(compiled, runner.groups)):
        head = c.as_text().splitlines()[0]
        assert re.match(rf"HloModule jit_dualcore_g{gi:02d}_{g.core}\b",
                        head), head


# --------------------------------------------------------------------------
# the fleet's spans
# --------------------------------------------------------------------------
FLEET = ("mobilenet_v1", "squeezenet")


def _fleet(**kw):
    """mobilenet_v1 and squeezenet on the XLA path behind one fleet."""
    return build_cnn_fleet(list(FLEET), use_pallas=False, fuse=False,
                           **kw)[0]


def _fleet_requests(n=4):
    return [Request(x, model=FLEET[i % 2])
            for i, x in enumerate(_images(n))]


def test_fleet_slot_spans_nest_around_the_members():
    rec = []
    fleet = _fleet(burst=2, record=rec)
    reg = Registry()
    fleet.obs = reg
    assert all(m.engine.obs is reg for m in fleet.members)
    for r in _fleet_requests(2):
        fleet.submit(r)
    fleet.step()
    spans = reg.spans()
    names = [s[2] for s in spans]
    assert [s[2] for s in spans if s[3] is None] == ["fleet.step"]
    assert [s[2] for s in spans if s[3] == 0] == ["fleet.lower",
                                                  "fleet.execute"]
    execute = names.index("fleet.execute")
    dispatch = [s for s in spans if s[2] == "slot.dispatch"]
    assert len(dispatch) == 4 and {s[3] for s in dispatch} == {execute}
    assert sorted(s[6] for s in dispatch) == sorted(FLEET * 2)
    calls = [s for s in spans if s[2] == "group.call"]
    assert [(s[4], s[5]) for s in calls] == \
        [(rid, group) for _, rid, group, _ in rec]
    assert {s[6] for s in calls} == set(FLEET)
    for s in calls:
        parent = spans[s[3]]
        assert parent[2] == "slot.dispatch" and parent[6] == s[6]
    retire = [s for s in spans if s[2] == "slot.retire"]
    assert sorted(s[6] for s in retire) == sorted(FLEET)
    assert {s[3] for s in retire} == {execute}
    assert all(t1 is not None for _, t1, *_ in spans)


def test_fleet_without_a_registry_records_nothing(clock_calls):
    fleet = _fleet()
    for r in _fleet_requests(2):
        fleet.submit(r)
    fleet.drain()                        # compile outside the count
    del clock_calls[:]
    for r in _fleet_requests(2):
        fleet.submit(r)
    fleet.drain()
    assert clock_calls == []
    disabled = Registry(enabled=False)
    fleet.obs = disabled
    for r in _fleet_requests(2):
        fleet.submit(r)
    assert fleet.drain().metrics.completed == 6
    assert clock_calls == [] and disabled.spans() == []
    assert disabled.snapshot()["histograms"] == {}


@pytest.mark.parametrize("co_dispatch, members", [(None, 2), (0, 1)])
def test_fleet_members_per_slot_counts_the_co_dispatched(co_dispatch,
                                                         members):
    """The executor's always-on registry observes every slot's RUNs,
    with no span registry set."""
    fleet = _fleet(co_dispatch=co_dispatch)
    reg = fleet.executor.obs
    for r in _fleet_requests(2):
        fleet.submit(r)
    fleet.step()

    def hist():
        return reg.snapshot(domain="wall")["histograms"][
            "fleet_members_per_slot"]["series"]["pool=pool0"]

    assert hist()["n"] == 1 and hist()["sum"] == members
    fleet.drain()
    runs = sum(isinstance(r.instr, Run) for r in fleet.stream)
    slots = len({r.slot for r in fleet.stream})
    assert hist()["sum"] == runs and hist()["n"] == slots
    assert "fleet_members_per_slot" not in \
        reg.snapshot(domain="slot")["histograms"]
    assert fleet.obs is None


def test_fleet_replay_is_bitwise_with_spans_on():
    arrivals = poisson_arrivals(4, rate=1.0, seed=0)
    off = _fleet()
    res_off = replay(off, _fleet_requests(), arrivals)
    on = _fleet()
    on.obs = Registry()
    compiled = compile_fleet(on, _fleet_requests(), arrivals)
    res_on = replay(on, _fleet_requests(), arrivals)
    assert on.obs.spans()
    assert stream_signature(on.stream) == stream_signature(compiled) == \
        stream_signature(off.stream)
    fresh = _fleet()
    fresh.obs = Registry()
    res_rep = fresh.executor.replay(compiled, _fleet_requests(), arrivals)
    assert res_rep.metrics.completed == res_off.metrics.completed == 4
    for a, b, c in zip(res_off.outputs, res_on.outputs, res_rep.outputs):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        np.testing.assert_array_equal(np.asarray(a), np.asarray(c))
