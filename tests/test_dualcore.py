"""Schedule <-> runtime agreement: the pipelined dual-core executor must
run exactly the analytical schedule (slot offsets) and reproduce the
sequential forward bit-for-bit (ISSUE-3 satellite)."""
import jax
import numpy as np
import pytest

from repro.core.arch import BoardModel, DUAL_BASELINE
from repro.core.scheduler import best_schedule, build_schedule
from repro.dualcore.program import build_program
from repro.dualcore.runtime import DualCoreRunner, build_exec_plan
from repro.models.cnn import build_model
from repro.models.zoo import get_graph

B = BoardModel()
MODELS = ("mobilenet_v1", "mobilenet_v2", "squeezenet")


def _balanced(graph):
    return build_schedule(graph, DUAL_BASELINE, B, "balanced")


def _images(n, size=48, batch=1):
    return [jax.random.normal(k, (batch, size, size, 3))
            for k in jax.random.split(jax.random.PRNGKey(0), n)]


# --------------------------------------------------------------------------
# exec-plan structure
# --------------------------------------------------------------------------
@pytest.mark.parametrize("model", MODELS)
@pytest.mark.parametrize("scheme", ("layer_type", "balanced"))
def test_exec_plan_covers_and_alternates(model, scheme):
    g = get_graph(model)
    sched = (_balanced(g) if scheme == "balanced"
             else build_schedule(g, DUAL_BASELINE, B, scheme))
    prog = build_program(g, use_pallas=True, fuse=False)
    plan = build_exec_plan(prog, sched)
    es = plan.exec_schedule
    assert es.validate_alternating()
    names = [n for gr in plan.groups for n in gr.layers]
    assert names == [l.name for l in g.topological_order()]
    # the exec twin is a real Schedule: T_b2 and the simulator apply to
    # exactly what the runtime executes
    assert es.t_b2() >= max(es.group_latencies)


def test_exec_plan_accepts_load_balanced_schedules():
    """Alg.1 splits layers into .a/.b halves across cores; the runtime maps
    each base layer to the core holding its dominant split."""
    g = get_graph("mobilenet_v1")
    sched = best_schedule(g, DUAL_BASELINE, B)     # includes +lb candidates
    prog = build_program(g, use_pallas=True, fuse=False)
    plan = build_exec_plan(prog, sched)
    names = [n for gr in plan.groups for n in gr.layers]
    assert sorted(names) == sorted(l.name for l in g.layers)


def test_exec_plan_rejects_foreign_schedule():
    g1, g2 = get_graph("mobilenet_v1"), get_graph("squeezenet")
    sched = _balanced(g2)
    prog = build_program(g1, use_pallas=True, fuse=False)
    with pytest.raises(ValueError, match="does not cover"):
        build_exec_plan(prog, sched)


@pytest.mark.parametrize("model", MODELS)
def test_model_side_pipeline_speedup(model):
    """Acceptance: two-stream pipelined throughput >= 1.2x sequential,
    model-side, for the schedule the runtime actually executes."""
    g = get_graph(model)
    prog = build_program(g, use_pallas=True, fuse=False)
    es = build_exec_plan(prog, _balanced(g)).exec_schedule
    assert 2 * sum(es.group_latencies) / es.t_b2() >= 1.2


# --------------------------------------------------------------------------
# execution order: the Fig.4b slot offsets, for real
# --------------------------------------------------------------------------
def test_pipelined_order_matches_schedule_slot_offsets():
    params, _, g = build_model("mobilenet_v1")
    runner = DualCoreRunner("mobilenet_v1", params, _balanced(g),
                            use_pallas=False, fuse=False)
    n_g = len(runner.groups)
    record = []
    runner.run_pipelined(_images(3, size=32), record=record)
    # stream i executes group k exactly at slot i + k (one-slot offset)
    assert [(s, i, gi) for s, i, gi, _ in record] == \
        [(slot, i, slot - i) for slot in range(n_g + 2)
         for i in range(3) if 0 <= slot - i < n_g]
    # within a slot, neighbouring streams run on different cores (the
    # alternation invariant realised at execution time)
    by_slot: dict = {}
    for slot, _i, _gi, core in record:
        by_slot.setdefault(slot, []).append(core)
    for slot, cores in by_slot.items():
        assert all(a != b for a, b in zip(cores, cores[1:])), (slot, cores)
    assert any(len(set(c)) == 2 for c in by_slot.values())


def test_degenerate_single_group_still_runs():
    # squeezenet under layer_type has no dwconv -> everything on the c-core
    params, fwd, g = build_model("squeezenet")
    sched = build_schedule(g, DUAL_BASELINE, B, "layer_type")
    runner = DualCoreRunner("squeezenet", params, sched, use_pallas=False,
                            fuse=False)
    assert len(runner.groups) == 1
    (x,) = _images(1, size=32)
    out = runner.run_pipelined([x])[0]
    # the one group is jitted (jit_groups=True), so it is the whole forward
    # compiled as one program: compare with the forward compiled the same
    # way — XLA's fusion of the jitted program rounds differently from
    # eager op-by-op execution (<= 5e-7 apart on CPU), so only like vs like
    # is bitwise
    np.testing.assert_array_equal(np.asarray(out),
                                  np.asarray(jax.jit(fwd)(params, x)))


# --------------------------------------------------------------------------
# bitwise agreement with the sequential Pallas forward (CPU interpret)
# --------------------------------------------------------------------------
@pytest.mark.parametrize("model", [
    "mobilenet_v1",
    pytest.param("mobilenet_v2", marks=pytest.mark.slow),
    pytest.param("squeezenet", marks=pytest.mark.slow),
])
def test_pipelined_bitwise_equals_sequential_pallas(model):
    """The pipelined runtime partitions the *same* step program the
    sequential ``use_pallas=True`` forward runs, so outputs must be
    bitwise-identical (eager group execution, CPU interpret kernels)."""
    params, fwd, g = build_model(model)
    runner = DualCoreRunner(model, params, _balanced(g), use_pallas=True,
                            fuse=True, jit_groups=False)
    imgs = _images(2)
    outs = runner.run_pipelined(imgs)
    for x, out in zip(imgs, outs):
        ref = fwd(params, x, use_pallas=True)
        np.testing.assert_array_equal(np.asarray(out), np.asarray(ref))


def test_multi_stream_pipelining_matches_forward():
    """Four staggered streams (beyond the paper's two) still reproduce the
    per-image forward exactly, jit-compiled groups included."""
    params, fwd, g = build_model("mobilenet_v1")
    runner = DualCoreRunner("mobilenet_v1", params, _balanced(g),
                            use_pallas=False, fuse=False, jit_groups=True)
    imgs = _images(4, size=32)
    outs = runner.run_pipelined(imgs)
    for x, out in zip(imgs, outs):
        np.testing.assert_array_equal(np.asarray(out),
                                      np.asarray(fwd(params, x)))


def test_group_fusion_degrades_to_per_layer_on_xla_path():
    """The fused-block kernels are Pallas-only: with use_pallas=False the
    default fuse='group' must not emit fused pallas_calls, and the output
    must stay bitwise-equal to the XLA forward."""
    params, fwd, g = build_model("mobilenet_v1")
    runner = DualCoreRunner("mobilenet_v1", params, _balanced(g),
                            use_pallas=False, fuse="group")
    assert all(len(s.layers) == 1
               for gr in runner.groups for s in gr.steps)
    (x,) = _images(1, size=32)
    out = runner.run_pipelined([x])[0]
    np.testing.assert_array_equal(np.asarray(out),
                                  np.asarray(fwd(params, x)))


def test_group_fusion_stays_inside_core_groups():
    """fuse='group' re-fuses dw->pw chains only when the schedule kept the
    pair on one core; fused pallas_calls must never straddle a boundary."""
    params, fwd, g = build_model("mobilenet_v1")
    runner = DualCoreRunner("mobilenet_v1", params, _balanced(g),
                            use_pallas=True, fuse="group")
    fused = [s for gr in runner.groups for s in gr.steps
             if len(s.layers) > 1]
    assert fused, "balanced schedule should leave some dw->pw pairs whole"
    for gr in runner.groups:
        for s in gr.steps:
            assert set(s.layers) <= set(gr.layers)
    # still the same function, just a different kernel partitioning
    (x,) = _images(1)
    out = runner.run_pipelined([x])[0]
    ref = fwd(params, x, use_pallas=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-3, atol=2e-3)
