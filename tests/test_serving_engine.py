"""The shared streaming engine API (ISSUE-4): CNN engine bitwise-equal to
the sequential forward, slot-refill traces under bursty arrivals, queue
backpressure bounds, admission policies, and the LM engine's submit/step
lifecycle (mid-flight joins, shim parity)."""
import jax
import numpy as np
import pytest

from repro.core.arch import BoardModel, DUAL_BASELINE
from repro.core.scheduler import build_schedule
from repro.dualcore.runtime import DualCoreRunner
from repro.models.cnn import build_model
from repro.serving import (DualCoreEngine, DualMeshEngine, Engine,
                           FixedRateAdmission, GreedyAdmission, QueueFull,
                           Request, percentile, poisson_arrivals, replay,
                           stream_images)

B = BoardModel()


def _runner(model, **kw):
    params, fwd, g = build_model(model)
    sched = build_schedule(g, DUAL_BASELINE, B, "balanced")
    return DualCoreRunner(model, params, sched, **kw), params, fwd


def _images(n, size=48, batch=1):
    return [jax.random.normal(k, (batch, size, size, 3))
            for k in jax.random.split(jax.random.PRNGKey(0), n)]


# --------------------------------------------------------------------------
# API basics
# --------------------------------------------------------------------------
def test_percentile_interpolates():
    xs = [1.0, 2.0, 3.0, 4.0]
    assert percentile(xs, 0) == 1.0
    assert percentile(xs, 100) == 4.0
    assert percentile(xs, 50) == pytest.approx(2.5)
    assert np.isnan(percentile([], 50))


def test_poisson_arrivals_fixed_and_monotone():
    a = poisson_arrivals(16, rate=1.0, seed=0)
    assert a == poisson_arrivals(16, rate=1.0, seed=0)   # deterministic
    assert a[0] == 0
    assert all(x <= y for x, y in zip(a, a[1:]))
    assert a != poisson_arrivals(16, rate=1.0, seed=1)


def test_zero_capacity_queue_rejected():
    """max_queue=0 could never admit work — replay() would spin forever
    retrying QueueFull; both engines must reject it at construction."""
    runner, _, _ = _runner("mobilenet_v1", use_pallas=False)
    with pytest.raises(ValueError, match="max_queue"):
        DualCoreEngine(runner, max_queue=0)


def test_poisson_arrivals_rejects_nonpositive_rate():
    with pytest.raises(ValueError, match="rate"):
        poisson_arrivals(4, rate=0.0)
    with pytest.raises(ValueError, match="rate"):
        poisson_arrivals(4, rate=-1.0)


def test_admission_policies_clamp():
    g = GreedyAdmission()
    assert g.admit(queued=5, in_flight=2, capacity=4) == 2
    assert g.admit(queued=1, in_flight=4, capacity=4) == 0
    f = FixedRateAdmission(per_step=1)
    assert f.admit(queued=5, in_flight=0, capacity=4) == 1
    assert f.admit(queued=0, in_flight=0, capacity=4) == 0


def test_engines_satisfy_protocol():
    runner, _, _ = _runner("mobilenet_v1", use_pallas=False)
    assert isinstance(DualCoreEngine(runner), Engine)


# --------------------------------------------------------------------------
# CNN engine: correctness
# --------------------------------------------------------------------------
@pytest.mark.parametrize("model", [
    "mobilenet_v1",
    pytest.param("mobilenet_v2", marks=pytest.mark.slow),
    pytest.param("squeezenet", marks=pytest.mark.slow),
])
def test_cnn_engine_bitwise_equals_run_sequential(model):
    """The streaming engine dispatches the same jitted exec groups the
    strictly serialized baseline runs, so outputs must be bitwise-identical
    (CPU interpret Pallas kernels); and they are the sequential Pallas
    forward's to rounding."""
    runner, params, fwd = _runner(model, use_pallas=True)
    imgs = _images(2)
    res = stream_images(runner, imgs)
    refs = runner.run_sequential(imgs)
    for x, out, ref in zip(imgs, res.outputs, refs):
        np.testing.assert_array_equal(np.asarray(out), np.asarray(ref))
        np.testing.assert_allclose(
            np.asarray(out), np.asarray(fwd(params, x, use_pallas=True)),
            rtol=2e-3, atol=2e-3)
    assert res.metrics.completed == 2
    assert all(m.finished_at is not None for m in res.metrics.requests)


def test_cnn_engine_slot_refill_trace_bursty_arrivals():
    """Admission refills the group-0 slot online: request r admitted at
    slot s runs group k at slot s+k exactly — including through the bubble
    an empty queue leaves behind."""
    runner, _, _ = _runner("mobilenet_v1", use_pallas=False)
    n_g = len(runner.groups)
    imgs = _images(3, size=32)
    rec = []
    eng = DualCoreEngine(runner, record=rec)
    eng.submit(imgs[0])
    eng.step()
    eng.step()                        # queue empty: bubble at slot 1
    eng.submit(imgs[1])
    eng.submit(imgs[2])
    eng.drain()
    admit = {0: 0, 1: 2, 2: 3}        # rid -> admission slot
    expect = sorted(((s, r, s - admit[r]) for r in admit
                     for s in range(admit[r], admit[r] + n_g)),
                    key=lambda t: (t[0], admit[t[1]]))
    assert [(s, r, g) for s, r, g, _ in rec] == expect
    # the bubble breaks the one-slot offset, so (unlike the saturated
    # case) adjacent streams may share a core within a slot — the device
    # queue serializes them; only the slot arithmetic is invariant


def test_cnn_engine_saturated_trace_matches_run_pipelined():
    """With every request available at slot 0 the engine reproduces the
    static ``run_pipelined`` dispatch schedule exactly (the shim test in
    test_dualcore covers the shim; this drives the engine directly)."""
    runner, _, _ = _runner("mobilenet_v1", use_pallas=False)
    n_g = len(runner.groups)
    rec = []
    stream_images(runner, _images(3, size=32), record=rec)
    assert [(s, i, g) for s, i, g, _ in rec] == \
        [(slot, i, slot - i) for slot in range(n_g + 2)
         for i in range(3) if 0 <= slot - i < n_g]


def test_cnn_engine_backpressure_and_flight_bound():
    runner, _, _ = _runner("mobilenet_v1", use_pallas=False)
    imgs = _images(4, size=32)
    eng = DualCoreEngine(runner, max_queue=2)
    eng.submit(imgs[0])
    eng.submit(imgs[1])
    with pytest.raises(QueueFull):
        eng.submit(imgs[2])
    eng.step()                        # admits one -> queue frees a slot
    eng.submit(imgs[2])               # now accepted
    while eng.has_work:
        assert eng.in_flight <= eng.capacity
        eng.step()
    res = eng.result()
    assert res.metrics.completed == 3
    assert [o.shape for o in res.outputs] == [(1, 1000)] * 3


def test_cnn_engine_replay_retries_on_backpressure():
    """replay() pushes submissions past QueueFull to later steps; every
    request still completes, in submission order, bitwise-equal to the
    plain forward."""
    runner, params, fwd = _runner("mobilenet_v1", use_pallas=False)
    imgs = _images(5, size=32)
    eng = DualCoreEngine(runner, max_queue=1)
    res = replay(eng, [Request(x) for x in imgs], [0, 0, 0, 1, 2])
    assert res.metrics.completed == 5
    for x, out in zip(imgs, res.outputs):
        np.testing.assert_array_equal(np.asarray(out),
                                      np.asarray(fwd(params, x)))
    # waiting in the queue shows up as wait time, not lost requests
    assert all(m.wait_s >= 0 for m in res.metrics.requests)


def test_cnn_engine_single_group_chain():
    """squeezenet under layer_type collapses to one exec group: capacity 1,
    admit-and-retire within a slot."""
    params, fwd, g = build_model("squeezenet")
    sched = build_schedule(g, DUAL_BASELINE, B, "layer_type")
    runner = DualCoreRunner("squeezenet", params, sched, use_pallas=False)
    eng = DualCoreEngine(runner)
    assert eng.capacity == 1
    (x,) = _images(1, size=32)
    eng.submit(x)
    done = eng.step()
    assert len(done) == 1
    # one jitted group == the whole forward as one compiled program;
    # bitwise only against the forward compiled the same way (eager
    # op-by-op execution rounds differently)
    np.testing.assert_array_equal(np.asarray(done[0].output),
                                  np.asarray(jax.jit(fwd)(params, x)))


# --------------------------------------------------------------------------
# LM engine
# --------------------------------------------------------------------------
@pytest.fixture(scope="module")
def lm_runner():
    from repro.configs.registry import get_smoke
    from repro.dualmesh import DualMeshRunner, split_mesh
    from repro.lm.model import init_params

    cfg = get_smoke("qwen2_0_5b")
    params = init_params(cfg, jax.random.PRNGKey(0))
    return DualMeshRunner(cfg, params, split_mesh(jax.devices(), 0.5),
                          max_len=32), cfg


def test_lm_engine_lifecycle_and_shapes(lm_runner):
    runner, cfg = lm_runner
    p = jax.random.randint(jax.random.PRNGKey(1), (1, 4), 0, cfg.vocab)
    eng = DualMeshEngine(runner, group_size=2)
    t = eng.submit(Request(p, gen_steps=3))
    assert t.rid == 0 and eng.queued == 1 and not eng.in_flight
    eng.submit(Request(p, gen_steps=3))
    eng.step()                         # one admission per slot (stagger)
    assert eng.queued == 1 and eng.in_flight == 1
    eng.submit(Request(p, gen_steps=2))    # mid-flight join
    res = eng.drain()
    assert [o.shape for o in res.outputs] == [(1, 7), (1, 7), (1, 6)]
    assert res.stats["decode_tokens"] == 3 * 1 + 2 * 1 + 3 * 1
    assert all(m.latency_s >= m.service_s >= 0
               for m in res.metrics.requests)


def test_lm_engine_in_flight_cap_below_group_size_terminates(lm_runner):
    """max_in_flight < group_size must not livelock: with admission
    stalled at the cap, the fusion gate fuses the streams it has instead
    of waiting for group_size that can never accumulate."""
    runner, cfg = lm_runner
    p = jax.random.randint(jax.random.PRNGKey(1), (1, 4), 0, cfg.vocab)
    eng = DualMeshEngine(runner, group_size=2, max_in_flight=1)
    eng.submit(Request(p, gen_steps=2))
    eng.submit(Request(p, gen_steps=2))
    for _ in range(50):                 # bounded: a livelock would exhaust
        if not eng.has_work:
            break
        eng.step()
    res = eng.result()
    assert not eng.has_work
    assert [o.shape for o in res.outputs] == [(1, 6), (1, 6)]
    assert res.stats["fused_sizes"] == [1, 1]   # capacity-stalled fusion


def test_lm_engine_backpressure(lm_runner):
    runner, cfg = lm_runner
    p = jax.random.randint(jax.random.PRNGKey(1), (1, 4), 0, cfg.vocab)
    eng = DualMeshEngine(runner, group_size=1, max_queue=1)
    eng.submit(Request(p, gen_steps=1))
    with pytest.raises(QueueFull):
        eng.submit(Request(p, gen_steps=1))
    res = eng.drain()
    assert res.metrics.completed == 1


def test_lm_serve_shim_matches_engine(lm_runner):
    """DualMeshRunner.serve is now a submit-everything shim — identical
    outputs and token accounting to driving the engine directly."""
    runner, cfg = lm_runner
    prompts = [jax.random.randint(k, (1, 6), 0, cfg.vocab)
               for k in jax.random.split(jax.random.PRNGKey(2), 3)]
    shim = runner.serve(prompts, gen_steps=4, group_size=2)
    eng = DualMeshEngine(runner, group_size=2)
    for p in prompts:
        eng.submit(Request(p, gen_steps=4))
    res = eng.drain()
    for a, b in zip(shim.outputs, res.outputs):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    for key in ("prefill_tokens", "decode_tokens", "total_tokens",
                "fused_sizes", "n_streams"):
        assert shim.stats[key] == res.stats[key], key
