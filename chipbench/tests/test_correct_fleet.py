"""The fleet's references and ``correct`` on ``fleet-poisson`` at a test
size: each new reference agrees with the program's forwards on seeded
weights, a sound run through the fleet passes, and an altered logit and a
misrouted model each fail."""
import functools

import jax
import numpy as np
import pytest

from chipbench import harness, spec
from chipbench.refops import init_params
from chipbench.tests import faults

SEED = 2 ** 31 + 79
SIZE = 32


@pytest.fixture(scope="module")
def bench(tmp_path_factory):
    """One small benchmark tree for the module."""
    from chipbench.tests.conftest import make_bench

    return make_bench(tmp_path_factory.mktemp("bench"), image_size=SIZE,
                      traffic={"fleet-poisson": {"rate_img_s": 10}})


def run(bench, **kw):
    """One short run of ``fleet-poisson``, the chip guard skipped."""
    root, home = bench
    return harness.run_cell(root, "fleet-poisson", SEED, 1.0, False,
                            t_start=0.0, require_tpu=False, home=home, **kw)


@pytest.mark.parametrize("model", ["mobilenet_v1", "squeezenet"])
def test_reference_agrees_with_the_program(bench, model):
    """On seeded weights the reference's logits match the program's
    sequential forward and its dual-core runner within the cell's
    limit."""
    from repro.core.arch import BoardModel, DUAL_BASELINE
    from repro.core.scheduler import build_schedule
    from repro.dualcore.runtime import DualCoreRunner
    from repro.models.cnn import FORWARDS
    from repro.models.zoo import get_graph

    root, home = bench
    b = spec.Bench(root, home)
    config = b.config("zoo_fleet")
    arch = harness.arch_at(config, config["models"][model])
    ref = b.model(arch["family"])
    params = init_params(ref.layers(arch), jax.random.PRNGKey(SEED % 97))
    x = jax.random.normal(jax.random.PRNGKey(1), (2, SIZE, SIZE, 3))

    want = np.asarray(jax.jit(functools.partial(
        ref.forward, arch=arch, precision="highest"))(params, x))
    seq = np.asarray(FORWARDS[model](params, x))
    sched = build_schedule(get_graph(model), DUAL_BASELINE, BoardModel(),
                           config["scheme"])
    runner = DualCoreRunner(model, params, sched, use_pallas=False)
    dual = np.concatenate([np.asarray(y) for y in
                           runner.run_pipelined([x[:1], x[1:]])])
    assert want.shape == (2, arch["classes"])
    limit = config["check"]["max_rel_err"][model]
    for got in (seq, dual):
        assert harness.rel_err(got, want) <= limit


def test_sound_fleet_run_is_correct(bench):
    """The three networks served through the fleet with the seed's
    weights pass every check, each model's error beside its limit, and
    the cell reports its latency beside its throughput and set-up."""
    line = run(bench)
    assert line["correct"], line["checks"]
    assert set(line["metrics"]) == {"throughput_img_s", "p50_ms",
                                    "setup_s"}
    assert line["metrics"]["p50_ms"]["value"] > 0
    assert line["failed"] == 0 and line["attempted"] == 10
    for m in ("mobilenet_v1", "mobilenet_v2", "squeezenet"):
        c = line["checks"][f"max_rel_err.{m}"]
        assert 0 < c["value"] <= c["limit"]
    assert line["checks"]["compiles_in_window"]["value"] == 0
    assert list(line)[-1] == "checks"


def misrouted(src: str, dst: str):
    """Every request for ``src`` served by ``dst``'s member: ``src``'s
    answers swapped for ``dst``'s."""

    def fault(system):
        route = system.engine.router.route
        system.engine.router.route = \
            lambda req: dst if req.model == src else route(req)

    return fault


def test_an_altered_and_a_swapped_answer_are_not_correct(bench,
                                                         monkeypatch):
    """One logit of mobilenet_v2 moved by 1e-3 of the largest, and
    mobilenet_v1's answers swapped for squeezenet's, each fail their own
    model's limit; squeezenet's answers stay within theirs."""
    altered = faults.answer_altered("mobilenet_v2")
    swapped = misrouted("mobilenet_v1", "squeezenet")

    def both(system):
        altered(system)
        swapped(system)

    faults.plant(monkeypatch, both)
    line = run(bench)
    assert not line["correct"]
    checks = line["checks"]
    for m in ("mobilenet_v2", "mobilenet_v1"):
        assert checks[f"max_rel_err.{m}"]["value"] > \
            checks[f"max_rel_err.{m}"]["limit"], m
    sq = checks["max_rel_err.squeezenet"]
    assert sq["value"] <= sq["limit"]
