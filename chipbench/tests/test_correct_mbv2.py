"""``correct`` on mobilenet_v2's cells at a test size: a sound run passes,
each fault the cell can have fails, and so does the control."""
import pytest

from chipbench import harness
from chipbench.tests import faults

SEED = 2 ** 31 + 77


@pytest.fixture(scope="module")
def bench(tmp_path_factory):
    """One small benchmark tree for the module."""
    from chipbench.tests.conftest import make_bench

    return make_bench(tmp_path_factory.mktemp("bench"),
                      traffic={"mbv2-poisson": {"rate_img_s": 10},
                               "closed-b32-c32": {"images_per_request": 8}})


def run(bench, cell, **kw):
    """One short run of ``cell`` at the test size, the chip guard skipped."""
    root, home = bench
    return harness.run_cell(root, cell, SEED, 1.0, False, t_start=0.0,
                            require_tpu=False, home=home, **kw)


def test_sound_backlog_run_is_correct(bench):
    """The unbroken path passes every check."""
    line = run(bench, "mbv2-backlog-b32")
    assert line["correct"], line["checks"]
    assert line["failed"] == 0 and line["attempted"] >= 32
    assert list(line)[-1] == "checks"


def test_half_the_batch_left_out_is_not_correct(bench, monkeypatch):
    """Half of each request's answers copied from the other half fails."""
    faults.plant(monkeypatch, faults.half_batch_left_out("mobilenet_v2"))
    line = run(bench, "mbv2-backlog-b32")
    assert not line["correct"]
    assert line["checks"]["max_rel_err.mobilenet_v2"]["value"] > 0.1


def test_an_altered_answer_is_not_correct(bench, monkeypatch):
    """One logit moved by 1e-3 of the largest fails."""
    faults.plant(monkeypatch, faults.answer_altered("mobilenet_v2"))
    line = run(bench, "mbv2-poisson")
    assert not line["correct"]
    c = line["checks"]["max_rel_err.mobilenet_v2"]
    assert c["value"] > c["limit"]


def test_the_control_is_not_correct(bench):
    """The reference at bf16x3 in the program's place fails the limit
    through the run's own check, while the program's own answers of the
    same run pass it."""
    line = run(bench, "mbv2-poisson", control="bf16x3")
    assert not line["correct"]
    c = line["checks"]["max_rel_err.mobilenet_v2"]
    assert c["value"] > c["limit"]
    p = line["program_checks"]["max_rel_err.mobilenet_v2"]
    assert p["value"] <= p["limit"]
