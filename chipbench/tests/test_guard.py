"""The run refuses, printing no result, where it cannot measure the chip."""
import os
import pathlib
import shutil
import subprocess
import sys

import pytest

from chipbench import harness, spec

REPO = pathlib.Path(__file__).resolve().parents[2]


def test_refuses_on_a_platform_that_is_not_a_tpu():
    """The CPU is refused: no fallback."""
    with pytest.raises(spec.Refused, match="not 'tpu'"):
        harness.run_cell(REPO, "mbv2-poisson", 1, 1.0, False, t_start=0.0)


def test_refuses_a_device_kind_missing_from_peaks():
    """A device kind with no published peaks is refused."""
    with pytest.raises(spec.Refused, match="peaks.json"):
        harness.run_cell(REPO, "mbv2-poisson", 1, 1.0, False, t_start=0.0,
                         require_tpu=False)


def test_refuses_an_unknown_cell():
    """A cell not in ``BENCHMARK.json`` is refused."""
    with pytest.raises(spec.Refused, match="no workload"):
        harness.run_cell(REPO, "no-such-cell", 1, 1.0, False, t_start=0.0)


def run_py(cwd, *args):
    """Run the benchmark's command in ``cwd`` on the CPU."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    return subprocess.run(
        [sys.executable, "chipbench/run.py", "--workload", "mbv2-poisson",
         "--seed", "5", "--seconds", "1", "--trace", "0", *args],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def test_command_exits_nonzero_without_a_tpu():
    """No TPU: non-zero exit and no result line."""
    p = run_py(REPO)
    assert p.returncode != 0
    assert "not 'tpu'" in p.stderr
    assert p.stdout.strip() == ""


def test_command_exits_nonzero_with_only_the_benchmark_files(tmp_path):
    """Without the program beside it: non-zero exit and no result line."""
    shutil.copy(REPO / "BENCHMARK.json", tmp_path)
    shutil.copytree(REPO / "chipbench", tmp_path / "chipbench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    p = run_py(tmp_path)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
