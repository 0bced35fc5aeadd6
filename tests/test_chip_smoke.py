"""chip_smoke.py's control flow, rehearsed on the CPU.

The smoke itself needs a TPU; here its phase function serves one model at
32 px through the interpret-mode kernels and checks every output against
the XLA reference, and its entry point must refuse the CPU outright.
"""
import importlib.util
import pathlib

import pytest

ROOT = pathlib.Path(__file__).resolve().parents[1]


@pytest.fixture(scope="module")
def chip_smoke():
    spec = importlib.util.spec_from_file_location(
        "chip_smoke", ROOT / "chip_smoke.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_phase_serves_and_matches_reference_at_32px(chip_smoke, capsys):
    (rep,) = chip_smoke.run_phase(
        "cnn", ["cnn", "mobilenet_v1", "--requests", "2"], image_size=32,
        require_kernels=False)         # interpret mode: no tpu_custom_call
    assert rep["model"] == "mobilenet_v1" and rep["requests"] == 2
    assert rep["exec_groups"] > 1
    assert rep["c_devices"] == rep["p_devices"] == [0]   # one device
    assert rep["max_rel_err"] <= chip_smoke.TOL
    out = capsys.readouterr().out
    assert "platform=cpu" in out and "kernels=interpret" in out
    assert "smoke timings, not measurements" in out


def test_main_refuses_cpu(chip_smoke, capsys):
    assert chip_smoke.main([]) != 0
    cap = capsys.readouterr()
    assert "'cpu'" in cap.err and "not 'tpu'" in cap.err
    assert '"ok"' not in cap.out
