"""The CI perf-regression gate must pass on identical reports and trip on
an injected slowdown (ISSUE-3 satellite)."""
import copy
import json
import os
import sys

import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))            # repo root -> benchmarks pkg

from benchmarks.compare_bench import compare, extract_metrics, main  # noqa: E402

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

REPORT = {
    "fused_pw_dw_pw": [
        {"shape": "14x14x64 t6 s1", "unfused_ms": 30.0, "fused_ms": 10.0},
        {"shape": "7x7x160 t6 s1", "unfused_ms": 24.0, "fused_ms": 8.0},
    ],
    "fused_dw_pw": [
        {"shape": "14x14x256->256 s1", "unfused_ms": 30.0,
         "fused_ms": 12.0},
    ],
    "measured": {
        "mobilenet_v1": {"pipelined_ms": 350.0, "sequential_ms": 360.0},
    },
    "fleet": {"aggregate_fps": 7.0,
              "baseline": {"best_fps": 5.0}},
}


def test_extract_gates_only_our_legs():
    m = extract_metrics(REPORT)
    # shape-labelled, stable keys; baseline legs (unfused/sequential
    # timings, best_fps baseline throughput) are not gated
    assert "fused_pw_dw_pw/14x14x64 t6 s1/fused_ms" in m
    assert "measured/mobilenet_v1/pipelined_ms" in m
    assert "fleet/aggregate_fps" in m
    assert len(m) == 5
    assert not any("unfused" in k or "sequential" in k
                   or "best_fps" in k for k in m)


def test_identical_reports_pass():
    regs, _ = compare(REPORT, copy.deepcopy(REPORT))
    assert regs == []


def test_gate_trips_on_injected_3x_regression():
    fresh = copy.deepcopy(REPORT)
    fresh["fused_pw_dw_pw"][0]["fused_ms"] *= 3.0
    regs, _ = compare(REPORT, fresh, threshold=2.0)
    assert len(regs) == 1
    assert regs[0].key == "fused_pw_dw_pw/14x14x64 t6 s1/fused_ms"
    assert regs[0].ratio == pytest.approx(3.0)


def test_gate_tolerates_sub_threshold_noise_and_new_entries():
    fresh = copy.deepcopy(REPORT)
    fresh["fused_pw_dw_pw"][0]["fused_ms"] *= 1.9         # < 2x: noise
    fresh["fused_dw_pw"].append(
        {"shape": "7x7x1024->1024 s1", "fused_ms": 99.0})  # new: not gated
    del fresh["measured"]["mobilenet_v1"]                  # gone: not gated
    regs, notes = compare(REPORT, fresh)
    assert regs == []
    assert any("new entry" in n for n in notes)
    assert any("disappeared" in n for n in notes)


def test_higher_better_gate_trips_on_throughput_drop():
    """aggregate_fps gates in the opposite direction: fresh falling below
    baseline / threshold fails; a latency-style doubling does not."""
    fresh = copy.deepcopy(REPORT)
    fresh["fleet"]["aggregate_fps"] = 3.0          # 7.0 -> 3.0: > 2x drop
    regs, _ = compare(REPORT, fresh, threshold=2.0)
    assert [r.key for r in regs] == ["fleet/aggregate_fps"]
    assert regs[0].ratio == pytest.approx(3.0 / 7.0)


def test_higher_better_gate_tolerates_gains_and_noise():
    fresh = copy.deepcopy(REPORT)
    fresh["fleet"]["aggregate_fps"] = 14.0         # 2x GAIN: never a fail
    regs, _ = compare(REPORT, fresh, threshold=2.0)
    assert regs == []
    fresh["fleet"]["aggregate_fps"] = 4.0          # 1.75x drop < threshold
    regs, notes = compare(REPORT, fresh, threshold=2.0)
    assert regs == []
    assert any("higher-better" in n for n in notes)


def test_noise_floor_skips_micro_timings():
    base = {"fused_dw_pw": [{"shape": "tiny", "fused_ms": 0.05}]}
    fresh = {"fused_dw_pw": [{"shape": "tiny", "fused_ms": 0.5}]}   # 10x!
    regs, notes = compare(base, fresh, min_ms=1.0)
    assert regs == []
    assert any("noise floor" in n for n in notes)


def test_main_exit_codes(tmp_path):
    base_p = tmp_path / "base.json"
    fresh_p = tmp_path / "fresh.json"
    base_p.write_text(json.dumps(REPORT))
    fresh = copy.deepcopy(REPORT)
    fresh["measured"]["mobilenet_v1"]["pipelined_ms"] *= 3.0
    fresh_p.write_text(json.dumps(fresh))
    assert main(["--baseline", str(base_p), "--fresh", str(base_p)]) == 0
    assert main(["--baseline", str(base_p), "--fresh", str(fresh_p)]) == 1


def test_committed_baselines_have_gated_entries():
    """The gate is only meaningful if the committed artifacts expose gated
    metrics — guard against silently renaming the fields."""
    for fname in ("BENCH_kernels.json", "BENCH_dualcore.json",
                  "BENCH_serving.json", "BENCH_fleet.json"):
        with open(os.path.join(REPO, fname)) as f:
            report = json.load(f)
        assert extract_metrics(report), f"{fname} has no gated entries"
    with open(os.path.join(REPO, "BENCH_fleet.json")) as f:
        fleet = json.load(f)
    assert "fleet/aggregate_fps" in extract_metrics(fleet)
