"""A configuration, a traffic mix, a metric and a cell are picked up from
added files and entries alone: no file of the benchmark is edited."""
import hashlib
import json

import pytest

from chipbench import harness, spec


def digest(home):
    """Hash of every file under ``home``."""
    return {p.relative_to(home): hashlib.sha256(p.read_bytes()).hexdigest()
            for p in sorted(home.rglob("*")) if p.is_file()}


def test_added_files_are_found_by_name(small_bench):
    """A new cell of new files runs and reports the new metric."""
    root, home = small_bench()
    before = digest(home)
    cfg = json.loads((home / "configs" / "mobilenet_v2.json").read_text())
    (home / "configs" / "mbv2_theta25.json").write_text(
        json.dumps(dict(cfg, theta=0.25)))
    (home / "traffic" / "closed-b2-c4.json").write_text(json.dumps(
        {"loop": "closed", "outstanding": 4, "images_per_request": 2,
         "payload_pool": 2}))
    (home / "metrics" / "requests_done.py").write_text(
        '"""Requests completed ok in the window."""\n\n\n'
        "def read(run, name):\n"
        "    return float(len(run.ok_in_window()))\n")
    spec_ = json.loads((root / "BENCHMARK.json").read_text())
    spec_["workloads"].append({"name": "mbv2-closed", "config":
                               "mbv2_theta25", "traffic":
                               "closed-b2-c4", "chips": 1, "why": "test"})
    spec_["end_to_end"].append({"name": "requests_done", "unit": "1",
                                "better": "higher", "bound": 0.05,
                                "source": "host_clock",
                                "workloads": ["mbv2-closed"]})
    (root / "BENCHMARK.json").write_text(json.dumps(spec_))
    after = digest(home)
    assert {k: v for k, v in after.items() if k in before} == before

    bench = spec.Bench(root, home)
    cell = bench.cell("mbv2-closed")
    names = [m["name"] for m in bench.metrics_for(cell, traced=False)]
    assert names == ["throughput_img_s", "setup_s", "requests_done"]
    line = harness.run_cell(root, "mbv2-closed", 2 ** 31 + 1, 1.0, False,
                            t_start=0.0, require_tpu=False, home=home)
    assert line["correct"], line["checks"]
    assert line["metrics"]["requests_done"]["value"] >= 1
    assert line["attempted"] >= 4 and line["failed"] == 0


def test_one_reader_serves_a_family_of_metric_names(small_bench):
    """``metrics/<name>.py`` where it exists, else the reader of the
    name's family: ``device_idle.<cells>`` and ``<family>_roofline``."""
    root, home = small_bench()
    bench = spec.Bench(root, home)
    assert bench.metric("device_idle.rate").__name__.endswith(
        "device_idle")
    assert bench.metric("depthwise_roofline").__name__.endswith(
        "roofline")
    (home / "metrics" / "pool_roofline.py").write_text(
        "def read(run, name):\n    return 1.0\n")
    assert bench.metric("pool_roofline").__name__.endswith(
        "pool_roofline")
    with pytest.raises(spec.Refused, match="no metrics file"):
        bench.metric("no_such_metric")
