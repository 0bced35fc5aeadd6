"""Shared set-up of the benchmark's CPU tests: a benchmark tree in a
temporary directory, a copy of the real one with the images shrunk to a
size the Pallas interpreter serves in seconds, and the CPU given the v5e's
peaks (the run's device guard is skipped where a test says so)."""
from __future__ import annotations

import json
import pathlib
import shutil
import sys

import pytest

REPO = pathlib.Path(__file__).resolve().parents[2]
for p in (str(REPO / "src"), str(REPO)):
    if p not in sys.path:
        sys.path.insert(0, p)


def make_bench(tmp: pathlib.Path, *, image_size: int = 32,
               traffic: dict | None = None) -> tuple[pathlib.Path,
                                                     pathlib.Path]:
    """``(root, home)`` of a copy of the benchmark: every configuration at
    ``image_size``, the traffic files updated from ``traffic`` (name ->
    keys), and a ``cpu`` row in ``peaks.json``."""
    from chipbench import spec

    home = tmp / "chipbench"
    shutil.copytree(spec.HOME, home, ignore=shutil.ignore_patterns(
        "tests", "data", "__pycache__"))
    for p in (home / "configs").glob("*.json"):
        c = json.loads(p.read_text())
        c["image_size"] = image_size
        p.write_text(json.dumps(c))
    for name, keys in (traffic or {}).items():
        p = home / "traffic" / f"{name}.json"
        p.write_text(json.dumps(dict(json.loads(p.read_text()), **keys)))
    peaks = json.loads((home / "peaks.json").read_text())
    peaks["cpu"] = peaks["TPU v5 lite"]
    (home / "peaks.json").write_text(json.dumps(peaks))
    shutil.copy(REPO / "BENCHMARK.json", tmp / "BENCHMARK.json")
    return tmp, home


@pytest.fixture
def small_bench(tmp_path):
    """Factory of :func:`make_bench` trees in ``tmp_path``."""
    return lambda **kw: make_bench(tmp_path, **kw)
