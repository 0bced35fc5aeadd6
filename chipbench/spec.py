"""Finds everything by name: cells in ``BENCHMARK.json``, and the files of
configurations, traffic mixes, metrics, reference models and system
adapters under the benchmark's directory.

Adding a cell, configuration, mix or metric adds files and entries; no
file here lists them.
"""
from __future__ import annotations

import importlib.util
import json
import pathlib
import re
import sys
from types import ModuleType

NAME = re.compile(r"[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}\Z")
HOME = pathlib.Path(__file__).resolve().parent


class Refused(RuntimeError):
    """The run cannot be made here: no result line is printed."""


def _name(kind: str, name: str) -> str:
    if not isinstance(name, str) or not NAME.match(name):
        raise Refused(f"{kind} name {name!r} is not a valid name")
    return name


class Bench:
    """``BENCHMARK.json`` at ``root`` and the benchmark's files in
    ``home`` (``chipbench/`` unless a test points elsewhere)."""

    def __init__(self, root: pathlib.Path, home: pathlib.Path = HOME):
        self.root = pathlib.Path(root)
        self.home = pathlib.Path(home)
        path = self.root / "BENCHMARK.json"
        try:
            self.spec = json.loads(path.read_text())
        except OSError as e:
            raise Refused(f"cannot read {path}: {e}") from None
        self._modules: dict[str, ModuleType] = {}

    # -- entries of BENCHMARK.json --------------------------------------
    def cell(self, name: str) -> dict:
        """The ``workloads`` entry named ``name``."""
        for w in self.spec["workloads"]:
            if w["name"] == name:
                return w
        raise Refused(f"no workload {name!r} in BENCHMARK.json (have "
                      f"{[w['name'] for w in self.spec['workloads']]})")

    def metrics_for(self, cell: dict, traced: bool) -> list[dict]:
        """The cell's end-to-end metrics (``traced`` False) or per-layer
        metrics (True).  A metric without ``workloads`` goes to every cell
        that reports the end-to-end metric it moves (per-layer) or to
        every cell (end-to-end)."""
        e2e = [m for m in self.spec["end_to_end"]
               if cell["name"] in m.get("workloads", [cell["name"]])]
        if not traced:
            return e2e
        names = {m["name"] for m in e2e}
        return [m for m in self.spec["per_layer"]
                if (cell["name"] in m["workloads"] if "workloads" in m
                    else m["moves"] in names)]

    # -- files found by name --------------------------------------------
    def _json(self, kind: str, name: str) -> dict:
        path = self.home / kind / f"{_name(kind, name)}.json"
        try:
            return json.loads(path.read_text())
        except OSError as e:
            raise Refused(f"no {kind} file for {name!r}: {e}") from None

    def config(self, name: str) -> dict:
        """``configs/<name>.json``."""
        return self._json("configs", name)

    def traffic(self, name: str) -> dict:
        """``traffic/<name>.json``."""
        return self._json("traffic", name)

    def _module(self, kind: str, name: str) -> ModuleType:
        path = self.home / kind / f"{_name(kind, name)}.py"
        key = f"chipbench_{kind}_{name}".replace(".", "_").replace("-", "_")
        if key in self._modules:
            return self._modules[key]
        spec = importlib.util.spec_from_file_location(key, path)
        if spec is None or not path.is_file():
            raise Refused(f"no {kind} file for {name!r} ({path})")
        mod = importlib.util.module_from_spec(spec)
        sys.modules[key] = mod
        spec.loader.exec_module(mod)
        self._modules[key] = mod
        return mod

    def metric(self, name: str) -> ModuleType:
        """The reader of metric ``name``, ``read(run, name) -> float |
        None``: ``metrics/<name>.py`` where that file exists; else one
        reader serves a family of names, ``metrics/device_idle.py`` for
        ``device_idle.<cells>`` and ``metrics/roofline.py`` for
        ``<family>_roofline``."""
        for file in (name, name.split(".")[0], name.rsplit("_", 1)[-1]):
            if (self.home / "metrics" / f"{_name('metric', file)}.py"
                    ).is_file():
                return self._module("metrics", file)
        raise Refused(f"no metrics file for {name!r}")

    def model(self, family: str) -> ModuleType:
        """``models/<family>.py``: the plain reference of one
        architecture, ``layers(arch)`` and ``forward(params, x, arch,
        precision)``."""
        return self._module("models", family)

    def system(self, kind: str) -> ModuleType:
        """``systems/<kind>.py``: builds the program's serving objects for
        a configuration, ``build(config, params, devices, span)``."""
        return self._module("systems", kind)

    def peaks(self, device_kind: str) -> dict:
        """The published peaks of ``device_kind``; refuses a kind that the
        table does not hold."""
        table = json.loads((self.home / "peaks.json").read_text())
        if device_kind not in table:
            raise Refused(f"device kind {device_kind!r} is not in "
                          f"peaks.json (have {sorted(table)}); a device "
                          f"without published peaks cannot be measured")
        return table[device_kind]
