"""Finds the benchmark's parts by the names ``BENCHMARK.json`` gives them.

Adding a configuration, a traffic mix, a per-layer metric or a cell's
limits means adding a file here and an entry there; nothing in this module
names one of them.
"""
from __future__ import annotations

import importlib
import importlib.util
import json
from pathlib import Path

PACKAGE = Path(__file__).resolve().parent
ROOT = PACKAGE.parent


def _json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


def benchmark(root: Path = ROOT) -> dict:
    return _json(root / "BENCHMARK.json")


def cell(bench: dict, name: str) -> dict:
    for w in bench["workloads"]:
        if w["name"] == name:
            return w
    raise KeyError(f"no workload {name!r} in BENCHMARK.json "
                   f"(has: {', '.join(w['name'] for w in bench['workloads'])})")


def config(name: str) -> dict:
    return _json(PACKAGE / "configs" / f"{name}.json")


def traffic(name: str) -> dict:
    return _json(PACKAGE / "traffic" / f"{name}.json")


def limits(workload: str) -> dict:
    return _json(PACKAGE / "limits" / f"{workload}.json")


def peaks(device_kind: str) -> dict:
    table = _json(PACKAGE / "peaks.json")
    if device_kind not in table:
        raise KeyError(f"no published peaks for device kind {device_kind!r} "
                       f"in peaks.json (has: {', '.join(table)})")
    return table[device_kind]


def driver(mix: dict):
    """The traffic-driver module that runs a mix (``drivers/<name>.py``)."""
    return importlib.import_module(f"cellbench.drivers.{mix['driver']}")


def end_to_end(bench: dict, cell_: dict) -> list[dict]:
    """The cell's end-to-end metrics: those that list it, and those that
    list no cells."""
    return [m for m in bench["end_to_end"]
            if "workloads" not in m or cell_["name"] in m["workloads"]]


def per_layer(bench: dict, cell_: dict) -> list[dict]:
    """The cell's per-layer metrics: those whose ``workloads`` list it."""
    return [m for m in bench["per_layer"] if cell_["name"] in m["workloads"]]


def reader(metric: str):
    """``read(observation) -> float | None`` of ``layer_metrics/<metric>.py``."""
    path = PACKAGE / "layer_metrics" / f"{metric}.py"
    spec = importlib.util.spec_from_file_location(
        f"cellbench.layer_metrics.{metric}", path)
    if spec is None:
        raise FileNotFoundError(f"no reader for per-layer metric {metric!r}")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read
