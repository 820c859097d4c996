"""Finds a cell's parts by name: ``BENCHMARK.json`` at the checkout's root,
``configs/<config>.json``, ``traffic/<traffic>.json``,
``limits/<workload>.json`` and one reader per metric in
``metrics/<metric>.py``.  Adding a cell or a metric adds files; no code here
changes."""

from __future__ import annotations

import importlib.util
import json
from pathlib import Path

HERE = Path(__file__).resolve().parent
ROOT = HERE.parent


def load_json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


def benchmark(root: Path = ROOT) -> dict:
    return load_json(root / "BENCHMARK.json")


def workload(name: str, bench: dict) -> dict:
    for w in bench["workloads"]:
        if w["name"] == name:
            return w
    raise KeyError(f"no workload {name!r} in BENCHMARK.json")


def config(name: str, base: Path = HERE) -> dict:
    return load_json(base / "configs" / f"{name}.json")


def traffic(name: str, base: Path = HERE) -> dict:
    return load_json(base / "traffic" / f"{name}.json")


def limits(workload_name: str, base: Path = HERE) -> dict:
    return load_json(base / "limits" / f"{workload_name}.json")


def metrics_for(workload_name: str, bench: dict, kind: str) -> list[dict]:
    """The ``end_to_end`` or ``per_layer`` metrics that this cell reports:
    those without a ``workloads`` list, and those whose list names it."""
    return [m for m in bench[kind]
            if workload_name in m.get("workloads", [workload_name])]


def reader(metric_name: str, base: Path = HERE):
    """The ``read(run)`` function of ``metrics/<metric_name>.py``."""
    path = base / "metrics" / f"{metric_name}.py"
    mod_spec = importlib.util.spec_from_file_location(
        f"chipbench_metric_{metric_name.replace('.', '_').replace('-', '_')}", path)
    mod = importlib.util.module_from_spec(mod_spec)
    mod_spec.loader.exec_module(mod)
    return mod.read
