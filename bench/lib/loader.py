"""Find the parts of a cell by the names ``BENCHMARK.json`` gives them."""

from __future__ import annotations

import importlib.util
import json
import os
import sys
from types import ModuleType
from typing import Dict, List

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)


def load_json(path: str) -> Dict:
    with open(path) as f:
        return json.load(f)


def load_module(path: str) -> ModuleType:
    """Import a file of the benchmark by path; its name may hold dots
    (``metrics/plan_ms.run.py``)."""
    name = "bench_part_" + os.path.relpath(path, BENCH).replace(os.sep, "__").replace(".", "_")
    if name in sys.modules:
        return sys.modules[name]
    spec = importlib.util.spec_from_file_location(name, path)
    if spec is None or spec.loader is None:
        raise FileNotFoundError(path)
    module = importlib.util.module_from_spec(spec)
    sys.modules[name] = module
    try:
        spec.loader.exec_module(module)
    except BaseException:
        del sys.modules[name]
        raise
    return module


def spec(root: str = ROOT) -> Dict:
    return load_json(os.path.join(root, "BENCHMARK.json"))


def cell(benchmark: Dict, name: str) -> Dict:
    for w in benchmark["workloads"]:
        if w["name"] == name:
            return w
    raise KeyError(f"no workload {name!r} in BENCHMARK.json")


def config(benchmark: Dict, name: str, root: str = ROOT) -> Dict:
    for c in benchmark["configs"]:
        if c["name"] == name:
            return load_json(os.path.join(root, c["file"]))
    raise KeyError(f"no config {name!r} in BENCHMARK.json")


def traffic(name: str) -> Dict:
    return load_json(os.path.join(BENCH, "workloads", f"{name}.json"))


def part(kind: str, name: str) -> ModuleType:
    """``traffic/<name>.py``, ``reference/<name>.py`` or ``metrics/<name>.py``."""
    return load_module(os.path.join(BENCH, kind, f"{name}.py"))


def metrics_for(benchmark: Dict, cell_name: str, group: str) -> List[Dict]:
    """The ``end_to_end`` or ``per_layer`` metrics this cell reports: those
    listing it under ``workloads``, and those with no such list."""
    return [
        m for m in benchmark[group]
        if cell_name in m.get("workloads", [cell_name])
    ]
