"""Finds everything of a cell by name: ``BENCHMARK.json`` at the root,
``bench/configs/<config>.json``, ``bench/traffic/<traffic>.json``, the
driver ``bench/drivers/<kind>.py`` that a traffic file names, and one
reader ``bench/metrics/<metric>.py`` per per-layer metric.  A new cell,
configuration, traffic mix or metric is new files plus entries in
``BENCHMARK.json``; nothing here changes."""
from __future__ import annotations

import importlib.util
import json
from pathlib import Path
from types import ModuleType
from typing import Dict, List

ROOT = Path(__file__).resolve().parents[2]
BENCH = ROOT / "bench"


def load_json(path: Path) -> Dict:
    with open(path) as f:
        return json.load(f)


def benchmark(root: Path = ROOT) -> Dict:
    return load_json(root / "BENCHMARK.json")


def cell(bench: Dict, name: str) -> Dict:
    for w in bench["workloads"]:
        if w["name"] == name:
            return w
    raise KeyError(f"no workload {name!r} in BENCHMARK.json "
                   f"(have {[w['name'] for w in bench['workloads']]})")


def config(bench: Dict, name: str) -> Dict:
    for c in bench["configs"]:
        if c["name"] == name:
            return load_json(ROOT / c["file"])
    raise KeyError(f"no config {name!r} in BENCHMARK.json")


def traffic(name: str) -> Dict:
    return load_json(BENCH / "traffic" / f"{name}.json")


def _module(path: Path, modname: str) -> ModuleType:
    if not path.is_file():
        raise FileNotFoundError(path)
    spec = importlib.util.spec_from_file_location(modname, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def driver(kind: str) -> ModuleType:
    return _module(BENCH / "drivers" / f"{kind}.py", f"bench_driver_{kind}")


def reader(metric: str) -> ModuleType:
    return _module(BENCH / "metrics" / f"{metric}.py",
                   "bench_metric_" + metric.replace(".", "_"))


def _applies(metric: Dict, cell_name: str) -> bool:
    return "workloads" not in metric or cell_name in metric["workloads"]


def end_to_end(bench: Dict, cell_name: str) -> List[Dict]:
    return [m for m in bench["end_to_end"] if _applies(m, cell_name)]


def per_layer(bench: Dict, cell_name: str) -> List[Dict]:
    return [m for m in bench["per_layer"] if _applies(m, cell_name)]
