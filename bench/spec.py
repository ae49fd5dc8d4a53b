"""The benchmark's description, read from ``BENCHMARK.json`` and the files it
names.

Everything that belongs to one cell is found by name, so a cell, a traffic
mix or a per-layer metric is added by adding files and entries:

  * a configuration: the ``file`` its entry in ``BENCHMARK.json`` names;
  * a traffic mix:   ``bench/traffic/<traffic>.json``;
  * a cell's limits: ``bench/limits/<workload>.json`` (the correctness
                     limits and the readings they were set from);
  * a driver:        ``bench/drivers/<driver>.py``, named by the mix;
  * a metric reader: ``bench/metrics/<metric>.py``, for each per-layer
                     metric.
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
import pathlib
from typing import Any, Dict, List, Optional

BENCH_DIR = pathlib.Path(__file__).resolve().parent
ROOT = BENCH_DIR.parent


def load_benchmark(root: pathlib.Path = ROOT) -> Dict[str, Any]:
    with open(root / "BENCHMARK.json") as f:
        return json.load(f)


def load_json(path: pathlib.Path) -> Dict[str, Any]:
    with open(path) as f:
        return json.load(f)


def load_module(path: pathlib.Path, name: str):
    """Import a file by path: metric readers carry dots in their names."""
    spec = importlib.util.spec_from_file_location(name, path)
    if spec is None or spec.loader is None:
        raise FileNotFoundError(path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def traffic_path(traffic: str, root: pathlib.Path = ROOT) -> pathlib.Path:
    return root / "bench" / "traffic" / f"{traffic}.json"


def limits_path(workload: str, root: pathlib.Path = ROOT) -> pathlib.Path:
    return root / "bench" / "limits" / f"{workload}.json"


def driver_path(driver: str, root: pathlib.Path = ROOT) -> pathlib.Path:
    return root / "bench" / "drivers" / f"{driver}.py"


def metric_path(metric: str, root: pathlib.Path = ROOT) -> pathlib.Path:
    return root / "bench" / "metrics" / f"{metric}.py"


def reports(metric: Dict[str, Any], workload: str,
            bench: Dict[str, Any]) -> bool:
    """Whether a metric is reported in a cell: its ``workloads`` list, or
    for a per-layer metric without one, every cell that reports the
    end-to-end metric it moves."""
    if "workloads" in metric:
        return workload in metric["workloads"]
    if "moves" in metric:
        e2e = {m["name"]: m for m in bench["end_to_end"]}
        return reports(e2e[metric["moves"]], workload, bench)
    return True


@dataclasses.dataclass
class Cell:
    """One workload entry with every file it names, resolved."""
    name: str
    chips: int
    config: Dict[str, Any]
    traffic: Dict[str, Any]
    limits: Dict[str, Any]
    end_to_end: List[Dict[str, Any]]
    per_layer: List[Dict[str, Any]]
    driver_file: pathlib.Path
    root: pathlib.Path

    def limit(self, check: str) -> float:
        return float(self.limits["checks"][check]["limit"])

    def reader(self, metric: str):
        return load_module(metric_path(metric, self.root),
                           "bench_metric_" + metric.replace(".", "_"))


def resolve(workload: str, root: pathlib.Path = ROOT,
            bench: Optional[Dict[str, Any]] = None) -> Cell:
    bench = bench if bench is not None else load_benchmark(root)
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise KeyError(f"no workload {workload!r} in BENCHMARK.json "
                       f"(have {sorted(cells)})")
    w = cells[workload]
    cfg_entry = {c["name"]: c for c in bench["configs"]}[w["config"]]
    config = load_json(root / cfg_entry["file"])
    traffic = load_json(traffic_path(w["traffic"], root))
    limits = load_json(limits_path(workload, root))
    return Cell(
        name=workload, chips=int(w["chips"]), config=config, traffic=traffic,
        limits=limits,
        end_to_end=[m for m in bench["end_to_end"]
                    if reports(m, workload, bench)],
        per_layer=[m for m in bench["per_layer"]
                   if reports(m, workload, bench)],
        driver_file=driver_path(traffic["driver"], root), root=root)
