"""Finds a cell's parts by the names `BENCHMARK.json` gives them.

Everything that belongs to one configuration, traffic mix, cell or
per-layer metric is a file of its own, so adding one is adding files:

  BENCHMARK.json                 cells, metrics, configuration entries
  <config file>                  as `configs[].file` names it
  bench/traffic/<traffic>.json   a mix: its `driver` and parameters
  bench/drivers/<driver>.py      the general generator a mix names
  bench/limits/<cell>.json       the limits `correct` is judged by
  bench/metrics/<metric>.py      a per-layer metric's reader, `read(record)`;
                                 a metric `<base>.<part>` with no file of
                                 its own is read by `bench/metrics/<base>.py`
"""
from __future__ import annotations

import importlib.util
import json
import os
from typing import Callable, List, NamedTuple, Optional

BENCH_DIR = "bench"


class Cell(NamedTuple):
    workload: dict
    config: dict
    traffic: dict
    limits: dict
    end_to_end: List[dict]     # the cell's end-to-end metrics
    per_layer: List[dict]      # the cell's per-layer metrics


def _load_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def _reports(metric: dict, workload: str) -> bool:
    return workload in metric.get("workloads", [workload])


def load_cell(root: str, workload: str) -> Cell:
    spec = _load_json(os.path.join(root, "BENCHMARK.json"))
    cells = {w["name"]: w for w in spec["workloads"]}
    if workload not in cells:
        raise KeyError(f"no workload {workload!r} in BENCHMARK.json "
                       f"(known: {sorted(cells)})")
    w = cells[workload]
    configs = {c["name"]: c for c in spec["configs"]}
    config = _load_json(os.path.join(root, configs[w["config"]]["file"]))
    traffic = _load_json(os.path.join(root, BENCH_DIR, "traffic",
                                      f"{w['traffic']}.json"))
    limits = _load_json(os.path.join(root, BENCH_DIR, "limits",
                                     f"{workload}.json"))
    e2e = [m for m in spec["end_to_end"] if _reports(m, workload)]
    e2e_names = {m["name"] for m in e2e}
    per_layer = [m for m in spec["per_layer"]
                 if m["moves"] in e2e_names and _reports(m, workload)]
    return Cell(w, config, traffic, limits, e2e, per_layer)


def load_driver(root: str, name: str):
    return _load_module(os.path.join(root, BENCH_DIR, "drivers",
                                     f"{name}.py"), f"bench_driver_{name}")


def load_metric(root: str, name: str) -> Callable[[dict], Optional[float]]:
    """The `read(record)` of a per-layer metric's module: its own, else
    that of its base name (`mfu.train` and `mfu.serve` share `mfu.py`)."""
    metrics = os.path.join(root, BENCH_DIR, "metrics")
    path = os.path.join(metrics, f"{name}.py")
    if not os.path.exists(path):
        path = os.path.join(metrics, f"{name.split('.')[0]}.py")
    return _load_module(path, f"bench_metric_{name}").read


def _load_module(path: str, module_name: str):
    spec = importlib.util.spec_from_file_location(
        module_name.replace(".", "_").replace("-", "_"), path)
    if spec is None or spec.loader is None:
        raise ImportError(f"cannot load {path}")
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module
