"""Resolve a cell of `BENCHMARK.json` to the files that define it.

A cell names a configuration and a traffic mix; each lives in a file of its
own, found by name, so a later change adds a cell by adding files and
entries and edits none:

  configs/<config>.json   sizes and settings as run (driver named inside)
  configs/<config>.py     the configuration's plain reference
  traffic/<traffic>.json  the traffic mix's parameters
  drivers/<driver>.py     the general driver for that kind of configuration
  metrics/<metric>.py     one reader per per-layer metric
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
import os
from typing import Dict, List

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH_DIR)


class CellError(ValueError):
    """A cell, or a file it names, is missing or malformed."""


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config_name: str
    traffic_name: str
    config: Dict
    traffic: Dict
    end_to_end: List[Dict]      # the end-to-end metrics this cell reports
    per_layer: List[Dict]       # the per-layer metrics this cell reports


def load_benchmark(root: str = ROOT) -> Dict:
    path = os.path.join(root, "BENCHMARK.json")
    if not os.path.isfile(path):
        raise CellError(f"no BENCHMARK.json at {root}")
    with open(path) as f:
        return json.load(f)


def _read_json(path: str) -> Dict:
    if not os.path.isfile(path):
        raise CellError(f"missing file {os.path.relpath(path, ROOT)}")
    with open(path) as f:
        return json.load(f)


def load_module(path: str, name: str):
    """Import a file by path: per-name files (`device_idle.fleet.py`,
    `danube4b-mamba2-pool.py`) are not importable by dotted name."""
    if not os.path.isfile(path):
        raise CellError(f"missing file {os.path.relpath(path, ROOT)}")
    spec = importlib.util.spec_from_file_location(
        "chipbench_" + name.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def config_path(bench: Dict, config_name: str) -> str:
    for c in bench["configs"]:
        if c["name"] == config_name:
            return os.path.join(ROOT, c["file"])
    raise CellError(f"configuration {config_name!r} is not in BENCHMARK.json")


def traffic_path(traffic_name: str) -> str:
    return os.path.join(BENCH_DIR, "traffic", traffic_name + ".json")


def driver_path(driver: str) -> str:
    return os.path.join(BENCH_DIR, "drivers", driver + ".py")


def reference_path(config_name: str) -> str:
    return os.path.join(BENCH_DIR, "configs", config_name + ".py")


def metric_path(metric_name: str) -> str:
    return os.path.join(BENCH_DIR, "metrics", metric_name + ".py")


def _reports(metric: Dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def resolve(name: str, bench: Dict = None) -> Cell:
    bench = bench or load_benchmark()
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise CellError(f"unknown workload {name!r}; known: {sorted(cells)}")
    w = cells[name]
    config = _read_json(config_path(bench, w["config"]))
    traffic = _read_json(traffic_path(w["traffic"]))
    e2e = [m for m in bench["end_to_end"] if _reports(m, name)]
    e2e_names = {m["name"] for m in e2e}
    per_layer = [m for m in bench["per_layer"]
                 if (name in m["workloads"] if "workloads" in m
                     else m["moves"] in e2e_names)]
    needed = [driver_path(config["driver"]), reference_path(w["config"])] \
        + [metric_path(m["name"]) for m in per_layer]
    for path in needed:
        if not os.path.isfile(path):
            raise CellError(f"cell {name}: missing file "
                            f"{os.path.relpath(path, ROOT)}")
    return Cell(name=name, chips=int(w["chips"]), config_name=w["config"],
                traffic_name=w["traffic"], config=config, traffic=traffic,
                end_to_end=e2e, per_layer=per_layer)
