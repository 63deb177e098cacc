"""Finds a cell's files by the names `BENCHMARK.json` gives."""

from __future__ import annotations

import importlib
import json
import os
from dataclasses import dataclass
from typing import Any, Dict, List

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
REPO_DIR = os.path.dirname(BENCH_DIR)


def _load(path: str) -> Any:
    with open(path) as f:
        return json.load(f)


@dataclass
class Cell:
    name: str
    chips: int
    config: Dict[str, Any]        # benchmarks/configs/<config>.json
    traffic: Dict[str, Any]       # benchmarks/traffic/<traffic>.json
    query: Any                    # benchmarks/queries/<query>.py
    end_to_end: List[Dict[str, Any]]
    per_layer: List[Dict[str, Any]]


def _in_cell(metric: Dict[str, Any], cell_name: str) -> bool:
    return cell_name in metric.get("workloads", [cell_name])


def load_cell(name: str) -> Cell:
    bench = _load(os.path.join(REPO_DIR, "BENCHMARK.json"))
    found = [w for w in bench["workloads"] if w["name"] == name]
    if not found:
        raise SystemExit(f"benchmark: no workload {name!r} in "
                         f"BENCHMARK.json")
    w = found[0]
    cfg_entry = next(c for c in bench["configs"] if c["name"] == w["config"])
    traffic = _load(os.path.join(BENCH_DIR, "traffic",
                                 w["traffic"] + ".json"))
    return Cell(
        name=name, chips=int(w["chips"]),
        config=_load(os.path.join(REPO_DIR, cfg_entry["file"])),
        traffic=traffic,
        query=importlib.import_module(
            f"benchmarks.queries.{traffic['query']}"),
        end_to_end=[m for m in bench["end_to_end"] if _in_cell(m, name)],
        per_layer=[m for m in bench["per_layer"] if _in_cell(m, name)])


def metric_spec(metric_name: str) -> Dict[str, Any]:
    return _load(os.path.join(BENCH_DIR, "layer_metrics",
                              metric_name + ".json"))
