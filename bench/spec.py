"""Finds the pieces of a cell by name: its entry in ``BENCHMARK.json``,
its configuration file, its traffic file, its limits file and the reader
of each of its metrics. Nothing here names a cell, so a cell that a later
change adds is found from its files alone."""
from __future__ import annotations

import importlib.util
import json
import os
from dataclasses import dataclass, field
from typing import Callable, Dict, List

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH_DIR)


def _json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


@dataclass
class Metric:
    name: str
    unit: str
    entry: dict
    read: Callable


@dataclass
class Cell:
    name: str
    entry: dict
    config: dict
    traffic: dict
    limits: dict
    end_to_end: List[Metric] = field(default_factory=list)
    per_layer: List[Metric] = field(default_factory=list)


def load_reader(name: str, bench_dir: str = BENCH_DIR) -> Callable:
    """``bench/metrics/<name>.py``'s ``read(run)``, loaded by path (metric
    names may hold dots)."""
    path = os.path.join(bench_dir, "metrics", f"{name}.py")
    spec = importlib.util.spec_from_file_location(
        "bench_metric_" + name.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def _applies(metric: dict, cell: str, e2e_names: set) -> bool:
    if "workloads" in metric:
        return cell in metric["workloads"]
    # a metric without a list is reported wherever the metric it moves is
    return metric.get("moves") in e2e_names if "moves" in metric else True


def load_cell(name: str, root: str = ROOT) -> Cell:
    bench_dir = os.path.join(root, "bench")
    spec = _json(os.path.join(root, "BENCHMARK.json"))
    entries = {w["name"]: w for w in spec["workloads"]}
    if name not in entries:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json "
                       f"(have {sorted(entries)})")
    entry = entries[name]
    configs = {c["name"]: c for c in spec["configs"]}
    config = _json(os.path.join(root, configs[entry["config"]]["file"]))
    traffic = _json(os.path.join(bench_dir, "traffic",
                                 f"{entry['traffic']}.json"))
    limits = _json(os.path.join(bench_dir, "limits", f"{name}.json"))
    cell = Cell(name, entry, config, traffic, limits)
    e2e = [m for m in spec["end_to_end"] if _applies(m, name, set())]
    names = {m["name"] for m in e2e}
    cell.end_to_end = [Metric(m["name"], m["unit"], m,
                              load_reader(m["name"], bench_dir)) for m in e2e]
    cell.per_layer = [Metric(m["name"], m["unit"], m,
                             load_reader(m["name"], bench_dir))
                      for m in spec["per_layer"] if _applies(m, name, names)]
    return cell


def peaks(device_kind: str, bench_dir: str = BENCH_DIR) -> Dict[str, float]:
    """The chip's published peaks; an unknown kind is an error."""
    table = _json(os.path.join(bench_dir, "peaks.json"))
    if device_kind not in table["devices"]:
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       f"bench/peaks.json (have {sorted(table['devices'])})")
    return table["devices"][device_kind]
