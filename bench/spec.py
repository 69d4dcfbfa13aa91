"""What one cell is: its entry in ``BENCHMARK.json``, found by name.

A configuration is the file ``BENCHMARK.json`` names for it; a traffic
mix is ``bench/traffic/<name>.json``; a per-layer metric is a reader
``bench/metrics/<name>.py`` that defines ``read(ctx)``.  Adding any of
them adds a file and an entry, and edits no file that is there.
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
from pathlib import Path
from typing import Callable, Dict, List, Optional

ROOT = Path(__file__).resolve().parents[1]


@dataclasses.dataclass(frozen=True)
class Metric:
    name: str
    unit: str


@dataclasses.dataclass(frozen=True)
class Cell:
    name: str
    chips: int
    config_name: str
    config: dict
    traffic_name: str
    traffic: dict
    end_to_end: List[Metric]
    per_layer: List[Metric]
    root: Path

    def reader(self, metric: str) -> Callable:
        """The ``read(ctx)`` function of ``bench/metrics/<metric>.py``."""
        path = self.root / "bench" / "metrics" / f"{metric}.py"
        mod_spec = importlib.util.spec_from_file_location(
            f"bench_metric_{metric.replace('.', '_').replace('-', '_')}", path)
        mod = importlib.util.module_from_spec(mod_spec)
        mod_spec.loader.exec_module(mod)
        return mod.read


def _metric(entry: dict) -> Metric:
    return Metric(name=entry["name"], unit=entry["unit"])


def _applies(entry: dict, cell: str) -> bool:
    listed = entry.get("workloads")
    return listed is None or cell in listed


def load_benchmark(root: Path = ROOT) -> dict:
    with open(root / "BENCHMARK.json") as f:
        return json.load(f)


def load_cell(name: str, root: Path = ROOT) -> Cell:
    """Resolve a workload of ``<root>/BENCHMARK.json`` to its config,
    traffic and metrics.  Raises ``KeyError`` for an unknown name."""
    bench = load_benchmark(root)
    work: Optional[dict] = next(
        (w for w in bench["workloads"] if w["name"] == name), None)
    if work is None:
        known = sorted(w["name"] for w in bench["workloads"])
        raise KeyError(f"unknown workload {name!r}; known: {known}")
    cfg_entry = next(c for c in bench["configs"] if c["name"] == work["config"])
    with open(root / cfg_entry["file"]) as f:
        config = json.load(f)
    with open(root / "bench" / "traffic" / f"{work['traffic']}.json") as f:
        traffic = json.load(f)
    return Cell(
        name=name, chips=int(work["chips"]),
        config_name=cfg_entry["name"], config=config,
        traffic_name=work["traffic"], traffic=traffic,
        end_to_end=[_metric(m) for m in bench["end_to_end"]
                    if _applies(m, name)],
        per_layer=[_metric(m) for m in bench["per_layer"]
                   if _applies(m, name)],
        root=root)


def read_metrics(cell: Cell, ctx) -> Dict[str, dict]:
    """Run every per-layer reader of the cell; a reader that finds
    nothing to read returns ``None`` and its metric is left out."""
    out = {}
    for m in cell.per_layer:
        value = cell.reader(m.name)(ctx)
        if value is not None:
            out[m.name] = {"value": float(value), "unit": m.unit}
    return out
