"""What one cell is: its entry in ``BENCHMARK.json``, found by name.

A configuration is the file ``BENCHMARK.json`` names for it; a traffic
mix is ``bench/traffic/<name>.json``; a per-layer metric is a reader
``bench/metrics/<name>.py`` that defines ``read(ctx)``.  Adding any of
them adds a file and an entry, and edits no file that is there.

A configuration states:

* ``interior``: a side (a square domain) or one extent per axis, of the
  rank of the program's stencil; the frame is ``radius`` cells more on
  every side;
* ``engine`` and its ``schedule`` (:data:`bench.harness.PATHS`): an
  engine of ``compile_plan`` reads ``d``, ``s_tb`` and ``k_on`` (2-D);
  ``box_tb`` reads ``tiles`` (one count per axis), ``time_depth`` (steps
  a round) and ``k_on`` (steps a fused call), for any rank; ``sharded``
  reads ``mesh`` and ``k_ici``;
* the reference's update: ``coefficients``, a tap table ``2r+1`` long
  along each axis of the domain's rank, or instead ``"update":
  "<name>"``, the function ``update(at)`` of ``bench/updates/<name>.py``
  (:mod:`bench.reference`), again a file of its own.
"""
from __future__ import annotations

import dataclasses
import functools
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
        return _module(self.root / "bench" / "metrics" / f"{metric}.py",
                       "bench_metric").read

    def update(self) -> Optional[Callable]:
        """The ``update(at)`` function of ``bench/updates/<name>.py`` where
        the configuration names one (``"update": "<name>"``), else
        ``None``: the reference's tap sum."""
        name = self.config.get("update")
        if name is None:
            return None
        return _update_module(self.root / "bench" / "updates" / f"{name}.py",
                              "bench_update").update


def _module(path: Path, prefix: str):
    """The module of the file ``path``."""
    name = path.stem.replace(".", "_").replace("-", "_")
    mod_spec = importlib.util.spec_from_file_location(f"{prefix}_{name}",
                                                      path)
    mod = importlib.util.module_from_spec(mod_spec)
    mod_spec.loader.exec_module(mod)
    return mod


# an update's module is loaded once a process: the reference's compiled
# programs are keyed on the function
_update_module = functools.lru_cache(maxsize=None)(_module)


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
