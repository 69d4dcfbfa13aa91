"""Fixtures of the benchmark's tests: a copy of the benchmark at a size
the CPU runs in a second."""
from __future__ import annotations

import json
import shutil
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
for p in (str(ROOT / "src"), str(ROOT)):
    if p not in sys.path:
        sys.path.insert(0, p)

# tiny sizes per radius: interior side, steps per solve, s_tb
TINY = {1: (96, 8, 4), 4: (128, 8, 4)}


def make_tiny_root(dst: Path) -> Path:
    """A benchmark root like the repo's, every configuration cut to a
    tiny domain and a few steps; metrics and traffic copied as they are."""
    (dst / "bench" / "configs").mkdir(parents=True)
    shutil.copytree(ROOT / "bench" / "metrics", dst / "bench" / "metrics")
    shutil.copytree(ROOT / "bench" / "traffic", dst / "bench" / "traffic")
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    for entry in bench["configs"]:
        cfg = json.loads((ROOT / entry["file"]).read_text())
        side, steps, s_tb = TINY[int(cfg["radius"])]
        cfg["interior"], cfg["steps_per_solve"] = side, steps
        cfg["schedule"]["s_tb"] = s_tb
        (dst / entry["file"]).write_text(json.dumps(cfg))
    (dst / "BENCHMARK.json").write_text(json.dumps(bench))
    return dst


@pytest.fixture
def tiny_root(tmp_path):
    return make_tiny_root(tmp_path / "root")
