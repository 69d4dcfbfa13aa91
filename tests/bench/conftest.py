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

# tiny sizes per radius (another radius: max(96, 32r), 8, 4): interior
# side, steps per solve, s_tb; a sharded configuration keeps its mesh and
# k_ici (8 steps are two rounds of k_ici=4, and 96 + 2 framed rows split
# evenly over a 2x2 mesh)
TINY = {1: (96, 8, 4), 4: (128, 8, 4)}


def cut_to_tiny(cfg: dict) -> dict:
    """``cfg`` cut to a tiny domain and a few steps, whatever its rank
    and schedule: the interior per axis, the steps, a round's depth (to
    a whole part of the steps), and a box schedule's tiles where a tile
    would fall under its round's apron."""
    r = int(cfg["radius"])
    side, steps, depth = TINY.get(r, (max(96, 32 * r), 8, 4))
    interior = cfg["interior"]
    if isinstance(interior, int):
        cfg["interior"] = side
    else:
        # three or more axes: 16 + 16r a side
        n = side if len(interior) == 2 else 16 + 16 * r
        cfg["interior"] = [n] * len(interior)
    cfg["steps_per_solve"] = steps
    sched = cfg["schedule"]
    if "s_tb" in sched:
        sched["s_tb"] = depth
    if "time_depth" in sched:
        t = max(k for k in range(1, min(sched["time_depth"], depth) + 1)
                if steps % k == 0)
        sched["time_depth"] = t
        inner = cfg["interior"]
        inner = [inner] * 2 if isinstance(inner, int) else inner
        tiles = []
        for n, count in zip(inner, sched["tiles"]):
            while count > 1 and n // count < t * r:
                count -= 1
            tiles.append(count)
        sched["tiles"] = tiles
    return cfg


def make_tiny_root(dst: Path, src: Path = ROOT) -> Path:
    """A benchmark root like ``src``'s (the repo's), every configuration
    cut to a tiny domain and a few steps (:func:`cut_to_tiny`); metrics,
    traffic and updates copied as they are."""
    (dst / "bench" / "configs").mkdir(parents=True)
    for part in ("metrics", "traffic", "updates"):
        shutil.copytree(src / "bench" / part, dst / "bench" / part)
    bench = json.loads((src / "BENCHMARK.json").read_text())
    for entry in bench["configs"]:
        cfg = json.loads((src / entry["file"]).read_text())
        (dst / entry["file"]).write_text(json.dumps(cut_to_tiny(cfg)))
    (dst / "BENCHMARK.json").write_text(json.dumps(bench))
    return dst


@pytest.fixture
def tiny_root(tmp_path):
    return make_tiny_root(tmp_path / "root")
