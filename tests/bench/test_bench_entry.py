"""``bench/run.py`` refuses to run off the chip: without a TPU, and in a
directory that holds only ``BENCHMARK.json`` and the benchmark's own
files.  Either way it exits non-zero and prints no result line."""
import json
import os
import shutil
import subprocess
import sys

import pytest

from conftest import ROOT


def _no_result_line(stdout: str) -> None:
    for line in stdout.splitlines():
        try:
            obj = json.loads(line)
        except ValueError:
            continue
        assert not (isinstance(obj, dict) and "correct" in obj), line


def _run(cwd, tmp_path, workload="box2d1r.ooc-49152"):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               JAX_COMPILATION_CACHE_DIR=str(tmp_path / "cache"))
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload", workload,
         "--seed", str(2**33 + 1), "--seconds", "1", "--trace", "0"],
        cwd=cwd, capture_output=True, text=True, env=env, timeout=300)


@pytest.mark.parametrize("workload", ["box2d1r.ooc-49152",
                                      "box2d1r.incore-12800",
                                      "box2d1r.mesh-2x2"])
def test_refuses_without_tpu(workload, tmp_path):
    r = _run(ROOT, tmp_path, workload)
    assert r.returncode != 0
    assert "needs a TPU" in r.stderr
    _no_result_line(r.stdout)


def test_refuses_with_only_the_benchmark_files(tmp_path):
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    lone = tmp_path / "lone"
    lone.mkdir()
    shutil.copy(ROOT / "BENCHMARK.json", lone)
    for p in bench["paths"]:
        shutil.copytree(ROOT / p, lone / p,
                        ignore=shutil.ignore_patterns("__pycache__"))
    r = _run(lone, tmp_path)
    assert r.returncode != 0
    _no_result_line(r.stdout)


def test_unknown_workload_is_refused(tmp_path):
    r = _run(ROOT, tmp_path, "no-such-cell")
    assert r.returncode != 0
    assert "unknown workload" in r.stderr
    _no_result_line(r.stdout)
