#!/usr/bin/env python3
"""Run one cell of the chip benchmark.

    python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

From the root of a checkout.  The cell is an entry of ``BENCHMARK.json``
(:mod:`bench.spec`).  With ``--trace 0`` the result line carries the
cell's end-to-end metrics, with ``--trace 1`` its per-layer metrics,
read from a profiler trace of the window.  The last line of standard
output is one JSON object: ``correct``, ``attempted``, ``failed``,
``metrics``, ``device`` (and ``breakdown`` when traced), and last
``checks``, each number compared beside its limit; the same numbers are
the last lines on standard error.

``--control 1`` puts the configuration's control (the reference in the
precision below the configuration's) in the program's place and runs no
window: its ``correct`` has to come out false.  It is for setting and
re-reading the limits; the benchmark's own runs leave it at 0.

Without a chip whose ``device_kind`` is in ``bench/peaks.json``, or with
fewer chips than the cell asks for, it exits non-zero and prints no
result line.  It never falls back to the CPU.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
# the package's directory itself off the path (its module names would
# shadow others), the checkout's sources and the package's parent on it
sys.path[:] = [str(ROOT / "src"), str(ROOT)] + [
    p for p in sys.path if Path(p or ".").resolve() != ROOT / "bench"]

from repro import compile_cache  # noqa: E402  (before any JAX work)

CACHE = compile_cache.enable()

import jax  # noqa: E402

# every compile goes to the persistent cache, however short
jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)

from bench import harness, spec  # noqa: E402


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--control", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    cell = spec.load_cell(args.workload)
    harness.check_device(cell.chips)
    harness.log_err(f"bench: {cell.name} seed={args.seed} "
                    f"seconds={args.seconds} trace={args.trace} "
                    f"control={args.control} "
                    f"compile cache {CACHE}")
    result = harness.run_cell(cell, args.seed, args.seconds,
                              bool(args.trace), T_START,
                              control=bool(args.control))
    print(json.dumps(result), flush=True)


if __name__ == "__main__":
    main()
