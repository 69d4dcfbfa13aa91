"""Chip benchmark of the SO2DR out-of-core stencil path.

Run one cell with ``python bench/run.py --workload <cell> --seed <n>
--seconds <s> --trace <0|1>`` from the root of a checkout.  Everything
that belongs to one configuration, one traffic mix or one per-layer
metric is a file of its own, found by the name ``BENCHMARK.json`` gives
it (:mod:`bench.spec`).
"""
