"""The executor's spans as the benchmark reads them.

* The two span readers, ``d2h_pull_gbps`` and ``kernel_dispatch_ms``,
  on the context of a whole traced run of each cell on the CPU at a tiny
  size (the profiler and the chip's peaks stood in for), and with the
  spans missing, as in a program that has none.
* A trace recorded on a TPU v5e with the spans: the 1024^2 SO2DR plan of
  box2d1r (d=4, 8 steps in one round, k_on=4, ``pallas_db``) the other
  recordings hold, traced over one solve after a warm-up one, with the
  benchmark's profiler options.  Every long idle gap is named by a
  program span, and the spans cover nearly all of the device's idle
  time in the window.
"""
import importlib
import math
import time
from pathlib import Path

import pytest

from bench import harness, spec, trace

DATA = Path(__file__).resolve().parent / "data"
KERNELS = r"^jit_(fused_stencil_band(_db)?|banded_fused_stencil)\("
RECORDED = DATA / "so2dr_box2d1r_1024_spans.xplane.pb"
CELLS = ["box2d1r.ooc-49152", "box2d4r.ooc-49152", "box2d1r.incore-12800"]
READERS = ("d2h_pull_gbps", "kernel_dispatch_ms")


def program_spans(planes, window_span: str = harness.WINDOW_SPAN):
    """The program's spans, ``(name, start_ns, end_ns, stats)``: the
    host events in the window that carry a ``run`` stat."""
    planes = list(planes)
    win = trace._window(planes, window_span)
    spans = []
    for p in planes:
        if p.name != trace.HOST_PLANE:
            continue
        for line in p.lines:
            for e in line.events:
                stats = dict(e.stats)
                if "run" in stats and trace._clip(
                        e.start_ns, e.start_ns + e.duration_ns, win):
                    spans.append((e.name, e.start_ns,
                                  e.start_ns + e.duration_ns, stats))
    return sorted(spans, key=lambda s: s[1])


def idle_under_spans(planes, window_span: str = harness.WINDOW_SPAN):
    """``(idle_ns, covered_ns)``: the window's time with no program on
    the first chip, and the part of it that lies under a program span."""
    planes = list(planes)
    win = trace._window(planes, window_span)
    device = next(p for p in planes if trace.DEVICE_PLANE.match(p.name)
                  and any(line.name == trace.MODULES for line in p.lines))
    mods = next(line for line in device.lines if line.name == trace.MODULES)
    busy = trace.union([iv for e in mods.events if (iv := trace._clip(
        e.start_ns, e.start_ns + e.duration_ns, win))])
    idle = trace.gaps(busy, win)
    spans = trace.union([(s, e) for _, s, e, _ in
                         program_spans(planes, window_span)])
    covered, j = 0.0, 0
    for s, e in idle:
        while j < len(spans) and spans[j][1] <= s:
            j += 1
        k = j
        while k < len(spans) and spans[k][0] < e:
            covered += min(e, spans[k][1]) - max(s, spans[k][0])
            k += 1
    return sum(e - s for s, e in idle), covered


def test_idle_under_spans_counts_the_overlap():
    class E:
        def __init__(self, name, s, e, **stats):
            self.name, self.start_ns, self.duration_ns = name, s, e - s
            self.stats = list(stats.items())

    class L:
        def __init__(self, name, events):
            self.name, self.events = name, events

    class P:
        def __init__(self, name, lines):
            self.name, self.lines = name, lines

    planes = [
        P("/device:TPU:0", [L(trace.MODULES, [E("jit_a(1)", 10, 20),
                                              E("jit_b(2)", 40, 50)])]),
        P(trace.HOST_PLANE, [L("python", [
            E(harness.WINDOW_SPAN, 0, 60), E("H2D", 0, 8, run=1),
            E("np.asarray(jax.Array)", 20, 40),
            E("D2H.pull", 25, 35, run=1), E("D2H.scatter", 52, 70, run=1)])]),
    ]
    # idle: [0,10) [20,40) [50,60); under spans: 8 + 10 + 8
    assert idle_under_spans(planes) == (40, 26)
    assert [n for n, *_ in program_spans(planes)] == [
        "H2D", "D2H.pull", "D2H.scatter"]


class _NoProfiler:
    """The profiler's place in a run on the CPU: the trace readers find
    a small window with one fused call."""

    def start(self):
        pass

    def stop(self):
        pass

    def reduce(self, kernel_pattern, log):
        return trace.TraceSummary(window_s=1.0, busy_s=0.5, kernel_s=0.25,
                                  kernel_calls=1, chips=1, top_ops=[],
                                  idle_gaps=[], lines=[])

    def close(self):
        pass


@pytest.fixture
def traced_context(tiny_root, monkeypatch):
    """Run a cell with ``trace`` on, on the CPU; return its result and
    the context its readers read."""
    from bench import peaks

    seen = {}
    read = harness.read_metrics

    def keep(cell, ctx):
        seen["ctx"] = ctx
        return read(cell, ctx)

    monkeypatch.setattr(harness, "_Tracer", _NoProfiler)
    monkeypatch.setattr(harness, "read_metrics", keep)
    row = peaks.lookup("TPU v5 lite")
    monkeypatch.setattr(peaks, "lookup", lambda kind: row)
    monkeypatch.setattr(peaks, "measure_f32_flops", lambda log: 4e12)

    def run(name):
        cell = spec.load_cell(name, tiny_root)
        res = harness.run_cell(cell, 2**33 + 11, 0.1, True,
                               time.perf_counter(), log=lambda _: None)
        return res, seen["ctx"]

    return run


@pytest.mark.parametrize("cell", CELLS)
def test_span_readers_on_a_traced_cpu_run(traced_context, cell):
    res, ctx = traced_context(cell)
    assert res["correct"] is True, res["checks"]
    m = res["metrics"]
    for name in READERS:
        assert math.isfinite(m[name]["value"]) and m[name]["value"] > 0
    assert m["d2h_pull_gbps"]["unit"] == "GB/s"
    assert m["kernel_dispatch_ms"]["unit"] == "ms"
    assert m["d2h_pull_gbps"]["value"] == pytest.approx(
        ctx.solves * ctx.stats.d2h_bytes / ctx.op_wall_s["D2H.pull"] / 1e9)
    assert m["kernel_dispatch_ms"]["value"] == pytest.approx(
        1e3 * ctx.op_wall_s["FusedKernel.call"]
        / (ctx.solves * len(ctx.kernel_ops)))
    # the barrier's phases lie inside its op seconds
    assert (ctx.op_wall_s["HostCommit.drain"] + ctx.op_wall_s["D2H.pull"]
            + ctx.op_wall_s["D2H.scatter"]) <= ctx.op_wall_s["HostCommit"]

    # without the spans (a program that has none) the readers read nothing
    for gone in ("D2H.pull", "FusedKernel.call"):
        ctx.op_wall_s.pop(gone)
    cellobj = spec.load_cell(cell)
    for name in READERS:
        assert cellobj.reader(name)(ctx) is None


@pytest.mark.parametrize("cell", CELLS)
def test_pulls_move_the_plans_d2h_bytes(tiny_root, cell, monkeypatch):
    """What the readers divide by: one ``D2H.pull`` per D2H box of the
    plan, moving exactly the plan's D2H bytes, and one
    ``FusedKernel.call`` per FusedKernel op of the plan."""
    from repro import compile_plan, get_stencil
    from repro.core.executor import DoubleBufferedExecutor
    from repro.core.plan import FusedKernel
    from repro.kernels.dispatch import DispatchPolicy

    from bench.workload import make_domain, solve_params

    lower = importlib.import_module("repro.core.lower")
    pulled = []
    commit = lower._Runtime.commit

    def tally(self):
        pulled.extend(rows.nbytes for _, rows, _ in self.staged)
        commit(self)

    monkeypatch.setattr(lower._Runtime, "commit", tally)
    p = solve_params(spec.load_cell(cell, tiny_root).config)
    plan = compile_plan(p.engine, get_stencil(p.stencil), p.Y, p.X, p.steps,
                        p.d, p.s_tb, p.k_on)
    ex = DoubleBufferedExecutor(policy=DispatchPolicy())
    ex.execute(plan, make_domain((p.Y, p.X), 5, 0.0, 1.0))
    es = ex.exec_stats
    assert es.op_counts["D2H.pull"] == len(pulled)
    assert sum(pulled) == plan.stats().d2h_bytes
    assert es.op_counts["FusedKernel.call"] == sum(
        isinstance(op, FusedKernel) for op in plan.ops)


def test_recorded_trace_names_gaps_by_program_span():
    from jax.profiler import ProfileData

    planes = list(ProfileData.from_file(str(RECORDED)).planes)
    s = trace.reduce_planes(planes, harness.WINDOW_SPAN, KERNELS)
    spans = program_spans(planes)
    names = {n for n, *_ in spans}
    assert {"H2D", "FusedKernel.call", "D2H.pull", "HostCommit.drain",
            "Execute.validate"} <= names
    assert len({st["run"] for *_, st in spans}) == 1
    for a, b in zip(spans, spans[1:]):
        assert a[2] <= b[1], (a, b)       # leaves: none holds another
    long = [(n, t) for n, t in s.idle_gaps if t > 1e-3]
    assert long
    assert all(n in names for n, _ in long), s.idle_gaps
    idle, covered = idle_under_spans(planes)
    assert 0 < idle and covered >= 0.9 * idle, (covered, idle)
