"""``writeback_hidden_pct``, the share of the write-back's host seconds
that the barrier did not wait for: its formula on hand-made seconds, 0
on the spans recorded on a v5e before write-backs streamed (the barrier
pulled and scattered every box itself), and on a lowered CPU solve."""
from collections import defaultdict
from pathlib import Path
from types import SimpleNamespace

import numpy as np
import pytest

from bench import harness, spec, trace

RECORDED = Path(__file__).resolve().parent / "data" / \
    "so2dr_box2d1r_1024_spans.xplane.pb"
CELLS = ["box2d1r.ooc-49152", "box2d4r.ooc-49152", "box2d1r.incore-12800"]


def _read(op_wall_s):
    return spec.load_cell(CELLS[0]).reader("writeback_hidden_pct")(
        SimpleNamespace(op_wall_s=op_wall_s))


def test_listed_for_every_cell():
    for name in CELLS:
        cell = spec.load_cell(name)
        assert "writeback_hidden_pct" in [m.name for m in cell.per_layer]


def test_formula_on_hand_made_seconds():
    assert _read({"HostCommit": 3.0, "D2H.pull": 2.0,
                  "D2H.scatter": 6.0}) == pytest.approx(62.5)
    assert _read({"HostCommit": 0.0, "D2H.pull": 2.0,
                  "D2H.scatter": 6.0}) == pytest.approx(100.0)
    # a barrier longer than the write-back hid none of it
    assert _read({"HostCommit": 9.0, "D2H.pull": 2.0,
                  "D2H.scatter": 6.0}) == 0.0
    assert _read({"HostCommit": 9.0}) is None
    assert _read({"D2H.pull": 2.0, "D2H.scatter": 6.0}) is None


def test_reads_zero_on_recorded_barrier_spans():
    """The recorded solve's spans, summed by name on the window's host
    thread; ``HostCommit`` had no span of its own and held its phases."""
    from jax.profiler import ProfileData

    planes = list(ProfileData.from_file(str(RECORDED)).planes)
    win = trace._window(planes, harness.WINDOW_SPAN)
    wall = defaultdict(float)
    for name, s, e in trace._host_events(planes, harness.WINDOW_SPAN, win):
        wall[name] += (e - s) / 1e9
    assert wall["D2H.pull"] > 0 and wall["D2H.scatter"] > 0
    wall["HostCommit"] = (wall["HostCommit.drain"] + wall["D2H.pull"]
                          + wall["D2H.scatter"])
    assert _read(dict(wall)) == 0.0


@pytest.mark.parametrize("engine", ["so2dr", "naive_tb"])
def test_on_a_lowered_solve(engine):
    """Read from a solve's own ``ExecStats``: a share in [0, 100] where
    boxes stream, and exactly 0 where every box is written back in the
    barrier (``naive_tb``)."""
    from repro import compile_plan, get_stencil
    from repro.core.lower import lower

    plan = compile_plan(engine, get_stencil("box2d1r"), 62, 40, 8, 3, 4, 2)
    x = np.random.default_rng(0).random(plan.shape, dtype=np.float32)
    _, _, es = lower(plan).execute(x, pipeline=True)
    value = _read(es.op_wall_s)
    assert 0.0 <= value <= 100.0
    if engine == "naive_tb":
        assert value == 0.0
