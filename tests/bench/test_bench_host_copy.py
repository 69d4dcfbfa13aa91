"""``host_copy_hidden_pct``, the share of the domain's copy that ran
under the device's work: found by name for the one-chip cells, its
formula on hand-made seconds (0 where the whole copy blocks inside
``Execute.validate``, near 100 where it runs in bands under the work,
``None`` with no ``Execute.validate``), and read from a lowered solve."""
import importlib
from types import SimpleNamespace

import numpy as np
import pytest

from bench import spec

CELLS = ["box2d1r.ooc-49152", "box2d4r.ooc-49152", "box2d1r.incore-12800"]


def _read(op_wall_s):
    return spec.load_cell(CELLS[0]).reader("host_copy_hidden_pct")(
        SimpleNamespace(op_wall_s=op_wall_s))


def test_listed_for_the_one_chip_cells_only():
    for name in CELLS:
        cell = spec.load_cell(name)
        assert "host_copy_hidden_pct" in [m.name for m in cell.per_layer]
    mesh = spec.load_cell("box2d1r.mesh-2x2")
    assert "host_copy_hidden_pct" not in [m.name for m in mesh.per_layer]


def test_formula_on_hand_made_seconds():
    # the parent: the whole copy inside Execute.validate
    assert _read({"Execute.validate": 10.9, "H2D": 0.3}) == 0.0
    # banded under the work, barely waited for
    assert _read({"Execute.validate": 1e-4, "HostCopy.band": 11.0,
                  "HostCopy.wait": 0.05}) == pytest.approx(
        100.0 * (1.0 - 0.0501 / 11.0001))
    assert _read({"Execute.validate": 0.0, "HostCopy.band": 4.0,
                  "HostCopy.wait": 1.0}) == pytest.approx(75.0)
    # waits longer than the copy hid none of it
    assert _read({"Execute.validate": 0.1, "HostCopy.band": 1.0,
                  "HostCopy.wait": 2.0}) == 0.0
    assert _read({"HostCopy.band": 4.0}) is None
    assert _read({}) is None


def test_on_a_lowered_solve(monkeypatch):
    """Read from a solve's own ``ExecStats``: a share in [0, 100] where
    the domain is copied in bands, exactly 0 where it is copied inline."""
    from repro import compile_plan, get_stencil
    from repro.core.lower import lower

    lower_mod = importlib.import_module("repro.core.lower")
    plan = compile_plan("so2dr", get_stencil("box2d1r"), 62, 40, 8, 3, 4, 2)
    x = np.random.default_rng(0).random(plan.shape, dtype=np.float32)
    compiled = lower(plan)
    _, _, es = compiled.execute(x, pipeline=True)
    assert "HostCopy.band" not in es.op_counts
    assert _read(es.op_wall_s) == 0.0
    monkeypatch.setattr(lower_mod, "HOST_COPY_BAND_BYTES", 4 * 40 * 4)
    _, _, es = compiled.execute(x, pipeline=True)
    assert es.op_counts["HostCopy.band"] == 16
    assert 0.0 <= _read(es.op_wall_s) <= 100.0
