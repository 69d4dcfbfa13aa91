"""A whole run of each cell, with the look for a chip skipped, on the CPU
at a tiny size: sound, it is ``correct``; with the timed path broken
underneath in each way the cell can break, ``correct`` comes out false."""
import dataclasses
import importlib
import time

import jax.numpy as jnp
import pytest

from bench import harness, spec

CELLS = ["box2d1r.ooc-49152", "box2d4r.ooc-49152", "box2d1r.incore-12800"]
OOC = CELLS[:2]


def _run(root, cell):
    c = spec.load_cell(cell, root)
    return harness.run_cell(c, 2**33 + 7, 0.2, False, time.perf_counter(),
                            log=lambda _: None)


def _wrap_kernels(monkeypatch, transform):
    from repro.kernels import dispatch

    select = dispatch.select_kernel

    def faulty(name, steps, policy):
        impl, fn = select(name, steps, policy)
        return impl, transform(fn)

    monkeypatch.setattr(dispatch, "select_kernel", faulty)


def _state_unchanged(fn):
    """A fused step that returns its band as it came, cropped to the
    shape the step would give."""
    from repro import get_stencil

    def step(band, name, steps, keep_top=False, keep_bottom=False):
        m = steps * get_stencil(name).radius
        return band[(0 if keep_top else m):
                    band.shape[0] - (0 if keep_bottom else m)]

    return step


def _answer_altered(fn):
    """A fused step whose result has one cell off, where it is made."""
    from repro import get_stencil

    def step(band, name, steps, keep_top=False, keep_bottom=False):
        out = fn(band, name, steps, keep_top=keep_top,
                 keep_bottom=keep_bottom)
        r = get_stencil(name).radius
        return out.at[r, r].add(0.01)

    return step


def _half_written_back(monkeypatch):
    """Every write-back carries only the first half of its rows."""
    lower = importlib.import_module("repro.core.lower")
    commit = lower._Runtime.commit

    def half(self):
        kept = []
        for sl, rows, codec in self.staged:
            h = rows.shape[0] // 2
            kept.append(((slice(sl[0].start, sl[0].start + h),) + sl[1:],
                         rows[:h], codec))
        self.staged[:] = kept
        commit(self)

    monkeypatch.setattr(lower._Runtime, "commit", half)


def _exchange_left_out(monkeypatch):
    """Region sharing hands zeros to the next chunk instead of rows."""
    executor = importlib.import_module("repro.core.executor")
    lower_mod = importlib.import_module("repro.core.lower")
    lower = executor.lower
    tag = lower_mod._TAG["BufferWrite"]

    def zeroed(fn):
        def run(rt):
            fn(rt)
            for i, b in enumerate(rt.bufs):
                if b is not None:
                    rt.bufs[i] = jnp.zeros_like(b)
        return run

    def faulty(plan, **kw):
        cp = lower(plan, **kw)
        new = {}
        for st in cp.stages:
            for b in st.ops:
                new[id(b)] = (b[0], zeroed(b[1]), b[2], b[3]) \
                    if b[0] == tag else b
        stages = tuple(dataclasses.replace(
            st, ops=tuple(new[id(b)] for b in st.ops),
            prefetch=tuple(new[id(b)] for b in st.prefetch),
            rest=tuple(new[id(b)] for b in st.rest)) for st in cp.stages)
        return dataclasses.replace(cp, stages=stages)

    monkeypatch.setattr(executor, "lower", faulty)


@pytest.mark.parametrize("cell", CELLS)
def test_sound_run_is_correct(tiny_root, cell):
    res = _run(tiny_root, cell)
    assert res["correct"] is True, res["checks"]
    assert res["attempted"] >= 1 and res["failed"] == 0
    assert list(res)[-1] == "checks"
    assert set(res["metrics"]) == {"cell_updates_per_s", "setup_s"}
    assert all(m["value"] > 0 for m in res["metrics"].values())


@pytest.mark.parametrize("cell", CELLS)
@pytest.mark.parametrize("fault", ["state_unchanged", "answer_altered",
                                   "half_written_back"])
def test_fault_makes_run_incorrect(tiny_root, cell, fault, monkeypatch):
    if fault == "state_unchanged":
        _wrap_kernels(monkeypatch, _state_unchanged)
    elif fault == "answer_altered":
        _wrap_kernels(monkeypatch, _answer_altered)
    else:
        _half_written_back(monkeypatch)
    res = _run(tiny_root, cell)
    assert res["correct"] is False, res["checks"]
    assert res["failed"] == 1


@pytest.mark.parametrize("cell", OOC)
def test_exchange_left_out_makes_run_incorrect(tiny_root, cell, monkeypatch):
    _exchange_left_out(monkeypatch)
    res = _run(tiny_root, cell)
    assert res["correct"] is False, res["checks"]


@pytest.mark.parametrize("cell", CELLS + ["box2d1r.mesh-2x2"])
def test_control_makes_run_incorrect(tiny_root, cell):
    """The configuration's control in the program's place, at the steps a
    solve of the cell runs, on a tiny domain: ``correct`` comes out
    false (no window runs)."""
    c = spec.load_cell(cell, tiny_root)
    steps = spec.load_cell(cell).config["steps_per_solve"]
    c = dataclasses.replace(c, config=dict(c.config, steps_per_solve=steps))
    res = harness.run_cell(c, 2**33 + 7, 0.2, False, time.perf_counter(),
                           log=lambda _: None, control=True)
    assert res["correct"] is False, res["checks"]
    assert res["failed"] == 1 and list(res)[-1] == "checks"
