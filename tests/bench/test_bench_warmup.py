"""Set-up warms one round up on the fewest leading rows whose plan has
every op shape of the cell's solve, so that nothing new is traced in the
window.  Plans are geometry only: no array is touched."""
import pytest

from bench import harness, spec, workload


@pytest.mark.parametrize("cell,chunks", [
    ("box2d1r.ooc-49152", 3), ("box2d4r.ooc-49152", 3),
    ("box2d1r.incore-12800", 1)])
def test_warm_up_covers_every_shape_of_the_solve(cell, chunks):
    from repro import compile_plan, get_stencil

    c = spec.load_cell(cell)
    p = workload.solve_params(c.config)
    st = get_stencil(p.stencil)
    plan = compile_plan(p.engine, st, p.Y, p.X, p.steps, p.d, p.s_tb, p.k_on)
    warm, rows = harness.warm_up_plan(p, st, plan)
    assert warm.d == chunks
    assert rows <= p.Y and warm.shape == (rows, p.X)
    assert warm.n == min(p.steps, p.s_tb)
    need = {harness._shape_key(op) for op in plan.ops}
    assert need <= {harness._shape_key(op) for op in warm.ops}


def test_shape_key_ignores_where_not_what():
    from repro import compile_plan, get_stencil

    st = get_stencil("box2d1r")
    a = compile_plan("so2dr", st, 6 * 64 + 2, 130, 8, 6, 8, 4)
    b = compile_plan("so2dr", st, 6 * 64 + 2, 258, 8, 6, 8, 4)
    keys = {harness._shape_key(op) for op in a.ops}
    # middle chunks repeat the same shapes at other rows
    assert len(keys) < len(a.ops)
    # another width is another program
    assert not keys & {harness._shape_key(op) for op in b.ops
                       if type(op).__name__ == "FusedKernel"}


@pytest.mark.parametrize("cell", ["box2d1r.ooc-49152", "box2d4r.ooc-49152",
                                  "box2d1r.incore-12800"])
def test_nothing_compiles_in_the_window(tiny_root, cell):
    import time

    lines = []
    res = harness.run_cell(spec.load_cell(cell, tiny_root), 2**33 + 5, 0.2,
                           False, time.perf_counter(), log=lines.append)
    assert res["correct"] is True
    assert any(" in 3 chunk(s) " in l or " in 1 chunk(s) " in l
               for l in lines if l.startswith("setup: warm-up"))
    assert "compile events in window: 0 events, 0 s" in lines
