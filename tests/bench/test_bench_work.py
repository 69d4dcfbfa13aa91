"""The useful-work counter (``bench/work.py``) and the peaks table."""
import json

import pytest

from bench import peaks, work

# (radius, flops/cell, band rows, band cols, steps, keep_top, keep_bottom,
#  cells, band bytes in + out): counted by hand, step by step
HAND = [
    # box2d1r: each step updates (H - 2) x (X - 2); a free side loses a row
    (1, 17, 20, 10, 2, True, True, 18 * 8 + 18 * 8, (20 + 20) * 10 * 4),
    (1, 17, 20, 10, 2, True, False, 18 * 8 + 17 * 8, (20 + 18) * 10 * 4),
    (1, 17, 20, 10, 2, False, True, 18 * 8 + 17 * 8, (20 + 18) * 10 * 4),
    (1, 17, 20, 10, 2, False, False, 18 * 8 + 16 * 8, (20 + 16) * 10 * 4),
    # box2d4r: (H - 8) x (X - 8); a free side loses 4 rows
    (4, 161, 40, 30, 2, True, True, 32 * 22 + 32 * 22, (40 + 40) * 30 * 4),
    (4, 161, 40, 30, 2, True, False, 32 * 22 + 28 * 22, (40 + 32) * 30 * 4),
    (4, 161, 40, 30, 2, False, True, 32 * 22 + 28 * 22, (40 + 32) * 30 * 4),
    (4, 161, 40, 30, 2, False, False, 32 * 22 + 24 * 22, (40 + 24) * 30 * 4),
]


@pytest.mark.parametrize("r,fpc,h,x,m,kt,kb,cells,nbytes", HAND)
def test_band_work_matches_hand_count(r, fpc, h, x, m, kt, kb, cells, nbytes):
    w = work.band_work(h, x, r, m, kt, kb, fpc, 4)
    assert (w.cells, w.flops, w.bytes) == (cells, cells * fpc, nbytes)


def test_band_too_small_is_refused():
    with pytest.raises(ValueError):
        work.band_work(8, 30, 4, 1, False, False, 161, 4)


def test_least_time_and_bound():
    w = work.Work(cells=10, flops=1000, bytes=100)
    assert w.least_seconds(hbm_bytes_per_s=10.0, flops_per_s=1000.0) == 10.0
    assert w.bound(10.0, 1000.0) == "hbm"
    assert w.least_seconds(1000.0, 10.0) == 100.0
    assert w.bound(1000.0, 10.0) == "compute"


@pytest.mark.parametrize("engine,stencil,n,d,s_tb", [
    ("so2dr", "box2d1r", 16, 4, 8), ("so2dr", "box2d4r", 8, 4, 4),
    ("incore", "box2d1r", 16, 1, 8)])
def test_plan_work_agrees_with_the_plans_own_accounting(engine, stencil, n,
                                                        d, s_tb):
    """A second witness: the planner's per-op accounting, written
    separately in the program, counts the same cells, flops and bytes."""
    from repro import compile_plan, get_stencil
    from repro.core.plan import FusedKernel

    st = get_stencil(stencil)
    side = 256 + 2 * st.radius
    plan = compile_plan(engine, st, side, side, n, d, s_tb, 4)
    ops = [op for op in plan.ops if isinstance(op, FusedKernel)]
    w = work.plan_work(ops, st.radius, st.flops_per_elem, 4)
    assert w.cells == sum(op.elements for op in ops)
    assert w.flops == sum(op.flops for op in ops)
    assert w.bytes == sum(op.hbm_bytes for op in ops)
    least = work.least_seconds(ops, st.radius, st.flops_per_elem, 4,
                               819e9, 30e12)
    assert least == pytest.approx(sum(
        work.band_work(op.shape_in[0], op.shape_in[1], st.radius, op.steps,
                       op.keep_lo[0], op.keep_hi[0], st.flops_per_elem, 4)
        .least_seconds(819e9, 30e12) for op in ops))


def test_peaks_table_has_v5e_and_refuses_unknown_kinds(tmp_path):
    row = peaks.lookup("TPU v5 lite")
    assert row["hbm_bytes_per_s"] == 819e9
    assert row["mxu_bf16_flops_per_s"] == 197e12
    with pytest.raises(KeyError, match="not in the peaks table"):
        peaks.lookup("cpu")
    table = tmp_path / "peaks.json"
    table.write_text(json.dumps({"TPU v9": {"hbm_bytes_per_s": 1.0}}))
    assert peaks.lookup("TPU v9", table)["hbm_bytes_per_s"] == 1.0
    with pytest.raises(KeyError):
        peaks.lookup("TPU v5 lite", table)
