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
    """A 2-D band is a box that keeps its frame columns."""
    w = work.box_work((h, x), r, m, (kt, True), (kb, True), fpc, 4)
    assert (w.cells, w.flops, w.bytes) == (cells, cells * fpc, nbytes)


def test_band_too_small_is_refused():
    with pytest.raises(ValueError):
        work.box_work((8, 30), 4, 1, (False, True), (False, True), 161, 4)


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
    calls = work.fused_work(ops, st.radius, st.flops_per_elem, 4)
    assert len(calls) == len(ops)
    for op, w in zip(ops, calls):
        assert (w.cells, w.flops, w.bytes) == (op.elements, op.flops,
                                               op.hbm_bytes)


# (gy0, gx0, h, w, Y, X, radius, steps, cells, elements read + written):
# counted by hand, step by step
SHARD_HAND = [
    # a band whole inside a 40x40 domain, halo 2 on each side: step 1
    # updates 8x10, step 2 the owned 6x8
    (10, 10, 10, 12, 40, 40, 1, 2, 8 * 10 + 6 * 8, 10 * 12 + 6 * 8),
    # a corner band: its top and left halos lie outside the domain, so
    # those sides stop at the frame (row and column 1): step 1 updates
    # rows 1-6 x columns 1-8, step 2 rows 1-5 x columns 1-7
    (-2, -2, 10, 12, 40, 40, 1, 2, 6 * 8 + 5 * 7, 8 * 10 + 6 * 8),
    # r = 4, one step on a 20x20 band inside the domain
    (20, 20, 20, 20, 60, 60, 4, 1, 12 * 12, 20 * 20 + 12 * 12),
]


@pytest.mark.parametrize("gy0,gx0,h,w,Y,X,r,m,cells,elems", SHARD_HAND)
def test_shard_work_matches_hand_count(gy0, gx0, h, w, Y, X, r, m, cells,
                                       elems):
    got = work.shard_work(gy0, gx0, h, w, Y, X, r, m, 17, 4)
    assert (got.cells, got.flops, got.bytes) == (cells, cells * 17,
                                                 elems * 4)


@pytest.mark.parametrize("mesh", [(1, 1), (2, 2), (2, 1)])
def test_shard_work_against_the_sharded_plan(mesh):
    """Each round, what the shards write tiles the domain and the last
    step updates the interior once; the program's masked calls update
    every cell of their band's centre at each step, at least the useful
    ones; and a one-chip mesh does no more than the domain's updates."""
    from repro import get_stencil
    from repro.core.plan import ShardKernel
    from repro.core.shard import compile_sharded

    st = get_stencil("box2d1r")
    side, n, k = 66, 8, 4
    plan = compile_sharded(st, side, side, n, k, mesh)
    ops = [op for s in plan.streams for op in s if isinstance(op, ShardKernel)]
    assert len(ops) == mesh[0] * mesh[1] * n // k
    calls = [work.shard_work(op.gy0, op.gx0, op.h, op.w, side, side, 1,
                             op.steps, 17, 4) for op in ops]
    last = [work.shard_work(op.gy0 + (k - 1), op.gx0 + (k - 1),
                            op.h - 2 * (k - 1), op.w - 2 * (k - 1), side,
                            side, 1, 1, 17, 4) for op in ops]
    interior = (side - 2) ** 2
    assert sum(w.cells for w in last) == n // k * interior
    for op, w in zip(ops, calls):
        assert w.cells <= op.elements
    useful = sum(w.cells for w in calls)
    assert useful >= plan.exact_elements
    assert (useful == plan.exact_elements) == (mesh == (1, 1))


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
