"""BoxTB: N-D tile grids + temporal blocking on the box plan IR.

The paper's trade, one level down from the chip mesh: split the domain
into an N-D tile grid, load each tile with a ``t*r``-cell trapezoid
apron on every non-frame side, advance ``t`` steps per H2D round trip,
and write back only the owned interior box.  Deeper ``t`` divides the
transfer rounds while the aprons grow redundant compute — the same
redundancy-for-communication exchange as the sharded engine's
``k_ici``, here against host DRAM instead of ICI.
"""
import math

import numpy as np
import jax.numpy as jnp
import pytest
from hypothesis import given, settings, strategies as stst

from repro.core.analytic import RTX3080_PAPER
from repro.core.autotune import autotune_box, trapezoid_redundant_elements
from repro.core.executor import (
    DoubleBufferedExecutor, DryRunExecutor, EagerExecutor,
)
from repro.core.oocore import compile_box_plan
from repro.core.plan import D2H, H2D
from repro.core.reference import run_reference
from repro.core.stencil import get_stencil


def test_heat3d_box_tb_matches_reference_with_temporal_blocking():
    """The acceptance run: a 3-D heat stencil out-of-core via box
    chunking with time depth >= 2, validated against the oracle."""
    st = get_stencil("heat3d1r")
    assert st.ndim == 3
    shape, n = (30, 26, 22), 6
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.standard_normal(shape), jnp.float32)
    ref = run_reference(x, st, n)
    scale = float(jnp.abs(ref).max())
    plan = compile_box_plan(st, shape, n, tiles=(2, 3, 2), time_depth=2)
    assert plan.k_off == 2 and plan.d == 12
    for ex in (EagerExecutor(), DoubleBufferedExecutor()):
        out = ex.execute(plan, x)[0]
        assert float(jnp.max(jnp.abs(out - ref))) / scale <= 1e-5


@settings(max_examples=8, deadline=None)
@given(t0=stst.integers(1, 3), t1=stst.integers(1, 3), t2=stst.integers(1, 2),
       depth=stst.integers(1, 3), n=stst.integers(2, 6),
       name=stst.sampled_from(("heat3d1r", "box2d1r", "star2d2r")))
def test_trapezoid_redundancy_matches_closed_form(t0, t1, t2, depth, n,
                                                  name):
    """Plan-derived redundant elements equal the trapezoid-apron closed
    form, for 2-D and 3-D domains, any tile grid x time depth."""
    st = get_stencil(name)
    r = st.radius
    shape = (16 * r + 2, 14 * r + 2, 12 * r + 2)[:st.ndim]
    tiles = (t0, t1, t2)[:st.ndim]
    try:
        plan = compile_box_plan(st, shape, n, tiles, depth)
    except ValueError:
        # infeasible: apron deeper than the smallest tile
        tsz = min((shape[a] - 2 * r) // tiles[a]
                  for a in range(st.ndim) if tiles[a] > 1)
        assert depth * r > tsz
        return
    _, stats = DryRunExecutor().execute(plan)
    want = trapezoid_redundant_elements(st, shape, n, tiles, depth)
    assert stats.redundant_elements == want
    assert stats.elements_computed == plan.exact_elements + want


def test_time_depth_divides_transfer_rounds():
    """t steps per round trip: H2D/D2H op counts and bytes shrink ~1/t
    while redundancy grows — the knob the autotuner sweeps."""
    st = get_stencil("heat3d1r")
    shape, n = (66, 66, 66), 8
    stats = {}
    for t in (1, 2, 4):
        plan = compile_box_plan(st, shape, n, (2, 2), t)
        h2d = [op for op in plan.ops if isinstance(op, H2D)]
        d2h = [op for op in plan.ops if isinstance(op, D2H)]
        assert len(h2d) == len(d2h) == math.ceil(n / t) * 4
        _, s = DryRunExecutor().execute(plan)
        stats[t] = s
    assert stats[4].h2d_bytes < stats[2].h2d_bytes < stats[1].h2d_bytes
    assert stats[4].redundant_elements > stats[2].redundant_elements \
        > stats[1].redundant_elements == 0
    # d2h writes exactly the owned interiors, once per round
    interior = math.prod(s - 2 for s in shape) * 4
    for t, s in stats.items():
        assert s.d2h_bytes == math.ceil(n / t) * interior


def test_box_tb_feasibility_and_validation_errors():
    st = get_stencil("heat3d1r")
    with pytest.raises(ValueError, match="infeasible along axis"):
        compile_box_plan(st, (34, 34, 34), 4, (8, 1), 8)
    with pytest.raises(ValueError, match="over-ranks"):
        compile_box_plan(st, (34, 34, 34), 4, (2, 2, 2, 2), 1)
    with pytest.raises(ValueError, match=">= 1"):
        compile_box_plan(st, (34, 34, 34), 4, (0, 2), 1)


def test_autotune_box_ranks_tile_grid_x_time_depth():
    """The sweep compiles real plans, skips infeasible combos, ranks by
    modeled time, and reports redundancy that matches the closed form."""
    st = get_stencil("heat3d1r")
    shape, n = (66, 66, 66), 8
    ranked = autotune_box(
        st, shape, n, RTX3080_PAPER,
        tile_grid=((1, 1, 1), (2, 2), (2, 2, 2), (16, 16)),
        time_depth_grid=(1, 2, 4, 64))
    assert ranked
    times = [c.time_s for c in ranked]
    assert times == sorted(times)
    combos = {(c.tiles, c.time_depth) for c in ranked}
    # t=64 never fits a 64-cell interior tiled 2x; 16x16 tiles only
    # admit shallow depths (4-cell tiles, r=1 -> t <= 4)
    assert all(t != 64 or tiles == (1, 1, 1) for tiles, t in combos)
    assert ((16, 16), 4) in combos and ((16, 16), 1) in combos
    for c in ranked:
        assert c.redundant_elements == trapezoid_redundant_elements(
            st, shape, n, c.tiles, c.time_depth)
        assert c.bottleneck in ("transfer", "kernel")
    # deeper blocking must help the modeled time when transfers dominate:
    # every config here is transfer-bound, so for a fixed tile grid the
    # t=4 plan beats t=1
    by = {(c.tiles, c.time_depth): c for c in ranked}
    assert by[((2, 2), 4)].time_s < by[((2, 2), 1)].time_s


def test_run_cli_rejects_bad_geometry_flags():
    """Unknown/incompatible --chunk-axis/--tile/--time-depth exit 2."""
    from benchmarks.run import main

    for argv in (
        ["--tile", "2,2"],                          # geometry without --dry-run
        ["--time-depth", "2"],
        ["--chunk-axis", "1"],
        ["--dry-run", "--chunk-axis", "2"],         # not a 2-D axis
        ["--dry-run", "--tile", "nope"],            # malformed
        ["--dry-run", "--tile", "2,2,2,2"],         # over-ranks the domain
        ["--dry-run", "--tile", "0,2"],
        ["--dry-run", "--time-depth", "0"],
        ["--dry-run", "--time-depth", "9999"],      # apron deeper than a tile
        ["--dry-run", "--chunk-axis", "1", "--tile", "2,2"],
    ):
        with pytest.raises(SystemExit) as exc:
            main(argv)
        assert exc.value.code == 2, argv
