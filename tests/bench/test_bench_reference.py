"""The benchmark's plain reference (``bench/reference.py``) against the
program's own oracle at small sizes, the windows it checks, and its
controls: the reference in the next precision down must fail the
configuration's limit."""
import json

import jax.numpy as jnp
import numpy as np
import pytest

from bench import reference, workload
from conftest import ROOT


def _config(name):
    return json.loads((ROOT / "bench" / "configs" / f"{name}.json").read_text())


@pytest.mark.parametrize("name,side,steps", [
    ("box2d1r-f32", 70, 12), ("box2d4r-f32", 72, 6)])
def test_reference_matches_run_reference(name, side, steps):
    from repro import get_stencil, run_reference

    cfg = _config(name)
    x = workload.make_domain((side, side), 3)
    oracle = np.asarray(run_reference(jnp.asarray(x),
                                      get_stencil(cfg["stencil"]), steps))
    size = 32
    wins = reference.windows(x.shape, cfg["radius"], [side // 2], seed=5,
                             size=size)
    ref = reference.reference_windows(x, cfg, steps, wins, size=size)
    got = np.stack([oracle[y:y + size, c:c + size] for _, y, c in wins])
    assert reference.max_rel_err(got, ref) <= 1e-6
    whole = reference.reference_windows(x, cfg, steps, [("all", 0, 0)],
                                        size=side)[0]
    assert reference.max_rel_err(oracle, whole) <= 1e-6
    assert reference.frame_cells_changed(oracle, x, cfg["radius"]) == 0


def test_coefficients_are_the_programs():
    from repro import get_stencil

    for name in ("box2d1r-f32", "box2d4r-f32", "box2d1r-f32-incore",
                 "box2d1r-f32-mesh2x2"):
        cfg = _config(name)
        np.testing.assert_array_equal(
            reference.coefficients(cfg),
            get_stencil(cfg["stencil"]).coeffs.astype(np.float32))


def test_windows_cover_the_fixed_places_and_follow_the_seed():
    shape, starts = (1000, 900), [250, 500, 750]
    wins = reference.windows(shape, 1, starts, seed=7, size=64)
    labels = [w[0] for w in wins]
    assert labels[:2] == ["corner", "far-corner"]
    assert [w for w in labels if w.startswith("chunk-boundary")] == [
        f"chunk-boundary@{b}" for b in starts]
    assert "tile-seam" in labels
    for label, y0, x0 in wins:
        assert 0 <= y0 <= shape[0] - 64 and 0 <= x0 <= shape[1] - 64
        if label.startswith("chunk-boundary@"):
            assert y0 < int(label.split("@")[1]) < y0 + 64
    assert wins == reference.windows(shape, 1, starts, seed=7, size=64)
    assert wins != reference.windows(shape, 1, starts, seed=8, size=64)


def test_region_origin_keeps_the_cone_inside_the_domain():
    assert reference.region_origin(100, 50, 10, 1000) == 90
    assert reference.region_origin(3, 50, 10, 1000) == 0
    assert reference.region_origin(980, 50, 10, 1000) == 950


def test_frame_cells_changed_counts_each_cell_once():
    x = np.zeros((10, 12), np.float32)
    out = x.copy()
    out[0, 0] = 1.0          # a corner: in two strips, one cell
    out[5, 11] = 1.0
    out[5, 5] = 1.0          # interior: not frame
    assert reference.frame_cells_changed(out, x, 1) == 2


@pytest.mark.parametrize("name,side", [
    ("box2d1r-f32", 96), ("box2d4r-f32", 136), ("box2d1r-f32-mesh2x2", 98)])
def test_control_fails_the_limit(name, side):
    """The reference computed in the precision below the configuration's
    (``control`` in its file), in the program's place, at the steps a
    solve of the cell runs, on a domain a test can hold."""
    cfg = _config(name)
    steps = int(cfg["steps_per_solve"])
    x = workload.make_domain((side, side), 11)
    wins = [("all", 0, 0)]
    ref = reference.reference_windows(x, cfg, steps, wins, size=side)
    ctl = reference.reference_windows(x, cfg, steps, wins, cfg["control"],
                                      size=side)
    assert reference.max_rel_err(ctl, ref) > cfg["limits"]["max_rel_err"]
    assert reference.max_rel_err(ref, ref) == 0.0


def test_unknown_mode_is_refused():
    cfg = _config("box2d1r-f32")
    x = workload.make_domain((20, 20), 1)
    with pytest.raises(ValueError, match="unknown mode"):
        reference.reference_windows(x, cfg, 2, [("all", 0, 0)], "fp8", size=20)
