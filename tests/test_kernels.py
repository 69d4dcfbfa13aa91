"""Pallas fused-stencil kernel vs the pure-jnp oracle (interpret mode)."""
import numpy as np
import jax.numpy as jnp
import pytest
from hypothesis import given, settings, strategies as st_h

from repro.core.executor import EagerExecutor
from repro.core.oocore import compile_plan
from repro.core.reference import run_reference
from repro.core.stencil import get_stencil
from repro.kernels.dispatch import DispatchPolicy
from repro.kernels.ops import fused_stencil
from repro.kernels.ref import multi_step_band

RNG = np.random.default_rng(7)


def _check(name, H, X, steps, kt, kb, tile=(16, 64), dtype=np.float32, tol=1e-5):
    x = RNG.standard_normal((H, X)).astype(dtype)
    xb = jnp.asarray(x)
    ref = multi_step_band(xb, name, steps, kt, kb)
    got = fused_stencil(xb, name, steps, kt, kb, tile=tile)
    err = np.abs(np.asarray(got, np.float32) - np.asarray(ref, np.float32))
    scale = np.abs(np.asarray(ref, np.float32)).max() + 1e-6
    assert err.max() / scale < tol, (name, H, X, steps, kt, kb, err.max())


@pytest.mark.parametrize("name", ["box2d1r", "box2d2r", "box2d4r", "gradient2d", "star2d3r"])
@pytest.mark.parametrize("steps", [1, 2, 4])
def test_kernel_matches_oracle(name, steps):
    for kt, kb in [(False, False), (True, False), (True, True)]:
        _check(name, 48, 160, steps, kt, kb)


def test_kernel_non_divisible_edges():
    # shapes chosen to exercise clamped DMA starts + padded output tiles
    _check("box2d2r", 37, 131, 2, False, True)
    _check("box2d1r", 41, 97, 4, True, False)


def test_kernel_bf16():
    _check("box2d1r", 64, 256, 4, True, False, dtype=np.float32, tol=1e-5)
    x = RNG.standard_normal((64, 256)).astype(np.float32)
    xb = jnp.asarray(x, jnp.bfloat16)
    ref = multi_step_band(xb, "box2d1r", 4, True, False)
    got = fused_stencil(xb, "box2d1r", 4, True, False, tile=(16, 64))
    err = np.abs(np.asarray(got, np.float32) - np.asarray(ref, np.float32)).max()
    assert err < 3e-2


def test_kernel_tiny_band_fallback():
    """A band smaller than one apron'd tile has no reference fallback:
    every kernel pads it into one tile, and the lowered executor names
    the kernel that ran."""
    _check("box2d4r", 20, 40, 2, True, True, tile=(256, 512))
    for impl, fn in IMPLS.items():
        _check_impl(fn, "box2d4r", 20, 40, 2, True, True, tile=(256, 512))
    st = get_stencil("box2d1r")
    x = RNG.standard_normal((14, 14)).astype(np.float32)
    plan = compile_plan("so2dr", st, 14, 14, 4, 2, 2, 2)
    ex = EagerExecutor(policy=DispatchPolicy(impl="pallas_db"))
    out, _ = ex.execute(plan, x)
    assert ex.exec_stats.kernel_impl == "pallas_db"
    ref = np.asarray(run_reference(jnp.asarray(x), st, 4))
    assert np.abs(out - ref).max() < 1e-5


def _impls():
    from repro.kernels.stencil_banded_mxu import banded_fused_stencil
    from repro.kernels.stencil_multistep import fused_stencil_band
    from repro.kernels.stencil_multistep_db import fused_stencil_band_db

    return {"pallas": fused_stencil_band, "pallas_db": fused_stencil_band_db,
            "mxu": banded_fused_stencil}


IMPLS = _impls()


def _check_impl(fn, name, H, X, steps, kt, kb, tile=(16, 128),
                dtype=jnp.float32, tol=1e-5):
    x = RNG.standard_normal((H, X)).astype(np.float32)
    xb = jnp.asarray(x, dtype)
    # oracle in f32 on the same (possibly bf16-rounded) input: the tiles
    # compute in f32, so only the final rounding to ``dtype`` differs
    ref = np.asarray(multi_step_band(xb.astype(jnp.float32), name, steps,
                                     kt, kb))
    got = fn(xb, name, steps, kt, kb, tile=tile)
    assert got.dtype == dtype and got.shape == ref.shape
    err = np.abs(np.asarray(got, np.float32) - ref).max()
    scale = np.abs(ref).max() + 1e-6
    assert err / scale < tol, (name, H, X, steps, kt, kb, err)


KEEPS = [(False, False), (True, False), (False, True), (True, True)]
# (stencil, steps, H, X) on a (16, 128) tile: widths off the 128-lane
# grid, and bands just above one apron'd tile (one extra output row or
# column spills into a second tile)
GEOMETRIES = [("box2d1r", 2, 21, 129), ("box2d2r", 2, 37, 200),
              ("gradient2d", 3, 40, 131), ("box2d4r", 1, 27, 300)]


@pytest.mark.parametrize("kt,kb", KEEPS)
@pytest.mark.parametrize("impl,name,steps,H,X", [
    (impl, *geo) for impl in IMPLS for geo in GEOMETRIES
    if impl != "mxu" or geo[0] != "gradient2d"])
def test_padded_geometry_matches_oracle(impl, name, steps, H, X, kt, kb):
    _check_impl(IMPLS[impl], name, H, X, steps, kt, kb)


@pytest.mark.parametrize("impl", sorted(IMPLS))
def test_bf16_band_per_impl(impl):
    _check_impl(IMPLS[impl], "box2d1r", 37, 150, 3, True, False,
                dtype=jnp.bfloat16, tol=1e-2)


@settings(max_examples=15, deadline=None)
@given(
    h=st_h.integers(20, 70),
    x=st_h.integers(30, 150),
    steps=st_h.integers(1, 3),
    r=st_h.sampled_from([1, 2]),
    kt=st_h.booleans(),
    kb=st_h.booleans(),
)
def test_kernel_property(h, x, steps, r, kt, kb):
    name = f"box2d{r}r"
    if h - 2 * steps * r + (kt + kb) * steps * r < 1 or x - 2 * steps * r < 1:
        return
    _check(name, h, x, steps, kt, kb)


def test_banded_mxu_kernel():
    """Beyond-paper MXU-banded kernel (EXPERIMENTS.md §4.3) ≡ oracle."""
    from repro.kernels.stencil_banded_mxu import banded_fused_stencil, mxu_wins
    from repro.core.stencil import get_stencil

    for name in ("box2d1r", "box2d4r"):
        for steps in (1, 2):
            for kt, kb in [(False, False), (True, True)]:
                x = RNG.standard_normal((48, 160)).astype(np.float32)
                ref = multi_step_band(jnp.asarray(x), name, steps, kt, kb)
                got = banded_fused_stencil(jnp.asarray(x), name, steps, kt, kb,
                                           tile=(16, 32))
                err = np.abs(np.asarray(got) - np.asarray(ref)).max()
                assert err < 2e-5, (name, steps, kt, kb, err)
    # the napkin math that motivates it (EXPERIMENTS.md §4.3)
    assert mxu_wins(get_stencil("box2d4r"))
    assert not mxu_wins(get_stencil("box2d4r"), tx=512)


def test_double_buffered_kernel():
    """DMA/compute-overlap variant (DESIGN.md §5) ≡ oracle."""
    from repro.kernels.stencil_multistep_db import fused_stencil_band_db

    for name in ("box2d1r", "gradient2d"):
        for steps in (1, 4):
            for kt, kb in [(False, False), (True, True)]:
                x = RNG.standard_normal((48, 160)).astype(np.float32)
                ref = multi_step_band(jnp.asarray(x), name, steps, kt, kb)
                got = fused_stencil_band_db(jnp.asarray(x), name, steps, kt, kb,
                                            tile=(16, 64))
                err = np.abs(np.asarray(got) - np.asarray(ref)).max()
                assert err < 1e-5, (name, steps, kt, kb, err)
