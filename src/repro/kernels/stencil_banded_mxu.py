"""MXU-banded fused stencil kernel (beyond-paper, EXPERIMENTS.md §4.3).

On v5e the VPU (3.9 TFLOP/s fp32) makes high-radius box stencils
compute-bound at a single step (DESIGN.md §2), killing the paper's fusion
win for box2d3r/4r.  This kernel re-casts each time step of a *linear*
stencil as ``(2r+1)`` banded matmuls that run on the 197 TFLOP/s MXU:

    out = sum_dy  shift_dy(tile) @ B_dy,     B_dy[x+dx, x] = c[dy, dx]

Both this kernel and the VPU kernels compute whole apron'd tiles, so
:func:`mxu_wins` charges each its own tile's work per output cell:
``(2r+1)`` ``(th, tw) @ (tw, tw)`` matmuls per step here, against
``flops_per_elem`` per tile cell on the VPU.  At the napkin rates the
recast wins at radius 4 (box2d4r, k_on = 4: ~53 ps vs ~58 ps per output
cell per step) and loses below.  The napkin charges the MXU its bf16
peak; f32 at ``Precision.HIGHEST`` takes several passes, so a measured
profile may move the threshold.

Same masked whole-tile update and padded geometry as
``stencil_multistep.py`` (:mod:`repro.kernels.band`): each step's row
shifts are whole-tile rolls, and the banded matrices map the full tile
width onto itself (taps that would leave the tile are dropped; those
cells are outside the valid region anyway).  The tile multiplies in f32
at ``Precision.HIGHEST``, so the recast keeps f32 accuracy; identical band
semantics; oracle-validated in interpret mode
(`tests/test_kernels.py::test_banded_mxu_kernel`).
"""
from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.stencil import Stencil, get_stencil
from repro.kernels import DEFAULT_TILE as VPU_TILE
from repro.kernels import MXU_TILE, BandTiling, band_tiling
from repro.kernels.band import (
    compiler_params, frame_mask, output_block, pad_band, shifted,
    tile_origin,
)

__all__ = ["banded_fused_stencil", "mxu_wins"]

DEFAULT_TILE = MXU_TILE  # lane dim 128 = MXU-native


def mxu_wins(st: Stencil, steps: int = 1, tx: int = MXU_TILE[1],
             vpu: float = 3.9e12, mxu: float = 197e12) -> bool:
    """Napkin check: does the banded-MXU recast beat the VPU path?

    Both kernels compute whole apron'd tiles (:func:`band_tiling`), so
    each side is charged its tile's work per output cell: the MXU runs
    ``2r+1`` ``(th, tw) @ (tw, tw)`` matmuls per step, the VPU
    ``flops_per_elem`` per tile cell (its default tile)."""
    if not st.is_linear:
        return False

    def tile(rows, lanes):
        g = band_tiling((rows + 2 * steps * st.radius, lanes), st.radius,
                        steps, False, False, (rows, lanes), 4)
        return g.tw, g.th * g.tw / (g.ty * g.tx)   # cells per output cell

    tw, mxu_cells = tile(MXU_TILE[0], tx)
    _, vpu_cells = tile(*VPU_TILE)
    t_mxu = mxu_cells * (2 * st.radius + 1) * 2 * tw / mxu
    t_vpu = vpu_cells * st.flops_per_elem / vpu
    return t_mxu < t_vpu


def _band_matrices(st: Stencil, tw: int) -> np.ndarray:
    """(2r+1, TW, TW) banded matrices, one per row offset dy:
    ``(rows @ B[dy])[:, x] = sum_dx c[dy, dx] * rows[:, x + dx - r]``."""
    r = st.radius
    n = 2 * r + 1
    out = np.zeros((n, tw, tw), np.float32)
    for dy in range(n):
        for dx in range(n):
            c = float(st.coeffs[dy, dx])
            for x in range(max(r - dx, 0), min(tw, tw + r - dx)):
                out[dy, x + dx - r, x] = c
    return out


def _kernel(x_hbm, bands_ref, o_ref, tile, sem, *, st: Stencil, steps: int,
            keep_top: bool, keep_bottom: bool, H: int, X: int,
            g: BandTiling):
    r = st.radius
    y0, x0 = tile_origin(pl.program_id(0), pl.program_id(1), g,
                         x_hbm.dtype.itemsize)
    copy = pltpu.make_async_copy(
        x_hbm.at[pl.ds(y0, g.th), pl.ds(x0, g.tw)], tile, sem)
    copy.start()
    copy.wait()
    updatable = frame_mask(y0, x0, g, r, H, X, keep_top, keep_bottom)
    t = tile[...].astype(jnp.float32)
    for _ in range(steps):
        # (2r+1) banded matmuls on the MXU, one per row offset
        at = shifted(t)
        acc = None
        for dy in range(2 * r + 1):
            term = jnp.dot(at((dy - r, 0)), bands_ref[dy],
                           precision=jax.lax.Precision.HIGHEST,
                           preferred_element_type=jnp.float32)
            acc = term if acc is None else acc + term
        t = jnp.where(updatable, acc, t)
    o_ref[...] = output_block(t, g).astype(o_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=("name", "steps", "keep_top", "keep_bottom", "tile", "interpret"),
)
def banded_fused_stencil(
    band: jnp.ndarray,
    name: str,
    steps: int,
    keep_top: bool = False,
    keep_bottom: bool = False,
    tile: Tuple[int, int] = DEFAULT_TILE,
    interpret: bool = True,
) -> jnp.ndarray:
    """Drop-in alternative to ``fused_stencil_band`` for linear stencils."""
    st = get_stencil(name)
    if not st.is_linear:
        raise ValueError(f"{name} is nonlinear; banded-MXU path needs coeffs")
    H, X = band.shape
    g = band_tiling((H, X), st.radius, steps, keep_top, keep_bottom, tile,
                    band.dtype.itemsize)
    # (n, TW, TW) band matrices: a small VMEM-resident input, the same
    # block for every tile
    bands = jnp.asarray(_band_matrices(st, g.tw))
    n = bands.shape[0]
    kern = functools.partial(_kernel, st=st, steps=steps, keep_top=keep_top,
                             keep_bottom=keep_bottom, H=H, X=X, g=g)
    out = pl.pallas_call(
        kern,
        grid=(g.ny, g.nx),
        in_specs=[
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec((n, g.tw, g.tw), lambda i, j: (0, 0, 0)),
        ],
        out_specs=pl.BlockSpec((g.ty, g.tx), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((g.ny * g.ty, g.nx * g.tx), band.dtype),
        scratch_shapes=[
            pltpu.VMEM((g.th, g.tw), band.dtype),
            pltpu.SemaphoreType.DMA,
        ],
        compiler_params=compiler_params("parallel", "parallel"),
        interpret=interpret,
    )(pad_band(band, g), bands)
    return out[:g.h_out, :X]
