"""Pallas TPU kernel: k_on-step fused 2-D stencil with on-chip (VMEM) reuse.

This is the TPU adaptation of the paper's AN5D-style multi-step kernels
(Sec. III/IV): each grid step DMAs one *overlapping* tile + apron from HBM
into VMEM, applies ``k_on`` time steps entirely in VMEM (the VREG/VMEM
analogue of the paper's register/shared-memory reuse), and writes the tile
back.  The tile aprons are recomputed by neighbouring tiles — the on-chip
incarnation of SO2DR's deliberate redundant computation.

Correctness scheme — the masked whole-tile update of
:mod:`repro.kernels.band`: the wrapper pads the band so every tile's DMA
window starts at an aligned offset and its output block sits at the
static in-tile offset ``(m*r, m*r)``.  Semantics match
:func:`repro.core.reference.multi_step_band` exactly (column frames
always preserved; ``keep_top``/``keep_bottom`` row frames), for any band
that yields at least one output row.
"""
from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.stencil import Stencil, get_stencil
from repro.kernels import DEFAULT_TILE, BandTiling, band_tiling
from repro.kernels.band import (
    compiler_params, frame_mask, fused_steps, output_block, pad_band,
    tile_origin,
)

__all__ = ["fused_stencil_band", "DEFAULT_TILE"]


def _kernel(x_hbm, o_ref, tile, sem, *, st: Stencil, steps: int,
            keep_top: bool, keep_bottom: bool, H: int, X: int,
            g: BandTiling):
    y0, x0 = tile_origin(pl.program_id(0), pl.program_id(1), g,
                         x_hbm.dtype.itemsize)
    copy = pltpu.make_async_copy(
        x_hbm.at[pl.ds(y0, g.th), pl.ds(x0, g.tw)], tile, sem)
    copy.start()
    copy.wait()
    updatable = frame_mask(y0, x0, g, st.radius, H, X, keep_top, keep_bottom)
    t = fused_steps(tile[...].astype(jnp.float32), st, steps, updatable)
    o_ref[...] = output_block(t, g).astype(o_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=("name", "steps", "keep_top", "keep_bottom", "tile", "interpret"),
)
def fused_stencil_band(
    band: jnp.ndarray,
    name: str,
    steps: int,
    keep_top: bool = False,
    keep_bottom: bool = False,
    tile: Tuple[int, int] = DEFAULT_TILE,
    interpret: bool = True,
) -> jnp.ndarray:
    """``steps`` fused stencil time steps on a (H, X) band.

    Drop-in kernel replacement for
    :func:`repro.core.reference.multi_step_band`.
    """
    st = get_stencil(name)
    H, X = band.shape
    g = band_tiling((H, X), st.radius, steps, keep_top, keep_bottom, tile,
                    band.dtype.itemsize)
    kern = functools.partial(_kernel, st=st, steps=steps, keep_top=keep_top,
                             keep_bottom=keep_bottom, H=H, X=X, g=g)
    out = pl.pallas_call(
        kern,
        grid=(g.ny, g.nx),
        in_specs=[pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=pl.BlockSpec((g.ty, g.tx), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((g.ny * g.ty, g.nx * g.tx), band.dtype),
        scratch_shapes=[
            pltpu.VMEM((g.th, g.tw), band.dtype),
            pltpu.SemaphoreType.DMA,
        ],
        compiler_params=compiler_params("parallel", "parallel"),
        interpret=interpret,
    )(pad_band(band, g))
    return out[:g.h_out, :X]
