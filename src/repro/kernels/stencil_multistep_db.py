"""Double-buffered variant of the fused stencil kernel.

The paper overlaps CPU↔GPU copies with kernel execution via CUDA streams
(Sec. II, N_strm = 3).  At L0 the TPU analogue is DMA/compute overlap
inside the kernel: two VMEM slots + two DMA semaphores, tile ``g+1``'s
HBM→VMEM copy issued before tile ``g``'s compute so the systolic/vector
units never wait on HBM in steady state.

Grid is 1-D over tiles (row-major) so the pipeline is explicit, and runs
in order (``arbitrary``): each step waits on the copy its predecessor
started.  Same masked whole-tile update and padded geometry as
``stencil_multistep.py`` (:mod:`repro.kernels.band`).
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

__all__ = ["fused_stencil_band_db"]


def _kernel(x_hbm, o_ref, tiles, sems, *, st: Stencil, steps: int,
            keep_top: bool, keep_bottom: bool, H: int, X: int,
            g: BandTiling):
    k = pl.program_id(0)
    itemsize = x_hbm.dtype.itemsize

    def copy(gi, slot):
        y0, x0 = tile_origin(gi // g.nx, gi % g.nx, g, itemsize)
        return pltpu.make_async_copy(
            x_hbm.at[pl.ds(y0, g.th), pl.ds(x0, g.tw)],
            tiles.at[slot], sems.at[slot])

    # prologue: the first tile fetches itself
    @pl.when(k == 0)
    def _():
        copy(k, 0).start()

    # steady state: prefetch the NEXT tile into the other slot
    @pl.when(k + 1 < g.n_tiles)
    def _():
        copy(k + 1, (k + 1) % 2).start()

    slot = k % 2
    copy(k, slot).wait()
    y0, x0 = tile_origin(k // g.nx, k % g.nx, g, itemsize)
    updatable = frame_mask(y0, x0, g, st.radius, H, X, keep_top, keep_bottom)
    t = fused_steps(tiles[slot].astype(jnp.float32), st, steps,
                    updatable)
    o_ref[...] = output_block(t, g).astype(o_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=("name", "steps", "keep_top", "keep_bottom", "tile", "interpret"),
)
def fused_stencil_band_db(
    band: jnp.ndarray,
    name: str,
    steps: int,
    keep_top: bool = False,
    keep_bottom: bool = False,
    tile: Tuple[int, int] = DEFAULT_TILE,
    interpret: bool = True,
) -> jnp.ndarray:
    st = get_stencil(name)
    H, X = band.shape
    g = band_tiling((H, X), st.radius, steps, keep_top, keep_bottom, tile,
                    band.dtype.itemsize)
    kern = functools.partial(_kernel, st=st, steps=steps, keep_top=keep_top,
                             keep_bottom=keep_bottom, H=H, X=X, g=g)
    nx = g.nx
    out = pl.pallas_call(
        kern,
        grid=(g.n_tiles,),
        in_specs=[pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=pl.BlockSpec((g.ty, g.tx), lambda k: (k // nx, k % nx)),
        out_shape=jax.ShapeDtypeStruct((g.ny * g.ty, g.nx * g.tx), band.dtype),
        scratch_shapes=[
            pltpu.VMEM((2, g.th, g.tw), band.dtype),
            pltpu.SemaphoreType.DMA((2,)),
        ],
        compiler_params=compiler_params("arbitrary"),
        interpret=interpret,
    )(pad_band(band, g))
    return out[:g.h_out, :X]
