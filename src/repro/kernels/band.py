"""Pieces the three fused-stencil kernels share.

Every kernel runs the same *masked whole-tile update*: one apron'd tile
of the padded band sits in VMEM, and each fused step recomputes every
tile cell from whole-tile shifts (``pltpu.roll``), then a global-index
mask puts the Dirichlet frame back (column frames always; row frames
when ``keep_top``/``keep_bottom``).  Shifts wrap at the tile edge, so
after ``s`` steps a cell is valid iff it is ``>= s*r`` from every tile
edge or backed by frame; the output block sits at the static offset
``(m*r, m*r)`` and is therefore always valid.  Geometry:
:class:`repro.kernels.BandTiling`.

Tiles compute in f32 whatever the band's dtype: Mosaic rotates only
32-bit data, and the v5e VPU has no bf16 arithmetic.  A bf16 band is
therefore rounded once per fused call, not once per step as in the
jnp reference.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.stencil import Stencil
from repro.kernels import VMEM_LIMIT_BYTES, BandTiling, sublanes

__all__ = ["pad_band", "compiler_params", "tile_origin", "frame_mask",
           "shifted", "fused_steps", "output_block"]


def pad_band(band: jnp.ndarray, g: BandTiling) -> jnp.ndarray:
    """Zero-pad a band to the tiling's ``(hp, xp)`` (see BandTiling)."""
    H, X = band.shape
    return jnp.pad(band, ((g.pad_top, g.hp - g.pad_top - H),
                          (g.pad_left, g.xp - g.pad_left - X)))


def compiler_params(*semantics: str) -> pltpu.CompilerParams:
    return pltpu.CompilerParams(dimension_semantics=semantics,
                                vmem_limit_bytes=VMEM_LIMIT_BYTES)


def tile_origin(i, j, g: BandTiling, itemsize: int):
    """Padded-band origin of output tile ``(i, j)``'s DMA window."""
    return (pl.multiple_of(i * g.ty, sublanes(itemsize)),
            pl.multiple_of(j * g.tx, 128))


def frame_mask(y0, x0, g: BandTiling, r: int, H: int, X: int,
               keep_top: bool, keep_bottom: bool) -> jnp.ndarray:
    """Cells of the tile at padded ``(y0, x0)`` that may update: band
    columns ``[r, X-r)`` always, band rows ``[r, H-r)`` on framed sides.
    Pad cells outside the band fall outside both ranges or outside every
    valid cell's dependency cone."""
    shape = (g.th, g.tw)
    grow = y0 - g.pad_top + jax.lax.broadcasted_iota(jnp.int32, shape, 0)
    gcol = x0 - g.pad_left + jax.lax.broadcasted_iota(jnp.int32, shape, 1)
    ok = (gcol >= r) & (gcol < X - r)
    if keep_top:
        ok &= grow >= r
    if keep_bottom:
        ok &= grow < H - r
    return ok


def shifted(t: jnp.ndarray):
    """Neighbour accessor over a whole tile: ``at((dy, dx))[i, j] ==
    t[i+dy, j+dx]``, wrapping at the tile edge.  Row shifts are shared
    between taps of one row offset."""
    th, tw = t.shape
    rows = {}

    def at(offset):
        dy, dx = offset
        if dy not in rows:
            rows[dy] = pltpu.roll(t, (-dy) % th, 0) if dy else t
        v = rows[dy]
        return pltpu.roll(v, (-dx) % tw, 1) if dx else v

    return at


def fused_steps(t: jnp.ndarray, st: Stencil, steps: int,
                updatable: jnp.ndarray) -> jnp.ndarray:
    """``steps`` masked whole-tile updates of the VPU kernels."""
    for _ in range(steps):
        t = jnp.where(updatable, st.step_shifted(shifted(t)), t)
    return t


def output_block(t: jnp.ndarray, g: BandTiling) -> jnp.ndarray:
    """The ``(ty, tx)`` output block at in-tile ``(halo, halo)``, rolled
    to the tile origin so the slice is aligned."""
    if g.halo:
        t = pltpu.roll(pltpu.roll(t, g.th - g.halo, 0), g.tw - g.halo, 1)
    return t[:g.ty, :g.tx]
