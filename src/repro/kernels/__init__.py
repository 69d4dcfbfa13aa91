"""Pallas TPU kernels for the paper's compute hot-spot.

* stencil_multistep     — k_on-step fused kernel (VMEM-resident steps)
* stencil_multistep_db  — + DMA/compute overlap (double buffering)
* stencil_banded_mxu    — beyond-paper MXU recast for high radii
* band                  — in-kernel pieces the three kernels share
* dispatch              — registry selecting the best implementation per
                          (stencil kind, radius, steps, backend)
* ops                   — jit'd wrappers;  ref — pure-jnp oracles

The band geometry lives here, free of JAX imports, so the kernels and
the cost model (:func:`repro.kernels.dispatch.kernel_op_features`) cut
a band into tiles, and bound its VMEM, with one definition.
"""
from __future__ import annotations

import dataclasses
from typing import Tuple

__all__ = ["DEFAULT_TILE", "MXU_TILE", "VMEM_LIMIT_BYTES", "BandTiling",
           "band_tiling", "ceil_div", "round_up", "sublanes"]

# default VMEM tile for the VPU kernels (rows, lanes)
DEFAULT_TILE = (256, 512)
# MXU-native tile: lane dim 128 matches the systolic array
MXU_TILE = (DEFAULT_TILE[0], 128)
# scoped VMEM every fused kernel asks the compiler for
# (pltpu.CompilerParams.vmem_limit_bytes); the cost model refuses tilings
# above it.  A v5e core has 128 MiB of VMEM; the default scope is smaller.
VMEM_LIMIT_BYTES = 64 * 1024**2
# tile-sized f32 temporaries a fused step keeps live (rolled neighbours,
# accumulator, frame mask) — the modelled part of a kernel's VMEM
LIVE_TILES = 6
LANES = 128


def ceil_div(a: int, b: int) -> int:
    return -(-a // b)


def round_up(a: int, b: int) -> int:
    return ceil_div(a, b) * b


def sublanes(itemsize: int) -> int:
    """Rows of one native (sublane x 128) tile: 8 for f32, 16 for bf16."""
    return 8 * max(4 // itemsize, 1)


@dataclasses.dataclass(frozen=True)
class BandTiling:
    """How a fused call cuts a ``(H, X)`` band into apron'd VMEM tiles.

    The wrapper pads the band by ``pad_top``/``pad_left`` (and zeros
    below and to the right, up to ``(hp, xp)``) so that output tile
    ``(i, j)`` — a ``(ty, tx)`` block at output ``(i*ty, j*tx)`` — is
    computed from the ``(th, tw)`` window at padded ``(i*ty, j*tx)``,
    with its output at the static in-tile offset ``(halo, halo)``.  Every
    DMA start and block is then aligned to the native ``(sublane, 128)``
    tiling.  Pad cells are either frame-masked or outside every valid
    output cell's dependency cone, so they never change a result.
    """

    halo: int        # m*r apron per side
    h_out: int       # true output rows
    ty: int
    tx: int          # output block
    ny: int
    nx: int          # output grid
    th: int
    tw: int          # apron'd VMEM tile, rounded to (sublane, 128)
    pad_top: int
    pad_left: int
    hp: int
    xp: int          # padded band shape

    @property
    def n_tiles(self) -> int:
        return self.ny * self.nx

    def vmem_bytes(self, itemsize: int, slots: int = 1,
                   mxu_taps: int = 0) -> int:
        """Modelled VMEM of one kernel instance: the DMA slots, the
        pipelined output block (two buffers), the live f32 temporaries,
        and (MXU path) the double-buffered banded matrices."""
        tile = self.th * self.tw
        return (slots * tile * itemsize
                + 2 * self.ty * self.tx * itemsize
                + LIVE_TILES * tile * 4
                + 2 * mxu_taps * self.tw * self.tw * 4)


def band_tiling(shape: Tuple[int, int], radius: int, steps: int,
                keep_top: bool, keep_bottom: bool, tile: Tuple[int, int],
                itemsize: int) -> BandTiling:
    """The :class:`BandTiling` of one fused call; raises ``ValueError``
    when the band is too short to produce a row after ``steps`` steps."""
    H, X = shape
    halo = steps * radius
    h_out = H - 2 * halo + (int(keep_top) + int(keep_bottom)) * halo
    if h_out <= 0:
        raise ValueError(f"band of {H} rows too small for {steps} fused steps")
    sub = sublanes(itemsize)
    ty = min(round_up(tile[0], sub), round_up(h_out, sub))
    tx = min(round_up(tile[1], LANES), round_up(X, LANES))
    ny, nx = ceil_div(h_out, ty), ceil_div(X, tx)
    th = round_up(ty + 2 * halo, sub)
    tw = round_up(tx + 2 * halo, LANES)
    return BandTiling(
        halo=halo, h_out=h_out, ty=ty, tx=tx, ny=ny, nx=nx, th=th, tw=tw,
        pad_top=halo if keep_top else 0, pad_left=halo,
        hp=(ny - 1) * ty + th, xp=(nx - 1) * tx + tw)
