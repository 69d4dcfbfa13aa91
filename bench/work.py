"""Useful work of the fused stencil steps, counted from shapes alone.

Whatever kernel runs a fused call, and however it pads or tiles the
band, the call has to update the same cells: at each of its ``m``
steps, every cell of the band that is not frame and whose neighbours
are all still valid.  A band of ``H`` rows and full width ``X`` (its
``r`` frame columns on both sides) loses ``r`` rows per step on each
side that is not the domain's frame, so step ``s`` updates
``(H_{s-1} - 2r) * (X - 2r)`` cells, with
``H_s = H_{s-1} - 2r + (keep_top + keep_bottom) * r``.  The least HBM
traffic is the band read once and its result written once.

A shard's band on a chip mesh (:func:`shard_work`) holds a halo on all
four sides and loses ``r`` per step on each, clipped to the domain's
interior: on a side at the domain's edge the halo is outside the domain
and the frame holds.
"""
from __future__ import annotations

import dataclasses
from typing import Iterable, List


@dataclasses.dataclass(frozen=True)
class Work:
    cells: int    # cell-updates the call must make
    flops: int
    bytes: int    # band in + band out

    def __add__(self, other: "Work") -> "Work":
        return Work(self.cells + other.cells, self.flops + other.flops,
                    self.bytes + other.bytes)

    def least_seconds(self, hbm_bytes_per_s: float, flops_per_s: float) -> float:
        return max(self.bytes / hbm_bytes_per_s, self.flops / flops_per_s)

    def bound(self, hbm_bytes_per_s: float, flops_per_s: float) -> str:
        return ("hbm" if self.bytes / hbm_bytes_per_s
                >= self.flops / flops_per_s else "compute")


def band_work(h_in: int, width: int, radius: int, steps: int,
              keep_top: bool, keep_bottom: bool, flops_per_cell: int,
              itemsize: int) -> Work:
    """Useful work of ``steps`` fused steps on one ``(h_in, width)`` band."""
    r = radius
    h, cells = h_in, 0
    for _ in range(steps):
        if h - 2 * r <= 0:
            raise ValueError(f"band of {h_in} rows too small for {steps} steps")
        cells += (h - 2 * r) * (width - 2 * r)
        h = h - 2 * r + (int(keep_top) + int(keep_bottom)) * r
    return Work(cells=cells, flops=cells * flops_per_cell,
                bytes=(h_in + h) * width * itemsize)


def fused_work(kernel_ops: Iterable, radius: int, flops_per_cell: int,
               itemsize: int) -> List[Work]:
    """:func:`band_work` of each of a plan's fused-kernel ops (anything
    with ``shape_in``, ``steps``, ``keep_lo`` and ``keep_hi``)."""
    return [band_work(op.shape_in[0], op.shape_in[1], radius, op.steps,
                      op.keep_lo[0], op.keep_hi[0], flops_per_cell, itemsize)
            for op in kernel_ops]


def _overlap(lo: int, hi: int, lo_in: int, hi_in: int) -> int:
    return max(0, min(hi, hi_in) - max(lo, lo_in))


def shard_work(gy0: int, gx0: int, h: int, w: int, Y: int, X: int,
               radius: int, steps: int, flops_per_cell: int,
               itemsize: int) -> Work:
    """Useful work of ``steps`` fused steps on a shard's ``(h, w)`` band
    at global ``(gy0, gx0)`` of a framed ``(Y, X)`` domain: at step
    ``s`` the cells of the interior still ``s * r`` inside the band are
    updated; the part of the band inside the domain is read once, and
    what is left of it after the steps written once."""
    r = radius
    cells = 0
    for s in range(1, steps + 1):
        cells += (_overlap(gy0 + s * r, gy0 + h - s * r, r, Y - r)
                  * _overlap(gx0 + s * r, gx0 + w - s * r, r, X - r))
    m = steps * r
    read = _overlap(gy0, gy0 + h, 0, Y) * _overlap(gx0, gx0 + w, 0, X)
    written = (_overlap(gy0 + m, gy0 + h - m, 0, Y)
               * _overlap(gx0 + m, gx0 + w - m, 0, X))
    return Work(cells=cells, flops=cells * flops_per_cell,
                bytes=(read + written) * itemsize)
