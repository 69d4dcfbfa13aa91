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
"""
from __future__ import annotations

import dataclasses
from typing import Iterable


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


def plan_work(kernel_ops: Iterable, radius: int, flops_per_cell: int,
              itemsize: int) -> Work:
    """Sum of :func:`band_work` over a plan's fused-kernel ops (anything
    with ``shape_in``, ``steps``, ``keep_lo`` and ``keep_hi``)."""
    total = Work(0, 0, 0)
    for op in kernel_ops:
        h_in, width = op.shape_in
        total = total + band_work(h_in, width, radius, op.steps,
                                  op.keep_lo[0], op.keep_hi[0],
                                  flops_per_cell, itemsize)
    return total


def least_seconds(kernel_ops: Iterable, radius: int, flops_per_cell: int,
                  itemsize: int, hbm_bytes_per_s: float,
                  flops_per_s: float) -> float:
    """Sum over calls of each call's least time on the chip."""
    return sum(
        band_work(op.shape_in[0], op.shape_in[1], radius, op.steps,
                  op.keep_lo[0], op.keep_hi[0], flops_per_cell,
                  itemsize).least_seconds(hbm_bytes_per_s, flops_per_s)
        for op in kernel_ops)
