"""Useful work of the fused stencil steps, counted from shapes alone.

Whatever kernel runs a fused call, and however it pads or tiles its
box, the call has to update the same cells: at each of its ``m`` steps,
every cell of the box that is not frame and whose neighbours are all
still valid.  A box (:func:`box_work`) loses ``r`` per step on every
side that is not the domain's frame, and its ``r`` frame cells pass
through on a side that is; its steps update the trapezoid's boxes, an
apron's redundant cells included: each step the box's interior, every
axis ``2r`` short.  A 2-D band of ``H`` rows and full width ``X`` keeps
its frame columns, so step ``s`` updates ``(H_{s-1} - 2r) * (X - 2r)``
cells, with ``H_s = H_{s-1} - 2r + (keep_top + keep_bottom) * r``.  The
least HBM traffic is the box read once and its result written once.

A shard's band on a chip mesh (:func:`shard_work`) holds a halo on all
four sides and loses ``r`` per step on each, clipped to the domain's
interior: on a side at the domain's edge the halo is outside the domain
and the frame holds.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Iterable, List, Sequence


@dataclasses.dataclass(frozen=True)
class Work:
    cells: int    # cell-updates the call must make
    flops: int
    bytes: int    # box in + box out

    def __add__(self, other: "Work") -> "Work":
        return Work(self.cells + other.cells, self.flops + other.flops,
                    self.bytes + other.bytes)

    def least_seconds(self, hbm_bytes_per_s: float, flops_per_s: float) -> float:
        return max(self.bytes / hbm_bytes_per_s, self.flops / flops_per_s)

    def bound(self, hbm_bytes_per_s: float, flops_per_s: float) -> str:
        return ("hbm" if self.bytes / hbm_bytes_per_s
                >= self.flops / flops_per_s else "compute")


def box_work(shape_in: Sequence[int], radius: int, steps: int,
             keep_lo: Sequence[bool], keep_hi: Sequence[bool],
             flops_per_cell: int, itemsize: int) -> Work:
    """Useful work of ``steps`` fused steps on one N-D box of
    ``shape_in``: the box volumes of the trapezoid summed over its
    steps, the input box read once and the output box written once."""
    r = radius
    cur, cells = [int(n) for n in shape_in], 0
    for _ in range(steps):
        inner = [n - 2 * r for n in cur]
        if min(inner) <= 0:
            raise ValueError(f"box {tuple(shape_in)} too small for "
                             f"{steps} steps")
        cells += math.prod(inner)
        cur = [n - 2 * r + (int(kl) + int(kh)) * r
               for n, kl, kh in zip(cur, keep_lo, keep_hi)]
    return Work(cells=cells, flops=cells * flops_per_cell,
                bytes=(math.prod(shape_in) + math.prod(cur)) * itemsize)


def fused_work(kernel_ops: Iterable, radius: int, flops_per_cell: int,
               itemsize: int) -> List[Work]:
    """:func:`box_work` of each of a plan's fused-kernel ops (anything
    with ``shape_in``, ``steps``, ``keep_lo`` and ``keep_hi``)."""
    return [box_work(op.shape_in, radius, op.steps, op.keep_lo, op.keep_hi,
                     flops_per_cell, itemsize)
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
