"""The one generator every traffic mix goes through.

The configuration fixes the deployment: the stencil, the engine, the
interior of the domain (a side, for a square, or one extent per axis),
the steps of one solve and the schedule its engine reads
(:class:`SolveParams`, read by :func:`bench.harness.solve_params`).  The
generator is fixed: one client submits whole solves back to back, each
on the same host domain, uniform over ``[low, high)`` with the frame
included.  A traffic file (``bench/traffic/<name>.json``) sets ``low``
and ``high``.  :func:`make_domain` fills the domain by threads over
fixed blocks of its first axis, so that the same seed gives the same
domain whatever the thread count.
"""
from __future__ import annotations

import dataclasses
import math
import os
from concurrent.futures import ThreadPoolExecutor
from typing import Optional, Tuple

import numpy as np

# fixed blocks of the first axis, each with its own child of the seed's
# SeedSequence
BLOCKS = 64


@dataclasses.dataclass(frozen=True)
class SolveParams:
    engine: str       # a compile_plan engine name, "box_tb" or "sharded"
    stencil: str      # the program's stencil name
    radius: int
    shape: Tuple[int, ...]    # framed domain, one extent per axis
    steps: int        # time steps per solve
    d: Optional[int] = None           # compile_plan engines
    s_tb: Optional[int] = None
    k_on: Optional[int] = None        # compile_plan engines and box_tb
    mesh: Optional[Tuple[int, int]] = None    # the sharded engine
    k_ici: Optional[int] = None
    tiles: Optional[Tuple[int, ...]] = None   # box_tb: tiles per axis
    time_depth: Optional[int] = None          # box_tb: steps per round

    @property
    def Y(self) -> int:
        """Rows of a 2-D framed domain."""
        return self._plane[0]

    @property
    def X(self) -> int:
        """Columns of a 2-D framed domain."""
        return self._plane[1]

    @property
    def _plane(self) -> Tuple[int, int]:
        if len(self.shape) != 2:
            raise ValueError(f"the domain {self.shape} is not 2-D")
        return self.shape

    @property
    def chips(self) -> int:
        """Devices a solve runs on: the mesh's size, else one."""
        return self.mesh[0] * self.mesh[1] if self.mesh else 1

    @property
    def interior_updates(self) -> int:
        """Useful cell-updates of one solve: steps x interior cells."""
        r = self.radius
        return self.steps * math.prod(n - 2 * r for n in self.shape)


def make_domain(shape, seed: int, low: float = 0.0,
                high: float = 1.0) -> np.ndarray:
    """Uniform ``[low, high)`` float32 host array of ``shape`` from
    ``seed``, the frame included."""
    out = np.empty(shape, np.float32)
    kids = np.random.SeedSequence(int(seed)).spawn(BLOCKS)
    edges = np.linspace(0, shape[0], BLOCKS + 1).astype(int)

    def fill(i: int) -> None:
        gen = np.random.Generator(np.random.PCG64(kids[i]))
        rows = out[edges[i]:edges[i + 1]]
        gen.random(out=rows, dtype=np.float32)
        if (low, high) != (0.0, 1.0):
            rows *= np.float32(high - low)
            rows += np.float32(low)

    with ThreadPoolExecutor(min(os.cpu_count() or 1, 16)) as ex:
        for fut in [ex.submit(fill, i) for i in range(BLOCKS)]:
            fut.result()
    return out
