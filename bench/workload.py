"""The one generator every traffic mix goes through.

The configuration fixes the deployment: the stencil, the engine, the
interior side of the square domain, the steps of one solve (whole
rounds of ``s_tb``) and the schedule (:func:`solve_params`).  The
generator is fixed: one client submits whole solves back to back, each
on the same host domain, uniform over ``[low, high)`` with the frame
included.  A traffic file (``bench/traffic/<name>.json``) sets ``low``
and ``high``.  :func:`make_domain` fills the domain by threads over
fixed row blocks, so that the same seed gives the same domain whatever
the thread count.
"""
from __future__ import annotations

import dataclasses
import os
from concurrent.futures import ThreadPoolExecutor

import numpy as np

# fixed row blocks, each with its own child of the seed's SeedSequence
BLOCKS = 64


@dataclasses.dataclass(frozen=True)
class SolveParams:
    engine: str       # compile_plan engine name
    stencil: str      # the program's stencil name
    radius: int
    Y: int
    X: int            # framed domain
    steps: int        # time steps per solve
    d: int
    s_tb: int
    k_on: int

    @property
    def interior_updates(self) -> int:
        """Useful cell-updates of one solve: steps x interior cells."""
        r = self.radius
        return self.steps * (self.Y - 2 * r) * (self.X - 2 * r)


def solve_params(config: dict) -> SolveParams:
    """One solve of the configuration; raises ``ValueError`` for steps
    that are not whole rounds."""
    sched = config["schedule"]
    r = int(config["radius"])
    side = int(config["interior"]) + 2 * r
    steps, s_tb = int(config["steps_per_solve"]), int(sched["s_tb"])
    if steps % s_tb:
        raise ValueError(f"{steps} steps are not whole rounds of {s_tb}")
    return SolveParams(
        engine=config["engine"], stencil=config["stencil"], radius=r,
        Y=side, X=side, steps=steps, d=int(sched["d"]), s_tb=s_tb,
        k_on=int(sched["k_on"]))


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
