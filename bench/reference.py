"""Plain reference of a linear box stencil, and the check that decides
``correct``.

The reference imports nothing of the program.  Its coefficients are the
numbers in the configuration file; its update is a tap sum written out
here: every interior cell becomes ``sum c[dy, dx] * x[y+dy-r, x+dx-r]``,
and the ``r``-wide frame of the domain is held.  It runs on square
windows of the solved domain, each advanced from the input on a region
that reaches ``steps * r`` cells further (the dependency cone), so the
region's own edges never reach the window.

``mode`` picks the arithmetic:

* ``f32``   — float32 throughout: the reference.
* ``bf16``  — the same update in bfloat16: the control for a float32
  configuration computed on the vector unit.
* ``high3`` — float32 with every product taken as three bfloat16
  products (``a_hi*b_hi + a_hi*b_lo + a_lo*b_hi``), what a matrix unit
  does at ``Precision.HIGH``: the control for float32 at ``highest``.
"""
from __future__ import annotations

import functools
from typing import Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

MODES = ("f32", "bf16", "high3")

# side of each checked window
WINDOW = 256


def coefficients(config: dict) -> np.ndarray:
    """The configuration's ``(2r+1, 2r+1)`` tap weights, as float32."""
    c = np.asarray(config["coefficients"], np.float64).astype(np.float32)
    r = int(config["radius"])
    if c.shape != (2 * r + 1, 2 * r + 1):
        raise ValueError(f"coefficients {c.shape} do not match radius {r}")
    return c


def _split(v):
    hi = v.astype(jnp.bfloat16).astype(jnp.float32)
    lo = (v - hi).astype(jnp.bfloat16).astype(jnp.float32)
    return hi, lo


@functools.partial(jax.jit, static_argnames=("steps", "mode"))
def advance(regions: jnp.ndarray, held: jnp.ndarray, c: jnp.ndarray,
            steps: int, mode: str = "f32") -> jnp.ndarray:
    """``steps`` updates of a batch of ``(S, S)`` regions.

    ``held`` marks cells of the domain's frame, which keep their value;
    the outer ``r`` cells of a region that are not frame go stale, one
    ring per step, which the dependency-cone margin absorbs."""
    if mode not in MODES:
        raise ValueError(f"unknown mode {mode!r}; known: {MODES}")
    n = c.shape[0]
    r = n // 2
    S = regions.shape[-1]
    dt = jnp.bfloat16 if mode == "bf16" else jnp.float32
    x = regions.astype(dt)
    if mode == "high3":
        c_hi, c_lo = _split(c)
    cd = c.astype(dt)

    def step(_, x):
        acc = None
        if mode == "high3":
            x_hi, x_lo = _split(x)
        for dy in range(n):
            for dx in range(n):
                sl = (slice(None), slice(dy, dy + S - 2 * r),
                      slice(dx, dx + S - 2 * r))
                if mode == "high3":
                    term = (c_hi[dy, dx] * x_hi[sl] + c_hi[dy, dx] * x_lo[sl]
                            + c_lo[dy, dx] * x_hi[sl])
                else:
                    term = cd[dy, dx] * x[sl]
                acc = term if acc is None else acc + term
        new = x.at[:, r:S - r, r:S - r].set(acc)
        return jnp.where(held, x, new)

    return jax.lax.fori_loop(0, steps, step, x).astype(jnp.float32)


def region_origin(y0: int, size: int, margin: int, extent: int) -> int:
    """Start of the region of ``size`` cells that holds the window at
    ``y0`` plus ``margin`` on each side, moved inside ``[0, extent)``;
    where it is moved, that side of the window lies on the frame."""
    return min(max(y0 - margin, 0), extent - size)


def windows(shape: Tuple[int, int], radius: int, chunk_starts: Sequence[int],
            seed: int, n_random: int = 4, size: int = WINDOW,
            row_seams: Sequence[int] = (),
            col_seams: Sequence[int] = ()) -> List[Tuple[str, int, int]]:
    """The windows the check compares: ``(label, y0, x0)``.

    Fixed places first: both corners, where the held frame is; every
    chunk boundary, where region sharing hands rows from one chunk to
    the next; the domain's middle across a 512-column kernel tile
    seam.  Then ``n_random`` windows drawn from the seed.  Last, the
    seams between the shards of a mesh, where halos are exchanged:
    each row seam (the first row of a shard below another) at a drawn
    column, each column seam at a drawn row, and each point where a
    row seam crosses a column seam."""
    Y, X = shape
    rng = np.random.default_rng(np.random.SeedSequence([int(seed), 0x5EED]))

    def across(b: int, extent: int) -> int:
        return min(max(b - size // 2, 0), extent - size)

    out = [("corner", 0, 0), ("far-corner", Y - size, X - size)]
    for b in chunk_starts:
        x0 = int(rng.integers(0, X - size + 1))
        out.append((f"chunk-boundary@{b}", across(b, Y), x0))
    seam = max(min((X // 2) // 512 * 512 - size // 2, X - size), 0)
    out.append(("tile-seam", max(Y // 2 - size // 2, 0), seam))
    for i in range(n_random):
        out.append((f"random{i}", int(rng.integers(0, Y - size + 1)),
                    int(rng.integers(0, X - size + 1))))
    for b in row_seams:
        out.append((f"row-seam@{b}", across(b, Y),
                    int(rng.integers(0, X - size + 1))))
    for b in col_seams:
        out.append((f"col-seam@{b}", int(rng.integers(0, Y - size + 1)),
                    across(b, X)))
    for by in row_seams:
        for bx in col_seams:
            out.append((f"seam-cross@{by},{bx}", across(by, Y),
                        across(bx, X)))
    return out


def reference_windows(x: np.ndarray, config: dict, steps: int,
                      wins: Sequence[Tuple[str, int, int]], mode: str = "f32",
                      size: int = WINDOW, batch: int = 4) -> np.ndarray:
    """``(len(wins), size, size)`` reference values of the windows after
    ``steps`` steps from the host domain ``x``."""
    r = int(config["radius"])
    Y, X = x.shape
    margin = steps * r
    S = min(size + 2 * margin, Y, X)
    c = jnp.asarray(coefficients(config))
    out = []
    for i in range(0, len(wins), batch):
        part = wins[i:i + batch]
        regions, held, offs = [], [], []
        for _, y0, x0 in part:
            ry, rx = region_origin(y0, S, margin, Y), region_origin(x0, S, margin, X)
            regions.append(x[ry:ry + S, rx:rx + S])
            gy = np.arange(ry, ry + S)[:, None]
            gx = np.arange(rx, rx + S)[None, :]
            held.append((gy < r) | (gy >= Y - r) | (gx < r) | (gx >= X - r))
            offs.append((y0 - ry, x0 - rx))
        res = np.asarray(advance(jnp.asarray(np.stack(regions)),
                                 jnp.asarray(np.stack(held)), c, steps, mode))
        for k, (oy, ox) in enumerate(offs):
            out.append(res[k, oy:oy + size, ox:ox + size])
    return np.stack(out)


def max_rel_err(got: np.ndarray, ref: np.ndarray) -> float:
    """``max |got - ref| / max |ref|`` over every compared cell (NaN
    when either holds a NaN, which fails any limit)."""
    return float(np.max(np.abs(got.astype(np.float64) - ref))
                 / np.max(np.abs(ref)))


def frame_cells_changed(out: np.ndarray, x: np.ndarray, radius: int) -> int:
    """Cells of the held frame whose value the solve changed."""
    r = radius
    strips = (np.s_[:r, :], np.s_[-r:, :], np.s_[r:-r, :r], np.s_[r:-r, -r:])
    return int(sum(np.count_nonzero(out[s] != x[s]) for s in strips))


def window_size(shape: Tuple[int, int]) -> int:
    return min(WINDOW, *shape)


def compare(out: Optional[np.ndarray], x: np.ndarray, config: dict,
            steps: int, wins, control: Optional[str] = None
            ) -> Dict[str, float]:
    """The numbers ``correct`` is decided on, for a solved domain ``out``
    of input ``x``.  With ``control`` (a mode), the reference computed in
    that mode takes the program's place: ``out`` is not read, and the
    frame, which the reference holds by construction, is not compared."""
    size = window_size(x.shape)
    ref = reference_windows(x, config, steps, wins, size=size)
    if control is not None:
        got = reference_windows(x, config, steps, wins, control, size=size)
        return {"max_rel_err": max_rel_err(got, ref)}
    got = np.stack([out[y0:y0 + size, x0:x0 + size] for _, y0, x0 in wins])
    return {"max_rel_err": max_rel_err(got, ref),
            "frame_cells_changed": frame_cells_changed(out, x,
                                                       int(config["radius"]))}
