"""Plain reference of a stencil, and the check that decides ``correct``.

The reference imports nothing of the program.  Its update is one of two:

* a tap sum written out here (the default): every interior cell becomes
  ``sum c[o] * x[cell + o - r]`` over the ``2r+1`` offsets of each axis,
  with the configuration's ``coefficients``, a table of the domain's
  rank;
* an update the configuration names (``"update": "<name>"``):
  ``update(at)`` of ``bench/updates/<name>.py``, written in ``jnp``
  against a neighbour accessor: ``at(offset)`` (one integer per axis)
  gives the cells at ``offset`` from the ones being updated, all of one
  shape, which is the shape of the result.

The ``r``-wide frame of the domain is held.  The reference runs on
windows of the solved domain (squares, cubes), each advanced from the
input on a region that reaches ``steps * r`` cells further on every
axis (the dependency cone), so the region's own edges never reach the
window.

``mode`` picks the arithmetic:

* ``f32``   — float32 throughout: the reference.
* ``bf16``  — the same update in bfloat16: the control for a float32
  configuration computed on the vector unit.
* ``high3`` — float32 with every product taken as three bfloat16
  products (``a_hi*b_hi + a_hi*b_lo + a_lo*b_hi``), what a matrix unit
  does at ``Precision.HIGH``: the control for float32 at ``highest``;
  a tap sum's only.
"""
from __future__ import annotations

import functools
import itertools
from typing import Callable, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

MODES = ("f32", "bf16", "high3")

# side of each checked window, by the domain's rank: a 2-D window's
# dependency cone reaches steps * r further each way; a 3-D one's region
# (64 + 2 * 128 cells a side at 128 steps of r = 1: 131 MB) still fits
# a batch of them beside the domain
WINDOW = 256
WINDOW_3D = 64
WINDOW_SIDES = {2: WINDOW, 3: WINDOW_3D}


def coefficients(config: dict) -> np.ndarray:
    """The configuration's tap weights, a table of ``2r+1`` along each of
    its axes, as float32."""
    c = np.asarray(config["coefficients"], np.float64).astype(np.float32)
    r = int(config["radius"])
    if c.ndim < 1 or c.shape != (2 * r + 1,) * c.ndim:
        raise ValueError(f"coefficients {c.shape} do not match radius {r}")
    return c


def taps(config: dict) -> Tuple[Tuple[Tuple[int, ...], ...], np.ndarray]:
    """The tap sum's nonzero taps in table order: their offsets from the
    updated cell, and their float32 weights."""
    c = coefficients(config)
    r = int(config["radius"])
    idx = [i for i in np.ndindex(c.shape) if c[i] != 0.0]
    return (tuple(tuple(int(k) - r for k in i) for i in idx),
            np.asarray([c[i] for i in idx], np.float32))


def _split(v):
    hi = v.astype(jnp.bfloat16).astype(jnp.float32)
    lo = (v - hi).astype(jnp.bfloat16).astype(jnp.float32)
    return hi, lo


@functools.partial(jax.jit, static_argnames=("steps", "mode", "radius",
                                             "offsets", "update"))
def advance(regions: jnp.ndarray, held: jnp.ndarray, weights: jnp.ndarray,
            steps: int, mode: str = "f32", *, radius: int,
            offsets: Tuple[Tuple[int, ...], ...] = (),
            update: Optional[Callable] = None) -> jnp.ndarray:
    """``steps`` updates of a batch of regions (axis 0 the batch).

    The update is ``update`` where one is given, else the tap sum of
    ``weights`` at ``offsets``.  ``held`` marks cells of the domain's
    frame, which keep their value; the outer ``r`` cells of a region
    that are not frame go stale, one ring per step, which the
    dependency-cone margin absorbs."""
    if mode not in MODES:
        raise ValueError(f"unknown mode {mode!r}; known: {MODES}")
    if mode == "high3" and update is not None:
        raise ValueError("high3 splits a tap sum's products; a named "
                         "update has none")
    r = radius
    shape = regions.shape[1:]
    dt = jnp.bfloat16 if mode == "bf16" else jnp.float32
    x = regions.astype(dt)
    interior = (slice(None),) + tuple(slice(r, s - r) for s in shape)
    if mode == "high3":
        w_hi, w_lo = _split(weights)
    w = weights.astype(dt)

    def accessor(v):
        def at(offset):
            return v[(slice(None),) + tuple(
                slice(r + o, s - r + o) for o, s in zip(offset, shape))]
        return at

    def step(_, x):
        if update is not None:
            acc = update(accessor(x))
        else:
            acc = None
            if mode == "high3":
                hi, lo = (accessor(v) for v in _split(x))
            at = accessor(x)
            for i, off in enumerate(offsets):
                if mode == "high3":
                    term = (w_hi[i] * hi(off) + w_hi[i] * lo(off)
                            + w_lo[i] * hi(off))
                else:
                    term = w[i] * at(off)
                acc = term if acc is None else acc + term
        new = x.at[interior].set(acc.astype(dt))
        return jnp.where(held, x, new)

    return jax.lax.fori_loop(0, steps, step, x).astype(jnp.float32)


def region_origin(y0: int, size: int, margin: int, extent: int) -> int:
    """Start of the region of ``size`` cells that holds the window at
    ``y0`` plus ``margin`` on each side, moved inside ``[0, extent)``;
    where it is moved, that side of the window lies on the frame."""
    return min(max(y0 - margin, 0), extent - size)


def _axis_names(ndim: int) -> Tuple[str, ...]:
    return ("row", "col") if ndim == 2 else tuple(
        f"axis{a}" for a in range(ndim))


def windows(shape: Sequence[int], radius: int, chunk_starts: Sequence[int],
            seed: int, n_random: int = 4, size: int = WINDOW,
            seams: Sequence[Sequence[int]] = ()) -> List[tuple]:
    """The windows the check compares: ``(label, *origin)``, one origin
    index per axis of ``shape``.

    Fixed places first: both corners, where the held frame is; every
    chunk boundary (rows), where region sharing hands rows from one
    chunk to the next; on a 2-D domain its middle across a 512-column
    kernel tile seam.  Then ``n_random`` windows drawn from the seed.
    Last, the ``seams`` (per axis, the first index of each part after
    the first: between the shards of a mesh, the tiles of a box plan):
    each seam of each axis, drawn along the other axes, and each point
    where seams of two or more axes cross, drawn along the rest."""
    shape = tuple(int(n) for n in shape)
    nd = len(shape)
    rng = np.random.default_rng(np.random.SeedSequence([int(seed), 0x5EED]))
    seams = [list(s) for s in seams] + [[] for _ in range(nd - len(seams))]

    def across(b: int, extent: int) -> int:
        return min(max(b - size // 2, 0), extent - size)

    def drawn(fixed: Dict[int, int]) -> Tuple[int, ...]:
        return tuple(fixed[a] if a in fixed else
                     int(rng.integers(0, shape[a] - size + 1))
                     for a in range(nd))

    out = [("corner",) + (0,) * nd,
           ("far-corner",) + tuple(n - size for n in shape)]
    for b in chunk_starts:
        out.append((f"chunk-boundary@{b}",) + drawn({0: across(b, shape[0])}))
    if nd == 2:
        Y, X = shape
        seam = max(min((X // 2) // 512 * 512 - size // 2, X - size), 0)
        out.append(("tile-seam", max(Y // 2 - size // 2, 0), seam))
    for i in range(n_random):
        out.append((f"random{i}",) + drawn({}))
    names = _axis_names(nd)
    for a in range(nd):
        for b in seams[a]:
            out.append((f"{names[a]}-seam@{b}",)
                       + drawn({a: across(b, shape[a])}))
    for k in range(2, nd + 1):
        for axes in itertools.combinations(range(nd), k):
            for bs in itertools.product(*(seams[a] for a in axes)):
                at = dict(zip(axes, bs))
                label = ",".join(str(at[a]) if a in at else "*"
                                 for a in range(nd))
                out.append((f"seam-cross@{label}",) + drawn(
                    {a: across(b, shape[a]) for a, b in at.items()}))
    return out


def _held(origin: Sequence[int], region: Sequence[int],
          shape: Sequence[int], r: int) -> np.ndarray:
    """Cells of a region at ``origin`` that lie on the domain's frame."""
    held = np.zeros(tuple(region), bool)
    for a, (o, s, n) in enumerate(zip(origin, region, shape)):
        g = np.arange(o, o + s)
        held |= ((g < r) | (g >= n - r)).reshape(
            (1,) * a + (s,) + (1,) * (len(shape) - a - 1))
    return held


def reference_windows(x: np.ndarray, config: dict, steps: int,
                      wins: Sequence[tuple], mode: str = "f32",
                      size: int = WINDOW, batch: int = 4,
                      update: Optional[Callable] = None) -> np.ndarray:
    """``(len(wins), size, ..., size)`` reference values of the windows
    after ``steps`` steps from the host domain ``x``, by ``update``
    where one is given, else by the configuration's tap sum."""
    r = int(config["radius"])
    if update is None:
        rank = coefficients(config).ndim
        if rank != x.ndim:
            raise ValueError(f"coefficients of rank {rank} for a domain of "
                             f"rank {x.ndim}")
        offsets, weights = taps(config)
    else:
        offsets, weights = (), np.zeros((0,), np.float32)
    margin = steps * r
    region = tuple(min(size + 2 * margin, n) for n in x.shape)
    w = jnp.asarray(weights)
    out = []
    for i in range(0, len(wins), batch):
        part = wins[i:i + batch]
        regions, held, offs = [], [], []
        for _, *origin in part:
            ro = [region_origin(o, s, margin, n)
                  for o, s, n in zip(origin, region, x.shape)]
            regions.append(x[tuple(slice(o, o + s)
                                   for o, s in zip(ro, region))])
            held.append(_held(ro, region, x.shape, r))
            offs.append([o - q for o, q in zip(origin, ro)])
        res = np.asarray(advance(jnp.asarray(np.stack(regions)),
                                 jnp.asarray(np.stack(held)), w, steps, mode,
                                 radius=r, offsets=offsets, update=update))
        for k, off in enumerate(offs):
            out.append(res[(k,) + tuple(slice(o, o + size) for o in off)])
    return np.stack(out)


def max_rel_err(got: np.ndarray, ref: np.ndarray) -> float:
    """``max |got - ref| / max |ref|`` over every compared cell (NaN
    when either holds a NaN, which fails any limit)."""
    return float(np.max(np.abs(got.astype(np.float64) - ref))
                 / np.max(np.abs(ref)))


def frame_cells_changed(out: np.ndarray, x: np.ndarray, radius: int) -> int:
    """Cells of the held frame whose value the solve changed: each axis's
    two ``r``-thick faces, inside the frame of the axes before it, so
    that every cell is counted once."""
    r = radius
    inner = slice(r, -r)
    strips = []
    for a in range(x.ndim):
        rest = (slice(None),) * (x.ndim - a - 1)
        strips += [(inner,) * a + (slice(None, r),) + rest,
                   (inner,) * a + (slice(-r, None),) + rest]
    return int(sum(np.count_nonzero(out[s] != x[s]) for s in strips))


def window_size(shape: Sequence[int]) -> int:
    if len(shape) not in WINDOW_SIDES:
        raise ValueError(f"no window side for a domain of rank {len(shape)}")
    return min(WINDOW_SIDES[len(shape)], *shape)


def compare(out: Optional[np.ndarray], x: np.ndarray, config: dict,
            steps: int, wins, control: Optional[str] = None,
            update: Optional[Callable] = None) -> Dict[str, float]:
    """The numbers ``correct`` is decided on, for a solved domain ``out``
    of input ``x``, against ``update`` where the configuration names one
    (else its tap sum).  With ``control`` (a mode), the reference
    computed in that mode takes the program's place: ``out`` is not
    read, and the frame, which the reference holds by construction, is
    not compared."""
    size = window_size(x.shape)
    ref = reference_windows(x, config, steps, wins, size=size, update=update)
    if control is not None:
        got = reference_windows(x, config, steps, wins, control, size=size,
                                update=update)
        return {"max_rel_err": max_rel_err(got, ref)}
    got = np.stack([out[tuple(slice(o, o + size) for o in origin)]
                    for _, *origin in wins])
    return {"max_rel_err": max_rel_err(got, ref),
            "frame_cells_changed": frame_cells_changed(out, x,
                                                       int(config["radius"]))}
