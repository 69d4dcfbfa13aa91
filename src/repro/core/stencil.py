"""Stencil definitions.

A :class:`Stencil` is the computing template of the paper (Sec. II-A): every
interior element is updated from its neighbours within ``radius``.  The
registry mirrors the paper's benchmark suite (Table III):

* ``box2d{1,2,3,4}r`` — box-type, ``(2x+1)**2`` points, arithmetic intensity
  ``2*(2x+1)**2 - 1`` FLOPs/element,
* ``gradient2d``      — star-type, 5 points, 19 FLOPs/element (nonlinear),
* ``star2d{1..4}r``   — star-type axis-only stencils (extra, used in tests).

All stencils use the *interior-update* convention: an ``r``-wide Dirichlet
frame around the domain is held constant; only interior elements are updated.
The oracle in :mod:`repro.core.reference` and every out-of-core engine in
:mod:`repro.core.oocore` share this convention.
"""
from __future__ import annotations

import dataclasses
from typing import Callable, Dict, Sequence

import jax.numpy as jnp
import numpy as np

__all__ = ["Stencil", "get_stencil", "REGISTRY", "box_coeffs"]

# neighbour accessor: spatial offset -> neighbours aligned with the centre
Neighbours = Callable[[Sequence[int]], jnp.ndarray]


def box_coeffs(radius: int) -> np.ndarray:
    """Deterministic, non-separable, sum-to-one box coefficients.

    Distinct per-tap weights rule out accidental separable shortcuts in
    optimized kernels while keeping iterates bounded (weights sum to 1).
    """
    n = 2 * radius + 1
    iy, ix = np.mgrid[0:n, 0:n]
    w = 1.0 + 0.1 * iy + 0.01 * ix + 0.003 * iy * ix  # non-separable
    return (w / w.sum()).astype(np.float64)


def star_coeffs(radius: int) -> np.ndarray:
    """Axis-only (star) coefficients embedded in a (2r+1)x(2r+1) grid."""
    n = 2 * radius + 1
    c = np.zeros((n, n))
    for k in range(1, radius + 1):
        c[radius + k, radius] = c[radius - k, radius] = 0.35 / (2 * k * radius)
        c[radius, radius + k] = c[radius, radius - k] = 0.4 / (2 * k * radius)
    c[radius, radius] = 1.0 - c.sum()
    return c


@dataclasses.dataclass(frozen=True)
class Stencil:
    """An N-D stencil template (``ndim`` trailing spatial axes).

    The update is written once, against a neighbour accessor ``at``:
    ``at(offset)`` returns the array of neighbours at ``offset`` (one
    integer per spatial axis), aligned with the cells being updated.
    :meth:`step_valid` feeds it slices of the input (the valid region,
    every spatial extent shrinking by ``2r``); the Pallas kernels feed it
    whole-tile shifts (:meth:`step_shifted`).
    """

    name: str
    radius: int
    kind: str                    # "box" | "star" | "gradient" | "heat"
    flops_per_elem: int          # arithmetic intensity (paper Table III)
    points: int                  # taps read per output element
    _update: Callable[[Neighbours], jnp.ndarray]
    coeffs: np.ndarray | None = None   # (2r+1, 2r+1) for linear 2-D stencils
    ndim: int = 2                # spatial rank of the template

    def step_valid(self, x: jnp.ndarray) -> jnp.ndarray:
        """One time step on the valid interior: every spatial extent
        shrinks by ``2r`` (e.g. ``(H, W) -> (H-2r, W-2r)``)."""
        r = self.radius
        shape = x.shape[x.ndim - self.ndim:]

        def at(offset):
            return x[(Ellipsis,) + tuple(
                slice(r + o, s - r + o) for o, s in zip(offset, shape))]

        return self._update(at)

    def step_shifted(self, at: Neighbours) -> jnp.ndarray:
        """One time step given a caller-supplied neighbour accessor (the
        output has the shape of what ``at`` returns)."""
        return self._update(at)

    @property
    def is_linear(self) -> bool:
        return self.coeffs is not None


def _linear_update(coeffs: np.ndarray) -> Callable[[Neighbours], jnp.ndarray]:
    n = coeffs.shape[0]
    r = n // 2
    taps = [
        (dy - r, dx - r, float(coeffs[dy, dx]))
        for dy in range(n)
        for dx in range(n)
        if coeffs[dy, dx] != 0.0
    ]

    def update(at: Neighbours) -> jnp.ndarray:
        acc = None
        for dy, dx, c in taps:
            sl = at((dy, dx))
            term = jnp.asarray(c, sl.dtype) * sl
            acc = term if acc is None else acc + term
        return acc

    return update


def _gradient_update(at: Neighbours) -> jnp.ndarray:
    """5-point nonlinear gradient stencil (19 FLOPs/element).

    c + dt * (gn+gs+gw+ge) / sqrt(eps + gn^2+gs^2+gw^2+ge^2)  with
    g* the one-sided differences — an anisotropic-diffusion style update.
    """
    c = at((0, 0))
    gn = at((-1, 0)) - c
    gs = at((1, 0)) - c
    gw = at((0, -1)) - c
    ge = at((0, 1)) - c
    num = gn + gs + gw + ge
    den = gn * gn + gs * gs + gw * gw + ge * ge
    eps = jnp.asarray(1e-3, c.dtype)
    dt = jnp.asarray(0.1, c.dtype)
    return c + dt * num * jax_rsqrt(den + eps)


def jax_rsqrt(v: jnp.ndarray) -> jnp.ndarray:
    import jax

    return jax.lax.rsqrt(v)


def _make_box(radius: int) -> Stencil:
    c = box_coeffs(radius)
    pts = (2 * radius + 1) ** 2
    return Stencil(
        name=f"box2d{radius}r",
        radius=radius,
        kind="box",
        flops_per_elem=2 * pts - 1,
        points=pts,
        _update=_linear_update(c),
        coeffs=c,
    )


def _make_star(radius: int) -> Stencil:
    c = star_coeffs(radius)
    pts = 4 * radius + 1
    return Stencil(
        name=f"star2d{radius}r",
        radius=radius,
        kind="star",
        flops_per_elem=2 * pts - 1,
        points=pts,
        _update=_linear_update(c),
        coeffs=c,
    )


def _heat3d_update(at: Neighbours) -> jnp.ndarray:
    """3-D 7-point heat (star) stencil: explicit Euler Laplacian update.

    ``c + dt * (sum of 6 face neighbours - 6c)`` with ``dt = 0.1`` —
    weights sum to 1 and stay non-negative, so iterates remain bounded.
    """
    c = at((0, 0, 0))
    lap = (
        at((-1, 0, 0)) + at((1, 0, 0))
        + at((0, -1, 0)) + at((0, 1, 0))
        + at((0, 0, -1)) + at((0, 0, 1))
    )
    dt = jnp.asarray(0.1, c.dtype)
    six = jnp.asarray(6.0, c.dtype)
    return c + dt * (lap - six * c)


REGISTRY: Dict[str, Stencil] = {}
for _r in (1, 2, 3, 4):
    REGISTRY[f"box2d{_r}r"] = _make_box(_r)
    REGISTRY[f"star2d{_r}r"] = _make_star(_r)
REGISTRY["heat3d1r"] = Stencil(
    name="heat3d1r",
    radius=1,
    kind="heat",
    flops_per_elem=13,
    points=7,
    _update=_heat3d_update,
    coeffs=None,
    ndim=3,
)
REGISTRY["gradient2d"] = Stencil(
    name="gradient2d",
    radius=1,
    kind="gradient",
    flops_per_elem=19,
    points=5,
    _update=_gradient_update,
    coeffs=None,
)

PAPER_BENCHMARKS = ("box2d1r", "box2d2r", "box2d3r", "box2d4r", "gradient2d")


def get_stencil(name: str) -> Stencil:
    try:
        return REGISTRY[name]
    except KeyError:
        raise KeyError(f"unknown stencil {name!r}; known: {sorted(REGISTRY)}")
