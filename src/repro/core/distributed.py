"""L2 (inter-chip) SO2DR execution backend: shard_map + ppermute.

The paper stops at a single GPU.  Its core trade — redundant computation in
overlap regions in exchange for uninterrupted locality at the faster memory
level — applies unchanged one level up: shard the domain over the chip mesh
and exchange halos of depth ``k_ici * r`` via ``collective_permute`` once
per ``k_ici`` steps, with every rank redundantly advancing its ghost wedges
(communication-avoiding stencils).  ``k_ici = 1`` degenerates to classic
per-step halo exchange — the ResReu analogue at this level — and is the §Perf
baseline.

Since the sharded-plan refactor this module is the *execution backend* of
the plan IR, not a standalone engine: :mod:`repro.core.shard` compiles
``(shape, stencil, mesh shape, k_ici, n)`` into a typed
:class:`~repro.core.plan.ShardedPlan` (per-rank op streams, halo-exchange
ops, plan-derived ICI accounting), and :func:`execute_sharded_plan` here
runs such a plan through the jitted ``shard_map``/``ppermute`` program.
:func:`run_distributed` remains the plan-free convenience (and the
differential-test oracle next to :func:`repro.core.reference.run_reference`).

Implementation notes:

* 2-D domain decomposition (rows over one mesh axis, columns over another);
  corner halos ride along by exchanging rows first, then exchanging columns
  of the row-extended band.
* Dirichlet frames are enforced with a *global-index mask* inside the
  in-place centre update (:func:`masked_local_steps`, shared with the
  lowered single-device simulator in :mod:`repro.core.lower`), so the
  per-rank program is uniform (no rank-special shapes) and the zero-filled
  halos `ppermute` leaves at mesh edges are provably never read by valid
  cells.
"""
from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp
from jax import make_mesh, shard_map
from jax.sharding import AxisType, NamedSharding, PartitionSpec as P

from .lower import check_domain
from .stencil import Stencil, get_stencil

__all__ = [
    "distributed_stencil_step_fn", "run_distributed",
    "execute_sharded_plan", "masked_local_steps",
    "collective_bytes_per_round",
]


def _shift(x: jnp.ndarray, axis_name: str, direction: int, n_ranks: int) -> jnp.ndarray:
    """ppermute shift: rank p's payload goes to rank p + direction."""
    perm = [(p, p + direction) for p in range(n_ranks) if 0 <= p + direction < n_ranks]
    return jax.lax.ppermute(x, axis_name, perm)


def masked_local_steps(ext, st: Stencil, k: int, gy0, gx0, Yg: int, Xg: int):
    """``k`` fused stencil steps on an extended band, Dirichlet frames
    enforced by a global-index mask.

    ``ext`` covers global rows/cols ``[gy0, gy0+ey) x [gx0, gx0+ex)``
    (``gy0``/``gx0`` may be traced — the shard_map path derives them from
    ``axis_index``; the lowered simulator passes per-rank constants into
    one shared jit signature).  Shared by both execution backends so the
    per-rank math is one piece of code.
    """
    r = st.radius
    ey, ex = ext.shape
    # frame mask over the *centre* region only — masking the full band
    # cost an extra band-sized buffer per step (§Perf stencil iter1)
    grow = gy0 + r + jnp.arange(ey - 2 * r)   # global row per centre row
    gcol = gx0 + r + jnp.arange(ex - 2 * r)
    interior = (
        ((grow >= r) & (grow < Yg - r))[:, None]
        & ((gcol >= r) & (gcol < Xg - r))[None, :]
    )
    # unrolled k-step loop: k is small and static; unrolling lets XLA
    # fuse shift/FMA chains across steps instead of forcing a full
    # band materialization at every scan iteration (§Perf stencil iter2)
    for _ in range(k):
        centre = jnp.where(interior, st.step_valid(ext), ext[r:-r, r:-r])
        ext = ext.at[r:-r, r:-r].set(centre)
    return ext


def _local_rounds(
    own: jnp.ndarray,
    st: Stencil,
    k: int,
    rounds: int,
    row_axis: str,
    col_axis: str,
    n_rows_ranks: int,
    n_col_ranks: int,
    Yg: int,
    Xg: int,
) -> jnp.ndarray:
    """``rounds`` rounds of (halo exchange + k fused local steps)."""
    r = st.radius
    hk = k * r
    ly, lx = own.shape
    row_id = jax.lax.axis_index(row_axis)
    col_id = jax.lax.axis_index(col_axis)

    # global coordinates of the extended band (traced, uniform program)
    gy0 = row_id * ly - hk
    gx0 = col_id * lx - hk

    def one_round(own, _):
        # exchange row halos (full local width), then column halos of the
        # row-extended band (corners ride along)
        top = _shift(own[-hk:], row_axis, +1, n_rows_ranks)
        bot = _shift(own[:hk], row_axis, -1, n_rows_ranks)
        ext = jnp.concatenate([top, own, bot], axis=0)
        left = _shift(ext[:, -hk:], col_axis, +1, n_col_ranks)
        right = _shift(ext[:, :hk], col_axis, -1, n_col_ranks)
        ext = jnp.concatenate([left, ext, right], axis=1)

        ext = masked_local_steps(ext, st, k, gy0, gx0, Yg, Xg)
        return ext[hk:-hk, hk:-hk], None

    own, _ = jax.lax.scan(one_round, own, None, length=rounds)
    return own


def distributed_stencil_step_fn(
    name: str,
    k_ici: int,
    n_steps: int,
    mesh,
    row_axis: str = "data",
    col_axis: str = "model",
):
    """Build the jitted shard_map program advancing a framed global domain
    by ``n_steps`` (``ceil(n/k)`` rounds; n must be divisible by k for the
    uniform scan — the launcher enforces it)."""
    st = get_stencil(name)
    if n_steps % k_ici:
        raise ValueError("n_steps must be divisible by k_ici (uniform scan)")
    rounds = n_steps // k_ici
    n_row = mesh.shape[row_axis]
    n_col = mesh.shape[col_axis]

    def global_fn(x: jnp.ndarray) -> jnp.ndarray:
        Yg, Xg = x.shape

        def local(own):
            return _local_rounds(
                own, st, k_ici, rounds, row_axis, col_axis,
                n_row, n_col, Yg, Xg,
            )

        spec = P(row_axis, col_axis)
        return shard_map(
            local, mesh=mesh, in_specs=(spec,), out_specs=spec, check_vma=False
        )(x)

    return jax.jit(global_fn)


def run_distributed(x, name: str, n_steps: int, k_ici: int, mesh,
                    row_axis: str = "data", col_axis: str = "model"):
    fn = distributed_stencil_step_fn(name, k_ici, n_steps, mesh, row_axis, col_axis)
    return fn(x)


def execute_sharded_plan(plan, x, mesh=None, row_axis: str = "data",
                         col_axis: str = "model"):
    """Run a :class:`~repro.core.plan.ShardedPlan` on the shard_map
    backend.

    ``mesh`` defaults to a fresh ``plan.mesh_shape`` device mesh (the
    caller's environment must have enough devices); an explicit mesh
    must match the plan's shape.  The plan carries the full geometry, so
    this is the point where "one plan drives the multi-chip engine":
    the schedule the accounting was derived from is the schedule that
    executes."""
    # shared geometry checks, before any mesh is built: both backends
    # reject identically by construction
    if getattr(plan, "trailing", ()):
        raise ValueError(
            f"plan models trailing axes {plan.trailing}; trailing plans "
            "are dry-run-only (byte/flop accounting) and cannot execute")
    check_domain(plan, x)
    if mesh is None:
        mesh = make_mesh(plan.mesh_shape, (row_axis, col_axis),
                         axis_types=(AxisType.Auto,) * 2)
    shape = (mesh.shape[row_axis], mesh.shape[col_axis])
    if shape != tuple(plan.mesh_shape):
        raise ValueError(
            f"mesh shape {shape} does not match plan mesh {plan.mesh_shape}")
    fn = distributed_stencil_step_fn(plan.stencil, plan.k_ici, plan.n,
                                     mesh, row_axis, col_axis)
    # each device receives only its own block: a host-to-one-device copy
    # would put the whole global domain on the first device
    return fn(jax.device_put(x, NamedSharding(mesh, P(row_axis, col_axis))))


def collective_bytes_per_round(
    local_shape: Tuple[int, int], radius: int, k_ici: int, itemsize: int
) -> int:
    """Analytic per-rank ICI bytes per round (send side): two row halos of
    ``k*r`` rows (full width) + two column halos of the extended height.

    Since the sharded-plan refactor this is the *formula form* of
    :attr:`repro.core.plan.ShardedPlan.collective_bytes_per_round`, which
    derives the same number from the plan's HaloSend ops (equal for
    interior ranks; property-tested in ``tests/test_shard_plan.py``)."""
    ly, lx = local_shape
    hk = k_ici * radius
    rows = 2 * hk * lx
    cols = 2 * hk * (ly + 2 * hk)
    return (rows + cols) * itemsize
