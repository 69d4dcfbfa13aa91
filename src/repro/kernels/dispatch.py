"""Kernel-dispatch registry: pick the best fused-step implementation.

The paper's win is on *kernel execution* (Sec. V: 2.78x over the
redundancy-free out-of-core code), so which fused-kernel implementation
runs a plan's :class:`~repro.core.plan.FusedKernel` ops matters as much
as the schedule itself.  This module is the single place that knows the
candidates and when each one wins:

=============  ====================================================
impl           when it wins
=============  ====================================================
reference      pure-jnp oracle (:func:`multi_step_band`); fastest on
               CPU/interpret backends, and the numerics ground truth
pallas         VMEM-resident k_on-step kernel — on-chip reuse on TPU
pallas_db      + DMA/compute overlap (two VMEM slots); the steady-state
               TPU choice
mxu            banded-matmul recast; linear stencils whose radius makes
               the VPU path compute-bound (``mxu_wins``)
=============  ====================================================

:func:`select_kernel` resolves a :class:`DispatchPolicy` (``auto`` or an
explicit impl name) against ``(stencil, steps, backend)`` and returns a
``fused_step`` callable with the engine-facing signature
``fn(band, name, steps, keep_top=..., keep_bottom=...)``.  Implementation
modules are imported lazily so the default reference path never pulls
Pallas in.

:func:`modeled_kernel_time` is the autotuner hook: the Sec. III kernel
term specialised per implementation (per-step HBM streaming for the
reference path, tile-apron overhead and DMA/compute serialisation for the
Pallas paths, MXU-flop recast for the banded path), so the dispatch
policy and tile size sweep alongside ``(d, S_TB, k_on, codec)``.
"""
from __future__ import annotations

import dataclasses
import functools
import math
from typing import Callable, Dict, Optional, Tuple

import jax

from repro.core.stencil import Stencil, get_stencil
from repro.kernels import (
    DEFAULT_TILE, MXU_TILE, VMEM_LIMIT_BYTES, band_tiling,
)

__all__ = [
    "DispatchPolicy", "KernelImpl", "KERNEL_IMPLS",
    "register_kernel_impl", "select_kernel", "modeled_kernel_time",
    "kernel_op_features", "interpret_mode",
]

# engine-facing fused-step signature:
#   fn(band, stencil_name, steps, keep_top=..., keep_bottom=...) -> band
FusedStep = Callable[..., "jax.Array"]


@dataclasses.dataclass(frozen=True)
class DispatchPolicy:
    """How the lowering layer resolves FusedKernel ops to device code.

    ``impl``      — registry name, or ``"auto"`` (backend-driven choice).
    ``tile``      — VMEM tile override for the Pallas paths (None = the
                    implementation's default).
    ``interpret`` — force/deny Pallas interpret mode (None = interpret
                    off-TPU, compiled on TPU).
    ``backend``   — override backend detection (``"tpu"``/``"cpu"``/...);
                    None = ``jax.default_backend()``.
    ``bucket``    — let the lowering pass pad band heights to per-plan
                    shape buckets so chunks/rounds share one compiled
                    kernel signature (see :mod:`repro.core.lower`).
    """

    impl: str = "auto"
    tile: Optional[Tuple[int, int]] = None
    interpret: Optional[bool] = None
    backend: Optional[str] = None
    bucket: bool = True


@dataclasses.dataclass(frozen=True)
class KernelImpl:
    """One registered fused-kernel implementation."""

    name: str
    description: str
    make: Callable[[DispatchPolicy], FusedStep]   # lazy-imports the module
    supports: Callable[[Stencil, int], bool]      # (stencil, steps) -> ok
    default_tile: Tuple[int, int] = DEFAULT_TILE
    vmem_slots: int = 1      # apron'd tiles resident at once (db = 2)


def interpret_mode(policy: DispatchPolicy) -> bool:
    """Whether the Pallas impls run in interpret mode under ``policy``:
    the explicit setting, else interpret off-TPU and compiled on TPU."""
    if policy.interpret is not None:
        return policy.interpret
    return (policy.backend or jax.default_backend()) != "tpu"


def _make_reference(policy: DispatchPolicy) -> FusedStep:
    from repro.core.reference import multi_step_band

    return multi_step_band


def _make_pallas(policy: DispatchPolicy) -> FusedStep:
    from repro.kernels.stencil_multistep import fused_stencil_band

    tile = policy.tile or DEFAULT_TILE
    interpret = interpret_mode(policy)

    def step(band, name, steps, keep_top=False, keep_bottom=False):
        return fused_stencil_band(band, name, steps, keep_top=keep_top,
                                  keep_bottom=keep_bottom, tile=tile,
                                  interpret=interpret)

    return step


def _make_pallas_db(policy: DispatchPolicy) -> FusedStep:
    from repro.kernels.stencil_multistep_db import fused_stencil_band_db

    tile = policy.tile or DEFAULT_TILE
    interpret = interpret_mode(policy)

    def step(band, name, steps, keep_top=False, keep_bottom=False):
        return fused_stencil_band_db(band, name, steps, keep_top=keep_top,
                                     keep_bottom=keep_bottom, tile=tile,
                                     interpret=interpret)

    return step


def _make_mxu(policy: DispatchPolicy) -> FusedStep:
    from repro.kernels.stencil_banded_mxu import banded_fused_stencil

    tile = policy.tile or MXU_TILE
    interpret = interpret_mode(policy)

    def step(band, name, steps, keep_top=False, keep_bottom=False):
        return banded_fused_stencil(band, name, steps, keep_top=keep_top,
                                    keep_bottom=keep_bottom, tile=tile,
                                    interpret=interpret)

    return step


KERNEL_IMPLS: Dict[str, KernelImpl] = {}


def register_kernel_impl(impl: KernelImpl) -> KernelImpl:
    if impl.name in KERNEL_IMPLS:
        raise ValueError(f"kernel impl {impl.name!r} already registered")
    KERNEL_IMPLS[impl.name] = impl
    return impl


register_kernel_impl(KernelImpl(
    name="reference",
    description="pure-jnp multi_step_band (oracle; per-step HBM streaming)",
    make=_make_reference,
    supports=lambda st, steps: True,
))
register_kernel_impl(KernelImpl(
    name="pallas",
    description="VMEM-resident k_on-step Pallas kernel (on-chip reuse)",
    make=_make_pallas,
    supports=lambda st, steps: True,
))
register_kernel_impl(KernelImpl(
    name="pallas_db",
    description="Pallas kernel with DMA/compute overlap (double buffering)",
    make=_make_pallas_db,
    supports=lambda st, steps: True,
    vmem_slots=2,
))
register_kernel_impl(KernelImpl(
    name="mxu",
    description="banded-matmul MXU recast (linear stencils, high radius)",
    make=_make_mxu,
    supports=lambda st, steps: st.is_linear,
    default_tile=MXU_TILE,
))


def _auto_impl(st: Stencil, steps: int, backend: str) -> str:
    if backend == "tpu":
        from repro.kernels.stencil_banded_mxu import mxu_wins

        return "mxu" if mxu_wins(st, steps) else "pallas_db"
    # off-TPU (this container, CI) the XLA-fused jnp path beats
    # interpret-mode Pallas by orders of magnitude
    return "reference"


@functools.lru_cache(maxsize=64)
def _resolved_impl(name: str, policy: DispatchPolicy) -> FusedStep:
    """Memoized ``impl.make(policy)``: the same (impl, policy) always
    resolves to the *same callable object*, so the lowering layer's
    signature cache (keyed on the callable's identity) keeps hitting
    across repeated ``lower()`` calls."""
    return KERNEL_IMPLS[name].make(policy)


def select_kernel(
    stencil, steps: int, policy: Optional[DispatchPolicy] = None,
) -> Tuple[str, FusedStep]:
    """Resolve ``(stencil, steps, policy)`` to ``(impl_name, fused_step)``.

    ``policy.impl == "auto"`` picks per backend: MXU recast when
    ``mxu_wins``, the DMA-overlapped Pallas kernel otherwise on TPU, and
    the reference jnp path everywhere else.  An explicit impl name is
    validated against the stencil (e.g. ``mxu`` rejects nonlinear
    stencils at dispatch time, not inside the kernel)."""
    st = get_stencil(stencil) if isinstance(stencil, str) else stencil
    policy = policy or DispatchPolicy()
    backend = policy.backend or jax.default_backend()
    name = policy.impl
    if name == "auto":
        name = _auto_impl(st, steps, backend)
    try:
        impl = KERNEL_IMPLS[name]
    except KeyError:
        raise KeyError(
            f"unknown kernel impl {name!r}; known: {sorted(KERNEL_IMPLS)}")
    if not impl.supports(st, steps):
        raise ValueError(
            f"kernel impl {name!r} does not support stencil {st.name!r} "
            f"(steps={steps})")
    return name, _resolved_impl(name, policy)


# --------------------------------------------------------------- modeling


def kernel_op_features(impl_name: str, st, shape_in, steps: int,
                       keep_lo, keep_hi, itemsize: int,
                       hw=None, tile: Optional[Tuple[int, int]] = None):
    """Model features of ONE fused call under one implementation.

    Returns ``(mem_bytes, vpu_flops, mxu_flops)`` — the raw quantities
    the Sec. III kernel term divides by hardware rates — or ``None``
    when the implementation is infeasible for this geometry
    (unsupported stencil, non-banded op on a tiled 2-D kernel, or a
    kernel whose modelled VMEM — :meth:`repro.kernels.BandTiling.vmem_bytes`
    — exceeds the scoped limit the kernels request,
    ``min(hw.c_vmem, VMEM_LIMIT_BYTES)``, when ``hw`` models a VMEM).
    :func:`modeled_kernel_time` sums these over a plan; the calibration
    harness (:mod:`repro.core.calibrate`) fits measured wall clock
    against the same features, so fitted rates mean exactly what the
    model charges.

    Per-impl memory terms:

    * ``reference`` — no on-chip reuse across fused steps: every step
      streams the band through HBM once (read + write);
    * ``pallas`` / ``pallas_db`` / ``mxu`` — one apron'd tile DMA per
      output tile (the aligned :class:`repro.kernels.BandTiling` window)
      plus one exact band write per fused call; ``mxu`` also counts the
      whole-tile banded matmuls it runs.
    """
    impl = KERNEL_IMPLS[impl_name]
    if not impl.supports(st, steps):
        return None
    r, m = st.radius, steps
    from repro.core.plan import fused_box_geometry

    shape_out, _, flops, _ = fused_box_geometry(
        r, st.flops_per_elem, shape_in, m, keep_lo, keep_hi, itemsize)
    mem_bytes = 0.0
    mxu_flops = 0.0
    banded = len(shape_in) == 2 and keep_lo[1] and keep_hi[1]
    if impl_name == "reference":
        # per-step band read + write: extents shrink r/step per
        # non-frame side, mirroring fused_box_geometry
        cur = list(shape_in)
        for _ in range(m):
            nxt = [c - 2 * r + (int(kl) + int(kh)) * r
                   for c, kl, kh in zip(cur, keep_lo, keep_hi)]
            mem_bytes += (math.prod(cur) + math.prod(nxt)) * itemsize
            cur = nxt
    elif not banded:
        # the tiled 2-D kernels only run classic row bands; N-D box
        # plans are reference-only for now
        return None
    else:
        try:
            g = band_tiling(shape_in, r, m, keep_lo[0], keep_hi[0],
                            tile or impl.default_tile, itemsize)
        except ValueError:      # no output row left after m steps
            return None
        n_taps = 2 * r + 1 if impl_name == "mxu" else 0
        c_vmem = getattr(hw, "c_vmem", 0) if hw is not None else 0
        if c_vmem and g.vmem_bytes(itemsize, impl.vmem_slots, n_taps) > \
                min(c_vmem, VMEM_LIMIT_BYTES):
            return None
        # reads: one apron'd tile DMA per output tile; writes: exact band
        mem_bytes += g.n_tiles * g.th * g.tw * itemsize \
            + shape_out[0] * shape_in[1] * itemsize
        if impl_name == "mxu":
            # (2r+1) (th x tw) @ (tw x tw) matmuls per tile and step
            mxu_flops += m * g.n_tiles * n_taps * 2 * g.th * g.tw * g.tw
    return mem_bytes, float(flops), mxu_flops


def _profiled_rates(hw, impl_name: str, profile):
    """Hardware rates for one impl, overridden by a fitted
    :class:`~repro.core.calibrate.DeviceProfile` when it carries terms
    for that impl (duck-typed: anything with ``kernel_terms``)."""
    bw, vpu, mxu = hw.bw_dmem, hw.peak_vpu_flops, hw.peak_mxu_flops
    terms = getattr(profile, "kernel_terms", None)
    if terms and impl_name in terms:
        t = terms[impl_name]
        bw = t.get("bw_eff", bw)
        if impl_name == "mxu":
            mxu = t.get("flops_eff", mxu)
        else:
            vpu = t.get("flops_eff", vpu)
    return bw, vpu, mxu


def modeled_kernel_time(plan, hw, impl_name: str,
                        tile: Optional[Tuple[int, int]] = None,
                        profile=None):
    """Sec. III kernel term specialised per implementation.

    Walks the plan's FusedKernel ops, sums their
    :func:`kernel_op_features`, and returns ``(kernel_s, mem_s,
    compute_s)`` — or ``None`` when the implementation is infeasible for
    this plan (unsupported stencil, or the apron'd tile set does not fit
    VMEM on hardware that models a VMEM capacity).

    ``profile`` (a :class:`~repro.core.calibrate.DeviceProfile`)
    replaces the hand-entered HBM bandwidth and FLOP rate with this
    impl's *measured* effective rates when the profile carries a fit for
    it — the measured-cost half of "model proposes, hardware disposes".

    Overlap per impl: ``reference`` and ``pallas_db`` hide DMA under
    compute (``max``); the single-buffered ``pallas`` and the ``mxu``
    recast serialise them (``sum``).
    """
    if impl_name not in KERNEL_IMPLS:
        raise KeyError(
            f"unknown kernel impl {impl_name!r}; known: {sorted(KERNEL_IMPLS)}")
    mem_bytes = 0.0
    vpu_flops = 0.0
    mxu_flops = 0.0
    itemsize = plan.itemsize
    for op in plan.ops:
        if type(op).__name__ != "FusedKernel":
            continue
        st = get_stencil(op.stencil)
        feats = kernel_op_features(impl_name, st, op.shape_in, op.steps,
                                   op.keep_lo, op.keep_hi, itemsize,
                                   hw=hw, tile=tile)
        if feats is None:
            return None
        mem_bytes += feats[0]
        vpu_flops += feats[1]
        mxu_flops += feats[2]
    bw_dmem, peak_vpu, peak_mxu = _profiled_rates(hw, impl_name, profile)
    if impl_name == "mxu":
        compute_s = mxu_flops / peak_mxu
    else:
        compute_s = vpu_flops / peak_vpu
    mem_s = mem_bytes / bw_dmem
    if impl_name in ("reference", "pallas_db"):
        kernel_s = max(mem_s, compute_s)     # XLA / double-buffered overlap
    else:
        kernel_s = mem_s + compute_s         # single-buffered: DMA then compute
    return kernel_s, mem_s, compute_s
