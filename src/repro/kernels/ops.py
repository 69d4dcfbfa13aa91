"""Jit'd public wrappers for the Pallas kernels.

``fused_stencil`` auto-selects interpret mode off-TPU so the same call site
works on this CPU container (validation) and on a real TPU (deployment).
"""
from __future__ import annotations

import jax.numpy as jnp

from .dispatch import DispatchPolicy, interpret_mode
from .stencil_multistep import DEFAULT_TILE, fused_stencil_band

__all__ = ["fused_stencil", "kernel_fused_step"]


def fused_stencil(
    band: jnp.ndarray,
    name: str,
    steps: int,
    keep_top: bool = False,
    keep_bottom: bool = False,
    tile=DEFAULT_TILE,
    interpret: bool | None = None,
) -> jnp.ndarray:
    if interpret is None:
        interpret = interpret_mode(DispatchPolicy())
    return fused_stencil_band(
        band, name, steps, keep_top=keep_top, keep_bottom=keep_bottom,
        tile=tile, interpret=interpret,
    )


def kernel_fused_step(band, name, steps, keep_top=False, keep_bottom=False):
    """Signature-compatible ``fused_step`` for the out-of-core engines
    (:mod:`repro.core.oocore`), backed by the Pallas kernel."""
    return fused_stencil(band, name, steps, keep_top=keep_top, keep_bottom=keep_bottom)
