"""Ahead-of-time compiles of the fused-stencil kernels for a TPU v5e.

No chip is needed: the TPU compiler compiles for a described (not
attached) v5e topology, and refuses what Mosaic would refuse on the
chip — unaligned slices, unsupported primitives, VMEM over the limit.
The band is the paper's out-of-core width (Table III): 2400 interior
rows plus the k_on-step apron on both sides, by 38400 interior columns
plus the frame, f32, k_on = 4.

The topology is described inside a fixture (never at import: only one
process at a time may load the TPU library), and JAX's persistent
compilation cache is off around these compiles — an entry written for a
described chip cannot be read back without one.
"""
import os

import jax
import jax.numpy as jnp
import pytest

from repro.core.stencil import get_stencil

K_ON = 4
ROWS, COLS = 2400, 38400

CASES = [("pallas", "box2d1r"), ("pallas", "gradient2d"),
         ("pallas_db", "box2d1r"), ("pallas_db", "gradient2d"),
         ("mxu", "box2d4r")]


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def no_compile_cache():
    from jax.experimental.compilation_cache import compilation_cache

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _kernel(impl):
    if impl == "pallas":
        from repro.kernels.stencil_multistep import fused_stencil_band
        return fused_stencil_band
    if impl == "pallas_db":
        from repro.kernels.stencil_multistep_db import fused_stencil_band_db
        return fused_stencil_band_db
    from repro.kernels.stencil_banded_mxu import banded_fused_stencil
    return banded_fused_stencil


@pytest.mark.parametrize("impl,name", CASES)
def test_kernel_compiles_for_v5e(impl, name, one_chip, no_compile_cache):
    r = get_stencil(name).radius
    band = jax.ShapeDtypeStruct(
        (ROWS + 2 * K_ON * r, COLS + 2 * r), jnp.float32, sharding=one_chip)
    fn = _kernel(impl)
    compiled = jax.jit(
        lambda b: fn(b, name, K_ON, False, False, interpret=False)
    ).lower(band).compile()
    assert "tpu_custom_call" in compiled.as_text()
