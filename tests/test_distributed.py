"""L2 (ICI) distributed stencil: shard_map + ppermute ghost-cell expansion.

Multi-device correctness runs in a subprocess with 8 fake CPU devices
(via ``tests/_subproc.py``) so the main test session keeps its
single-device jax state (the dry-run is the only place allowed to see
512 devices).
"""
import numpy as np
import jax.numpy as jnp

from _subproc import run_fake_device_subprocess
from jax import make_mesh
from jax.sharding import AxisType
from repro.core.distributed import (
    collective_bytes_per_round, run_distributed,
)
from repro.core.reference import run_reference
from repro.core.stencil import get_stencil

_SUBPROC = r"""
import numpy as np, jax, jax.numpy as jnp
from jax import make_mesh
from jax.sharding import AxisType
from repro.core.distributed import run_distributed
from repro.core.reference import run_reference
from repro.core.stencil import get_stencil

mesh = make_mesh((4, 2), ("data", "model"),
                 axis_types=(AxisType.Auto,) * 2)
rng = np.random.default_rng(2)
for name in ("box2d1r", "gradient2d", "box2d2r"):
    st = get_stencil(name)
    x = rng.standard_normal((64, 128)).astype(np.float32)
    for n, k in [(6, 1), (6, 3), (8, 4)]:
        ref = np.asarray(run_reference(jnp.asarray(x), st, n))
        got = np.asarray(run_distributed(jnp.asarray(x), name, n, k, mesh))
        assert np.abs(got - ref).max() < 1e-5, (name, n, k)
print("SUBPROC_OK")
"""


def test_distributed_multidevice_subprocess():
    run_fake_device_subprocess(_SUBPROC, "SUBPROC_OK")


def test_distributed_single_device_mesh():
    """k_ici sweep on a trivial 1x1 mesh (runs in-process)."""
    mesh = make_mesh((1, 1), ("data", "model"),
                     axis_types=(AxisType.Auto,) * 2)
    st = get_stencil("box2d1r")
    rng = np.random.default_rng(5)
    x = rng.standard_normal((32, 32)).astype(np.float32)
    ref = np.asarray(run_reference(jnp.asarray(x), st, 6))
    got = np.asarray(run_distributed(jnp.asarray(x), "box2d1r", 6, 2, mesh))
    assert np.abs(got - ref).max() < 1e-5


def test_collective_overhead_model():
    """Ghost-cell expansion trades a small per-step byte overhead (the
    corner term, O(k*r^2)) for k x fewer collective phases per step — the
    L2 incarnation of the paper's kernel-interruption argument: ResReu's
    cost was per-step interruptions, not bytes."""
    ly, lx, r = 4096, 2048, 1
    per_step = [
        collective_bytes_per_round((ly, lx), r, k, 4) / k for k in (1, 4, 8)
    ]
    # bytes/step grow only by the corner term: (lx+ly+2kr)/(lx+ly+2r)
    assert per_step[2] / per_step[0] < 1.01
    # collective phases per step: 4/k (2 row + 2 col exchanges per round)
    phases = [4 / k for k in (1, 4, 8)]
    assert phases[2] == 0.5 < phases[0]
