"""Elastic restart: checkpoint on a 4-device mesh, restore onto 2 devices.

Runs in a subprocess (8 fake devices, via ``tests/_subproc.py``) so the
main session stays single-device.
"""
from _subproc import run_fake_device_subprocess

_SUBPROC = r"""
import numpy as np, jax, jax.numpy as jnp
from jax.sharding import PartitionSpec as P
from jax import make_mesh
from jax.sharding import AxisType
from repro.checkpoint import CheckpointManager
from repro.configs import get_smoke_config
from repro.models.api import build_model
from repro.launch.elastic import replan, reshard_restored

cfg = get_smoke_config("qwen3-0.6b")
model = build_model(cfg)
params = model.init_params(jax.random.PRNGKey(0))

mesh4 = make_mesh((4, 2), ("data", "model"),
                  axis_types=(AxisType.Auto,) * 2)
sh4 = replan(cfg, jax.eval_shape(lambda: model.init_params(jax.random.PRNGKey(0))), mesh4)
p4 = jax.tree.map(jax.device_put, params, sh4)

import tempfile
d = tempfile.mkdtemp()
mgr = CheckpointManager(d)
mgr.save(1, p4, extra_meta={"mesh": [4, 2]})

# "failure": restart on a smaller mesh (2 devices)
mesh2 = make_mesh((2, 1), ("data", "model"),
                  axis_types=(AxisType.Auto,) * 2)
restored, meta = mgr.restore(params)
sh2 = replan(cfg, jax.eval_shape(lambda: model.init_params(jax.random.PRNGKey(0))), mesh2)
p2 = reshard_restored(restored, sh2)

for a, b in zip(jax.tree.leaves(params), jax.tree.leaves(p2)):
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

# the resharded params still produce equivalent logits (bf16 compute +
# different cross-device reduction orders => tolerance, not bitwise)
batch = {"tokens": jnp.zeros((2, 8), jnp.int32), "labels": jnp.zeros((2, 8), jnp.int32)}
l_ref, _ = model.forward(params, batch)
with mesh2:
    l_new, _ = model.forward(p2, batch)
np.testing.assert_allclose(np.asarray(l_ref, np.float32), np.asarray(l_new, np.float32),
                           rtol=0.05, atol=0.05)
print("ELASTIC_OK")
"""


def test_elastic_reshard_subprocess():
    run_fake_device_subprocess(_SUBPROC, "ELASTIC_OK")
