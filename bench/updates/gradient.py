"""The system's own ``gradient2d`` update, written out for the reference:
a 5-point nonlinear star, 19 flops a cell,

    c + 0.1 * (gn + gs + gw + ge) / sqrt(1e-3 + gn^2 + gs^2 + gw^2 + ge^2)

with ``g*`` the differences from the centre ``c`` to its four face
neighbours.  The formula and its constants 0.1 and 1e-3 are the ones the
system registers for the stencil of this name; no published form of
``gradient2d`` (the AN5D benchmark suite's, which the paper's stencil
table draws on) is in this repository, so this update is the system's
definition restated and has not been checked against a published one.
``at(offset)`` gives the neighbours at ``offset``; the constants take the
cells' dtype, so the bf16 control runs the whole update in bfloat16.
"""
import jax.numpy as jnp


def update(at):
    c = at((0, 0))
    g = [at(o) - c for o in ((-1, 0), (1, 0), (0, -1), (0, 1))]
    num = g[0] + g[1] + g[2] + g[3]
    den = g[0] * g[0] + g[1] * g[1] + g[2] * g[2] + g[3] * g[3]
    eps = jnp.asarray(1e-3, c.dtype)
    dt = jnp.asarray(0.1, c.dtype)
    return c + dt * num / jnp.sqrt(den + eps)
