"""Share of the executor's wall time spent issuing H2D transfers.

``ExecStats.op_wall_s["H2D"]`` over ``ExecStats.wall_s``, summed over
the window's solves (host clock around each ``jnp.asarray`` of a host
row band)."""


def read(ctx):
    if not ctx.exec_wall_s or "H2D" not in ctx.op_wall_s:
        return None
    return 100.0 * ctx.op_wall_s["H2D"] / ctx.exec_wall_s
