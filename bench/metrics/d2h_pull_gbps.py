"""Achieved device-to-host rate of the barrier's pulls (link layer).

The plan's D2H bytes (exact: the boxes the pulls move) times the
window's solves, over ``ExecStats.op_wall_s["D2H.pull"]``, the host
seconds of the ``D2H.pull`` spans summed over them: each span wraps
one staged box's ``np.asarray``, after the barrier's ``HostCommit.drain``
has waited for the device and before ``D2H.scatter`` copies the rows
into the host array, so neither is in it."""


def read(ctx):
    pull_s = ctx.op_wall_s.get("D2H.pull")
    if not pull_s:
        return None
    return ctx.solves * ctx.stats.d2h_bytes / pull_s / 1e9
