"""Share of the executor's wall time spent in the HostCommit barrier.

``ExecStats.op_wall_s["HostCommit"]`` over ``ExecStats.wall_s``, both
summed over the window's solves (host clock, taken by the program
around each bound op).  The barrier waits for the device and copies the
staged rows into the host array."""


def read(ctx):
    if not ctx.exec_wall_s or "HostCommit" not in ctx.op_wall_s:
        return None
    return 100.0 * ctx.op_wall_s["HostCommit"] / ctx.exec_wall_s
