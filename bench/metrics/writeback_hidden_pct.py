"""Share of the write-back's host seconds that the barrier did not wait
for (executor layer).

``100 * max(0, 1 - op_wall_s["HostCommit"] / (op_wall_s["D2H.pull"] +
op_wall_s["D2H.scatter"]))``, each summed over the window's solves.
Where the barrier pulls and scatters every staged box itself, its
seconds hold the pulls and scatters and this reads 0; where boxes are
written back on other threads under the next chunks' kernels, the
barrier waits only for what is still in flight.  ``None`` when the
window made no pulls."""


def read(ctx):
    wall = ctx.op_wall_s
    if not wall.get("D2H.pull") or "HostCommit" not in wall:
        return None
    moved = wall["D2H.pull"] + wall.get("D2H.scatter", 0.0)
    return 100.0 * max(0.0, 1.0 - wall["HostCommit"] / moved)
