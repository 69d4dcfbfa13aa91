"""Share of the domain's copy into the solve's host array that ran under
the device's work (executor layer).

``100 * max(0, 1 - (op_wall_s["HostCopy.wait"] +
op_wall_s["Execute.validate"]) / (op_wall_s["HostCopy.band"] +
op_wall_s["Execute.validate"]))``, each summed over the window's solves,
a missing key as 0.  Where the whole copy is made inside
``Execute.validate``, before any transfer, this reads 0; where it runs
in bands on a thread of its own, only the waits of the writes and
barriers that needed rows not yet copied count against it.  ``None``
when the window has no ``Execute.validate``."""


def read(ctx):
    wall = ctx.op_wall_s
    if "Execute.validate" not in wall:
        return None
    validate = wall["Execute.validate"]
    copy = wall.get("HostCopy.band", 0.0) + validate
    if copy <= 0.0:
        return None
    blocked = wall.get("HostCopy.wait", 0.0) + validate
    return 100.0 * max(0.0, 1.0 - blocked / copy)
