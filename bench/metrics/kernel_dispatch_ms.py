"""Host milliseconds to issue one fused-step call (executor layer).

``ExecStats.op_wall_s["FusedKernel.call"]``, the host seconds of the
``FusedKernel.call`` spans summed over the window's solves, over the
calls they made: the solves times the plan's ``FusedKernel`` ops (one
span per op).  The bucket pad and crop are spans of their own and not
in it.  Where the host waits on the device before it can issue (a full
queue), the device's time shows here too."""


def read(ctx):
    call_s = ctx.op_wall_s.get("FusedKernel.call")
    if not call_s:
        return None
    return 1e3 * call_s / (ctx.solves * len(ctx.kernel_ops))
