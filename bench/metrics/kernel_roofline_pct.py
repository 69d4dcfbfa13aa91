"""The kernel calls' share of their roofline (kernels layer).

The least time the chips could take for the calls' useful work
(:mod:`bench.work`: cells each step must update, the box read once and
written once), each call at ``max(bytes / HBM bandwidth, flops / f32
rate)``, over the trace's device time of the same calls (the programs
``kernel_pattern`` matches, pad and crop included).  On a chip mesh the
calls of every chip are summed and the sum divided by the chips, as the
trace's time is averaged over them.  The f32 rate is the one the traced
run measured (:mod:`bench.peaks`)."""

import functools
import operator


def read(ctx):
    t = ctx.trace
    if t is None or not t.kernel_calls or not t.kernel_s:
        return None
    hbm = float(ctx.peaks["hbm_bytes_per_s"])
    rate = float(ctx.f32_flops_per_s)
    calls = ctx.kernel_work
    chips = ctx.cell.chips
    least = ctx.solves * sum(w.least_seconds(hbm, rate) for w in calls)
    if chips > 1:
        least /= chips
    total = functools.reduce(operator.add, calls)
    ctx.log(f"kernel_roofline: bound={total.bound(hbm, rate)} per solve "
            f"bytes={total.bytes} flops={total.flops} cells={total.cells}; "
            f"least_s={least!r} over kernel_s={t.kernel_s!r} in "
            f"{t.kernel_calls} calls ({ctx.solves} solves x {len(calls)} "
            f"calls in the plan over {chips} chip(s))")
    return 100.0 * least / t.kernel_s
