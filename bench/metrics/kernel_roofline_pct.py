"""The fused-step calls' share of their roofline (kernels layer).

The least time the chip could take for the calls' useful work
(:mod:`bench.work`: cells each step must update, the band read once and
written once), each call at ``max(bytes / HBM bandwidth, flops / f32
rate)``, over the trace's device time of the same calls, pad and crop
included.  The f32 rate is the one the traced run measured
(:mod:`bench.peaks`)."""

from bench import work


def read(ctx):
    t = ctx.trace
    if t is None or not t.kernel_calls or not t.kernel_s:
        return None
    cfg = ctx.cell.config
    hbm = float(ctx.peaks["hbm_bytes_per_s"])
    rate = float(ctx.f32_flops_per_s)
    ops = ctx.kernel_ops
    itemsize = ctx.plan.itemsize
    least = ctx.solves * work.least_seconds(
        ops, ctx.params.radius, int(cfg["flops_per_cell"]), itemsize,
        hbm, rate)
    total = work.plan_work(ops, ctx.params.radius, int(cfg["flops_per_cell"]),
                           itemsize)
    ctx.log(f"kernel_roofline: bound={total.bound(hbm, rate)} per solve "
            f"bytes={total.bytes} flops={total.flops} cells={total.cells}; "
            f"least_s={least!r} over kernel_s={t.kernel_s!r} in "
            f"{t.kernel_calls} calls ({ctx.solves} solves x {len(ops)} "
            f"calls in the plan)")
    return 100.0 * least / t.kernel_s
