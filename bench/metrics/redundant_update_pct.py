"""Redundant cell-updates as a share of the useful ones (plan layer).

SO2DR's on-chip trade: the overlap wedges it recomputes instead of
sending again.  Exact count from the plan's accounting."""


def read(ctx):
    s = ctx.stats
    return 100.0 * s.redundant_elements / ctx.params.interior_updates
