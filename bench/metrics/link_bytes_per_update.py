"""Host-link bytes per useful cell-update (plan layer, exact count).

H2D plus D2H bytes of one solve's plan, over the interior updates the
solve makes (steps x interior cells)."""


def read(ctx):
    s = ctx.stats
    return (s.h2d_bytes + s.d2h_bytes) / ctx.params.interior_updates
