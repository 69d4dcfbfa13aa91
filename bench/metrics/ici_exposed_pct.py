"""Share of the traced window in which a chip ran an inter-chip
collective (ici layer).

On each chip, the union of the window's ``XLA Ops`` events whose HLO
name matches the configuration's ``collective_pattern`` (the halo
``ppermute``'s ``collective-permute`` ops), over the window, averaged
over the chips (:mod:`bench.trace`).  A chip runs one op at a time, so
this is the halo exchange's time that no compute hides.  ``None`` where
the configuration gives no pattern or the trace holds no matching op."""


def read(ctx):
    t = ctx.trace
    if t is None or not t.collective_calls or not t.window_s:
        return None
    return 100.0 * t.collective_s / t.window_s
