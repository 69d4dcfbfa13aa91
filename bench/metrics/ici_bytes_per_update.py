"""Inter-chip halo bytes per useful cell-update (ici layer, exact count).

The send-side ``HaloSend`` bytes of one solve's sharded plan, summed
over the ranks (``stats().ici_bytes``), over the interior updates the
solve makes (steps x interior cells).  ``None`` for a plan that sends
nothing between chips."""


def read(ctx):
    ici = ctx.stats.ici_bytes
    if not ici:
        return None
    return ici / ctx.params.interior_updates
