"""fit.unattributed_ms — host milliseconds per fit that no span names: the
program's ``fit.wall`` span less the sum of its direct children, inside the
window.  It says how much of a fit the other span metrics cover."""

CHILDREN = ("fit.prepare", "slab_pool.lookup", "train.place_params",
            "train.dispatch", "train.sync", "train.demux", "train.health",
            "fit.finish", "fit.report")


def read(ctx, metric):
    wall, count = ctx.timing("fit.wall")
    if not count:
        return None
    return 1e3 * (wall - sum(ctx.timing(c)[0] for c in CHILDREN)) / count
