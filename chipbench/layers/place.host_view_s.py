"""place.host_view_s — seconds of set-up the host spent copying the packed
table into the slab's layout before any byte went to the device (the
program's ``place.host_view`` span): the first half of ``slab_pool.build``."""


def read(ctx, metric):
    seconds, count = ctx.timing("place.host_view", ctx.phase(metric))
    return seconds if count else None
