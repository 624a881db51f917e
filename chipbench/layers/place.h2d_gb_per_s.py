"""place.h2d_gb_per_s(.<suffix>) — bytes the slab pool placed over the seconds its
builder took (``slab_pool.bytes_placed`` / ``slab_pool.build``): the combined
host view and the host-to-device copy together, as the program does them."""


def read(ctx, metric):
    phase = ctx.phase(metric)
    seconds, count = ctx.timing("slab_pool.build", phase)
    placed = ctx.counter("slab_pool.bytes_placed", phase)
    if not count or not placed or seconds <= 0:
        return None
    return placed / seconds / 1e9
