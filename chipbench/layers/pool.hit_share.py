"""pool.hit_share — slab-pool hits over lookups inside the window, in %.
A sweep over one resident table expects 100."""


def read(ctx, metric):
    hits = ctx.counter("slab_pool.hits")
    lookups = hits + ctx.counter("slab_pool.misses")
    return 100.0 * hits / lookups if lookups else None
