"""pool.lookup_ms — host milliseconds per slab-pool lookup inside the window
(the program's ``slab_pool.lookup`` span: the table's content token, with its
CRC canaries, and the locked lookup; a sweep's lookups all hit)."""


def read(ctx, metric):
    seconds, count = ctx.timing("slab_pool.lookup")
    return 1e3 * seconds / count if count else None
