"""setup.cache_hit_share — of the programs set-up asked the persistent compile
cache for, the share it served (the program's ``compile.cache_hits`` over hits
+ ``compile.cache_misses``), in %: 100 on a warm cache, 0 on an empty one.
Where both are 0 (the cache off, or a program without the counters) it gives
nothing."""


def read(ctx, metric):
    hits = ctx.counter("compile.cache_hits", "setup")
    asked = hits + ctx.counter("compile.cache_misses", "setup")
    return 100.0 * hits / asked if asked else None
