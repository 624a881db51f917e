"""setup.compile_s — seconds of set-up the program spent building its
programs: its own ``compile.trace`` + ``compile.lower`` + ``compile.backend``
timings (one observation a program; ``compile.backend`` is JAX's event around
``compile_or_get_cached``, so a read from the persistent cache is in it).  On
a warm cache that is tracing, lowering and the reads; on a cold one the
compiles too: the part of ``setup_s`` that swings with the machine's cache.
A program without the ``compile.backend`` timing gives nothing, never 0."""

STAGES = ("compile.trace", "compile.lower", "compile.backend")


def read(ctx, metric):
    _seconds, programs = ctx.timing("compile.backend", "setup")
    if not programs:
        return None
    return sum(ctx.timing(name, "setup")[0] for name in STAGES)
