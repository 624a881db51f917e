"""train.extra_programs_per_fit — device programs a fit launches beside its
own: calls of every program of the traced window (``XLA Modules`` events)
whose name does not start with ``jit_bundled``, over ``jit_bundled``'s calls.
Each is a dispatch on the host and, where its result is awaited, a round
trip.  No trace, or no fit in it, gives nothing."""

PROGRAM = "jit_bundled"


def read(ctx, metric):
    if ctx.trace is None:
        return None
    from chipbench import trace_reduce

    _seconds, fits = trace_reduce.program_seconds(ctx.trace, PROGRAM)
    if not fits:
        return None
    _seconds, every = trace_reduce.program_seconds(ctx.trace, "")
    return (every - fits) / fits
