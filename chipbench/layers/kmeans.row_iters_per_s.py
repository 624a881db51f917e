"""kmeans.row_iters_per_s — rows assigned inside the window (the program's
``train.kmeans_row_iters`` counter: rows x Lloyd iterations of every KMeans
fit) over the device seconds of the ``jit_bundled`` programs in the trace, in
millions a second: the rate at which the chip takes a row through distance
product, argmin and its centroid's sum.  No trace, no program in it or a
program without the counter gives nothing."""

PROGRAM = "jit_bundled"


def read(ctx, metric):
    if ctx.trace is None:
        return None
    from chipbench import trace_reduce

    row_iters = ctx.counter("train.kmeans_row_iters")
    seconds, calls = trace_reduce.program_seconds(ctx.trace, PROGRAM)
    if not row_iters or not calls or seconds <= 0:
        return None
    return row_iters / seconds / 1e6
