"""mfu.fit — the whole window's share of the chip's peak, in %: the least time
the chip could take for every job that completed (the larger of the job's
operations over the FLOP peak and bytes over the HBM peak, counted by
``work.py`` from shapes: for these fits the bytes, 1 operation a byte) over the window's wall time, host work and stalls included.
It bounds what any kernel's roofline share can give end to end."""


def read(ctx, metric):
    fits = len(ctx.done)
    if not fits:
        return None
    least, _bound = ctx.work.least_seconds(ctx.job_work, ctx.peak)
    return 100.0 * least * fits / (ctx.window_end - ctx.window_start)
