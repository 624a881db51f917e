"""train.onepass_share — of the window's fused fits, the share whose program
holds the one-pass minibatch kernel (the program's ``train.onepass_fits`` over
its ``train.fused_runs``), in %.  Both sweeps expect 100.  A program that has
no such counter (it is there, at 0 or more, from the first fused fit of a
program that has the kernel) gives nothing."""

COUNTER = "train.onepass_fits"


def read(ctx, metric):
    if COUNTER not in ctx.snapshots["window"][1]["counters"]:
        return None
    fits = ctx.counter("train.fused_runs")
    return 100.0 * ctx.counter(COUNTER) / fits if fits else None
