"""train.dispatch_ms — host milliseconds per fit to enqueue the fused train
program (the program's ``train.dispatch`` timing), inside the window."""


def read(ctx, metric):
    seconds, count = ctx.timing("train.dispatch")
    return 1e3 * seconds / count if count else None
