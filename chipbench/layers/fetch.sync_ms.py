"""fetch.sync_ms — milliseconds per fit from the enqueue to the bundled result
on the host (the program's ``train.sync`` timing: the device's run of the
program and the one readback), inside the window."""


def read(ctx, metric):
    seconds, count = ctx.timing("train.sync")
    return 1e3 * seconds / count if count else None
