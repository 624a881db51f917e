"""device.idle_share.<suffix> — 1 minus the union of the intervals in which
an operation ran on the device over the traced window, in %."""


def read(ctx, metric):
    if ctx.trace is None or ctx.trace["window_s"] <= 0:
        return None
    return 100.0 * (1.0 - ctx.trace["busy_s"] / ctx.trace["window_s"])
