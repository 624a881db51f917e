"""train.place_params_ms — host milliseconds per fit to put the start
parameters on the device and copy them for donation (the program's
``train.place_params`` span), inside the window."""


def read(ctx, metric):
    seconds, count = ctx.timing("train.place_params")
    return 1e3 * seconds / count if count else None
