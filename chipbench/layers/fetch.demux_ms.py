"""fetch.demux_ms — host milliseconds per fit from the bundled result on the
host to a checked result: splitting the buffer, the loss list, the step
record and the counters (the program's ``train.demux`` span) and the memory
gauges and the health check (``train.health``), inside the window."""


def read(ctx, metric):
    demux, count = ctx.timing("train.demux")
    health, _ = ctx.timing("train.health")
    return 1e3 * (demux + health) / count if count else None
