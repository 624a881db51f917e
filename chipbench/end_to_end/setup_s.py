"""setup_s: process start to window start (data from the seed, pack,
placement, compiles or cache reads, warm-up).  Host clock."""


def read(ctx, metric):
    return ctx.setup_s
