"""fit_rows_per_s: training rows consumed (rows x epochs, summed over every
fit that completed) over the whole window, stalls included.  Host clock."""


def read(ctx, metric):
    rows = sum(j["rows"] for j in ctx.done)
    return rows / (ctx.window_end - ctx.window_start) if rows else None
