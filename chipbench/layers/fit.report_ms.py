"""fit.report_ms — host milliseconds per fit in the program's own exporter
(its ``fit.report`` span: a registry snapshot, a JSON line and a file append,
inside every fit while its registry is on, as the harness has it)."""


def read(ctx, metric):
    seconds, count = ctx.timing("fit.report")
    return 1e3 * seconds / count if count else None
