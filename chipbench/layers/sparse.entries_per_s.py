"""sparse.entries_per_s — stored entries the segment-CSR step consumed inside
the window (the program's ``train.sparse_entries`` counter: entries x epochs
of every sparse fit) over the device seconds of the ``jit_bundled`` programs
in the trace, in millions a second: the rate at which the chip gathers a
weight, multiplies, sums by row and scatters a gradient slot, an entry.  No
trace, no program in it or a program without the counter gives nothing."""

PROGRAM = "jit_bundled"


def read(ctx, metric):
    if ctx.trace is None:
        return None
    from chipbench import trace_reduce

    entries = ctx.counter("train.sparse_entries")
    seconds, calls = trace_reduce.program_seconds(ctx.trace, PROGRAM)
    if not entries or not calls or seconds <= 0:
        return None
    return entries / seconds / 1e6
