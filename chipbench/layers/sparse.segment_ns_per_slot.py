"""sparse.segment_ns_per_slot — device time a slot of the sparse step, in ns:
the device seconds of the ``jit_bundled`` programs in the trace over the slots
the sparse fits of the window walked (the program's ``train.sparse_slots``
counter: a step's padded width x steps x epochs, pads included).  On the
segment-CSR step a slot pays four random accesses (the take of the weights,
the sorted segment sum into the rows, the take of the error by row id, the
scatter into ``dim``); on the row-regular step two.  No trace, no program in
it or a program without the counter gives nothing."""

PROGRAM = "jit_bundled"


def read(ctx, metric):
    if ctx.trace is None:
        return None
    from chipbench import trace_reduce

    slots = ctx.counter("train.sparse_slots")
    seconds, calls = trace_reduce.program_seconds(ctx.trace, PROGRAM)
    if not slots or not calls or seconds <= 0:
        return None
    return 1e9 * seconds / slots
