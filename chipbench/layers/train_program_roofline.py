"""train_program_roofline — the fused train program's share of its roofline,
in %: the least time the chip could take for the fits it ran (``work.py``'s
bytes over the HBM peak, or operations over the FLOP peak, whichever is
larger: here the bytes) over the program's device time in the trace
(``XLA Modules`` events named ``jit_bundled``).  Nothing to read (no trace, or
the program did not run) gives nothing."""

PROGRAM = "jit_bundled"


def read(ctx, metric):
    if ctx.trace is None:
        return None
    from chipbench import trace_reduce

    seconds, calls = trace_reduce.program_seconds(ctx.trace, PROGRAM)
    if not calls or seconds <= 0:
        return None
    least, _bound = ctx.work.least_seconds(ctx.job_work, ctx.peak)
    return 100.0 * least * calls / seconds
