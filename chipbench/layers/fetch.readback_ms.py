"""fetch.readback_ms — what of ``fetch.sync_ms`` is not the chip working, in
milliseconds per fit: the program's ``train.sync`` span per fit (from the
enqueue's return to the bundled result on the host) less the device seconds
per call of the ``jit_bundled`` programs in the trace.  What is left is the
readback and the waits around the program's run."""

PROGRAM = "jit_bundled"


def read(ctx, metric):
    if ctx.trace is None:
        return None
    from chipbench import trace_reduce

    seconds, count = ctx.timing("train.sync")
    device, calls = trace_reduce.program_seconds(ctx.trace, PROGRAM)
    if not count or not calls:
        return None
    return 1e3 * (seconds / count - device / calls)
