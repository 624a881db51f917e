"""collective.share — of the time the chips were busy in the traced window,
the share their all-reduces took, in %: the SELF time of the device
operations named ``all-reduce*`` in the reduced trace (``all-reduce.<n>``,
or ``all-reduce-start.<n>`` / ``all-reduce-done.<n>`` where the compiler
splits one), averaged over the chips as ``busy_s`` is, over ``busy_s``.  A
data-parallel fit sums its gradient over the chips inside every SGD step,
under the program's ``fmt.train.psum`` scope; on one chip the compiler drops
the sum and there is no such operation.  No trace, or no all-reduce among the
operations the reduction keeps (its ten largest by self time), gives
nothing, never 0."""

PREFIX = "all-reduce"


def read(ctx, metric):
    if ctx.trace is None or ctx.trace["busy_s"] <= 0:
        return None
    kept = [s for name, s in ctx.trace["device_ops"]
            if name.startswith(PREFIX)]
    return 100.0 * sum(kept) / ctx.trace["busy_s"] if kept else None
