"""collective.us_per_step — microseconds a chip spends in all-reduces an SGD
step: the self time of the traced window's ``all-reduce*`` operations
(``collective.share``'s numerator, a chip) over the steps the window's fits
ran, which the program counts from shapes: ``train.psum_calls`` (PR 37: one
``psum`` a parameter leaf and two more, a step, an epoch, a fit) over the
``psum``s a step of a GLM with an intercept, 4 (the weights' gradient, the
intercept's, the loss sum, the weight sum).  What the four ask to carry is
``train.psum_bytes``: 3,148 bytes a step at 784 features, so the number is
latency, not bandwidth.  No trace, no all-reduce among the kept operations,
or a program without the counter, gives nothing, never 0."""

PREFIX = "all-reduce"
PSUMS_A_STEP = 4


def read(ctx, metric):
    if ctx.trace is None:
        return None
    kept = [s for name, s in ctx.trace["device_ops"]
            if name.startswith(PREFIX)]
    calls = ctx.counter("train.psum_calls")
    if not kept or not calls:
        return None
    return 1e6 * sum(kept) / (calls / PSUMS_A_STEP)
