"""fit_p95_ms: 95th percentile (nearest rank), over all fits of the window, of
one LogisticRegression.fit(table) call: from the call to coefficients and loss
history on the host.  Host clock."""

import math


def read(ctx, metric):
    times = sorted(j["end"] - j["start"] for j in ctx.done)
    if not times:
        return None
    return times[max(0, math.ceil(0.95 * len(times)) - 1)] * 1e3
