"""kmeans.init_ms — milliseconds a fit in the k-means++ init (the program's
``kmeans.init`` span: the gather of the seeded sample out of the resident
rows, k - 1 passes over it on the device, and the centroids' way back to the
host).  A program without the span gives nothing."""


def read(ctx, metric):
    seconds, count = ctx.timing("kmeans.init")
    return 1e3 * seconds / count if count else None
