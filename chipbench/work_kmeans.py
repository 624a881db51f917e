"""The bytes and operations that the ALGORITHM needs for one fit of KMeans
(``fit_work``), which the ``refit_kmeans`` kind reports through its
``work()``: counted from shapes only, never from what the program did.

A Lloyd iteration has to read every row once (float32): one pass can compute
the row's distances to the k centroids, its nearest, and its share of that
centroid's sum.  The distance product costs a multiply and an add a row, a
feature and a centroid (``2 x rows x features x k``); the sums add each row
into one centroid (``rows x features``).  The centroids (k x features) are not
counted: they stay on the chip.  At k = 100 that is 50 operations a byte, so
the least time is the bytes' at the chip's bfloat16 peak, and still the bytes'
were the product made of three bfloat16 passes; made of six (``highest``) it
is the MXU's.  The roofline credits the algorithm one pass at the published
peak, whatever the precision costs.

k-means++ (k - 1 passes over a sample of at most 100,000 rows) is not
counted: it is 5% of an iteration's bytes a pass and no part of the Lloyd
work a restart repeats.

``resident_bytes`` is what the program holds on the device: the rows and a
mask a row, float32.
"""

from __future__ import annotations

from chipbench.work import BYTES_F32


def fit_work(config: dict) -> dict:
    """Work of one KMeans fit (``maxIter`` Lloyd iterations, tol 0) on the
    whole table."""
    rows, dim = int(config["rows"]), int(config["features"])
    k, iterations = int(config["k"]), int(config["maxIter"])
    bytes_per_iteration = rows * dim * BYTES_F32
    flops_per_iteration = 2 * rows * dim * k + rows * dim
    return {
        "rows": rows, "k": k, "iterations": iterations,
        "bytes_per_iteration": bytes_per_iteration,
        "flops_per_iteration": flops_per_iteration,
        "bytes": bytes_per_iteration * iterations,
        "flops": flops_per_iteration * iterations,
        "resident_bytes": rows * (dim + 1) * BYTES_F32,
    }
