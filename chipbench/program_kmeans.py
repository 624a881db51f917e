"""The calls into ``flink_ml_tpu`` that the ``refit_kmeans`` kind makes and
``program.py`` does not have: a ``Table`` of one dense vector column, the
public ``KMeans`` estimator, and a fit's answer.  Everything else (counters,
pool release, the must-be-zero counters) is ``program.py``'s.

The import below is of a name that came with this cell (PR 31): on a program
without it (one whose k-means++ is minutes of host a fit) the kind fails to
load, at once and with nothing printed, instead of running for minutes.
"""

from __future__ import annotations

import numpy as np

from flink_ml_tpu.lib.clustering import kmeans_plus_plus_rows  # noqa: F401


def table(X):
    from flink_ml_tpu.table.schema import DataTypes, Schema
    from flink_ml_tpu.table.table import Table

    return Table.from_columns(
        Schema.of(("features", DataTypes.DENSE_VECTOR)), {"features": X})


def kmeans(config: dict, seed: int):
    from flink_ml_tpu.lib import KMeans

    return (KMeans().set_vector_col("features").set_prediction_col("cluster")
            .set_k(int(config["k"])).set_max_iter(int(config["maxIter"]))
            .set_tol(float(config["tol"])).set_seed(int(seed)))


def fit_answer(model) -> dict:
    """What one fit returned to its caller, as host arrays."""
    return {
        "centroids": np.asarray(model.centroids(), np.float64),
        "costs": np.asarray(model.train_costs_, np.float64),
        "epochs": int(model.train_epochs_),
        # the centroids every iteration started from: the comparison takes
        # one reference iteration from each
        "trail": np.asarray(model.train_centroids_, np.float32),
    }
