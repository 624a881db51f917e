"""The calls into ``flink_ml_tpu`` that the ``refit_sparse`` kind makes and
``program.py`` does not have: a ``Table`` whose vector column is a
``CsrRows`` (``SPARSE_VECTOR``), and the public ``LogisticRegression`` told
the column's width.  Everything else (counters, pool release, the answer)
is ``program.py``'s.
"""

from __future__ import annotations

from chipbench import program


def table(dim, indptr, indices, values, y):
    from flink_ml_tpu.ops.batch import CsrRows
    from flink_ml_tpu.table.schema import DataTypes, Schema
    from flink_ml_tpu.table.table import Table

    return Table.from_columns(
        Schema.of(("features", DataTypes.SPARSE_VECTOR), ("label", "double")),
        {"features": CsrRows(dim, indptr, indices, values), "label": y})


def logreg(config: dict, learning_rate: float, reg: float):
    """The estimator on its default sparse route (``numHotFeatures`` unset:
    plain segment-CSR), ``numFeatures`` from the configuration."""
    return (program.logreg(config, learning_rate, reg)
            .set_num_features(int(config["numFeatures"])))
