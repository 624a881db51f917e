"""The adapter to the system under test.

What every job kind takes from ``flink_ml_tpu`` goes through here: the
``obs`` registry's counters and timings, where the program writes its own
files, the slab pool's ``clear`` and the must-be-zero counters; and the calls
the ``refit`` kind makes, ``Table`` and the public ``LogisticRegression``.  A
kind that drives another part of the program brings its own calls in its own
file.  The yardstick (data, references, comparison, work, peaks, trace
reduction) imports none of the program.
"""

from __future__ import annotations

import os

import numpy as np

#: counters that must stay ZERO over a run: each is a way a run "works"
#: without the device having done the work (copied from chip_smoke.py)
MUST_BE_ZERO = (
    "fused.pallas_fallbacks", "fused.pallas_interpreted",
    "train.pallas_interpreted", "pipeline.plan_fallback_batches",
    "serve.fallbacks", "serve.dispatch_failures", "serving.failed_requests",
    "serving.shed", "pressure.ooms", "pressure.bisections",
    "fault.retries", "fault.giveups", "warmstart.save_failures",
    "warmstart.degraded",
)


def prepare(out_dir: str) -> None:
    """Point the program's own files (RunReports, traces, flight dumps) into
    the checkout and switch its registry on.  Call before the first use."""
    os.environ["FMT_OBS_REPORTS"] = os.path.join(out_dir, "reports")
    os.environ["FMT_TRACE_DIR"] = os.path.join(out_dir, "traces")
    os.environ["FMT_FLIGHT_DIR"] = os.path.join(out_dir, "flight")
    from flink_ml_tpu import obs

    obs.enable()
    obs.reset()


def snapshot() -> dict:
    """{"counters": {...}, "timings": {name: {"count", "total_s"}}}."""
    from flink_ml_tpu import obs

    snap = obs.registry().snapshot()
    return {
        "counters": dict(snap["counters"]),
        "timings": {k: {"count": v["count"], "total_s": v["total_s"]}
                    for k, v in snap["timings"].items()},
    }


def table(X, y):
    from flink_ml_tpu.table.schema import DataTypes, Schema
    from flink_ml_tpu.table.table import Table

    return Table.from_columns(
        Schema.of(("features", DataTypes.DENSE_VECTOR), ("label", "double")),
        {"features": X, "label": y})


def logreg(config: dict, learning_rate: float, reg: float):
    from flink_ml_tpu.lib import LogisticRegression

    return (LogisticRegression().set_vector_col("features")
            .set_label_col("label").set_prediction_col("pred")
            .set_prediction_detail_col("proba")
            .set_learning_rate(float(learning_rate)).set_reg(float(reg))
            .set_global_batch_size(int(config["globalBatchSize"]))
            .set_max_iter(int(config["maxIter"]))
            .set_tol(float(config["tol"]))
            .set_with_intercept(bool(config["withIntercept"])))


def fit_answer(model) -> dict:
    """What one fit returned to its caller, as host arrays."""
    return {
        "coef": np.asarray(model.coefficients(), np.float64),
        "intercept": float(model.intercept()),
        "losses": np.asarray(model.train_losses_, np.float64),
    }


def release() -> None:
    """Drop every placed slab, so that the reference has the chip."""
    import gc

    from flink_ml_tpu.table import slab_pool

    slab_pool.pool().clear()
    gc.collect()
