"""Convergence + pipeline-integration tests for the GLM estimators.

Tier (4)/(5) of the translated test strategy (SURVEY.md §4): end-to-end fit
on fixed seeds with accuracy/parameter-recovery assertions, running psum-based
training on the virtual 8-device CPU mesh.
"""

import os

import numpy as np

from flink_ml_tpu.api.core import load_stage
from flink_ml_tpu.api.pipeline import Pipeline
from flink_ml_tpu.lib import (
    LinearRegression,
    LinearRegressionModel,
    LogisticRegression,
)
from flink_ml_tpu.ops.vector import DenseVector
from flink_ml_tpu.table.schema import DataTypes, Schema
from flink_ml_tpu.table.table import Table


def linreg_data(n=200, seed=0):
    rng = np.random.RandomState(seed)
    X = rng.randn(n, 3)
    true_w = np.array([2.0, -1.0, 0.5])
    y = X @ true_w + 3.0 + 0.01 * rng.randn(n)
    schema = Schema.of(
        ("f0", "double"), ("f1", "double"), ("f2", "double"), ("label", "double")
    )
    t = Table.from_columns(
        schema, {"f0": X[:, 0], "f1": X[:, 1], "f2": X[:, 2], "label": y}
    )
    return t, true_w


def logreg_data(n=400, seed=1):
    rng = np.random.RandomState(seed)
    X = rng.randn(n, 4)
    true_w = np.array([1.5, -2.0, 1.0, 0.0])
    logits = X @ true_w - 0.5
    y = (logits + 0.3 * rng.randn(n) > 0).astype(np.float64)
    vectors = [DenseVector(row) for row in X]
    schema = Schema.of(("features", DataTypes.DENSE_VECTOR), ("label", "double"))
    return Table.from_columns(schema, {"features": vectors, "label": y})


class TestLinearRegression:
    def test_recovers_coefficients_full_batch(self):
        t, true_w = linreg_data()
        est = (
            LinearRegression()
            .set_feature_cols(["f0", "f1", "f2"])
            .set_label_col("label")
            .set_prediction_col("pred")
            .set_learning_rate(0.1)
            .set_max_iter(200)
        )
        model = est.fit(t)
        np.testing.assert_allclose(model.coefficients(), true_w, atol=0.05)
        assert abs(model.intercept() - 3.0) < 0.05

    def test_minibatch_sgd_converges(self):
        t, true_w = linreg_data()
        model = (
            LinearRegression()
            .set_feature_cols(["f0", "f1", "f2"])
            .set_label_col("label")
            .set_prediction_col("pred")
            .set_learning_rate(0.05)
            .set_global_batch_size(64)
            .set_max_iter(150)
            .fit(t)
        )
        np.testing.assert_allclose(model.coefficients(), true_w, atol=0.1)

    def test_transform_schema_and_values(self):
        t, _ = linreg_data(50)
        model = (
            LinearRegression()
            .set_feature_cols(["f0", "f1", "f2"])
            .set_label_col("label")
            .set_prediction_col("pred")
            .set_max_iter(100)
            .fit(t)
        )
        (out,) = model.transform(t)
        assert out.schema.field_names == ["f0", "f1", "f2", "label", "pred"]
        resid = np.asarray(out.col("pred")) - np.asarray(t.col("label"))
        assert np.sqrt(np.mean(resid**2)) < 0.2

    def test_tol_early_stop(self):
        t, _ = linreg_data()
        model = (
            LinearRegression()
            .set_feature_cols(["f0", "f1", "f2"])
            .set_label_col("label")
            .set_prediction_col("pred")
            .set_learning_rate(0.2)
            .set_max_iter(500)
            .set_tol(1e-6)
            .fit(t)
        )
        assert model.train_epochs_ < 500

    def test_save_load_roundtrip(self, tmp_path):
        t, _ = linreg_data(50)
        model = (
            LinearRegression()
            .set_feature_cols(["f0", "f1", "f2"])
            .set_label_col("label")
            .set_prediction_col("pred")
            .set_max_iter(50)
            .fit(t)
        )
        path = os.path.join(tmp_path, "lrm")
        model.save(path)
        loaded = load_stage(path)
        assert isinstance(loaded, LinearRegressionModel)
        np.testing.assert_allclose(loaded.coefficients(), model.coefficients())
        (out,) = loaded.transform(t)
        (orig,) = model.transform(t)
        np.testing.assert_allclose(out.col("pred"), orig.col("pred"))

    def test_no_intercept(self):
        t, true_w = linreg_data()
        model = (
            LinearRegression()
            .set_feature_cols(["f0", "f1", "f2"])
            .set_label_col("label")
            .set_prediction_col("pred")
            .set_with_intercept(False)
            .set_max_iter(100)
            .fit(t)
        )
        assert model.intercept() == 0.0


class TestLogisticRegression:
    def test_accuracy_on_separable_data(self):
        t = logreg_data()
        model = (
            LogisticRegression()
            .set_vector_col("features")
            .set_label_col("label")
            .set_prediction_col("pred")
            .set_prediction_detail_col("prob")
            .set_learning_rate(0.5)
            .set_max_iter(150)
            .fit(t)
        )
        (out,) = model.transform(t)
        acc = np.mean(np.asarray(out.col("pred")) == np.asarray(t.col("label")))
        assert acc > 0.93
        probs = np.asarray(out.col("prob"))
        assert np.all((probs >= 0) & (probs <= 1))
        # prob and hard label agree
        np.testing.assert_array_equal(probs > 0.5, np.asarray(out.col("pred")) == 1.0)

    def test_auc_parity_with_numpy_reference(self):
        """AUC of the device-trained model matches a plain-numpy full-batch GD
        implementation of the same optimization (the 'identical AUC' criterion
        of the north star, ROADMAP.md)."""
        t = logreg_data(300, seed=7)
        lr, iters = 0.5, 120
        model = (
            LogisticRegression()
            .set_vector_col("features")
            .set_label_col("label")
            .set_prediction_col("pred")
            .set_learning_rate(lr)
            .set_max_iter(iters)
            .fit(t)
        )
        X = t.features_dense("features")
        y = np.asarray(t.col("label"), dtype=np.float64)

        w = np.zeros(4)
        b = 0.0
        for _ in range(iters):
            p = 1 / (1 + np.exp(-(X @ w + b)))
            err = p - y
            w -= lr * (X.T @ err) / len(y)
            b -= lr * err.sum() / len(y)

        def auc(scores):
            order = np.argsort(scores)
            ranks = np.empty(len(scores))
            ranks[order] = np.arange(1, len(scores) + 1)
            pos = y == 1
            n_pos, n_neg = pos.sum(), (~pos).sum()
            return (ranks[pos].sum() - n_pos * (n_pos + 1) / 2) / (n_pos * n_neg)

        auc_np = auc(X @ w + b)
        auc_tpu = auc(model.predict_proba(t))
        assert abs(auc_np - auc_tpu) < 1e-3

    def test_pipeline_integration(self):
        """Estimator inside a Pipeline: fit chains into a PipelineModel."""
        t = logreg_data(200, seed=3)
        est = (
            LogisticRegression()
            .set_vector_col("features")
            .set_label_col("label")
            .set_prediction_col("pred")
            .set_max_iter(80)
            .set_learning_rate(0.5)
        )
        pipeline = Pipeline([est])
        pmodel = pipeline.fit(t)
        (out,) = pmodel.transform(t)
        acc = np.mean(np.asarray(out.col("pred")) == np.asarray(t.col("label")))
        assert acc > 0.9


class TestTrainMetrics:
    def test_fused_fit_records_throughput(self):
        t, _ = linreg_data(100)
        from flink_ml_tpu.lib import LinearRegression

        model = (LinearRegression().set_feature_cols(["f0", "f1", "f2"])
                 .set_label_col("label").set_prediction_col("p")
                 .set_learning_rate(0.05).set_max_iter(7).fit(t))
        s = model.train_metrics_.summary(skip_warmup=0)
        assert s["total_samples"] == 7 * 100
        assert s["samples_per_sec"] > 0
