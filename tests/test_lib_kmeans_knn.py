"""KMeans + Knn tests: cluster recovery, assignment correctness, kNN accuracy
vs a numpy brute-force reference, save/load round-trips."""

import os

import numpy as np
import pytest

from flink_ml_tpu.api.core import load_stage
from flink_ml_tpu.lib.clustering import KMeans, KMeansModel, kmeans_plus_plus
from flink_ml_tpu.lib.knn import Knn, KnnModel
from flink_ml_tpu.ops.vector import DenseVector
from flink_ml_tpu.table.schema import DataTypes, Schema
from flink_ml_tpu.table.table import Table


def blob_data(n_per=60, seed=0):
    rng = np.random.RandomState(seed)
    centers = np.array([[0.0, 0.0], [8.0, 0.0], [0.0, 8.0]])
    X = np.concatenate(
        [c + 0.4 * rng.randn(n_per, 2) for c in centers]
    )
    labels = np.repeat(np.arange(3), n_per).astype(np.float64)
    vectors = [DenseVector(row) for row in X]
    schema = Schema.of(("features", DataTypes.DENSE_VECTOR), ("label", "double"))
    t = Table.from_columns(schema, {"features": vectors, "label": labels})
    return t, X, labels, centers


class TestKMeans:
    def test_recovers_blob_centers(self):
        t, X, _, centers = blob_data()
        model = (
            KMeans()
            .set_vector_col("features")
            .set_k(3)
            .set_max_iter(30)
            .set_prediction_col("cluster")
            .fit(t)
        )
        found = model.centroids()
        # each true center has a found centroid within 0.2
        for c in centers:
            assert np.min(np.linalg.norm(found - c, axis=1)) < 0.2

    def test_assignments_are_consistent(self):
        t, X, labels, _ = blob_data()
        model = (
            KMeans()
            .set_vector_col("features")
            .set_k(3)
            .set_max_iter(30)
            .set_prediction_col("cluster")
            .set_prediction_detail_col("dist")
            .fit(t)
        )
        (out,) = model.transform(t)
        assigned = np.asarray(out.col("cluster"))
        # same true blob -> same cluster id
        for g in range(3):
            ids = assigned[labels == g]
            assert len(np.unique(ids)) == 1
        # distance detail is the distance to the assigned centroid
        cents = model.centroids()
        expect = np.linalg.norm(X - cents[assigned.astype(int)], axis=1)
        np.testing.assert_allclose(np.asarray(out.col("dist")), expect, atol=1e-4)

    def test_tol_early_stop_and_cost(self):
        t, *_ = blob_data()
        model = (
            KMeans()
            .set_vector_col("features")
            .set_k(3)
            .set_max_iter(100)
            .set_tol(1e-4)
            .set_prediction_col("cluster")
            .fit(t)
        )
        assert model.train_epochs_ < 100
        assert model.train_cost_ > 0

    def test_save_load(self, tmp_path):
        t, *_ = blob_data()
        model = (
            KMeans()
            .set_vector_col("features")
            .set_k(3)
            .set_max_iter(20)
            .set_prediction_col("cluster")
            .fit(t)
        )
        path = os.path.join(tmp_path, "kmeans")
        model.save(path)
        loaded = load_stage(path)
        assert isinstance(loaded, KMeansModel)
        np.testing.assert_allclose(loaded.centroids(), model.centroids())

    def test_k_exceeds_rows_raises(self):
        t, *_ = blob_data(n_per=1)
        with pytest.raises(ValueError):
            KMeans().set_vector_col("features").set_k(10).set_prediction_col(
                "c"
            ).fit(t)

    def test_kmeans_plus_plus_spreads_centers(self):
        X = np.array([[0.0, 0.0], [0.1, 0.0], [10.0, 10.0], [10.1, 10.0]])
        centers = kmeans_plus_plus(X, 2, 0)
        # the two centers come from different corners
        d = np.linalg.norm(centers[0] - centers[1])
        assert d > 5


class TestKnn:
    def test_matches_numpy_bruteforce(self):
        t, X, labels, _ = blob_data(seed=2)
        rng = np.random.RandomState(3)
        Q = rng.randn(40, 2) * 4 + 2
        qschema = Schema.of(("features", DataTypes.DENSE_VECTOR),)
        qt = Table.from_columns(
            qschema, {"features": [DenseVector(r) for r in Q]}
        )
        k = 5
        model = (
            Knn()
            .set_vector_col("features")
            .set_label_col("label")
            .set_k(k)
            .set_prediction_col("pred")
            .set_prediction_detail_col("nearest")
            .fit(t)
        )
        (out,) = model.transform(qt)

        # numpy reference
        d = ((Q[:, None, :] - X[None, :, :]) ** 2).sum(-1)
        idx = np.argsort(d, axis=1)[:, :k]
        votes = labels[idx]
        expect = []
        for row in votes:
            vals, counts = np.unique(row, return_counts=True)
            expect.append(vals[np.argmax(counts)])
        np.testing.assert_array_equal(np.asarray(out.col("pred")), expect)
        np.testing.assert_allclose(
            np.asarray(out.col("nearest")),
            np.sqrt(d.min(axis=1)),
            rtol=1e-4, atol=1e-4,
        )

    def test_training_accuracy_k1(self):
        t, X, labels, _ = blob_data(seed=4)
        model = (
            Knn()
            .set_vector_col("features")
            .set_label_col("label")
            .set_k(1)
            .set_prediction_col("pred")
            .fit(t)
        )
        (out,) = model.transform(t)
        np.testing.assert_array_equal(np.asarray(out.col("pred")), labels)

    def test_save_load(self, tmp_path):
        t, *_ = blob_data(n_per=10)
        model = (
            Knn()
            .set_vector_col("features")
            .set_label_col("label")
            .set_k(3)
            .set_prediction_col("pred")
            .fit(t)
        )
        path = os.path.join(tmp_path, "knn")
        model.save(path)
        loaded = load_stage(path)
        assert isinstance(loaded, KnnModel)
        (out,) = loaded.transform(t)
        (orig,) = model.transform(t)
        np.testing.assert_array_equal(out.col("pred"), orig.col("pred"))

    def test_bf16_distances_opt_in(self):
        """bf16Distances: well-separated data classifies identically; the
        flag is opt-in because exact ties/bit-parity are not guaranteed."""
        t, X, labels, _ = blob_data(seed=8)
        rng = np.random.RandomState(9)
        Q = rng.randn(40, 2) * 4 + 2
        qt = Table.from_columns(
            Schema.of(("features", DataTypes.DENSE_VECTOR),),
            {"features": [DenseVector(r) for r in Q]},
        )

        def preds(bf16):
            m = (
                Knn().set_vector_col("features").set_label_col("label")
                .set_k(5).set_prediction_col("pred")
                .set_bf16_distances(bf16).fit(t)
            )
            return np.asarray(m.transform(qt)[0].col("pred"))

        np.testing.assert_array_equal(preds(True), preds(False))

    def test_non_contiguous_labels(self):
        """Labels need not be 0..c-1 — e.g. {-1, 7}."""
        schema = Schema.of(("features", DataTypes.DENSE_VECTOR), ("label", "double"))
        X = np.array([[0.0], [0.1], [5.0], [5.1]])
        y = np.array([-1.0, -1.0, 7.0, 7.0])
        t = Table.from_columns(
            schema, {"features": [DenseVector(r) for r in X], "label": y}
        )
        model = (
            Knn()
            .set_vector_col("features")
            .set_label_col("label")
            .set_k(2)
            .set_prediction_col("pred")
            .fit(t)
        )
        (out,) = model.transform(t)
        np.testing.assert_array_equal(np.asarray(out.col("pred")), y)


class TestReviewRegressions:
    def test_knn_k_exceeding_train_size_raises(self):
        """Regression: k > training rows used to emit phantom class-0 votes."""
        schema = Schema.of(("features", DataTypes.DENSE_VECTOR), ("label", "double"))
        X = np.array([[0.0], [0.1], [5.0]])
        y = np.array([7.0, 7.0, -1.0])
        t = Table.from_columns(
            schema, {"features": [DenseVector(r) for r in X], "label": y}
        )
        model = (
            Knn().set_vector_col("features").set_label_col("label")
            .set_k(5).set_prediction_col("pred").fit(t)
        )
        with pytest.raises(ValueError, match="exceeds training-set size"):
            model.transform(t)

    def test_transform_on_empty_table(self):
        """Regression: 0-row transform used to crash on output rank."""
        t, *_ = blob_data(n_per=10)
        empty = t.slice_rows(0, 0)

        km = (
            KMeans().set_vector_col("features").set_k(3)
            .set_max_iter(5).set_prediction_col("c").fit(t)
        )
        (out,) = km.transform(empty)
        assert out.num_rows() == 0

        kn = (
            Knn().set_vector_col("features").set_label_col("label")
            .set_k(3).set_prediction_col("p").fit(t)
        )
        (out2,) = kn.transform(empty)
        assert out2.num_rows() == 0


class TestKMeansFusedCheckpoint:
    def _est(self, max_iter, ckpt=None, tol=0.0):
        e = (KMeans().set_vector_col("features").set_k(3)
             .set_max_iter(max_iter).set_prediction_col("c").set_seed(0))
        if tol:
            e.set_tol(tol)
        if ckpt:
            e.set_checkpoint_dir(str(ckpt)).set_checkpoint_interval(3)
        return e

    def test_resume_matches_uninterrupted(self, tmp_path):
        t, *_ = blob_data(seed=2)
        full = self._est(10).fit(t)
        ckpt = tmp_path / "km"
        self._est(6, ckpt).fit(t)
        resumed = self._est(10, ckpt).fit(t)
        assert resumed.train_epochs_ == 10
        np.testing.assert_allclose(
            resumed.centroids(), full.centroids(), rtol=1e-5, atol=1e-6
        )

    def test_converged_refit_is_noop(self, tmp_path):
        t, *_ = blob_data(seed=3)
        ckpt = tmp_path / "km2"
        first = self._est(100, ckpt, tol=1e-4).fit(t)
        assert first.train_epochs_ < 100
        again = self._est(100, ckpt, tol=1e-4).fit(t)
        assert again.train_epochs_ == first.train_epochs_
        np.testing.assert_array_equal(again.centroids(), first.centroids())

    def test_metrics_recorded(self):
        t, *_ = blob_data()
        model = self._est(5).fit(t)
        s = model.train_metrics_.summary(skip_warmup=0)
        assert s["total_samples"] == 5 * 180  # epochs * rows
        assert s["total_seconds"] > 0
