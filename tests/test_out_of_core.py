"""Out-of-core training tests (VERDICT r02 gap #1).

The contract under test: a fit that streams chunks from a file/source — with
an in-memory cap far smaller than the dataset — produces the *bit-identical*
model of the materialized in-memory fit, for any chunk size, because
step-major packing pins the row->SGD-step mapping regardless of chunking.
"""

import numpy as np
import pytest

from flink_ml_tpu.lib import LinearRegression, LogisticRegression
from flink_ml_tpu.ops.vector import SparseVector
from flink_ml_tpu.table.schema import DataTypes, Schema
from flink_ml_tpu.table.sources import (
    ChunkedTable,
    CollectionSource,
    CsvSource,
    LibSvmSource,
    ShardedSource,
)
from flink_ml_tpu.table.table import Table

SCHEMA = Schema.of(
    ("f0", "double"), ("f1", "double"), ("f2", "double"), ("label", "double")
)


def dense_data(n=5000, seed=7):
    rng = np.random.RandomState(seed)
    X = rng.randn(n, 3)
    y = X @ np.array([2.0, -1.0, 0.5]) + 1.0 + 0.01 * rng.randn(n)
    table = Table.from_columns(
        SCHEMA, {"f0": X[:, 0], "f1": X[:, 1], "f2": X[:, 2], "label": y}
    )
    return table, X, y


def make_estimator(cls=LinearRegression, batch=256, iters=5):
    return (
        cls()
        .set_feature_cols(["f0", "f1", "f2"])
        .set_label_col("label")
        .set_prediction_col("pred")
        .set_learning_rate(0.05)
        .set_global_batch_size(batch)
        .set_max_iter(iters)
    )


class _CountingSource(CollectionSource):
    """Fails the test if anything materializes the full table."""

    def __init__(self, rows, schema):
        super().__init__(rows, schema)
        self.full_reads = 0

    def read(self):
        self.full_reads += 1
        return super().read()

    def read_chunks(self, max_rows):
        table = self._table
        for start in range(0, table.num_rows(), max_rows):
            yield table.slice_rows(start, min(start + max_rows, table.num_rows()))


class TestDenseOutOfCore:
    def test_bit_matches_in_memory_fit(self):
        table, X, y = dense_data()
        in_mem = make_estimator().fit(table)
        source = _CountingSource(table.to_rows(), SCHEMA)
        chunked = ChunkedTable(source, chunk_rows=1024)
        streamed = make_estimator().fit(chunked)
        np.testing.assert_array_equal(
            streamed.coefficients(), in_mem.coefficients()
        )
        assert streamed.intercept() == in_mem.intercept()
        assert source.full_reads == 0, "out-of-core fit materialized the table"
        assert streamed.train_epochs_ == in_mem.train_epochs_
        np.testing.assert_allclose(
            streamed.train_losses_, in_mem.train_losses_, rtol=1e-6
        )

    def test_chunk_size_invariance(self):
        table, _, _ = dense_data(3000)
        rows = table.to_rows()
        results = []
        for chunk_rows in (257, 1024, 2999, 5000):
            chunked = ChunkedTable(CollectionSource(rows, SCHEMA), chunk_rows)
            results.append(make_estimator(iters=3).fit(chunked).coefficients())
        for r in results[1:]:
            np.testing.assert_array_equal(r, results[0])

    def test_respects_memory_cap_and_trains_larger_dataset(self, tmp_path):
        """A CSV deliberately larger than the chunk cap streams through
        bounded chunks and still bit-matches the materialized fit."""
        table, X, y = dense_data(20000, seed=3)
        path = tmp_path / "big.csv"
        np.savetxt(path, np.column_stack([X, y]), delimiter=",", fmt="%.17g")
        cap_rows = 2048
        source = CsvSource(str(path), SCHEMA)
        max_seen = 0
        for chunk in source.read_chunks(cap_rows):
            max_seen = max(max_seen, chunk.num_rows())
        assert max_seen <= cap_rows
        in_mem = make_estimator(iters=3).fit(source.read())
        streamed = make_estimator(iters=3).fit(
            ChunkedTable(source, chunk_rows=cap_rows)
        )
        np.testing.assert_array_equal(
            streamed.coefficients(), in_mem.coefficients()
        )

    def test_sharded_source_matches_single_file(self, tmp_path):
        table, X, y = dense_data(4000, seed=11)
        data = np.column_stack([X, y])
        whole = tmp_path / "whole.csv"
        np.savetxt(whole, data, delimiter=",", fmt="%.17g")
        for i, lo in enumerate(range(0, 4000, 1000)):
            np.savetxt(
                tmp_path / f"part-{i:05d}.csv", data[lo : lo + 1000],
                delimiter=",", fmt="%.17g",
            )
        sharded = ShardedSource.glob(
            str(tmp_path / "part-*.csv"), lambda p: CsvSource(p, SCHEMA)
        )
        m1 = make_estimator(iters=3).fit(
            ChunkedTable(CsvSource(str(whole), SCHEMA), chunk_rows=640)
        )
        m2 = make_estimator(iters=3).fit(ChunkedTable(sharded, chunk_rows=640))
        np.testing.assert_array_equal(m2.coefficients(), m1.coefficients())

    def test_tol_early_stop_parity(self):
        table, _, _ = dense_data(2000)
        est = lambda: make_estimator(iters=200).set_tol(1e-3)  # noqa: E731
        in_mem = est().fit(table)
        streamed = est().fit(
            ChunkedTable(CollectionSource(table.to_rows(), SCHEMA), 512)
        )
        assert streamed.train_epochs_ == in_mem.train_epochs_
        np.testing.assert_array_equal(
            streamed.coefficients(), in_mem.coefficients()
        )

    def test_checkpoint_resume_matches_uninterrupted(self, tmp_path):
        table, _, _ = dense_data(2000)
        rows = table.to_rows()
        full = make_estimator(iters=6).fit(
            ChunkedTable(CollectionSource(rows, SCHEMA), 512)
        )
        ckpt = str(tmp_path / "ck")

        def est(iters):
            return (
                make_estimator(iters=iters)
                .set_checkpoint_dir(ckpt)
                .set_checkpoint_interval(2)
            )

        est(3).fit(ChunkedTable(CollectionSource(rows, SCHEMA), 512))
        resumed = est(6).fit(ChunkedTable(CollectionSource(rows, SCHEMA), 512))
        assert resumed.train_epochs_ == 6
        np.testing.assert_allclose(
            resumed.coefficients(), full.coefficients(), rtol=1e-6, atol=1e-9
        )

    def test_spill_bit_matches_direct_stream(self, tmp_path):
        """spill=True (binary blocks re-streamed from disk after epoch 1)
        replays the identical schedule: bit-equal to the direct stream."""
        table, X, y = dense_data(6000, seed=13)
        path = tmp_path / "d.csv"
        np.savetxt(path, np.column_stack([X, y]), delimiter=",", fmt="%.17g")
        source = CsvSource(str(path), SCHEMA)
        direct = make_estimator(iters=4).fit(ChunkedTable(source, 1500))
        spilled = make_estimator(iters=4).fit(
            ChunkedTable(source, 1500, spill=True)
        )
        np.testing.assert_array_equal(
            spilled.coefficients(), direct.coefficients()
        )

    def test_requires_explicit_batch_size(self):
        table, _, _ = dense_data(100)
        chunked = ChunkedTable(CollectionSource(table.to_rows(), SCHEMA), 64)
        with pytest.raises(ValueError, match="globalBatchSize"):
            make_estimator(batch=0).fit(chunked)


def sparse_data(n=3000, dim=500, nnz=8, seed=5):
    rng = np.random.RandomState(seed)
    true_w = rng.randn(dim) * (rng.rand(dim) < 0.2)
    vectors, labels = [], []
    for _ in range(n):
        idx = np.sort(rng.choice(dim, size=nnz, replace=False))
        vals = rng.randn(nnz)
        score = float(vals @ true_w[idx])
        labels.append(1.0 if score + 0.3 * rng.randn() > 0 else 0.0)
        vectors.append(SparseVector(dim, idx, vals))
    schema = Schema.of(("features", DataTypes.SPARSE_VECTOR), ("label", "double"))
    table = Table.from_columns(schema, {"features": vectors, "label": labels})
    return table, vectors, np.asarray(labels), dim


def _per_object(table):
    """``table`` with its CSR column as one ``SparseVector`` a row.  A
    per-object column packs segment-CSR, byte for byte what the CSR column
    packed before PR 28 and what the out-of-core chunk program still reads,
    where the CSR column itself may now take the row-regular step."""
    return Table.from_rows(table.to_rows(), table.schema)


def _packed_row_regular(table):
    """Whether the fit of ``table`` left a row-regular stack in its pack
    cache."""
    from flink_ml_tpu.lib.common import EllMinibatchStack

    return any(isinstance(stack, EllMinibatchStack)
               for stack in table._pack_cache.values())


class TestSparseOutOfCore:
    def make_est(self, dim, iters=4):
        return (
            LogisticRegression()
            .set_vector_col("features")
            .set_label_col("label")
            .set_prediction_col("pred")
            .set_num_features(dim)
            .set_learning_rate(0.1)
            .set_global_batch_size(256)
            .set_max_iter(iters)
        )

    def test_bit_matches_in_memory_sparse_fit(self):
        table, vectors, labels, dim = sparse_data()
        in_mem = self.make_est(dim).fit(table)
        chunked = ChunkedTable(
            CollectionSource(table.to_rows(), table.schema), chunk_rows=700
        )
        streamed = self.make_est(dim).fit(chunked)
        np.testing.assert_array_equal(
            streamed.coefficients(), in_mem.coefficients()
        )
        assert streamed.intercept() == in_mem.intercept()

    def test_libsvm_stream_matches_materialized(self, tmp_path):
        table, vectors, labels, dim = sparse_data(n=1500)
        path = tmp_path / "data.svm"
        with open(path, "w") as f:
            for label, v in zip(labels, vectors):
                feats = " ".join(
                    f"{int(i) + 1}:{val:.17g}" for i, val in zip(v.indices, v.vals)
                )
                f.write(f"{label:g} {feats}\n")
        source = LibSvmSource(str(path), n_features=dim)
        streamed = self.make_est(dim, iters=3).fit(
            ChunkedTable(source, chunk_rows=400)
        )
        # the stream's step is segment-CSR; the in-memory fit of the same
        # rows on that step (a per-object column packs it: _per_object)
        # returns the same bytes
        in_mem = self.make_est(dim, iters=3).fit(_per_object(source.read()))
        np.testing.assert_array_equal(
            streamed.coefficients(), in_mem.coefficients()
        )
        assert streamed.intercept() == in_mem.intercept()

    def test_libsvm_stream_matches_the_row_regular_fit_to_rounding(
            self, tmp_path):
        """The materialized CSR column itself takes the row-regular step
        (PR 28) where the stream keeps segment-CSR: the same update, a
        row's products summed in another order, so float32 rounding and not
        the bytes."""
        table, vectors, labels, dim = sparse_data(n=1500)
        path = tmp_path / "data.svm"
        with open(path, "w") as f:
            for label, v in zip(labels, vectors):
                feats = " ".join(
                    f"{int(i) + 1}:{val:.17g}" for i, val in zip(v.indices, v.vals)
                )
                f.write(f"{label:g} {feats}\n")
        source = LibSvmSource(str(path), n_features=dim)
        materialized = source.read()
        in_mem = self.make_est(dim, iters=3).fit(materialized)
        assert _packed_row_regular(materialized)
        streamed = self.make_est(dim, iters=3).fit(
            ChunkedTable(source, chunk_rows=400)
        )
        np.testing.assert_allclose(
            streamed.coefficients(), in_mem.coefficients(),
            rtol=2e-5, atol=1e-7,
        )
        np.testing.assert_allclose(
            streamed.intercept(), in_mem.intercept(), rtol=2e-5, atol=1e-7
        )

    def test_chunked_libsvm_requires_dim(self, tmp_path):
        path = tmp_path / "d.svm"
        path.write_text("1 1:0.5 3:1.0\n0 2:0.25\n")
        source = LibSvmSource(str(path))
        with pytest.raises(ValueError, match="n_features"):
            next(source.read_chunks(10))

    def test_sparse_spill_bit_matches_direct_stream(self, tmp_path):
        """The two-leaf (ints, floats) sparse batch survives the npz
        round-trip bit-exactly."""
        table, vectors, labels, dim = sparse_data(n=1200)
        path = tmp_path / "s.svm"
        with open(path, "w") as f:
            for label, v in zip(labels, vectors):
                feats = " ".join(
                    f"{int(i) + 1}:{val:.17g}" for i, val in zip(v.indices, v.vals)
                )
                f.write(f"{label:g} {feats}\n")
        source = LibSvmSource(str(path), n_features=dim)
        direct = self.make_est(dim, iters=3).fit(ChunkedTable(source, 500))
        spilled = self.make_est(dim, iters=3).fit(
            ChunkedTable(source, 500, spill=True)
        )
        np.testing.assert_array_equal(
            spilled.coefficients(), direct.coefficients()
        )

    def test_overflowing_nnz_budget_fails_loudly(self):
        table, vectors, labels, dim = sparse_data(n=600, nnz=4)
        # densify the tail: the estimate from the stream head undershoots
        rng = np.random.RandomState(0)
        rows = table.to_rows()
        dense_tail = []
        for _, label in rows[-100:]:
            idx = np.sort(rng.choice(dim, size=400, replace=False))
            dense_tail.append((SparseVector(dim, idx, rng.randn(400)), label))
        source = CollectionSource(rows[:-100] + dense_tail, table.schema)
        with pytest.raises(ValueError, match="nnz_pad"):
            self.make_est(dim, iters=2).fit(ChunkedTable(source, chunk_rows=200))


class TestKMeansOutOfCore:
    def make_est(self, iters=8, tol=0.0):
        from flink_ml_tpu.lib import KMeans

        return (
            KMeans().set_feature_cols(["f0", "f1", "f2"])
            .set_prediction_col("cluster").set_k(5)
            .set_max_iter(iters).set_tol(tol).set_seed(7)
        )

    def test_matches_in_memory_fit(self):
        """Same init (stream-head sample == full sample under the cap), same
        Lloyd schedule; centroids agree to accumulation-order tolerance."""
        table, _, _ = dense_data(4000, seed=21)
        in_mem = self.make_est().fit(table)
        chunked = ChunkedTable(
            CollectionSource(table.to_rows(), SCHEMA), chunk_rows=900
        )
        streamed = self.make_est().fit(chunked)
        assert streamed.train_epochs_ == in_mem.train_epochs_
        np.testing.assert_allclose(
            np.sort(streamed.centroids(), axis=0),
            np.sort(in_mem.centroids(), axis=0),
            rtol=1e-4, atol=1e-5,
        )
        np.testing.assert_allclose(
            streamed.train_cost_, in_mem.train_cost_, rtol=1e-4
        )

    def test_streams_larger_than_cap_csv(self, tmp_path):
        table, X, y = dense_data(15000, seed=22)
        path = tmp_path / "km.csv"
        np.savetxt(path, np.column_stack([X, y]), delimiter=",", fmt="%.17g")
        source = CsvSource(str(path), SCHEMA)
        in_mem = self.make_est(iters=5).fit(source.read())
        streamed = self.make_est(iters=5).fit(
            ChunkedTable(source, chunk_rows=2048, spill=True)
        )
        np.testing.assert_allclose(
            np.sort(streamed.centroids(), axis=0),
            np.sort(in_mem.centroids(), axis=0),
            rtol=1e-4, atol=1e-5,
        )

    def test_checkpoint_resume(self, tmp_path):
        table, _, _ = dense_data(3000, seed=23)
        rows = table.to_rows()
        full = self.make_est(iters=6).fit(
            ChunkedTable(CollectionSource(rows, SCHEMA), 800)
        )
        ckpt = str(tmp_path / "ck")

        def est(iters):
            return (
                self.make_est(iters=iters)
                .set_checkpoint_dir(ckpt)
                .set_checkpoint_interval(2)
            )

        est(3).fit(ChunkedTable(CollectionSource(rows, SCHEMA), 800))
        resumed = est(6).fit(ChunkedTable(CollectionSource(rows, SCHEMA), 800))
        assert resumed.train_epochs_ == 6
        np.testing.assert_allclose(
            resumed.centroids(), full.centroids(), rtol=1e-5, atol=1e-6
        )

    def test_init_sample_is_uniform_over_grouped_stream(self):
        """Over-cap, cluster-grouped data: the reservoir init sample must
        cover the whole stream, not just its head."""
        from flink_ml_tpu.lib.out_of_core import reservoir_sample_rows

        rows = [(float(i), 0.0, 0.0, 0.0) for i in range(10000)]
        table_src = CollectionSource(rows, SCHEMA)
        chunked = ChunkedTable(table_src, chunk_rows=1000)
        rng = np.random.RandomState(0)
        sample, seen = reservoir_sample_rows(
            chunked.chunks(),
            lambda t: (t.numeric_matrix(["f0"]),),
            cap=500, rng=rng,
        )
        assert seen == 10000 and sample.shape == (500, 1)
        # head-biased sampling would put everything under 500; uniform
        # sampling spreads across [0, 10000)
        assert np.median(sample) > 3000
        assert sample.max() > 9000


def mesh_2d(data, model):
    """Context manager swapping the default environment onto a
    (data x model) mesh for the duration."""
    import contextlib

    import jax

    from flink_ml_tpu.parallel.mesh import create_mesh
    from flink_ml_tpu.utils.environment import MLEnvironmentFactory

    @contextlib.contextmanager
    def ctx():
        env = MLEnvironmentFactory.get_default()
        old = env.get_mesh()
        env.set_mesh(
            create_mesh({"data": data, "model": model},
                        jax.devices()[: data * model])
        )
        try:
            yield
        finally:
            env.set_mesh(old)

    return ctx()


class TestOutOfCore2D:
    """The north-star configuration: rows stream over the 'data' axis while
    the sparse weight vector shards over 'model' (Criteo-scale data AND a
    wider-than-one-chip model at once)."""

    def _mesh(self, data, model):
        return mesh_2d(data, model)

    def test_sparse_2d_stream_matches_in_memory_2d(self):
        table, vectors, labels, dim = sparse_data(n=2000, dim=501)

        def est():
            return (
                LogisticRegression().set_vector_col("features")
                .set_label_col("label").set_prediction_col("p")
                .set_num_features(dim).set_learning_rate(0.1)
                .set_global_batch_size(256).set_max_iter(4)
            )

        with self._mesh(4, 2):
            in_mem = est().fit(table)
            streamed = est().fit(
                ChunkedTable(CollectionSource(table.to_rows(), table.schema), 700)
            )
        assert streamed.coefficients().shape == (dim,)
        np.testing.assert_array_equal(
            streamed.coefficients(), in_mem.coefficients()
        )
        assert streamed.intercept() == in_mem.intercept()

    def test_sparse_2d_matches_1d_result(self):
        table, vectors, labels, dim = sparse_data(n=1600, dim=500)

        def est():
            return (
                LogisticRegression().set_vector_col("features")
                .set_label_col("label").set_prediction_col("p")
                .set_num_features(dim).set_learning_rate(0.1)
                .set_global_batch_size(256).set_max_iter(3)
            )

        chunked = lambda: ChunkedTable(  # noqa: E731
            CollectionSource(table.to_rows(), table.schema), 600
        )
        with self._mesh(4, 2):
            w2 = est().fit(chunked()).coefficients()
        with self._mesh(8, 1):
            w1 = est().fit(chunked()).coefficients()
        np.testing.assert_allclose(w2, w1, rtol=1e-5, atol=1e-7)

    def test_dense_stream_on_2d_mesh(self):
        table, _, _ = dense_data(3000)
        with self._mesh(4, 2):
            streamed = make_estimator(iters=3).fit(
                ChunkedTable(CollectionSource(table.to_rows(), SCHEMA), 800)
            )
            in_mem = make_estimator(iters=3).fit(table)
        np.testing.assert_array_equal(
            streamed.coefficients(), in_mem.coefficients()
        )

    def test_kmeans_stream_on_2d_mesh(self):
        table, _, _ = dense_data(2400, seed=31)
        from flink_ml_tpu.lib import KMeans

        def est():
            return (
                KMeans().set_feature_cols(["f0", "f1", "f2"])
                .set_prediction_col("c").set_k(4).set_max_iter(4).set_seed(2)
            )

        chunked = lambda: ChunkedTable(  # noqa: E731
            CollectionSource(table.to_rows(), SCHEMA), 600
        )
        with self._mesh(4, 2):
            c2 = est().fit(chunked()).centroids()
        with self._mesh(8, 1):
            c1 = est().fit(chunked()).centroids()
        np.testing.assert_allclose(
            np.sort(c2, axis=0), np.sort(c1, axis=0), rtol=1e-4, atol=1e-5
        )


class TestPipelineIntegration:
    def test_single_stage_pipeline_accepts_chunked_table(self):
        """Pipeline.fit passes a ChunkedTable straight to the estimator
        (the reference's pipeline over a partitioned source)."""
        from flink_ml_tpu.api.pipeline import Pipeline

        table, _, _ = dense_data(2000)
        chunked = ChunkedTable(CollectionSource(table.to_rows(), SCHEMA), 512)
        pipeline_model = Pipeline([make_estimator(iters=3)]).fit(chunked)
        direct = make_estimator(iters=3).fit(
            ChunkedTable(CollectionSource(table.to_rows(), SCHEMA), 512)
        )
        (out,) = pipeline_model.transform(table)
        direct_out = direct.transform(table)[0]
        np.testing.assert_array_equal(
            np.asarray(out.col("pred")), np.asarray(direct_out.col("pred"))
        )

    def test_dense_vector_col_stream_peeks_dim(self):
        """vectorCol dense streaming with no numFeatures pins the width by
        peeking one chunk, then bit-matches the in-memory fit."""
        from flink_ml_tpu.ops.vector import DenseVector

        rng = np.random.RandomState(17)
        X = rng.randn(3000, 4)
        y = X @ np.array([1.0, -1.0, 2.0, 0.5]) + 0.2
        schema = Schema.of(("features", DataTypes.DENSE_VECTOR), ("label", "double"))
        rows = [(DenseVector(r), float(v)) for r, v in zip(X, y)]
        table = Table.from_rows(rows, schema)

        def est():
            return (
                LinearRegression().set_vector_col("features")
                .set_label_col("label").set_prediction_col("p")
                .set_learning_rate(0.05).set_global_batch_size(256)
                .set_max_iter(3)
            )

        in_mem = est().fit(table)
        streamed = est().fit(
            ChunkedTable(CollectionSource(rows, schema), chunk_rows=700)
        )
        np.testing.assert_array_equal(
            streamed.coefficients(), in_mem.coefficients()
        )


class TestStreamedInference:
    def test_transform_chunks_matches_whole_transform(self, tmp_path):
        """Scoring a file chunk by chunk (model resident on device across
        chunks) equals scoring the materialized table, and the CSV sink
        round-trips the streamed output."""
        from flink_ml_tpu.utils.persistence import write_csv_chunks

        table, X, y = dense_data(6000, seed=41)
        path = tmp_path / "in.csv"
        np.savetxt(path, np.column_stack([X, y]), delimiter=",", fmt="%.17g")
        source = CsvSource(str(path), SCHEMA)
        model = make_estimator(iters=3).fit(ChunkedTable(source, 1500))

        whole = model.transform(source.read())[0]
        streamed = Table.concat(
            list(model.transform_chunks(ChunkedTable(source, 1100)))
        )
        np.testing.assert_array_equal(
            np.asarray(streamed.col("pred")), np.asarray(whole.col("pred"))
        )

        out_path = tmp_path / "scored.csv"
        n = write_csv_chunks(
            model.transform_chunks(ChunkedTable(source, 1100)), str(out_path)
        )
        assert n == 6000
        out_schema = Schema.of(
            *[(name, "double") for name in streamed.schema.field_names]
        )
        read_back = CsvSource(str(out_path), out_schema, skip_header=True).read()
        np.testing.assert_allclose(
            np.asarray(read_back.col("pred")),
            np.asarray(whole.col("pred")), rtol=1e-15,
        )

    def test_pipeline_model_streams_inference_too(self, tmp_path):
        from flink_ml_tpu.api.pipeline import Pipeline

        table, X, y = dense_data(3000, seed=43)
        path = tmp_path / "p.csv"
        np.savetxt(path, np.column_stack([X, y]), delimiter=",", fmt="%.17g")
        source = CsvSource(str(path), SCHEMA)
        pm = Pipeline([make_estimator(iters=3)]).fit(ChunkedTable(source, 800))
        whole = pm.transform(source.read())[0]
        streamed = Table.concat(list(pm.transform_chunks(ChunkedTable(source, 700))))
        np.testing.assert_array_equal(
            np.asarray(streamed.col("pred")), np.asarray(whole.col("pred"))
        )


class TestFeatureInteractions:
    """Combinations of out-of-core features that could interact badly:
    spill x checkpoint x kill, sharded libsvm files, 2-D x spill."""

    def test_spill_plus_checkpoint_resume(self, tmp_path):
        _, X, y = dense_data(4000, seed=51)
        path = tmp_path / "d.csv"
        np.savetxt(path, np.column_stack([X, y]), delimiter=",", fmt="%.17g")
        source = CsvSource(str(path), SCHEMA)
        full = make_estimator(iters=6).fit(
            ChunkedTable(source, 1000, spill=True)
        )
        ckpt = str(tmp_path / "ck")

        def est(iters):
            return (
                make_estimator(iters=iters)
                .set_checkpoint_dir(ckpt).set_checkpoint_interval(2)
            )

        est(3).fit(ChunkedTable(source, 1000, spill=True))
        resumed = est(6).fit(ChunkedTable(source, 1000, spill=True))
        assert resumed.train_epochs_ == 6
        np.testing.assert_allclose(
            resumed.coefficients(), full.coefficients(), rtol=1e-6, atol=1e-9
        )

    def test_sharded_libsvm_files_stream(self, tmp_path):
        table, vectors, labels, dim = sparse_data(n=1800)
        per = 600
        for s in range(3):
            with open(tmp_path / f"part-{s}.svm", "w") as f:
                for i in range(s * per, (s + 1) * per):
                    v = vectors[i]
                    feats = " ".join(
                        f"{int(j) + 1}:{val:.17g}"
                        for j, val in zip(v.indices, v.vals)
                    )
                    f.write(f"{labels[i]:g} {feats}\n")
        sharded = ShardedSource.glob(
            str(tmp_path / "part-*.svm"),
            lambda p: LibSvmSource(p, n_features=dim),
        )
        est = (
            LogisticRegression().set_vector_col("features")
            .set_label_col("label").set_prediction_col("p")
            .set_num_features(dim).set_learning_rate(0.1)
            .set_global_batch_size(256).set_max_iter(3)
        )
        streamed = est.fit(ChunkedTable(sharded, chunk_rows=500))
        in_mem = (
            LogisticRegression().set_vector_col("features")
            .set_label_col("label").set_prediction_col("p")
            .set_num_features(dim).set_learning_rate(0.1)
            .set_global_batch_size(256).set_max_iter(3)
            .fit(_per_object(sharded.read()))  # the stream's step: the bytes
        )
        np.testing.assert_array_equal(
            streamed.coefficients(), in_mem.coefficients()
        )
        assert streamed.intercept() == in_mem.intercept()

    def test_2d_mesh_with_spill(self, tmp_path):
        table, vectors, labels, dim = sparse_data(n=1200, dim=500)
        path = tmp_path / "s.svm"
        with open(path, "w") as f:
            for label, v in zip(labels, vectors):
                feats = " ".join(
                    f"{int(i) + 1}:{val:.17g}"
                    for i, val in zip(v.indices, v.vals)
                )
                f.write(f"{label:g} {feats}\n")
        source = LibSvmSource(str(path), n_features=dim)

        def est():
            return (
                LogisticRegression().set_vector_col("features")
                .set_label_col("label").set_prediction_col("p")
                .set_num_features(dim).set_learning_rate(0.1)
                .set_global_batch_size(256).set_max_iter(4)
            )

        with mesh_2d(4, 2):
            direct = est().fit(ChunkedTable(source, 400))
            spilled = est().fit(ChunkedTable(source, 400, spill=True))
        np.testing.assert_array_equal(
            spilled.coefficients(), direct.coefficients()
        )


class _ParseCountingSource:
    """Counts full chunk-stream iterations of the wrapped source — each one
    is a text parse the chunk cache exists to eliminate."""

    def __init__(self, inner):
        self.inner = inner
        self.chunk_reads = 0

    def schema(self):
        return self.inner.schema()

    def read_chunks(self, max_rows):
        self.chunk_reads += 1
        return self.inner.read_chunks(max_rows)

    def read(self):
        return self.inner.read()


class TestChunkSpillCache:
    """VERDICT r4 #3: fold the layout pre-pass into the spill pass — fits
    with a full pre-pass read the text source exactly once."""

    def _libsvm(self, tmp_path, n=1200, dim=400, nnz=6):
        table, vectors, labels, dim = sparse_data(n=n, dim=dim, nnz=nnz)
        path = tmp_path / "c.svm"
        with open(path, "w") as f:
            for label, v in zip(labels, vectors):
                feats = " ".join(
                    f"{int(i) + 1}:{val:.17g}"
                    for i, val in zip(v.indices, v.vals)
                )
                f.write(f"{label:g} {feats}\n")
        return LibSvmSource(str(path), n_features=dim), dim

    def test_replay_matches_recorded_chunks(self, tmp_path):
        from flink_ml_tpu.table.sources import chunk_cache

        source, dim = self._libsvm(tmp_path)
        counting = _ParseCountingSource(source)
        chunked = ChunkedTable(counting, chunk_rows=300, spill=True)
        with chunk_cache(chunked) as cached:
            first = [
                (np.asarray(t.col("label")).copy(), t.col("features"))
                for t in cached.chunks()
            ]
            second = [
                (np.asarray(t.col("label")), t.col("features"))
                for t in cached.chunks()
            ]
        assert counting.chunk_reads == 1  # second pass replayed binary
        assert len(first) == len(second)
        for (y1, v1), (y2, v2) in zip(first, second):
            np.testing.assert_array_equal(y1, y2)
            np.testing.assert_array_equal(
                np.asarray(v1.indices), np.asarray(v2.indices)
            )
            np.testing.assert_array_equal(
                np.asarray(v1.values), np.asarray(v2.values)
            )
            np.testing.assert_array_equal(
                np.asarray(v1.indptr), np.asarray(v2.indptr)
            )

    def test_partial_pass_leaves_cache_incomplete(self, tmp_path):
        from flink_ml_tpu.table.sources import chunk_cache

        source, dim = self._libsvm(tmp_path)
        counting = _ParseCountingSource(source)
        chunked = ChunkedTable(counting, chunk_rows=300, spill=True)
        with chunk_cache(chunked) as cached:
            it = cached.chunks()
            next(it)  # schema/width peek shape: consume one chunk, stop
            close = getattr(it, "close", None)
            if close:
                close()
            full = list(cached.chunks())  # re-records from text
            again = list(cached.chunks())  # replays
        assert counting.chunk_reads == 2
        assert len(full) == len(again)

    def test_uncacheable_column_falls_back_to_reparsing(self, tmp_path):
        from flink_ml_tpu.table.sources import chunk_cache

        table, vectors, labels, dim = sparse_data(n=400)
        # CollectionSource chunks carry per-row SparseVector objects (an
        # object column) -> uncacheable; behavior must be unchanged
        source = _ParseCountingSource(
            CollectionSource(table.to_rows(), table.schema)
        )
        chunked = ChunkedTable(source, chunk_rows=150, spill=True)
        with chunk_cache(chunked) as cached:
            a = sum(t.num_rows() for t in cached.chunks())
            b = sum(t.num_rows() for t in cached.chunks())
        assert a == b == 400
        assert source.chunk_reads == 2  # no caching: both passes parse

    def test_kmeans_ooc_fit_parses_text_once(self, tmp_path):
        rng = np.random.RandomState(0)
        X = rng.randn(900, 8)
        path = tmp_path / "k.csv"
        np.savetxt(path, X, delimiter=",")
        from flink_ml_tpu.lib import KMeans
        from flink_ml_tpu.table.sources import CsvSource

        schema = Schema.of(*[(f"f{i}", "double") for i in range(8)])
        source = _ParseCountingSource(CsvSource(str(path), schema))
        est = (
            KMeans().set_feature_cols([f"f{i}" for i in range(8)])
            .set_prediction_col("c").set_k(5).set_max_iter(3).set_seed(1)
        )
        est.fit(ChunkedTable(source, 250, spill=True))
        # init reservoir pass records; first Lloyd epoch replays binary;
        # steady epochs read the packed spill
        assert source.chunk_reads == 1


class TestChunkSpillCacheInterleaving:
    """ADVICE r5 low: an abandoned partial recording generator resumed
    after (or interleaved with) a second chunks() pass must never splice
    its descriptors into the other pass's replay sequence — descriptors
    publish atomically on exhaustion."""

    def _cached(self, tmp_path, n=900):
        from flink_ml_tpu.table.sources import ChunkSpillCache

        table, vectors, labels, dim = sparse_data(n=n, dim=120, nnz=4)
        path = tmp_path / "i.svm"
        with open(path, "w") as f:
            for label, v in zip(labels, vectors):
                feats = " ".join(
                    f"{int(i) + 1}:{val:.17g}"
                    for i, val in zip(v.indices, v.vals)
                )
                f.write(f"{label:g} {feats}\n")
        source = _ParseCountingSource(LibSvmSource(str(path), n_features=dim))
        chunked = ChunkedTable(source, chunk_rows=300, spill=True)
        return ChunkSpillCache(chunked, str(tmp_path / "cache")), source

    def test_interleaved_passes_replay_coherently(self, tmp_path):
        cached, source = self._cached(tmp_path)
        it1 = cached.chunks()  # recording pass 1 ...
        first1 = next(it1)
        it2 = cached.chunks()  # ... interleaved with recording pass 2
        chunks2 = [np.asarray(t.col("label")).copy() for t in it2]
        rest1 = [np.asarray(t.col("label")).copy() for t in it1]
        assert len(chunks2) == 3
        assert 1 + len(rest1) == 3
        # both passes parsed text (neither replay); the cache holds ONE
        # coherent pass, never a splice of the two
        replay = [np.asarray(t.col("label")) for t in cached.chunks()]
        assert len(replay) == 3
        for got, want in zip(replay, chunks2):
            np.testing.assert_array_equal(got, want)
        assert source.chunk_reads == 2  # the replay pass read no text

    def test_abandoned_partial_pass_does_not_publish(self, tmp_path):
        cached, source = self._cached(tmp_path)
        it = cached.chunks()
        next(it)  # partial: one chunk consumed, generator dropped
        close = getattr(it, "close", None)
        if close:
            close()
        assert not cached._complete
        assert cached._chunks == []  # nothing published by the partial pass
        full = [np.asarray(t.col("label")).copy() for t in cached.chunks()]
        assert cached._complete
        replay = [np.asarray(t.col("label")) for t in cached.chunks()]
        for got, want in zip(replay, full):
            np.testing.assert_array_equal(got, want)
        assert source.chunk_reads == 2
