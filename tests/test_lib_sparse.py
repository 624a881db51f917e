"""Sparse (Criteo-shape) training path tests: the segment-CSR fused loop must
match the dense path on identical data, scale to wide feature spaces without
densifying, and score sparsely at transform time."""

import contextlib

import numpy as np
import pytest

from flink_ml_tpu.lib import LinearRegression, LogisticRegression
from flink_ml_tpu.lib.common import pack_sparse_minibatches
from flink_ml_tpu.ops.vector import DenseVector, SparseVector
from flink_ml_tpu.table.schema import DataTypes, Schema
from flink_ml_tpu.table.table import Table

SCHEMA = Schema.of(("features", DataTypes.SPARSE_VECTOR), ("label", "double"))


def sparse_data(n=300, dim=50, nnz=5, seed=0):
    rng = np.random.RandomState(seed)
    true_w = np.zeros(dim)
    k = min(10, dim)
    true_w[:k] = rng.randn(k) * 2
    vecs, ys = [], []
    for _ in range(n):
        idx = np.sort(rng.choice(dim, nnz, replace=False))
        val = rng.randn(nnz)
        x = np.zeros(dim)
        x[idx] = val
        vecs.append(SparseVector(dim, idx.astype(np.int64), val))
        ys.append(float((x @ true_w) > 0))
    return vecs, np.asarray(ys), true_w


def make_tables(vecs, ys, dim):
    sparse_t = Table.from_columns(SCHEMA, {"features": vecs, "label": ys})
    dense_schema = Schema.of(("features", DataTypes.DENSE_VECTOR), ("label", "double"))
    dense_vecs = [DenseVector(v.to_dense().values) for v in vecs]
    dense_t = Table.from_columns(dense_schema, {"features": dense_vecs, "label": ys})
    return sparse_t, dense_t


class TestPackSparse:
    def test_layout_roundtrip(self):
        vecs, ys, _ = sparse_data(n=10, dim=8, nnz=2)
        s = pack_sparse_minibatches(vecs, ys, n_dev=2, global_batch_size=4)
        assert s.mb == 2 and s.dim == 8
        # reconstruct row 0 from the packed layout
        idx = s.ints[0, 0]
        rid = s.ints[0, 1]
        vals = s.floats[0, : s.nnz_pad]
        x0 = np.zeros(8)
        mask = rid == 0
        np.add.at(x0, idx[mask], vals[mask])
        np.testing.assert_allclose(x0, vecs[0].to_dense().values, rtol=1e-6)
        # y/w segments
        np.testing.assert_allclose(s.floats[0, s.nnz_pad], ys[0])
        assert s.floats[0, s.nnz_pad + s.mb] == 1.0

    def test_padding_rows_have_zero_weight(self):
        vecs, ys, _ = sparse_data(n=5, dim=8, nnz=2)
        s = pack_sparse_minibatches(vecs, ys, n_dev=2, global_batch_size=4)
        w = s.floats[:, s.nnz_pad + s.mb :]
        assert w.sum() == 5.0  # exactly the real rows


class TestSparseLogisticRegression:
    def test_matches_dense_path(self):
        """Same data, same hyperparams: sparse and dense training agree."""
        vecs, ys, _ = sparse_data()
        sparse_t, dense_t = make_tables(vecs, ys, 50)

        def fit(t):
            return (
                LogisticRegression()
                .set_vector_col("features")
                .set_label_col("label")
                .set_prediction_col("pred")
                .set_learning_rate(0.5)
                .set_max_iter(60)
                .set_global_batch_size(64)
                .fit(t)
            )

        ms = fit(sparse_t)
        md = fit(dense_t)
        np.testing.assert_allclose(
            ms.coefficients(), md.coefficients(), rtol=1e-4, atol=1e-5
        )
        np.testing.assert_allclose(ms.intercept(), md.intercept(), atol=1e-5)

    def test_sparse_transform_scores(self):
        vecs, ys, _ = sparse_data(seed=2)
        sparse_t, dense_t = make_tables(vecs, ys, 50)
        model = (
            LogisticRegression()
            .set_vector_col("features")
            .set_label_col("label")
            .set_prediction_col("pred")
            .set_prediction_detail_col("prob")
            .set_learning_rate(0.5)
            .set_max_iter(80)
            .fit(sparse_t)
        )
        (out_s,) = model.transform(sparse_t)
        (out_d,) = model.transform(dense_t)
        np.testing.assert_allclose(
            out_s.col("prob"), out_d.col("prob"), rtol=1e-4, atol=1e-5
        )
        acc = np.mean(np.asarray(out_s.col("pred")) == ys)
        assert acc > 0.85

    def test_wide_feature_space(self):
        """numFeatures pins a dimension far wider than any observed index."""
        vecs, ys, _ = sparse_data(n=100, dim=40, nnz=3, seed=3)
        sparse_t, _ = make_tables(vecs, ys, 40)
        model = (
            LogisticRegression()
            .set_vector_col("features")
            .set_label_col("label")
            .set_prediction_col("pred")
            .set_num_features(1 << 16)
            .set_max_iter(30)
            .set_learning_rate(0.5)
            .fit(sparse_t)
        )
        assert model.coefficients().shape == (1 << 16,)

    def test_tol_early_stop_sparse(self):
        vecs, ys, _ = sparse_data(seed=4)
        sparse_t, _ = make_tables(vecs, ys, 50)
        model = (
            LogisticRegression()
            .set_vector_col("features")
            .set_label_col("label")
            .set_prediction_col("pred")
            .set_learning_rate(1.0)
            .set_max_iter(500)
            .set_tol(1e-4)
            .set_reg(0.1)
            .fit(sparse_t)
        )
        assert model.train_epochs_ < 500


def _power_law_data(n=400, dim=64, seed=3):
    """A skewed table: features [0, 8) stand in most rows (three of each
    row's five stored values), the rest are drawn from the long tail."""
    rng = np.random.RandomState(seed)
    true_w = rng.randn(dim)
    vecs, ys = [], []
    for _ in range(n):
        hot = rng.choice(8, 3, replace=False)
        cold = 8 + rng.choice(dim - 8, 2, replace=False)
        idx = np.sort(np.concatenate([hot, cold]))
        val = np.ones(idx.size)
        x = np.zeros(dim)
        x[idx] = val
        vecs.append(SparseVector(dim, idx.astype(np.int64), val))
        ys.append(float((x @ true_w) > 0))
    return vecs, np.asarray(ys)


def _skewed_est(dim=64, max_iter=20, batch=64, **kw):
    est = (
        LogisticRegression().set_vector_col("features")
        .set_label_col("label").set_prediction_col("pred")
        .set_num_features(dim).set_learning_rate(0.5)
        .set_max_iter(max_iter).set_global_batch_size(batch)
    )
    for k, v in kw.items():
        getattr(est, f"set_{k}")(v)
    return est


def _skewed_stream(vecs, ys, chunk_rows=64):
    from flink_ml_tpu.table.sources import ChunkedTable, CollectionSource

    return ChunkedTable(CollectionSource(list(zip(vecs, ys)), SCHEMA),
                        chunk_rows=chunk_rows)


@contextlib.contextmanager
def _on_mesh(axes):
    from flink_ml_tpu.parallel.mesh import create_mesh
    from flink_ml_tpu.utils.environment import MLEnvironmentFactory

    env = MLEnvironmentFactory.get_default()
    old = env.get_mesh()
    env.set_mesh(create_mesh(axes))
    try:
        yield
    finally:
        env.set_mesh(old)


def _skewed_checkpoint_resume(tmp_path):
    """In memory: a fit stopped at epoch 6 and resumed from its
    checkpoint lands on the uninterrupted fit."""
    vecs, ys = _power_law_data(n=200)
    t = Table.from_columns(SCHEMA, {"features": vecs, "label": ys})
    full = _skewed_est(max_iter=12).fit(t)
    ck = str(tmp_path / "ck")
    _skewed_est(max_iter=6, checkpoint_dir=ck,
                checkpoint_interval=3).fit(t)
    resumed = _skewed_est(max_iter=12, checkpoint_dir=ck,
                          checkpoint_interval=3).fit(t)
    assert resumed.train_epochs_ == full.train_epochs_ == 12
    np.testing.assert_allclose(resumed.coefficients(), full.coefficients(),
                               rtol=1e-6, atol=1e-7)


def _skewed_out_of_core_bit_matches_in_memory(tmp_path):
    """Streamed training equals the in-memory fit of the same object
    column bit for bit: both step segment-CSR on one schedule."""
    vecs, ys = _power_law_data(n=400)
    t = Table.from_columns(SCHEMA, {"features": vecs, "label": ys})
    m_mem = _skewed_est().fit(t)
    m_ooc = _skewed_est().fit(_skewed_stream(vecs, ys, chunk_rows=96))
    np.testing.assert_array_equal(m_ooc.coefficients(), m_mem.coefficients())
    assert m_ooc.intercept() == m_mem.intercept()


def _skewed_out_of_core_checkpoint_resume(tmp_path):
    """A streamed fit killed after epoch 6 and resumed lands on the
    uninterrupted result."""
    vecs, ys = _power_law_data(n=300)
    full = _skewed_est(max_iter=12).fit(_skewed_stream(vecs, ys))
    ck = str(tmp_path / "ck")
    _skewed_est(max_iter=6, checkpoint_dir=ck,
                checkpoint_interval=3).fit(_skewed_stream(vecs, ys))
    resumed = _skewed_est(max_iter=12, checkpoint_dir=ck,
                          checkpoint_interval=3).fit(_skewed_stream(vecs, ys))
    # a resumed engine re-places loaded host params, which can fuse
    # differently at the sub-ulp level (test_out_of_core.py's resume test)
    np.testing.assert_allclose(resumed.coefficients(), full.coefficients(),
                               rtol=1e-6, atol=1e-9)


def _skewed_out_of_core_resume_under_another_layout(tmp_path):
    """A streamed checkpoint holds the weights in the table's own ids: a
    resume under another width is refused, and one on a ('data', 'model')
    mesh continues where the 1-D fit stopped."""
    vecs, ys = _power_law_data(n=200)
    full = _skewed_est(max_iter=12).fit(_skewed_stream(vecs, ys))
    ck = str(tmp_path / "ck")
    _skewed_est(max_iter=6, checkpoint_dir=ck,
                checkpoint_interval=3).fit(_skewed_stream(vecs, ys))
    with pytest.raises(TypeError, match="incompatible shapes"):
        _skewed_est(dim=65, max_iter=12, checkpoint_dir=ck,
                    checkpoint_interval=3).fit(_skewed_stream(vecs, ys))
    with _on_mesh({"data": 4, "model": 2}):
        resumed = _skewed_est(max_iter=12, checkpoint_dir=ck,
                              checkpoint_interval=3).fit(
            _skewed_stream(vecs, ys))
    np.testing.assert_allclose(resumed.coefficients(), full.coefficients(),
                               rtol=1e-6, atol=1e-7)


def _skewed_out_of_core_2d_matches_1d(tmp_path):
    """Rows streamed over 'data' with the weights sharded over 'model'
    give the 1-D streamed fit to float32 rounding."""
    vecs, ys = _power_law_data(n=300)
    m1 = _skewed_est().fit(_skewed_stream(vecs, ys))
    with _on_mesh({"data": 4, "model": 2}):
        m2 = _skewed_est().fit(_skewed_stream(vecs, ys))
    np.testing.assert_allclose(m2.coefficients(), m1.coefficients(),
                               rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(m2.intercept(), m1.intercept(), atol=1e-6)


def _skewed_in_memory_2d_matches_1d(tmp_path):
    """The builders on a ('data', 'model') mesh give the 1-D fit of the
    same stack to float32 rounding: only the sums' grouping changes."""
    import jax
    import jax.numpy as jnp

    from flink_ml_tpu.lib.common import train_glm_sparse
    from flink_ml_tpu.parallel.mesh import create_mesh

    vecs, ys = _power_law_data()
    s = pack_sparse_minibatches(vecs, ys, n_dev=4, global_batch_size=64)

    def fit(mesh):
        start = (jnp.zeros((s.dim,), jnp.float32), jnp.zeros((), jnp.float32))
        return train_glm_sparse(start, s, "logistic", mesh,
                                learning_rate=0.5, max_iter=15)

    r1 = fit(create_mesh({"data": 4}, jax.devices()[:4]))
    r2 = fit(create_mesh({"data": 4, "model": 2}))
    np.testing.assert_allclose(r2.params[0], r1.params[0],
                               rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(r2.params[1], r1.params[1], atol=1e-6)
    np.testing.assert_allclose(r2.losses, r1.losses, rtol=1e-5)


def _skewed_2d_width_no_multiple_of_the_model_axis(tmp_path):
    """33 features over a model axis of 2: the placer pads the weight
    vector to 34, and the fit returns the table's 33 weights, as the 1-D
    fit does."""
    vecs, ys = _power_law_data(n=200, dim=33)
    t = Table.from_columns(SCHEMA, {"features": vecs, "label": ys})
    m1 = _skewed_est(dim=33, max_iter=8, batch=32).fit(t)
    with _on_mesh({"data": 4, "model": 2}):
        m2 = _skewed_est(dim=33, max_iter=8, batch=32).fit(t)
    assert m2.coefficients().shape == (33,)
    assert np.all(np.isfinite(m2.coefficients()))
    np.testing.assert_allclose(m2.coefficients(), m1.coefficients(),
                               rtol=1e-5, atol=1e-6)


def _skewed_estimator_on_a_data_model_mesh(tmp_path):
    """The estimator on a ('data', 'model') mesh predicts as the 1-D fit
    and agrees with its weights to float32 rounding."""
    vecs, ys = _power_law_data(n=300)
    t = Table.from_columns(SCHEMA, {"features": vecs, "label": ys})
    m1 = _skewed_est(max_iter=30, batch=32).fit(t)
    with _on_mesh({"data": 2, "model": 4}):
        m2 = _skewed_est(max_iter=30, batch=32).fit(t)
    (p1,) = m1.transform(t)
    (p2,) = m2.transform(t)
    np.testing.assert_array_equal(np.asarray(p2.col("pred")),
                                  np.asarray(p1.col("pred")))
    np.testing.assert_allclose(m2.coefficients(), m1.coefficients(),
                               rtol=1e-5, atol=1e-6)


def _skewed_out_of_core_text_fit_parses_once(tmp_path):
    """A spilled LIBSVM stream parses its text in ONE full pass (beside
    the pad estimate's two-chunk head): later epochs replay the packed
    spill, and the fit equals the unspilled one, which parses the text
    every epoch."""
    from flink_ml_tpu.table.sources import ChunkedTable, LibSvmSource

    vecs, ys = _power_law_data(n=1500)
    path = tmp_path / "skewed.svm"
    with open(path, "w") as f:
        for label, v in zip(ys, vecs):
            feats = " ".join(f"{int(i) + 1}:{val:.17g}"
                             for i, val in zip(v.indices, v.vals))
            f.write(f"{label:g} {feats}\n")

    class Counting:
        def __init__(self):
            self.inner = LibSvmSource(str(path), n_features=64)
            self.passes, self.chunks = 0, 0

        def schema(self):
            return self.inner.schema()

        def read_chunks(self, max_rows):
            self.passes += 1
            for chunk in self.inner.read_chunks(max_rows):
                self.chunks += 1
                yield chunk

        def read(self):
            return self.inner.read()

    spilled, unspilled = Counting(), Counting()
    est = lambda: _skewed_est(max_iter=3, batch=256)  # noqa: E731
    m_spill = est().fit(ChunkedTable(spilled, 500, spill=True))
    m_text = est().fit(ChunkedTable(unspilled, 500))
    assert (spilled.passes, spilled.chunks) == (2, 2 + 3)
    assert (unspilled.passes, unspilled.chunks) == (1 + 3, 2 + 3 * 3)
    np.testing.assert_array_equal(m_spill.coefficients(),
                                  m_text.coefficients())


_SKEWED_CASES = {
    "checkpoint_resume": _skewed_checkpoint_resume,
    "out_of_core_bit_matches_in_memory":
        _skewed_out_of_core_bit_matches_in_memory,
    "out_of_core_checkpoint_resume": _skewed_out_of_core_checkpoint_resume,
    "out_of_core_resume_under_another_layout":
        _skewed_out_of_core_resume_under_another_layout,
    "out_of_core_2d_matches_1d": _skewed_out_of_core_2d_matches_1d,
    "in_memory_2d_matches_1d": _skewed_in_memory_2d_matches_1d,
    "2d_width_no_multiple_of_the_model_axis":
        _skewed_2d_width_no_multiple_of_the_model_axis,
    "estimator_on_a_data_model_mesh": _skewed_estimator_on_a_data_model_mesh,
    "out_of_core_text_fit_parses_once": _skewed_out_of_core_text_fit_parses_once,
}


@pytest.mark.parametrize("case", sorted(_SKEWED_CASES))
def test_the_plain_route_on_a_skewed_table(case, tmp_path):
    """The one sparse route on a power-law table, wherever a fit can run:
    checkpointed, streamed, on a ('data', 'model') mesh."""
    _SKEWED_CASES[case](tmp_path)


class TestLayoutFloors:
    def test_min_floors_are_schedule_neutral(self):
        """Packing with min_nnz_pad / min_steps floors (the multi-process
        agree_max repack) trains bit-identically to the unfloored pack —
        pad entries carry zero weight and extra steps carry zero rows."""
        import jax.numpy as jnp

        from flink_ml_tpu.lib.common import train_glm_sparse
        from flink_ml_tpu.parallel.mesh import default_mesh

        vecs, ys, _ = sparse_data(n=120, dim=40, nnz=4, seed=8)
        mesh = default_mesh()
        base = pack_sparse_minibatches(vecs, ys, n_dev=8, global_batch_size=32)
        floored = pack_sparse_minibatches(
            vecs, ys, n_dev=8, global_batch_size=32,
            min_nnz_pad=base.nnz_pad * 2, min_steps=base.steps + 3,
        )
        assert floored.nnz_pad == base.nnz_pad * 2
        assert floored.steps == base.steps + 3
        p0 = lambda: (  # noqa: E731
            jnp.zeros((40,), jnp.float32), jnp.zeros((), jnp.float32)
        )
        r1 = train_glm_sparse(p0(), base, "logistic", mesh,
                              learning_rate=0.5, max_iter=10)
        r2 = train_glm_sparse(p0(), floored, "logistic", mesh,
                              learning_rate=0.5, max_iter=10)
        np.testing.assert_array_equal(
            np.asarray(r1.params[0]), np.asarray(r2.params[0])
        )
        np.testing.assert_array_equal(
            np.asarray(r1.params[1]), np.asarray(r2.params[1])
        )

    def test_agree_max_single_process_identity(self):
        from flink_ml_tpu.parallel.mesh import agree_max

        assert agree_max(512, 7) == (512, 7)

    def test_layout_prescan_predicts_pack_exactly(self):
        """sparse_layout_floors must predict the pack's natural layout for
        both column forms — a divergence would hang multi-process runs
        (the estimator asserts this at fit time too)."""
        from flink_ml_tpu.lib.common import (
            sparse_layout_floors,
            sparse_row_counts,
        )
        from flink_ml_tpu.ops.batch import CsrRows

        for n, nnz, gbs in [(120, 4, 32), (37, 2, 0), (64, 7, 16)]:
            vecs, ys, _ = sparse_data(n=n, dim=40, nnz=nnz, seed=n)
            s = pack_sparse_minibatches(vecs, ys, n_dev=4,
                                        global_batch_size=gbs)
            counts = sparse_row_counts(vecs)
            assert sparse_layout_floors(counts, 4, gbs) == (s.nnz_pad, s.steps)
            # CSR column form: same counts, same prediction
            indptr = np.concatenate([[0], np.cumsum(counts)])
            csr = CsrRows(
                40, indptr,
                np.concatenate([v.indices for v in vecs]),
                np.concatenate([v.vals for v in vecs]),
            )
            np.testing.assert_array_equal(sparse_row_counts(csr), counts)


class TestSparseLinearRegression:
    def test_sparse_squared_loss_converges(self):
        rng = np.random.RandomState(5)
        dim = 30
        true_w = np.zeros(dim)
        true_w[:5] = [1.0, -2.0, 3.0, 0.5, -1.5]
        vecs, ys = [], []
        for _ in range(400):
            idx = np.sort(rng.choice(dim, 4, replace=False))
            val = rng.randn(4)
            x = np.zeros(dim)
            x[idx] = val
            vecs.append(SparseVector(dim, idx.astype(np.int64), val))
            ys.append(x @ true_w + 2.0)
        t = Table.from_columns(SCHEMA, {"features": vecs, "label": np.asarray(ys)})
        model = (
            LinearRegression()
            .set_vector_col("features")
            .set_label_col("label")
            .set_prediction_col("pred")
            .set_learning_rate(0.3)
            .set_max_iter(300)
            .fit(t)
        )
        np.testing.assert_allclose(model.coefficients()[:5], true_w[:5], atol=0.1)
        assert abs(model.intercept() - 2.0) < 0.1


class TestSparseValidation:
    def test_out_of_range_index_raises_in_training(self):
        vecs = [SparseVector(100, np.array([50]), np.array([1.0]))]
        t = Table.from_columns(SCHEMA, {"features": vecs, "label": [1.0]})
        with pytest.raises(ValueError, match="out of range"):
            (LogisticRegression().set_vector_col("features")
             .set_label_col("label").set_prediction_col("p")
             .set_num_features(10).set_max_iter(2).fit(t))

    def test_empty_sparse_vector_rows_train(self):
        """An all-zeros sparse row (even with unknown size) is legal."""
        vecs = [
            SparseVector(5, np.array([1]), np.array([2.0])),
            SparseVector(),  # unknown size, zero nnz
            SparseVector(5, np.array([3]), np.array([-1.0])),
        ]
        t = Table.from_columns(
            SCHEMA, {"features": vecs, "label": [1.0, 0.0, 0.0]}
        )
        model = (LogisticRegression().set_vector_col("features")
                 .set_label_col("label").set_prediction_col("p")
                 .set_max_iter(5).fit(t))
        assert model.coefficients().shape == (5,)

    def test_varied_batch_sizes_share_compiled_scorer(self):
        vecs, ys, _ = sparse_data(n=100, dim=20, nnz=3, seed=9)
        t, _ = make_tables(vecs, ys, 20)
        model = (LogisticRegression().set_vector_col("features")
                 .set_label_col("label").set_prediction_col("p")
                 .set_max_iter(10).fit(t))
        # different row counts must not blow up (and should reuse buckets)
        for n in (1, 7, 63, 100):
            (out,) = model.transform(t.slice_rows(0, n))
            assert out.num_rows() == n


class TestNativeMalformed:
    def test_trailing_colon_rejected(self, tmp_path):
        """Regression: 'idx:' at line end must not consume the next label."""
        from flink_ml_tpu import native
        if not native.available():
            pytest.skip("native library not built")
        p = tmp_path / "bad.svm"
        p.write_text("1 2:\n0 3:1.5\n")
        with pytest.raises(ValueError):
            native.read_libsvm(str(p), None, False)


def test_unsorted_csr_rows_pack_sorted():
    """CSR columns from file order may carry per-row ids out of order; the
    pack must restore the per-row ascending invariant (the per-object
    pack's rows ascend, and the CSR pack lays the same bytes)."""
    from flink_ml_tpu.lib.common import pack_sparse_minibatches
    from flink_ml_tpu.ops.batch import CsrRows

    indptr = np.array([0, 3, 5, 8], dtype=np.int64)
    indices = np.array([7, 3, 9, 4, 1, 0, 6, 2], dtype=np.int64)  # unsorted
    values = np.arange(8, dtype=np.float64) + 1.0
    rows = CsrRows(16, indptr, indices, values)
    y = np.array([1.0, 0.0, 1.0])
    s = pack_sparse_minibatches(rows, y, n_dev=1, global_batch_size=4)
    idx = s.ints[0, 0, :]
    rid = s.ints[0, 1, :]
    valid = rid < s.mb
    # per-row ascending after the pack
    for r in range(3):
        ids = idx[valid & (rid == r)]
        assert np.all(np.diff(ids) > 0), ids
    # entries conserved with their values
    got = sorted(zip(idx[valid].tolist(), s.floats[0, : s.nnz_pad][valid].tolist()))
    want = sorted(zip(indices.tolist(), values.tolist()))
    assert got == want


class TestCsrEmptyRowPack:
    """ADVICE r5 high (the tier-1 red test): CSR packing raised IndexError
    whenever the column carried empty trailing rows — interior indptr
    entries equal to nnz_total put nnz_total-1 into the length-(nnz_total-1)
    adjacent-pair mask.  Any libsvm file ending in a featureless row
    crashed the vectorized ingestion path."""

    def _pack_both(self, indptr, indices, values, dim):
        from flink_ml_tpu.lib.common import pack_sparse_minibatches
        from flink_ml_tpu.ops.batch import CsrRows

        indptr = np.asarray(indptr, dtype=np.int64)
        indices = np.asarray(indices, dtype=np.int64)
        values = np.asarray(values, dtype=np.float64)
        n = len(indptr) - 1
        y = np.arange(n, dtype=np.float64)
        csr_stack = pack_sparse_minibatches(
            CsrRows(dim, indptr, indices, values), y, 1, n, dim=dim
        )
        vecs = [
            SparseVector(dim, indices[indptr[i]:indptr[i + 1]],
                         values[indptr[i]:indptr[i + 1]])
            for i in range(n)
        ]
        row_stack = pack_sparse_minibatches(vecs, y, 1, n, dim=dim)
        return csr_stack, row_stack

    def test_trailing_empty_row(self):
        # the ADVICE repro: indptr=[0,2,3,3], fully sorted indices
        s_csr, s_row = self._pack_both(
            [0, 2, 3, 3], [1, 4, 2], [1.0, 2.0, 3.0], dim=8
        )
        np.testing.assert_array_equal(s_csr.ints, s_row.ints)
        np.testing.assert_array_equal(s_csr.floats, s_row.floats)
        assert s_csr.n_rows == 3

    def test_leading_and_interior_empty_rows(self):
        s_csr, s_row = self._pack_both(
            [0, 0, 2, 2, 3], [3, 5, 0], [1.0, 2.0, 3.0], dim=8
        )
        np.testing.assert_array_equal(s_csr.ints, s_row.ints)
        np.testing.assert_array_equal(s_csr.floats, s_row.floats)

    def test_trailing_empty_row_with_unsorted_indices(self):
        # the sort path must also survive empty-row indptr repeats
        s_csr, s_row = self._pack_both(
            [0, 2, 4, 4], [4, 1, 9, 2], [1.0, 2.0, 3.0, 4.0], dim=16
        )
        np.testing.assert_array_equal(s_csr.ints, s_row.ints)
        np.testing.assert_array_equal(s_csr.floats, s_row.floats)

    def test_trailing_empty_rows_train_end_to_end(self):
        from flink_ml_tpu.ops.batch import CsrRows

        rng = np.random.RandomState(3)
        n, dim, nnz = 60, 12, 3
        indptr = [0]
        idx_all, val_all = [], []
        for i in range(n):
            k = 0 if i in (0, n - 1, n - 2) else nnz  # empty head + tail
            idx = np.sort(rng.choice(dim, k, replace=False))
            idx_all.append(idx)
            val_all.append(rng.randn(k))
            indptr.append(indptr[-1] + k)
        rows = CsrRows(
            dim,
            np.asarray(indptr, dtype=np.int64),
            np.concatenate(idx_all).astype(np.int64),
            np.concatenate(val_all),
        )
        y = (rng.randn(n) > 0).astype(np.float64)
        t = Table.from_columns(SCHEMA, {"features": rows, "label": y})
        model = (LogisticRegression().set_vector_col("features")
                 .set_label_col("label").set_prediction_col("p")
                 .set_num_features(dim).set_max_iter(3).fit(t))
        assert model.train_epochs_ >= 1


# -- the row-regular (ELL) step layout (PR 28) ---------------------------------
#
# The plain route on one process and a 1-D mesh packs a CSR column row-regular
# where ``mb * width <= _ELL_MAX_SLOT_RATIO * nnz_pad`` (what the pack observes
# of the rows' widths) and segment-CSR where not; every other route reads
# segment-CSR, byte for byte what it read before.


def _csr_table_parts(counts, dim, seed, duplicate_in_row=None, sort=True):
    """A CSR column of ``len(counts)`` rows with the given stored-entry
    counts; ``duplicate_in_row`` stores that row's first id twice."""
    rng = np.random.RandomState(seed)
    parts = []
    for r, c in enumerate(counts):
        ids = rng.choice(dim, c, replace=False)
        if sort:
            ids = np.sort(ids)
        if duplicate_in_row == r and c >= 2:
            ids[1] = ids[0]
        parts.append(ids)
    indptr = np.concatenate([[0], np.cumsum(counts)]).astype(np.int64)
    indices = (np.concatenate(parts) if len(parts) else
               np.zeros(0)).astype(np.int32)
    values = rng.randn(len(indices)).astype(np.float32)
    y = (rng.rand(len(counts)) < 0.4).astype(np.float64)
    return indptr, indices, values, y


def _csr_rows(dim, indptr, indices, values):
    from flink_ml_tpu.ops.batch import CsrRows

    return CsrRows(dim, indptr, indices, values)


_ELL_STEP_TABLES = {
    # name: (counts, duplicate_in_row); 16 rows a global batch over 2 devices
    "uniform": (np.full(48, 5), None),
    "ragged": (np.random.RandomState(5).randint(3, 6, 48), None),
    "duplicate_id": (np.full(48, 5), 7),
    "empty_row": (np.where(np.arange(48) % 11 == 3, 0, 4), None),
    "padded_last_step": (np.random.RandomState(6).randint(2, 5, 41), None),
}


@pytest.mark.parametrize("with_intercept", [True, False],
                         ids=["intercept", "no_intercept"])
@pytest.mark.parametrize("kind", ["logistic", "squared"])
@pytest.mark.parametrize("name", sorted(_ELL_STEP_TABLES))
def test_the_row_regular_step_equals_the_segment_csr_step(
        name, kind, with_intercept):
    """One minibatch gradient, both layouts of the same rows, every step of
    the table: equal to float32 rounding (a row's products are summed in
    another order), pads and weight-0 rows adding nothing."""
    import jax.numpy as jnp

    from flink_ml_tpu.lib import common

    counts, dup = _ELL_STEP_TABLES[name]
    dim = 40
    indptr, indices, values, y = _csr_table_parts(counts, dim, 17, dup)
    rows = _csr_rows(dim, indptr, indices, values)
    csr = pack_sparse_minibatches(rows, y, 2, 16, dim=dim)
    ell = pack_sparse_minibatches(rows, y, 2, 16, dim=dim, row_regular=True)
    assert isinstance(csr, common.SparseMinibatchStack)
    assert isinstance(ell, common.EllMinibatchStack)
    assert ell.width == counts.max() and ell.mb == csr.mb == 8
    assert ell.ints.shape == (len(csr.ints), ell.width, 8)
    assert ell.floats.shape == (len(csr.ints), ell.width + 2, 8)
    assert ell.n_entries == csr.n_entries == counts.sum()
    # the weights row marks exactly the table's rows
    assert ell.floats[:, ell.width + 1].sum() == len(counts)
    rng = np.random.RandomState(3)
    params = (jnp.asarray(rng.randn(dim), jnp.float32),
              jnp.asarray(0.3, jnp.float32))
    step_csr = common.make_sparse_mb_grad_step(
        kind, csr.mb, csr.nnz_pad, dim, with_intercept)
    step_ell = common.make_ell_mb_grad_step(
        kind, ell.mb, ell.width, dim, with_intercept)
    for g in range(len(csr.ints)):
        (gw_c, gb_c), loss_c, w_c = step_csr(
            params, (jnp.asarray(csr.ints[g]), jnp.asarray(csr.floats[g])))
        (gw_e, gb_e), loss_e, w_e = step_ell(
            params, (jnp.asarray(ell.ints[g]), jnp.asarray(ell.floats[g])))
        assert gw_e.dtype == gw_c.dtype == jnp.float32
        np.testing.assert_allclose(gw_e, gw_c, rtol=2e-5, atol=2e-6)
        np.testing.assert_allclose(gb_e, gb_c, rtol=2e-5, atol=2e-6)
        np.testing.assert_allclose(loss_e, loss_c, rtol=2e-5, atol=2e-6)
        assert float(w_e) == float(w_c)
        if not with_intercept:
            assert float(gb_e) == 0.0


def _sparse_est(dim, batch, epochs=2, lr=0.5):
    return (LogisticRegression().set_vector_col("features")
            .set_label_col("label").set_prediction_col("pred")
            .set_num_features(dim).set_global_batch_size(batch)
            .set_learning_rate(lr).set_max_iter(epochs))


def _sparse_table(dim, indptr, indices, values, y):
    return Table.from_columns(SCHEMA, {
        "features": _csr_rows(dim, indptr, indices, values), "label": y})


def _packed(table):
    """The stacks a fit left in the table's pack cache."""
    return list(table._pack_cache.values())


@pytest.fixture
def sparse_counters(tmp_path, monkeypatch):
    from flink_ml_tpu import obs

    monkeypatch.setenv("FMT_OBS_REPORTS", str(tmp_path / "reports"))
    obs.reset()
    obs.enable()
    yield lambda: obs.registry().snapshot()["counters"]
    obs.disable()
    obs.reset()


def _mesh_devices():
    from flink_ml_tpu.utils.environment import MLEnvironmentFactory

    return len(MLEnvironmentFactory.get_default().get_mesh().devices.flat)


def test_a_fit_at_the_rehearsal_shape_lands_within_the_cells_limits():
    """``LogisticRegression.fit`` of a ``CsrRows`` column on the row-regular
    step against the benchmark's plain reference, at the sparse cell's
    rehearsal shape and under the cell's own limits."""
    import json
    import os
    import sys

    from flink_ml_tpu.lib import common

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    if root not in sys.path:
        sys.path.insert(0, root)
    from chipbench import data_sparse, program, program_sparse, references

    with open(os.path.join(root, "chipbench", "configs",
                           "criteo_sparse_lr.json")) as f:
        config = json.load(f)
    config.update(config["rehearsal"])
    with open(os.path.join(root, "chipbench", "limits",
                           "criteo_sparse_lr.sweep.json")) as f:
        limits = json.load(f)
    dim, batch = config["numFeatures"], config["globalBatchSize"]
    indptr, indices, values, y = data_sparse.make_rows(
        config["data"], config["rows"], dim, 2**31 + 28)
    table = program_sparse.table(dim, indptr, indices, values, y)
    reference = references.load(config["reference"])
    width = config["nnz_per_row"]
    ref_table = reference.Table(indices.reshape(-1, width),
                                values.reshape(-1, width), y, dim, batch)
    for lr, reg in ((0.1, 0.0), (0.5, 1e-4)):
        answer = program.fit_answer(
            program_sparse.logreg(config, lr, reg).fit(table))
        gaps = reference.gaps(
            answer, ref_table.fit(lr, reg, config["maxIter"]))
        assert gaps["coef_gap"] <= limits["coef_gap"]["limit"], gaps
        assert gaps["loss_gap"] <= limits["loss_gap"]["limit"], gaps
    (stack,) = _packed(table)
    assert isinstance(stack, common.EllMinibatchStack) and stack.width == 39


def test_a_repeated_row_regular_fit_returns_the_same_bytes():
    from flink_ml_tpu.lib import common

    counts = np.random.RandomState(8).randint(2, 7, 700)
    table = _sparse_table(60, *_csr_table_parts(counts, 60, 21))
    a = _sparse_est(60, 128).fit(table)
    b = _sparse_est(60, 128).fit(table)
    (stack,) = _packed(table)
    assert isinstance(stack, common.EllMinibatchStack)
    assert np.asarray(a.coefficients()).tobytes() == \
        np.asarray(b.coefficients()).tobytes()
    assert a.intercept() == b.intercept()
    assert list(a.train_losses_) == list(b.train_losses_)


def test_rows_out_of_ascending_order_give_the_sorted_rows_answer():
    """The row-regular pack does NOT sort a row's entries (its step sums a
    row whatever the order: the order check and its argsort do not run), so
    a file-order column gives the sorted column's answer to float32
    rounding, not its bytes."""
    from flink_ml_tpu.lib import common

    counts = np.random.RandomState(9).randint(3, 7, 500)
    indptr, indices, values, y = _csr_table_parts(counts, 80, 23, sort=False)
    order = np.concatenate([
        lo + np.argsort(indices[lo:hi], kind="stable")
        for lo, hi in zip(indptr[:-1], indptr[1:])])
    assert not np.array_equal(order, np.arange(len(indices)))
    shuffled = _sparse_table(80, indptr, indices, values, y)
    ordered = _sparse_table(80, indptr, indices[order], values[order], y)
    a = _sparse_est(80, 128).fit(shuffled)
    b = _sparse_est(80, 128).fit(ordered)
    for table in (shuffled, ordered):
        (stack,) = _packed(table)
        assert isinstance(stack, common.EllMinibatchStack)
    # the pack kept the stored order: the first row's ids, as stored
    (stack,) = _packed(shuffled)
    assert np.array_equal(stack.ints[0, :counts[0], 0], indices[:counts[0]])
    np.testing.assert_allclose(a.coefficients(), b.coefficients(),
                               rtol=1e-5, atol=1e-7)
    np.testing.assert_allclose(a.intercept(), b.intercept(), atol=1e-7)


# -- the choice and its counters -----------------------------------------------


def test_uniform_rows_take_the_row_regular_step_and_count_it(sparse_counters):
    n_dev, width, epochs = _mesh_devices(), 6, 3
    rows, batch = 1000, 32 * n_dev
    table = _sparse_table(
        90, *_csr_table_parts(np.full(rows, width), 90, 31))
    _sparse_est(90, batch, epochs=epochs).fit(table)
    counted = sparse_counters()
    blocks = n_dev * -(-rows // batch)
    assert counted["train.sparse_fits"] == 1
    assert counted["train.sparse_ell_fits"] == 1
    assert "train.sparse_ell_declined" not in counted
    assert counted["train.sparse_entries"] == rows * width * epochs
    assert counted["train.sparse_slots"] == width * 32 * blocks * epochs


def _one_long_row(n_dev, long_width):
    """``256 * n_dev`` rows in one global batch: every row stores 4 entries
    but the first, so one device's step holds ``1020 + long_width`` entries
    (``nnz_pad`` 1536 for a long row of 5 to 516) and the row-regular
    layout would walk ``256 * long_width`` slots a device."""
    counts = np.full(256 * n_dev, 4)
    counts[0] = long_width
    return _csr_table_parts(counts, 2000, 37)


@pytest.mark.parametrize(
    "long_width, classes", [(10, 1), (11, 2), (17, 2), (18, 0)],
    ids=["one_width_just_inside", "one_width_just_outside",
         "classed_just_inside", "classed_just_outside"])
def test_the_rule_splits_tables_by_the_slots_they_would_walk(
        long_width, classes, sparse_counters):
    """The rule's three outcomes, each side of both lines (a device's step
    is two lane blocks: the long row's block at its width, the other at 4)."""
    from flink_ml_tpu.lib import common

    n_dev = _mesh_devices()
    assert common._ELL_MAX_SLOT_RATIO == 1.75
    # 256 * 10 = 2560 <= 1.75 * 1536 = 2688 < 256 * 11 = 2816, and in two
    # classes 128 * 17 + 128 * 4 = 2688 <= 2688 < 128 * 18 + 128 * 4 = 2816
    parts = _one_long_row(n_dev, long_width)
    table = _sparse_table(2000, *parts)
    _sparse_est(2000, 256 * n_dev, epochs=1).fit(table)
    (stack,) = _packed(table)
    counted = sparse_counters()
    assert counted["train.sparse_fits"] == 1
    assert counted["train.sparse_ell_fits"] == int(classes > 0)
    assert counted.get("train.sparse_ell_declined", 0) == int(classes == 0)
    assert counted["train.sparse_ell_classes"] == classes
    if classes == 1:
        assert isinstance(stack, common.EllMinibatchStack)
        assert (stack.mb, stack.width) == (256, long_width)
        assert counted["train.sparse_slots"] == 256 * long_width * n_dev
        assert counted["train.sparse_ell_slots_reckoned"] == \
            256 * long_width * n_dev
    elif classes == 2:
        assert isinstance(stack, common.ClassedEllMinibatchStack)
        assert stack.classes == ((128, long_width), (128, 4))
        reckoned = 128 * long_width + 128 * 4
        assert stack.slots == (-(-reckoned // 512) | 1) * 512
        assert counted["train.sparse_slots"] == stack.slots * n_dev
        assert counted["train.sparse_ell_slots_reckoned"] == reckoned * n_dev
    else:
        assert counted["train.sparse_ell_slots_reckoned"] == \
            (128 * long_width + 128 * 4) * n_dev  # what failed the rule
        # segment-CSR, byte for byte what the pack lays without the flag
        plain = pack_sparse_minibatches(
            _csr_rows(2000, *parts[:3]), parts[3], n_dev, 256 * n_dev,
            dim=2000)
        assert isinstance(stack, common.SparseMinibatchStack)
        assert stack.ell_declined and not plain.ell_declined
        assert stack.nnz_pad == plain.nnz_pad == 1536
        assert stack.ints.tobytes() == plain.ints.tobytes()
        assert stack.floats.tobytes() == plain.floats.tobytes()
        assert counted["train.sparse_slots"] == 1536 * n_dev


def _parents_segment_csr(parts, dim, n_dev, batch):
    """The parent commit's stack for these rows: the per-object pack, which
    this PR does not touch (and which the CSR pack equals byte for byte)."""
    indptr, indices, values, y = parts
    vecs = [SparseVector(dim, indices[a:b].astype(np.int64),
                         values[a:b].astype(np.float64))
            for a, b in zip(indptr[:-1], indptr[1:])]
    return pack_sparse_minibatches(vecs, y, n_dev, batch, dim=dim)


def _assert_segment_csr_as_the_parent_packs(stack, parts, dim, n_dev, batch):
    from flink_ml_tpu.lib import common

    want = _parents_segment_csr(parts, dim, n_dev, batch)
    assert isinstance(stack, common.SparseMinibatchStack)
    assert not stack.ell_declined
    assert (stack.steps, stack.mb, stack.nnz_pad, stack.dim) == \
        (want.steps, want.mb, want.nnz_pad, want.dim)
    assert stack.ints.tobytes() == want.ints.tobytes()
    assert stack.floats.tobytes() == want.floats.tobytes()


def test_a_two_d_mesh_still_reads_a_segment_csr_stack(sparse_counters):
    import jax

    from flink_ml_tpu.parallel.mesh import create_mesh
    from flink_ml_tpu.utils.environment import MLEnvironmentFactory

    if len(jax.devices()) < 8:
        pytest.skip("needs 8 devices for a (2, 4) mesh")
    parts = _csr_table_parts(np.full(600, 5), 72, 43)
    table = _sparse_table(72, *parts)
    env = MLEnvironmentFactory.get_default()
    old = env.get_mesh()
    env.set_mesh(create_mesh({"data": 2, "model": 4}))
    try:
        _sparse_est(72, 32).fit(table)
    finally:
        env.set_mesh(old)
    (stack,) = _packed(table)
    _assert_segment_csr_as_the_parent_packs(stack, parts, 72, 2, 32)
    counted = sparse_counters()
    assert counted["train.sparse_fits"] == 1
    assert counted["train.sparse_ell_fits"] == 0
    assert "train.sparse_ell_declined" not in counted


def test_the_out_of_core_path_still_packs_segment_csr(monkeypatch):
    from flink_ml_tpu.lib import common
    from flink_ml_tpu.lib import out_of_core as oc
    from flink_ml_tpu.table.sources import ChunkedTable, CollectionSource

    n_dev = _mesh_devices()
    indptr, indices, values, y = _csr_table_parts(np.full(400, 5), 64, 47)
    vecs = [SparseVector(64, indices[a:b].astype(np.int64),
                         values[a:b].astype(np.float64))
            for a, b in zip(indptr[:-1], indptr[1:])]
    seen = []

    def recording(*args, **kwargs):
        stack = common.pack_sparse_minibatches(*args, **kwargs)
        seen.append((kwargs.get("row_regular", False), stack))
        return stack

    monkeypatch.setattr(oc, "pack_sparse_minibatches", recording)
    _sparse_est(64, 8 * n_dev).fit(
        ChunkedTable(CollectionSource(list(zip(vecs, y)), SCHEMA),
                     chunk_rows=96))
    assert seen
    for asked, stack in seen:
        assert asked is False
        assert isinstance(stack, common.SparseMinibatchStack)
        assert not stack.ell_declined


def _resume_from_a_retired_route_checkpoint(tmp_path, vecs, ys):
    """The retired route's streamed checkpoints held permuted weights of
    the table's own shape on a 1-D mesh: a resume from one (its meta
    carries ``hotcold_layout``) is refused, and the same checkpoint
    without the stamp resumes as usual."""
    import glob
    import json

    from flink_ml_tpu.api import load_stage

    ck = str(tmp_path / "ck")
    _skewed_est(max_iter=2, checkpoint_dir=ck,
                checkpoint_interval=1).fit(_skewed_stream(vecs, ys))
    metas = sorted(glob.glob(f"{ck}/*.json"))
    assert metas
    for path in metas:
        with open(path) as f:
            meta = json.load(f)
        meta["hotcold_layout"] = {"model_size": 1, "hot_k_eff": 16,
                                  "dim_pad": 64, "perm_crc": 0}
        with open(path, "w") as f:
            json.dump(meta, f)
    legacy = load_stage(str(tmp_path / "legacy"))
    legacy.set_checkpoint_dir(ck).set_checkpoint_interval(1)
    with pytest.raises(ValueError, match="retired numHotFeatures"):
        legacy.fit(_skewed_stream(vecs, ys))
    for path in metas:
        with open(path) as f:
            meta = json.load(f)
        del meta["hotcold_layout"]
        with open(path, "w") as f:
            json.dump(meta, f)
    resumed = legacy.fit(_skewed_stream(vecs, ys))
    full = load_stage(str(tmp_path / "plain")).fit(_skewed_stream(vecs, ys))
    assert resumed.train_epochs_ == full.train_epochs_ == 4
    np.testing.assert_allclose(resumed.coefficients(), full.coefficients(),
                               rtol=1e-6, atol=1e-9)


@pytest.mark.parametrize("where",
                         ["in_memory", "out_of_core", "out_of_core_resume"])
def test_a_saved_estimator_with_the_retired_hot_keys_fits_as_without(
        where, tmp_path, sparse_counters):
    """An estimator saved while ``numHotFeatures`` / ``hotSlabMode`` were
    parameters still loads: the keys ride along unread, and the fit is
    the one the same JSON without them gives, to the byte, on the one
    sparse route (row-regular in memory on a 1-D mesh).  A streamed
    checkpoint that route wrote is refused, not resumed."""
    import json

    from flink_ml_tpu.api import load_stage

    vecs, ys = _power_law_data(n=256)
    counts = np.asarray([len(v.indices) for v in vecs])
    indptr = np.concatenate([[0], np.cumsum(counts)]).astype(np.int64)
    indices = np.concatenate([v.indices for v in vecs]).astype(np.int32)
    values = np.concatenate([v.vals for v in vecs]).astype(np.float32)
    _skewed_est(max_iter=4).save(str(tmp_path / "plain"))
    with open(tmp_path / "plain" / "stage.json") as f:
        stage = json.load(f)
    params = json.loads(stage["params"])
    params.update(numHotFeatures=json.dumps(16),
                  hotSlabMode=json.dumps("stream"))
    stage["params"] = json.dumps(params)
    (tmp_path / "legacy").mkdir()
    with open(tmp_path / "legacy" / "stage.json", "w") as f:
        json.dump(stage, f)

    def fit(name):
        est = load_stage(str(tmp_path / name))
        if where == "in_memory":
            table = _sparse_table(64, indptr, indices, values, ys)
        else:
            table = _skewed_stream(vecs, ys)
        before = sparse_counters().get("train.sparse_ell_fits", 0)
        model = est.fit(table)
        return est, model, sparse_counters().get("train.sparse_ell_fits",
                                                 0) - before

    if where == "out_of_core_resume":
        _resume_from_a_retired_route_checkpoint(tmp_path, vecs, ys)
        return
    legacy, m_legacy, ell_legacy = fit("legacy")
    assert {"numHotFeatures", "hotSlabMode"} <= set(legacy.get_params().keys())
    _plain, m_plain, ell_plain = fit("plain")
    np.testing.assert_array_equal(m_legacy.coefficients(),
                                  m_plain.coefficients())
    assert m_legacy.intercept() == m_plain.intercept()
    assert ell_legacy == ell_plain == (1 if where == "in_memory" else 0)
