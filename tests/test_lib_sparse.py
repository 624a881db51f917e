"""Sparse (Criteo-shape) training path tests: the segment-CSR fused loop must
match the dense path on identical data, scale to wide feature spaces without
densifying, and score sparsely at transform time."""

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


class TestHotColdSplit:
    """Hot/cold sparse training (VERDICT r3 item 1): the top-K frequent
    features stream through a dense MXU slab; the cold tail stays
    segment-CSR.  On the CPU test mesh the slab path runs the identical
    program (bf16 emulated)."""

    def _power_law_data(self, n=400, dim=64, seed=3):
        """Skewed frequencies: features [0, 8) appear in most rows."""
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

    def test_split_conserves_entries_and_picks_frequent(self):
        import jax.numpy as jnp

        from flink_ml_tpu.lib.common import split_hot_cold

        vecs, ys = self._power_law_data()
        s = pack_sparse_minibatches(vecs, ys, n_dev=2, global_batch_size=64)
        h = split_hot_cold(s, hot_k=8, pad_multiple=8,
                           slab_dtype=jnp.float32)
        # the 8 ever-present features become slab positions
        assert h.hot_k == 8
        np.testing.assert_array_equal(np.sort(h.perm[:8]), np.arange(8))
        np.testing.assert_array_equal(h.inv_perm[h.perm], np.arange(s.dim))
        # entry conservation: every valid entry lands exactly once
        valid = (s.ints[:, 1, :] < s.mb).sum()
        hot_n = (h.hot_ints[:, 1, :] < s.mb).sum()
        cold_n = (h.cold.ints[:, 1, :] < s.mb).sum()
        assert hot_n + cold_n == valid
        assert hot_n == 400 * 3 and cold_n == 400 * 2
        # y/w tails preserved
        np.testing.assert_array_equal(
            h.cold.floats[:, h.cold.nnz_pad:], s.floats[:, s.nnz_pad:]
        )

    def test_f32_slab_matches_plain_sparse_fit(self):
        """With an f32 slab the hot/cold program is the same math as the
        plain segment-CSR program (different summation grouping only)."""
        import jax.numpy as jnp

        from flink_ml_tpu.lib.common import (
            split_hot_cold,
            train_glm_sparse,
            train_glm_sparse_hotcold,
        )
        from flink_ml_tpu.parallel.mesh import default_mesh

        vecs, ys = self._power_law_data()
        mesh = default_mesh()
        s = pack_sparse_minibatches(vecs, ys, n_dev=8, global_batch_size=64)
        h = split_hot_cold(s, hot_k=8, pad_multiple=8, slab_dtype=jnp.float32)
        p0 = (jnp.zeros((s.dim,), jnp.float32), jnp.zeros((), jnp.float32))
        rp = train_glm_sparse(
            (jnp.copy(p0[0]), jnp.copy(p0[1])), s, "logistic", mesh,
            learning_rate=0.5, max_iter=15,
        )
        rh = train_glm_sparse_hotcold(
            (jnp.copy(p0[0]), jnp.copy(p0[1])), h, "logistic", mesh,
            learning_rate=0.5, max_iter=15,
        )
        np.testing.assert_allclose(rh.params[0], rp.params[0],
                                   rtol=1e-4, atol=1e-5)
        np.testing.assert_allclose(rh.params[1], rp.params[1], atol=1e-5)
        np.testing.assert_allclose(rh.losses, rp.losses, rtol=1e-4)

    def test_estimator_hot_split_bf16(self):
        """numHotFeatures routes the fit through the slab path; binary
        feature values are exact in bf16, so predictions agree with the
        plain path."""
        vecs, ys = self._power_law_data(n=500)
        t = Table.from_columns(SCHEMA, {"features": vecs, "label": ys})

        def fit(hot):
            return (
                LogisticRegression().set_vector_col("features")
                .set_label_col("label").set_prediction_col("pred")
                .set_learning_rate(0.5).set_max_iter(40)
                .set_global_batch_size(64).set_num_hot_features(hot)
                .fit(t)
            )

        m_hot = fit(16)
        m_plain = fit(0)
        (ph,) = m_hot.transform(t)
        (pp,) = m_plain.transform(t)
        agree = np.mean(
            np.asarray(ph.col("pred")) == np.asarray(pp.col("pred"))
        )
        assert agree >= 0.98, agree
        acc = np.mean(np.asarray(ph.col("pred")) == ys)
        assert acc > 0.85, acc

    def test_hot_k_covering_all_features(self):
        """hot_k >= dim: everything is hot, the cold stack is empty pads."""
        import jax.numpy as jnp

        import jax

        from flink_ml_tpu.lib.common import split_hot_cold, train_glm_sparse_hotcold
        from flink_ml_tpu.parallel.mesh import create_mesh

        vecs, ys = self._power_law_data(n=200, dim=32)
        s = pack_sparse_minibatches(vecs, ys, n_dev=2, global_batch_size=32)
        h = split_hot_cold(s, hot_k=999, pad_multiple=8, slab_dtype=jnp.float32)
        assert h.hot_k == 32
        assert (h.cold.ints[:, 1, :] < s.mb).sum() == 0
        r = train_glm_sparse_hotcold(
            (jnp.zeros((32,), jnp.float32), jnp.zeros((), jnp.float32)),
            h, "logistic", create_mesh({"data": 2}, jax.devices()[:2]),
            learning_rate=0.5, max_iter=10,
        )
        assert np.all(np.isfinite(r.params[0]))

    def test_checkpoint_resume(self, tmp_path):
        import jax.numpy as jnp

        from flink_ml_tpu.iteration.checkpoint import CheckpointConfig
        from flink_ml_tpu.lib.common import split_hot_cold, train_glm_sparse_hotcold
        from flink_ml_tpu.parallel.mesh import default_mesh

        vecs, ys = self._power_law_data(n=200)
        mesh = default_mesh()
        s = pack_sparse_minibatches(vecs, ys, n_dev=8, global_batch_size=64)
        h = split_hot_cold(s, hot_k=8, pad_multiple=8, slab_dtype=jnp.float32)
        p0 = (jnp.zeros((s.dim,), jnp.float32), jnp.zeros((), jnp.float32))
        full = train_glm_sparse_hotcold(
            (jnp.copy(p0[0]), jnp.copy(p0[1])), h, "logistic", mesh,
            learning_rate=0.5, max_iter=12,
        )
        cfg = CheckpointConfig(directory=str(tmp_path / "ck"), every_n_epochs=5)
        chunked = train_glm_sparse_hotcold(
            (jnp.copy(p0[0]), jnp.copy(p0[1])), h, "logistic", mesh,
            learning_rate=0.5, max_iter=12, checkpoint=cfg,
        )
        np.testing.assert_allclose(chunked.params[0], full.params[0],
                                   rtol=1e-6, atol=1e-7)
        assert chunked.epochs == full.epochs == 12

    def test_dense_features_with_hot_k_rejected(self):
        rng = np.random.RandomState(0)
        X = rng.randn(40, 4)
        schema = Schema.of(("features", DataTypes.DENSE_VECTOR), ("label", "double"))
        t = Table.from_columns(
            schema,
            {"features": [DenseVector(r) for r in X],
             "label": (X[:, 0] > 0).astype(np.float64)},
        )
        with pytest.raises(ValueError, match="sparse vector columns"):
            (
                LogisticRegression().set_vector_col("features")
                .set_label_col("label").set_prediction_col("p")
                .set_num_hot_features(2).fit(t)
            )

    def _ooc_est(self, hot, dim, max_iter=20, **kw):
        est = (
            LogisticRegression().set_vector_col("features")
            .set_label_col("label").set_prediction_col("pred")
            .set_num_features(dim).set_learning_rate(0.5)
            .set_max_iter(max_iter).set_global_batch_size(64)
            .set_num_hot_features(hot)
        )
        for k, v in kw.items():
            getattr(est, f"set_{k}")(v)
        return est

    def test_out_of_core_bit_matches_in_memory(self):
        """Streamed hot/cold training equals the in-memory hot/cold fit
        bit for bit: same permutation (the counting pre-pass sees the same
        entries), same update schedule (step-major packing), same slab
        values (the in-program per-minibatch scatter adds the same bf16
        entries the resident-slab build does)."""
        from flink_ml_tpu.table.sources import ChunkedTable, CollectionSource

        vecs, ys = self._power_law_data(n=400)
        t = Table.from_columns(SCHEMA, {"features": vecs, "label": ys})
        rows = list(zip(vecs, ys))
        m_mem = self._ooc_est(8, 64).fit(t)
        m_ooc = self._ooc_est(8, 64).fit(
            ChunkedTable(CollectionSource(rows, SCHEMA), chunk_rows=96)
        )
        np.testing.assert_array_equal(
            m_ooc.coefficients(), m_mem.coefficients()
        )
        assert m_ooc.intercept() == m_mem.intercept()

    def test_out_of_core_checkpoint_resume(self, tmp_path):
        """A killed-and-resumed streamed hot/cold fit lands on the
        uninterrupted result: the resume re-derives the identical
        permutation from the deterministic counting pre-pass and continues
        from the permuted-space checkpoint."""
        from flink_ml_tpu.table.sources import ChunkedTable, CollectionSource

        vecs, ys = self._power_law_data(n=300)
        rows = list(zip(vecs, ys))

        def chunked():
            return ChunkedTable(CollectionSource(rows, SCHEMA), chunk_rows=64)

        full = self._ooc_est(8, 64, max_iter=12).fit(chunked())
        ck = str(tmp_path / "ck")
        # run half, then resume to completion
        self._ooc_est(8, 64, max_iter=6, checkpoint_dir=ck,
                      checkpoint_interval=3).fit(chunked())
        resumed = self._ooc_est(8, 64, max_iter=12, checkpoint_dir=ck,
                                checkpoint_interval=3).fit(chunked())
        # same tolerance as the plain OOC resume test: a resumed engine
        # re-places loaded host params, which can fuse differently at the
        # sub-ulp level (test_out_of_core.py:164)
        np.testing.assert_allclose(
            resumed.coefficients(), full.coefficients(),
            rtol=1e-6, atol=1e-9,
        )

    def test_out_of_core_checkpoint_rejects_layout_change(self, tmp_path):
        """A permuted-space stream checkpoint must refuse to resume under
        a different hot/cold layout (changed mesh model size permutes the
        same-shaped vector differently — silently wrong without the
        stamp)."""
        from flink_ml_tpu.parallel.mesh import create_mesh
        from flink_ml_tpu.table.sources import ChunkedTable, CollectionSource
        from flink_ml_tpu.utils.environment import MLEnvironmentFactory

        vecs, ys = self._power_law_data(n=200)
        rows = list(zip(vecs, ys))

        def chunked():
            return ChunkedTable(CollectionSource(rows, SCHEMA),
                                chunk_rows=64)

        ck = str(tmp_path / "ck")
        self._ooc_est(8, 64, max_iter=6, checkpoint_dir=ck,
                      checkpoint_interval=3).fit(chunked())
        env = MLEnvironmentFactory.get_default()
        old = env.get_mesh()
        env.set_mesh(create_mesh({"data": 4, "model": 2}))
        try:
            with pytest.raises(ValueError, match="different hot/cold"):
                self._ooc_est(8, 64, max_iter=12, checkpoint_dir=ck,
                              checkpoint_interval=3).fit(chunked())
        finally:
            env.set_mesh(old)

    def test_out_of_core_2d_mesh_matches_1d(self):
        """The full formulation matrix closes: hot/cold + out-of-core +
        feature-sharded (2-D) mesh.  The same streamed blocks feed the
        model-sharded chunk program (shard-local slab densify + masked
        cold + one psum), and predictions match the 1-D streamed fit."""
        from flink_ml_tpu.parallel.mesh import create_mesh
        from flink_ml_tpu.table.sources import ChunkedTable, CollectionSource
        from flink_ml_tpu.utils.environment import MLEnvironmentFactory

        vecs, ys = self._power_law_data(n=300)
        t = Table.from_columns(SCHEMA, {"features": vecs, "label": ys})
        rows = list(zip(vecs, ys))

        def chunked():
            return ChunkedTable(CollectionSource(rows, SCHEMA),
                                chunk_rows=64)

        m1 = self._ooc_est(8, 64).fit(chunked())
        env = MLEnvironmentFactory.get_default()
        old = env.get_mesh()
        env.set_mesh(create_mesh({"data": 4, "model": 2}))
        try:
            m2 = self._ooc_est(8, 64).fit(chunked())
        finally:
            env.set_mesh(old)
        (p1,) = m1.transform(t)
        (p2,) = m2.transform(t)
        agree = np.mean(
            np.asarray(p1.col("pred")) == np.asarray(p2.col("pred"))
        )
        assert agree >= 0.98, agree
        np.testing.assert_allclose(
            m2.coefficients(), m1.coefficients(), rtol=0.05, atol=0.02
        )

    def test_out_of_core_dense_with_hot_k_rejected(self):
        from flink_ml_tpu.table.sources import ChunkedTable, CollectionSource

        rng = np.random.RandomState(0)
        X = rng.randn(40, 4)
        schema = Schema.of(("features", DataTypes.DENSE_VECTOR),
                           ("label", "double"))
        rows = [(DenseVector(r), float(r[0] > 0)) for r in X]
        with pytest.raises(ValueError, match="sparse vector columns"):
            (
                LogisticRegression().set_vector_col("features")
                .set_label_col("label").set_prediction_col("p")
                .set_global_batch_size(16).set_num_hot_features(2)
                .fit(ChunkedTable(CollectionSource(rows, schema),
                                  chunk_rows=16))
            )

    def test_2d_f32_slab_matches_1d(self):
        """Feature-sharded hot/cold training (slab columns + weights over
        the 'model' axis, one psum completing logits) matches the 1-D path
        to f32 rounding — only the summation grouping changes."""
        import jax
        import jax.numpy as jnp

        from flink_ml_tpu.lib.common import (
            split_hot_cold,
            train_glm_sparse_hotcold,
        )
        from flink_ml_tpu.parallel.mesh import create_mesh

        vecs, ys = self._power_law_data()
        s = pack_sparse_minibatches(vecs, ys, n_dev=4, global_batch_size=64)
        p0 = lambda: (  # noqa: E731
            jnp.zeros((s.dim,), jnp.float32), jnp.zeros((), jnp.float32)
        )
        mesh1 = create_mesh({"data": 4}, jax.devices()[:4])
        h1 = split_hot_cold(s, hot_k=8, pad_multiple=8,
                            slab_dtype=jnp.float32)
        r1 = train_glm_sparse_hotcold(
            p0(), h1, "logistic", mesh1, learning_rate=0.5, max_iter=15
        )
        mesh2 = create_mesh({"data": 4, "model": 2})
        h2 = split_hot_cold(s, hot_k=8, pad_multiple=8,
                            slab_dtype=jnp.float32, model_size=2)
        assert h2.dim_pad >= s.dim and h2.hot_k % 2 == 0
        r2 = train_glm_sparse_hotcold(
            p0(), h2, "logistic", mesh2, learning_rate=0.5, max_iter=15
        )
        np.testing.assert_allclose(r2.params[0], r1.params[0],
                                   rtol=1e-5, atol=1e-6)
        np.testing.assert_allclose(r2.params[1], r1.params[1], atol=1e-6)
        np.testing.assert_allclose(r2.losses, r1.losses, rtol=1e-5)

    def test_2d_rounded_hot_k_dead_columns(self):
        """hot_k not divisible by the model axis rounds up; the dead slab
        columns stay at zero weight."""
        import jax.numpy as jnp

        from flink_ml_tpu.lib.common import (
            split_hot_cold,
            train_glm_sparse_hotcold,
        )
        from flink_ml_tpu.parallel.mesh import create_mesh

        vecs, ys = self._power_law_data(n=200, dim=33)
        s = pack_sparse_minibatches(vecs, ys, n_dev=4, global_batch_size=32)
        h = split_hot_cold(s, hot_k=7, pad_multiple=8,
                           slab_dtype=jnp.float32, model_size=2)
        assert h.hot_k == 8 and h.dim_pad % 2 == 0 and h.dim_pad >= 33
        r = train_glm_sparse_hotcold(
            (jnp.zeros((33,), jnp.float32), jnp.zeros((), jnp.float32)),
            h, "logistic", create_mesh({"data": 4, "model": 2}),
            learning_rate=0.5, max_iter=8,
        )
        assert r.params[0].shape == (33,)
        assert np.all(np.isfinite(r.params[0]))

    def test_model_sharded_mesh_estimator(self):
        """numHotFeatures on a ('data','model') mesh routes through the
        feature-sharded slab path; predictions agree with the 1-D fit."""
        from flink_ml_tpu.parallel.mesh import create_mesh
        from flink_ml_tpu.utils.environment import MLEnvironmentFactory

        vecs, ys = self._power_law_data(n=300)
        t = Table.from_columns(SCHEMA, {"features": vecs, "label": ys})

        def fit():
            return (
                LogisticRegression().set_vector_col("features")
                .set_label_col("label").set_prediction_col("pred")
                .set_learning_rate(0.5).set_max_iter(30)
                .set_global_batch_size(32).set_num_hot_features(8)
                .fit(t)
            )

        m1 = fit()
        env = MLEnvironmentFactory.get_default()
        old = env.get_mesh()
        env.set_mesh(create_mesh({"data": 2, "model": 4}))
        try:
            m2 = fit()
        finally:
            env.set_mesh(old)
        (p1,) = m1.transform(t)
        (p2,) = m2.transform(t)
        agree = np.mean(
            np.asarray(p1.col("pred")) == np.asarray(p2.col("pred"))
        )
        assert agree >= 0.98, agree
        # bf16 slab rounding differs only in grouping: coefficients close
        np.testing.assert_allclose(
            m2.coefficients(), m1.coefficients(), rtol=0.05, atol=0.02
        )


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

    def test_hotcold_floors_and_counts_are_neutral(self):
        """split_hot_cold with explicit (local) counts and the natural pads
        as floors reproduces the default split exactly — the multi-process
        agreement path is a no-op when there is one process."""
        import jax
        import jax.numpy as jnp

        from flink_ml_tpu.lib.common import (
            hotcold_entry_counts,
            hotcold_layout_floors,
            split_hot_cold,
            train_glm_sparse_hotcold,
        )
        from flink_ml_tpu.parallel.mesh import create_mesh

        vecs, ys, _ = sparse_data(n=200, dim=48, nnz=5, seed=12)
        s = pack_sparse_minibatches(vecs, ys, n_dev=4, global_batch_size=32)
        counts = hotcold_entry_counts(s)
        (hp, cp), plan = hotcold_layout_floors(s, 8, counts=counts)
        h_def = split_hot_cold(s, 8, slab_dtype=jnp.float32)
        h_agr = split_hot_cold(s, 8, slab_dtype=jnp.float32, counts=counts,
                               min_hot_pad=hp, min_cold_pad=cp, plan=plan)
        np.testing.assert_array_equal(h_agr.perm, h_def.perm)
        np.testing.assert_array_equal(h_agr.hot_ints, h_def.hot_ints)
        np.testing.assert_array_equal(h_agr.hot_vals, h_def.hot_vals)
        np.testing.assert_array_equal(h_agr.cold.ints, h_def.cold.ints)
        np.testing.assert_array_equal(h_agr.cold.floats, h_def.cold.floats)
        # larger floors widen the pads but keep training identical
        h_wide = split_hot_cold(s, 8, slab_dtype=jnp.float32, counts=counts,
                                min_hot_pad=hp * 2, min_cold_pad=cp * 2)
        assert h_wide.hot_ints.shape[2] == hp * 2
        mesh = create_mesh({"data": 4}, jax.devices()[:4])
        p0 = lambda: (  # noqa: E731
            jnp.zeros((s.dim,), jnp.float32), jnp.zeros((), jnp.float32)
        )
        r1 = train_glm_sparse_hotcold(p0(), h_def, "logistic", mesh,
                                      learning_rate=0.5, max_iter=8)
        r2 = train_glm_sparse_hotcold(p0(), h_wide, "logistic", mesh,
                                      learning_rate=0.5, max_iter=8)
        np.testing.assert_array_equal(
            np.asarray(r1.params[0]), np.asarray(r2.params[0])
        )

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


class TestHotColdStreamFormulation:
    """VERDICT r4 #1: the scalable in-memory formulation — slabs densify
    in-program per minibatch (HBM holds O(nnz), never O(rows x hot_k))."""

    def _data(self, n=500, dim=64, seed=3):
        rng = np.random.RandomState(seed)
        true_w = rng.randn(dim)
        vecs, ys = [], []
        for _ in range(n):
            hot = rng.choice(8, 3, replace=False)
            cold = 8 + rng.choice(dim - 8, 2, replace=False)
            idx = np.sort(np.concatenate([hot, cold]))
            x = np.zeros(dim)
            x[idx] = 1.0
            vecs.append(SparseVector(dim, idx.astype(np.int64), np.ones(5)))
            ys.append(float((x @ true_w) > 0))
        return vecs, np.asarray(ys)

    def _fit(self, t, mode, hot=16):
        return (
            LogisticRegression().set_vector_col("features")
            .set_label_col("label").set_prediction_col("pred")
            .set_learning_rate(0.5).set_max_iter(30)
            .set_global_batch_size(64).set_num_hot_features(hot)
            .set_hot_slab_mode(mode)
            .fit(t)
        )

    def test_stream_mode_matches_resident_mode(self):
        vecs, ys = self._data()
        t = Table.from_columns(SCHEMA, {"features": vecs, "label": ys})
        m_res = self._fit(t, "resident")
        m_str = self._fit(t, "stream")
        np.testing.assert_allclose(
            m_str.coefficients(), m_res.coefficients(), rtol=1e-5, atol=1e-7
        )

    def test_auto_mode_picks_stream_over_budget(self, monkeypatch):
        from flink_ml_tpu.lib import common as lc

        calls = {}
        orig = lc.train_glm_sparse_hotcold

        def spy(*a, **kw):
            calls["resident"] = kw.get("resident_slabs")
            return orig(*a, **kw)

        monkeypatch.setattr(
            "flink_ml_tpu.lib.glm.train_glm_sparse_hotcold", spy,
            raising=False,
        )
        # glm imports inside the method; patch at source module
        monkeypatch.setattr(lc, "train_glm_sparse_hotcold", spy)
        vecs, ys = self._data()
        t = Table.from_columns(SCHEMA, {"features": vecs, "label": ys})
        monkeypatch.setenv("FMT_HOT_SLAB_BUDGET_MB", "0")
        self._fit(t, "auto")
        assert calls["resident"] is False
        calls.clear()
        monkeypatch.setenv("FMT_HOT_SLAB_BUDGET_MB", "100000")
        self._fit(t, "auto")
        assert calls["resident"] is True

    def test_stream_mode_2d_matches_1d(self):
        import jax

        from flink_ml_tpu.lib.common import (
            split_hot_cold,
            train_glm_sparse_hotcold,
        )
        from flink_ml_tpu.parallel.mesh import create_mesh

        vecs, ys = self._data(n=300, dim=32)
        mesh = create_mesh({"data": 2, "model": 2},
                           devices=jax.devices()[:4])
        s = pack_sparse_minibatches(vecs, ys, n_dev=2, global_batch_size=32)
        import jax.numpy as jnp

        kw = dict(
            kind="logistic", learning_rate=0.5, max_iter=10, reg=0.0,
            tol=0.0, with_intercept=True, resident_slabs=False,
        )
        h2 = split_hot_cold(s, hot_k=8, pad_multiple=8,
                            slab_dtype=jnp.float32, model_size=2)
        w0 = (jnp.zeros((32,), jnp.float32), jnp.zeros((), jnp.float32))
        r2 = train_glm_sparse_hotcold(w0, h2, mesh=mesh, **kw)
        mesh1 = create_mesh({"data": 2}, devices=jax.devices()[:2])
        h1 = split_hot_cold(s, hot_k=8, pad_multiple=8,
                            slab_dtype=jnp.float32)
        r1 = train_glm_sparse_hotcold(w0, h1, mesh=mesh1, **kw)
        np.testing.assert_allclose(
            np.asarray(r2.params[0]), np.asarray(r1.params[0]),
            rtol=1e-5, atol=1e-7,
        )


def test_unsorted_csr_rows_pack_sorted():
    """CSR columns from file order may carry per-row ids out of order; the
    pack must restore the per-row ascending invariant (the hot-slab
    scatter declares its index tuples sorted)."""
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


def test_hot_cold_still_reads_a_segment_csr_stack(sparse_counters):
    from flink_ml_tpu.lib import common

    n_dev = _mesh_devices()
    parts = _csr_table_parts(np.full(600, 5), 70, 41)  # uniform: ELL's case
    table = _sparse_table(70, *parts)
    _sparse_est(70, 16 * n_dev).set_num_hot_features(8).fit(table)
    stacks = [s for s in _packed(table)
              if isinstance(s, (common.SparseMinibatchStack,
                                common.EllMinibatchStack))]
    (stack,) = stacks
    _assert_segment_csr_as_the_parent_packs(stack, parts, 70, n_dev,
                                            16 * n_dev)
    assert "train.sparse_ell_fits" not in sparse_counters()


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
