"""Table layer tests — parity with TableUtilTest, OutputColsHelperTest (44-194),
DataStreamConversionUtilTest failure modes, plus columnar/device-bridge coverage."""

import numpy as np
import pytest

from flink_ml_tpu.ops import DenseVector, SparseVector
from flink_ml_tpu.table import (
    CollectionSource,
    CsvSource,
    DataTypes,
    GeneratorSource,
    LibSvmSource,
    OutputColsHelper,
    Schema,
    Table,
    table_util,
)


def _schema():
    return Schema(["id", "f1", "f2"], [DataTypes.INT, DataTypes.FLOAT, DataTypes.DOUBLE])


class TestSchema:
    def test_case_insensitive_lookup(self):
        s = _schema()
        assert s.find_col_index("F1") == 1
        assert s.find_col_index("nope") == -1
        assert s.type_of("ID") == DataTypes.INT
        assert s.resolve("iD") == "id"

    def test_select_missing_raises(self):
        with pytest.raises(ValueError, match="not found"):
            _schema().select(["id", "zz"])

    def test_round_trip_dict(self):
        s = _schema()
        assert Schema.from_dict(s.to_dict()) == s


class TestTable:
    def test_from_rows_and_back(self):
        t = Table.from_rows([(1, 2.0, 3.0), (4, 5.0, 6.0)], _schema())
        assert t.num_rows() == 2
        assert t.to_rows()[1][0] == 4
        assert t.col("F2").tolist() == [3.0, 6.0]

    def test_row_arity_check(self):
        with pytest.raises(ValueError, match="arity"):
            Table.from_rows([(1, 2.0)], _schema())

    def test_ragged_columns_raise(self):
        with pytest.raises(ValueError, match="ragged"):
            Table(_schema(), {"id": np.zeros(2), "f1": np.zeros(3), "f2": np.zeros(2)})

    def test_select_with_column_slice(self):
        t = Table.from_rows([(1, 2.0, 3.0), (4, 5.0, 6.0)], _schema())
        sel = t.select(["id"])
        assert sel.schema.field_names == ["id"]
        t2 = t.with_column("pred", DataTypes.DOUBLE, [0.1, 0.9])
        assert t2.schema.field_names == ["id", "f1", "f2", "pred"]
        t3 = t2.with_column("f1", DataTypes.DOUBLE, [9.0, 9.0])  # replace keeps position
        assert t3.schema.field_names == ["id", "f1", "f2", "pred"]
        assert t3.col("f1").tolist() == [9.0, 9.0]
        assert t.slice_rows(1, 2).to_rows() == [(4, 5.0, 6.0)]

    def test_concat_and_batches(self):
        t = Table.from_rows([(1, 2.0, 3.0), (4, 5.0, 6.0), (7, 8.0, 9.0)], _schema())
        parts = list(t.iter_batches(2))
        assert [p.num_rows() for p in parts] == [2, 1]
        back = Table.concat(parts)
        assert back.to_rows() == t.to_rows()

    def test_vector_column_bridge(self):
        s = Schema(["features", "label"], [DataTypes.VECTOR, DataTypes.DOUBLE])
        t = Table.from_rows(
            [(DenseVector([1, 2]), 1.0), (SparseVector(2, [1], [5.0]), 0.0)], s
        )
        dense = t.features_dense("features")
        assert dense.tolist() == [[1, 2], [0, 5]]
        csr = t.features_csr("features", n_cols=2, pad_multiple=8)
        assert np.asarray(csr.to_dense()).tolist() == [[1, 2], [0, 5]]

    def test_vector_column_type_check(self):
        s = Schema(["features"], [DataTypes.VECTOR])
        with pytest.raises(TypeError, match="non-vector"):
            Table.from_rows([("not a vector",)], s)

    def test_numeric_matrix(self):
        t = Table.from_rows([(1, 2.0, 3.0), (4, 5.0, 6.0)], _schema())
        m = t.numeric_matrix(["f1", "f2"])
        assert m.tolist() == [[2, 3], [5, 6]]
        s2 = Schema(["a"], [DataTypes.STRING])
        t2 = Table.from_rows([("x",)], s2)
        with pytest.raises(ValueError, match="numeric"):
            t2.numeric_matrix(["a"])


class TestOutputColsHelper:
    """Mirrors OutputColsHelperTest.java:44-194 rule coverage."""

    def test_javadoc_example(self):
        helper = OutputColsHelper(
            _schema(), ["label"], [DataTypes.STRING], reserved_col_names=["id"]
        )
        rs = helper.get_result_schema()
        assert rs.field_names == ["id", "label"]
        assert rs.field_types == [DataTypes.INT, DataTypes.STRING]

    def test_reserve_all_default(self):
        helper = OutputColsHelper(_schema(), ["label"], [DataTypes.STRING])
        assert helper.get_result_schema().field_names == ["id", "f1", "f2", "label"]

    def test_output_overrides_in_place(self):
        helper = OutputColsHelper(_schema(), ["f1"], [DataTypes.STRING])
        rs = helper.get_result_schema()
        assert rs.field_names == ["id", "f1", "f2"]
        assert rs.field_types == [DataTypes.INT, DataTypes.STRING, DataTypes.DOUBLE]

    def test_merge_values(self):
        t = Table.from_rows([(1, 2.0, 3.0), (4, 5.0, 6.0)], _schema())
        helper = OutputColsHelper(
            t.schema, ["pred"], [DataTypes.DOUBLE], reserved_col_names=["id", "f2"]
        )
        out = helper.get_result_table(t, {"pred": [0.5, 0.7]})
        assert out.schema.field_names == ["id", "f2", "pred"]
        assert out.to_rows() == [(1, 3.0, 0.5), (4, 6.0, 0.7)]

    def test_missing_output_col_raises(self):
        t = Table.from_rows([(1, 2.0, 3.0)], _schema())
        helper = OutputColsHelper(t.schema, ["pred"], [DataTypes.DOUBLE])
        with pytest.raises(ValueError, match="did not produce"):
            helper.get_result_table(t, {"other": [1.0]})


class TestTableUtil:
    def test_temp_table_name_unique(self):
        assert table_util.get_temp_table_name() != table_util.get_temp_table_name()

    def test_find_col_index_null_raises(self):
        with pytest.raises(ValueError):
            table_util.find_col_index(["a"], None)
        assert table_util.find_col_index(["a", "B"], "b") == 1

    def test_assertions(self):
        s = Schema(["num", "txt", "vec"], [DataTypes.DOUBLE, DataTypes.STRING, DataTypes.VECTOR])
        table_util.assert_selected_col_exist(s.field_names, "num")
        with pytest.raises(ValueError):
            table_util.assert_selected_col_exist(s.field_names, "zz")
        table_util.assert_numerical_cols(s, "num")
        with pytest.raises(ValueError):
            table_util.assert_numerical_cols(s, "txt")
        table_util.assert_string_cols(s, "txt")
        with pytest.raises(ValueError):
            table_util.assert_string_cols(s, "vec")
        table_util.assert_vector_cols(s, "vec")
        with pytest.raises(ValueError):
            table_util.assert_vector_cols(s, "num")

    def test_typed_col_selection(self):
        s = Schema(["a", "b", "c"], [DataTypes.DOUBLE, DataTypes.STRING, DataTypes.INT])
        assert table_util.get_numeric_cols(s) == ["a", "c"]
        assert table_util.get_numeric_cols(s, exclude_cols=["A"]) == ["c"]
        assert table_util.get_string_cols(s) == ["b"]
        assert table_util.get_categorical_cols(s, ["a", "b"], None) == ["b"]
        assert table_util.get_categorical_cols(s, ["a", "b"], ["a"]) == ["a", "b"]
        with pytest.raises(ValueError, match="featureCols"):
            table_util.get_categorical_cols(s, ["a"], ["c"])

    def test_format_markdown(self):
        t = Table.from_rows([(1, 2.0, None)], Schema(["x", "y", "z"],
                            [DataTypes.INT, DataTypes.DOUBLE, DataTypes.STRING]))
        text = table_util.format(t)
        assert text.splitlines()[0] == "|x|y|z|"
        assert "null" in text.splitlines()[2]


class TestSources:
    def test_collection_source(self):
        src = CollectionSource([(1, 2.0, 3.0)], _schema())
        assert src.read().num_rows() == 1

    def test_csv_source(self, tmp_path):
        p = tmp_path / "data.csv"
        p.write_text("id,f1,vec\n1,2.5,1 2 3\n2,,0:1 4:5\n")
        s = Schema(["id", "f1", "vec"], [DataTypes.INT, DataTypes.DOUBLE, DataTypes.VECTOR])
        t = CsvSource(str(p), s, skip_header=True).read()
        assert t.num_rows() == 2
        assert t.col("id").tolist() == [1, 2]
        assert np.isnan(t.col("f1")[1])
        assert isinstance(t.col("vec")[0], DenseVector)
        assert isinstance(t.col("vec")[1], SparseVector)

    def test_csv_arity_error(self, tmp_path):
        p = tmp_path / "bad.csv"
        p.write_text("1,2\n")
        with pytest.raises(ValueError, match="fields"):
            CsvSource(str(p), _schema()).read()

    def test_libsvm_source(self, tmp_path):
        p = tmp_path / "d.svm"
        p.write_text("1 1:0.5 3:1.5  # comment\n-1 2:2.0\n\n")
        t = LibSvmSource(str(p)).read()
        assert t.col("label").tolist() == [1.0, -1.0]
        v0 = t.col("features")[0]
        assert v0.indices.tolist() == [0, 2] and v0.vals.tolist() == [0.5, 1.5]
        assert v0.size() == 3

    def test_generator_source_linear_timestamps(self):
        s = Schema(["v"], [DataTypes.INT])
        src = GeneratorSource.linear_timestamps([(1,), (2,), (3,)], 10, s)
        events = list(src.stream())
        assert events == [(0, (1,)), (10, (2,)), (20, (3,))]
        # re-iterable
        assert len(list(src.stream())) == 3


def test_output_cols_case_insensitive_override():
    """Regression: output col differing only in case overrides the input col
    in place instead of silently shadowing behind it."""
    from flink_ml_tpu.table.output_cols import OutputColsHelper

    schema = Schema.of(("f0", "double"), ("sum", "double"))
    t = Table.from_columns(schema, {"f0": [1.0, 2.0], "sum": [5.0, 6.0]})
    helper = OutputColsHelper(schema, ["Sum"], ["double"])
    assert helper.get_result_schema().field_names == ["f0", "Sum"]
    out = helper.get_result_table(t, {"Sum": np.asarray([100.0, 200.0])})
    np.testing.assert_allclose(out.col("sum"), [100.0, 200.0])
    np.testing.assert_allclose(out.col("Sum"), [100.0, 200.0])


def test_output_cols_reserved_case_insensitive():
    """Reserved names match case-insensitively like all other column lookup."""
    from flink_ml_tpu.table.output_cols import OutputColsHelper

    schema = Schema.of(("f0", "double"), ("label", "double"))
    helper = OutputColsHelper(schema, ["out"], ["double"], reserved_col_names=["Label"])
    assert helper.get_result_schema().field_names == ["label", "out"]


def test_tracing_helpers():
    """The span is what ``utils.tracing``'s ``annotate`` and ``timed`` were:
    a named region in the profiler's timeline and a wall-clock timing."""
    from flink_ml_tpu import obs

    assert obs.span("phase") is obs.span("step")  # off: the shared no-op
    obs.enable()
    try:
        with obs.span("phase") as timed:
            with obs.span("step"):
                pass
        stat = obs.registry().timing("phase")
        assert stat["count"] == 1 and stat["total_s"] == timed.seconds >= 0
        assert obs.registry().timing("step")["count"] == 1
    finally:
        obs.disable()
        obs.reset()


class TestMatrixBackedColumn:
    """Matrix-backed dense-vector columns: the million-row fast path — a 2D
    float array stored directly instead of rows of DenseVector objects."""

    def _table(self):
        X = np.arange(12, dtype=np.float32).reshape(4, 3)
        schema = Schema.of(("features", DataTypes.DENSE_VECTOR), ("label", "double"))
        return X, Table.from_columns(
            schema, {"features": X, "label": [0.0, 1.0, 0.0, 1.0]}
        )

    def test_features_dense_zero_copy(self):
        X, t = self._table()
        out = t.features_dense("features")
        assert out is X  # no conversion, no copy

    def test_features_dense_dim_pad(self):
        X, t = self._table()
        out = t.features_dense("features", dim=5)
        assert out.shape == (4, 5)
        np.testing.assert_allclose(out[:, :3], X)
        np.testing.assert_allclose(out[:, 3:], 0.0)

    def test_to_rows_wraps_dense_vectors(self):
        from flink_ml_tpu.ops.vector import DenseVector

        X, t = self._table()
        rows = t.to_rows()
        assert isinstance(rows[0][0], DenseVector)
        np.testing.assert_allclose(rows[2][0].values, X[2])
        assert rows[2][1] == 0.0

    def test_row_ops_slice_filter(self):
        X, t = self._table()
        sub = t.slice_rows(1, 3)
        np.testing.assert_allclose(sub.features_dense("features"), X[1:3])
        f = t.filter_rows(np.asarray([True, False, True, False]))
        np.testing.assert_allclose(f.features_dense("features"), X[[0, 2]])

    def test_train_matches_object_column(self):
        """A GLM fit over a matrix-backed column bit-matches the same fit
        over the equivalent DenseVector-object column."""
        from flink_ml_tpu.lib import LogisticRegression
        from flink_ml_tpu.ops.vector import DenseVector

        rng = np.random.RandomState(0)
        X = rng.randn(64, 5).astype(np.float64)
        y = (X @ rng.randn(5) > 0).astype(np.float64)
        schema = Schema.of(("features", DataTypes.DENSE_VECTOR), ("label", "double"))
        t_mat = Table.from_columns(schema, {"features": X, "label": y})
        t_obj = Table.from_columns(
            schema, {"features": [DenseVector(r) for r in X], "label": y}
        )

        def fit(t):
            m = (LogisticRegression().set_vector_col("features")
                 .set_label_col("label").set_prediction_col("p")
                 .set_learning_rate(0.5).set_max_iter(5).fit(t))
            return m.coefficients(), m.intercept()

        w1, b1 = fit(t_mat)
        w2, b2 = fit(t_obj)
        np.testing.assert_array_equal(w1, w2)
        assert b1 == b2


class TestPackCacheBounds:
    def test_lru_eviction(self):
        from flink_ml_tpu.table import table as table_mod

        schema = Schema.of(("x", "double"))
        t = Table.from_columns(schema, {"x": [1.0]})
        cap = table_mod._PACK_CACHE_CAPACITY
        builds = []
        for i in range(cap + 2):
            t.cached_pack(("k", i), lambda i=i: builds.append(i) or i)
        assert len(t._pack_cache) == cap
        # oldest entries evicted; re-requesting rebuilds
        t.cached_pack(("k", 0), lambda: builds.append("rebuild") or 0)
        assert "rebuild" in builds

    def test_hit_returns_same_object(self):
        schema = Schema.of(("x", "double"))
        t = Table.from_columns(schema, {"x": [1.0]})
        a = t.cached_pack("a", lambda: object())
        assert t.cached_pack("a", lambda: object()) is a

def test_features_dense_narrower_dim_raises():
    X, t = TestMatrixBackedColumn()._table()
    with pytest.raises(ValueError):
        t.features_dense("features", dim=2)


def test_concat_mixed_layouts():
    from flink_ml_tpu.ops.vector import DenseVector

    X, t_mat = TestMatrixBackedColumn()._table()
    schema = t_mat.schema
    t_obj = Table.from_rows([(DenseVector([9.0, 9.0, 9.0]), 5.0)], schema)
    out = Table.concat([t_mat, t_obj])
    assert out.num_rows() == 5
    np.testing.assert_allclose(out.features_dense("features")[:4], X)
    np.testing.assert_allclose(out.features_dense("features")[4], [9.0, 9.0, 9.0])


class TestCsrRowsColumn:
    """CSR-backed sparse columns: the contiguous-array counterpart of the
    matrix-backed dense column (native streaming feeds these)."""

    def _rows(self, n=20, dim=30, seed=0):
        from flink_ml_tpu.ops.batch import CsrRows
        from flink_ml_tpu.ops.vector import SparseVector

        rng = np.random.RandomState(seed)
        vecs = []
        for _ in range(n):
            k = rng.randint(0, 5)
            idx = np.sort(rng.choice(dim, k, replace=False))
            vecs.append(SparseVector(dim, idx, rng.randn(k)))
        return CsrRows.from_vectors(vecs, dim=dim), vecs

    def test_round_trip_and_indexing(self):
        rows, vecs = self._rows()
        assert len(rows) == len(vecs)
        for i in (0, 5, len(vecs) - 1, -1):
            got, want = rows[i], vecs[i]
            np.testing.assert_array_equal(got.indices, want.indices)
            np.testing.assert_array_equal(got.vals, want.vals)
        sub = rows[3:11]
        assert len(sub) == 8
        np.testing.assert_array_equal(sub[0].indices, vecs[3].indices)
        gathered = rows[np.array([7, 2, 19])]
        np.testing.assert_array_equal(gathered[1].vals, vecs[2].vals)
        masked = rows[np.arange(len(rows)) % 2 == 0]
        assert len(masked) == 10

    def test_concat(self):
        from flink_ml_tpu.ops.batch import CsrRows

        a, va = self._rows(seed=1)
        b, vb = self._rows(seed=2)
        cat = CsrRows.concat([a, b])
        assert len(cat) == len(va) + len(vb)
        np.testing.assert_array_equal(cat[len(va)].vals, vb[0].vals)

    def test_table_ops_on_csr_column(self):
        from flink_ml_tpu.ops.batch import CsrRows

        rows, vecs = self._rows()
        schema = Schema.of(("features", DataTypes.SPARSE_VECTOR), ("y", "double"))
        t = Table.from_columns(
            schema, {"features": rows, "y": np.arange(float(len(rows)))}
        )
        assert isinstance(t.col("features"), CsrRows)
        sliced = t.slice_rows(2, 6)
        assert sliced.num_rows() == 4
        np.testing.assert_array_equal(
            sliced.to_rows()[0][0].indices, vecs[2].indices
        )
        both = Table.concat([t, t])
        assert isinstance(both.col("features"), CsrRows)
        assert both.num_rows() == 2 * len(rows)
        csr = t.features_csr("features", n_cols=30)
        assert csr.n_rows == len(rows)

    def test_pack_paths_bit_identical(self):
        """The vectorized CSR packer must produce byte-identical minibatch
        stacks to the per-row SparseVector packer."""
        from flink_ml_tpu.lib.common import pack_sparse_minibatches

        rows, vecs = self._rows(n=533, dim=100, seed=3)
        y = np.random.RandomState(4).randn(533)
        for n_dev, gbs in ((1, 64), (4, 128), (8, 0)):
            a = pack_sparse_minibatches(vecs, y, n_dev, gbs, dim=100)
            b = pack_sparse_minibatches(rows, y, n_dev, gbs, dim=100)
            assert (a.steps, a.mb, a.nnz_pad, a.dim, a.n_rows) == (
                b.steps, b.mb, b.nnz_pad, b.dim, b.n_rows
            )
            np.testing.assert_array_equal(a.ints, b.ints)
            np.testing.assert_array_equal(a.floats, b.floats)

    def test_pack_csr_validates_range(self):
        from flink_ml_tpu.lib.common import pack_sparse_minibatches

        rows, _ = self._rows(n=10, dim=30)
        with pytest.raises(ValueError, match="out of range"):
            pack_sparse_minibatches(rows, np.zeros(10), 1, 4, dim=3)

    def test_features_dense_on_csr_column(self):
        rows, vecs = self._rows(n=15, dim=30)
        schema = Schema.of(("features", DataTypes.SPARSE_VECTOR))
        t = Table.from_columns(schema, {"features": rows})
        dense = t.features_dense("features")
        assert dense.shape == (15, 30)
        for i, v in enumerate(vecs):
            np.testing.assert_array_equal(dense[i], v.to_dense().values)
        wider = t.features_dense("features", dim=40)
        assert wider.shape == (15, 40)
        np.testing.assert_array_equal(wider[:, :30], dense)
        with pytest.raises(ValueError, match="out of range"):
            t.features_dense("features", dim=5)

    def test_csr_densify_sums_duplicates_and_rejects_negatives(self):
        from flink_ml_tpu.ops.batch import CsrRows

        dup = CsrRows(10, [0, 3], [2, 2, 5], [1.0, 2.5, -1.0])
        dense = dup.to_dense()
        assert dense[0, 2] == 3.5 and dense[0, 5] == -1.0
        neg = CsrRows(10, [0, 1], [-1], [1.0])
        with pytest.raises(ValueError, match="out of range"):
            neg.to_dense()
