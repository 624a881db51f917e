"""Native ingestion parity: the C++ CSV/libsvm readers must agree exactly
with the pure-Python fallbacks through the real table sources."""

import os

import numpy as np
import pytest

from flink_ml_tpu import native
from flink_ml_tpu.table.schema import Schema
from flink_ml_tpu.table.sources import CsvSource, LibSvmSource

pytestmark = pytest.mark.skipif(
    not native.available(), reason="native library not built"
)


@pytest.fixture
def csv_file(tmp_path):
    p = tmp_path / "data.csv"
    p.write_text(
        'x,y,name\n'
        '1.5,2,"alpha, ""quoted"""\n'
        '-3.25,4,beta\n'
        '0,0,\n'
    )
    return str(p)


@pytest.fixture
def libsvm_file(tmp_path):
    p = tmp_path / "data.svm"
    p.write_text(
        "1 1:0.5 3:2.0 7:1.25\n"
        "0 2:-1.5  # inline comment\n"
        "\n"
        "1 1:3.0 7:-0.5\n"
    )
    return str(p)


def _python_fallback(fn):
    """Run fn with the native path disabled (fresh binding state)."""
    os.environ["FLINK_ML_TPU_NO_NATIVE"] = "1"
    # reset the lazy-loader state so the env var takes effect
    native._tried, saved = False, native._lib
    native._lib = None
    try:
        return fn()
    finally:
        del os.environ["FLINK_ML_TPU_NO_NATIVE"]
        native._tried = True
        native._lib = saved


class TestCsvParity:
    def test_rows_match_python(self, csv_file):
        schema = Schema.of(("x", "double"), ("y", "long"), ("name", "string"))
        src = CsvSource(csv_file, schema, skip_header=True)
        native_rows = src.read().to_rows()
        python_rows = _python_fallback(lambda: src.read().to_rows())
        assert len(native_rows) == len(python_rows) == 3
        for a, b in zip(native_rows, python_rows):
            assert a == b
        assert native_rows[0][2] == 'alpha, "quoted"'

    def test_arity_mismatch_raises(self, tmp_path):
        p = tmp_path / "bad.csv"
        p.write_text("1,2\n3\n")
        schema = Schema.of(("x", "double"), ("y", "double"))
        with pytest.raises(ValueError, match="fields"):
            CsvSource(str(p), schema).read()


class TestLibSvmParity:
    def test_rows_match_python(self, libsvm_file):
        src = LibSvmSource(libsvm_file)
        t_native = src.read()
        t_python = _python_fallback(lambda: src.read())
        np.testing.assert_array_equal(t_native.col("label"), t_python.col("label"))
        for a, b in zip(t_native.col("features"), t_python.col("features")):
            assert a.size() == b.size()
            np.testing.assert_array_equal(a.indices, b.indices)
            np.testing.assert_allclose(a.vals, b.vals)

    def test_values(self, libsvm_file):
        t = LibSvmSource(libsvm_file).read()
        assert t.num_rows() == 3
        v0 = t.col("features")[0]
        assert list(v0.indices) == [0, 2, 6]
        np.testing.assert_allclose(v0.vals, [0.5, 2.0, 1.25])
        assert v0.size() == 7  # max index + 1, 1-based input

    def test_n_features_pins_dim(self, libsvm_file):
        t = LibSvmSource(libsvm_file, n_features=100).read()
        assert t.col("features")[0].size() == 100

    def test_malformed_raises(self, tmp_path):
        p = tmp_path / "bad.svm"
        p.write_text("1 notanindex:2\n")
        with pytest.raises(ValueError):
            LibSvmSource(str(p)).read()


class TestControlByteFallback:
    def test_quoted_control_bytes_fall_back_to_python(self, tmp_path):
        """A 0x1F byte inside a quoted cell is legal CSV; the native
        transport can't represent it, so the source must fall back."""
        p = tmp_path / "ctl.csv"
        p.write_bytes(b'x,name\n1.5,"a\x1fb"\n')
        schema = Schema.of(("x", "double"), ("name", "string"))
        rows = CsvSource(str(p), schema, skip_header=True).read().to_rows()
        assert rows == [(1.5, "a\x1fb")]
        assert native.read_csv(str(p), ",", False, 2) is None


class TestNativeChunkedReaders:
    """The streaming handles must deliver the same rows in the same order
    as read(), in bounded chunks."""

    def test_csv_doubles_chunks_match_read(self, tmp_path):
        rng = np.random.RandomState(0)
        data = rng.randn(997, 4)
        data[5, 2] = np.nan
        path = tmp_path / "n.csv"
        np.savetxt(path, data, delimiter=",", fmt="%.17g")
        schema = Schema.of(*[(f"c{i}", "double") for i in range(4)])
        src = CsvSource(str(path), schema)
        whole = src.read()
        chunks = list(src.read_chunks(100))
        assert all(c.num_rows() <= 100 for c in chunks)
        assert sum(c.num_rows() for c in chunks) == 997
        streamed = np.concatenate(
            [np.stack([c.col(f"c{i}") for i in range(4)], axis=1) for c in chunks]
        )
        ref = np.stack([whole.col(f"c{i}") for i in range(4)], axis=1)
        np.testing.assert_array_equal(streamed, ref)

    def test_csv_quoted_crlf_header(self, tmp_path):
        path = tmp_path / "q.csv"
        path.write_bytes(b'a,b\r\n"1.5",2\r\n"-2.25",\r\n3,4\r\n')
        schema = Schema.of(("a", "double"), ("b", "double"))
        chunks = list(CsvSource(str(path), schema, skip_header=True).read_chunks(2))
        got = np.concatenate(
            [np.stack([c.col("a"), c.col("b")], axis=1) for c in chunks]
        )
        np.testing.assert_array_equal(
            got, [[1.5, 2.0], [-2.25, np.nan], [3.0, 4.0]]
        )

    def test_csv_fallback_resumes_pure_parser(self, tmp_path, monkeypatch):
        """A cell the native strtod rejects but Python's float() accepts
        ('1_000') triggers mid-stream fallback with no row lost or doubled."""
        path = tmp_path / "f.csv"
        lines = [f"{i},{i * 2}" for i in range(50)]
        lines[30] = "1_000,60"
        path.write_text("\n".join(lines) + "\n")
        schema = Schema.of(("a", "double"), ("b", "double"))
        chunks = list(CsvSource(str(path), schema).read_chunks(7))
        a = np.concatenate([np.asarray(c.col("a")) for c in chunks])
        expected = np.arange(50.0)
        expected[30] = 1000.0
        np.testing.assert_array_equal(a, expected)

    def test_libsvm_chunks_match_read(self, tmp_path):
        rng = np.random.RandomState(1)
        path = tmp_path / "n.svm"
        with open(path, "w") as f:
            for i in range(333):
                idx = np.sort(rng.choice(50, 4, replace=False))
                pairs = " ".join(f"{j + 1}:{rng.randn():.9g}" for j in idx)
                f.write(f"{i % 2} {pairs}\n")
        src = LibSvmSource(str(path), n_features=50)
        whole = src.read()
        chunks = list(src.read_chunks(64))
        assert sum(c.num_rows() for c in chunks) == 333
        assert all(c.num_rows() <= 64 for c in chunks)
        whole_rows = whole.to_rows()
        streamed_rows = [r for c in chunks for r in c.to_rows()]
        assert len(whole_rows) == len(streamed_rows)
        for (l1, v1), (l2, v2) in zip(whole_rows, streamed_rows):
            assert l1 == l2
            np.testing.assert_array_equal(v1.indices, v2.indices)
            np.testing.assert_array_equal(v1.vals, v2.vals)

    def test_python_fallback_forced_matches_native(self, tmp_path, monkeypatch):
        rng = np.random.RandomState(2)
        data = rng.randn(200, 3)
        path = tmp_path / "p.csv"
        np.savetxt(path, data, delimiter=",", fmt="%.17g")
        schema = Schema.of(*[(f"c{i}", "double") for i in range(3)])
        native_chunks = list(CsvSource(str(path), schema).read_chunks(33))
        monkeypatch.setenv("FLINK_ML_TPU_NO_NATIVE", "1")
        monkeypatch.setattr(native, "_tried", False)
        monkeypatch.setattr(native, "_lib", None)
        pure_chunks = list(CsvSource(str(path), schema).read_chunks(33))
        monkeypatch.setattr(native, "_tried", False)
        monkeypatch.setattr(native, "_lib", None)
        assert len(native_chunks) == len(pure_chunks)
        for cn, cp in zip(native_chunks, pure_chunks):
            for c in schema.field_names:
                np.testing.assert_array_equal(
                    np.asarray(cn.col(c)), np.asarray(cp.col(c))
                )

    def test_hex_and_nan_payload_route_to_fallback_error(self, tmp_path):
        """strtod-only forms (hex floats, nan(payload)) must not silently
        parse: the stream falls back to the pure parser, which raises the
        same error read() raises."""
        path = tmp_path / "h.csv"
        path.write_text("1.0,2.0\n0x10,3.0\n")
        schema = Schema.of(("a", "double"), ("b", "double"))
        src = CsvSource(str(path), schema)
        with pytest.raises(ValueError):
            src.read()
        with pytest.raises(ValueError):
            list(src.read_chunks(10))

    def test_blank_first_line_consumed_as_header(self, tmp_path):
        """Pure csv.reader treats physical row 0 as the header even when
        blank; the native stream must match (same rows, same errors)."""
        path = tmp_path / "bh.csv"
        path.write_bytes(b"\na,b\n1,2\n")
        schema = Schema.of(("a", "double"), ("b", "double"))
        src = CsvSource(str(path), schema, skip_header=True)
        with pytest.raises(ValueError):
            src.read()  # 'a' is a data row once the blank header is skipped
        with pytest.raises(ValueError):
            list(src.read_chunks(10))

    def test_out_of_range_index_raises_like_pure_path(self, tmp_path):
        path = tmp_path / "oor.svm"
        path.write_text("1 7:2.0\n")
        src = LibSvmSource(str(path), n_features=3)
        with pytest.raises(ValueError, match="out of range|declared size"):
            list(src.read_chunks(10))

    def test_stream_generators_free_eof_buffers(self, tmp_path):
        """Exhausting the streams must not leak the EOF call's buffers
        (smoke: run many iterations; correctness asserted by valgrind-less
        proxy — the wrappers call fml_free on the n==0 path)."""
        path = tmp_path / "t.csv"
        path.write_text("1.0,2.0\n")
        schema = Schema.of(("a", "double"), ("b", "double"))
        for _ in range(50):
            assert sum(c.num_rows() for c in CsvSource(str(path), schema).read_chunks(4)) == 1


class TestBuiltFromTheseSources:
    """The .so is trusted by the digest of the sources it was built from,
    recorded beside it — never by mtime, which a copied tree loses."""

    def test_recorded_digest_matches_the_sources(self):
        assert native.available()
        with open(native._DIGEST) as f:
            assert f.read().strip() == native._source_digest()

    def test_a_foreign_so_is_rebuilt(self, monkeypatch):
        import shutil

        if not (shutil.which("make") and shutil.which("g++")):
            pytest.skip("no compiler to rebuild with")
        assert native.available()
        # an .so whose recorded digest is not these sources' (e.g. an
        # ignored file that rode along a copy, newer than everything)
        with open(native._DIGEST, "w") as f:
            f.write("built-from-something-else\n")
        os.utime(native._SO, None)
        builds = []
        real_run = native.subprocess.run

        def counting_run(cmd, **kw):
            builds.append(cmd)
            return real_run(cmd, **kw)

        monkeypatch.setattr(native.subprocess, "run", counting_run)
        # plain assignment, not monkeypatch: the handle to the rebuilt
        # library is the one the rest of the suite must keep using
        native._tried, native._lib = False, None
        assert native._load() is not None
        assert builds and "-B" in builds[0]
        with open(native._DIGEST) as f:
            assert f.read().strip() == native._source_digest()
