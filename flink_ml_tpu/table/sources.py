"""Table sources — bounded and unbounded.

Bounded sources materialize a full columnar Table (the analog of a Flink batch
source feeding `env.readCsvFile`, LinearRegression.java:91-102).  Unbounded
sources yield ``(event_time, row)`` pairs for the streaming driver, which
assigns windows the way IncrementalLearningSkeleton assigns event-time
tumbling windows (IncrementalLearningSkeleton.java:67-68).

CSV and LibSVM parsing route through the native C++ loader when it is built
(``flink_ml_tpu.native``), with a pure-Python fallback.
"""

from __future__ import annotations

import contextlib
import csv
import os
import shutil
import tempfile
from typing import Callable, Iterator, List, Optional, Sequence, Tuple

import numpy as np

from flink_ml_tpu import obs
from flink_ml_tpu.ops.codec import parse_vector
from flink_ml_tpu.ops.vector import SparseVector
from flink_ml_tpu.table.schema import DataTypes, Schema
from flink_ml_tpu.table.table import Table


class BoundedSource:
    """A source whose ``read()`` returns the complete Table.

    ``read_chunks(max_rows)`` is the out-of-core protocol: yield the same
    rows in the same order as ``read()``, as Tables of at most ``max_rows``
    rows each, without ever materializing the full dataset (file sources
    stream; the default slices a materialized read for in-memory sources).
    This is the analog of the reference's partitioned file read
    (LinearRegression.java:91-102 — `env.readCsvFile` produces a partitioned
    DataSet so no node holds the whole input).
    """

    def read(self) -> Table:  # pragma: no cover - interface
        raise NotImplementedError

    def schema(self) -> Schema:  # pragma: no cover - interface
        raise NotImplementedError

    def read_chunks(self, max_rows: int) -> Iterator[Table]:
        if max_rows <= 0:
            raise ValueError("max_rows must be positive")
        table = self.read()
        yield from table.iter_batches(max_rows)


class CollectionSource(BoundedSource):
    def __init__(self, rows: Sequence[Sequence], schema: Schema):
        self._schema = schema
        self._table = Table.from_rows(rows, schema)

    def read(self) -> Table:
        return self._table

    def schema(self) -> Schema:
        return self._schema


class CsvSource(BoundedSource):
    def __init__(
        self,
        path: str,
        schema: Schema,
        delimiter: str = ",",
        skip_header: bool = False,
    ):
        self.path = path
        self._schema = schema
        self.delimiter = delimiter
        self.skip_header = skip_header

    def schema(self) -> Schema:
        return self._schema

    def read(self) -> Table:
        names = self._schema.field_names
        types = self._schema.field_types
        cells = _read_csv_cells(self.path, self.delimiter, self.skip_header, len(names))
        cols = {n: [] for n in names}
        for raw in cells:
            for name, typ, cell in zip(names, types, raw):
                cols[name].append(_parse_cell(cell, typ))
        return Table.from_columns(self._schema, cols)

    def read_chunks(self, max_rows: int) -> Iterator[Table]:
        """Stream the file as Tables of at most ``max_rows`` rows — host
        residency is bounded by one chunk, never the whole file.

        All-float schemas stream through the native C++ doubles parser
        (one (rows, arity) float64 matrix per chunk, no per-cell Python);
        a non-numeric cell mid-stream falls back to the pure parser from
        that exact row.  Other schemas use the same pure-Python parser as
        ``read()``'s fallback (:func:`_iter_csv_rows`), so the streamed and
        materialized row streams cannot drift."""
        if max_rows <= 0:
            raise ValueError("max_rows must be positive")
        names = self._schema.field_names
        types = self._schema.field_types
        skip_rows = 0
        native = _native_lib()
        if native is not None and native.streaming_available() and all(
            t in (DataTypes.DOUBLE, DataTypes.FLOAT) for t in types
        ):
            try:
                for chunk in native.iter_csv_doubles(
                    self.path, self.delimiter, self.skip_header,
                    len(names), max_rows,
                ):
                    yield Table.from_columns(
                        self._schema,
                        {n: chunk[:, j] for j, n in enumerate(names)},
                    )
                return
            except native.NativeFallback as fb:
                skip_rows = fb.rows_delivered  # resume with the pure parser

        cols = {n: [] for n in names}
        count = 0
        for i, raw in enumerate(_iter_csv_rows(
            self.path, self.delimiter, self.skip_header, len(names)
        )):
            if i < skip_rows:
                continue
            for name, typ, cell in zip(names, types, raw):
                cols[name].append(_parse_cell(cell, typ))
            count += 1
            if count == max_rows:
                yield Table.from_columns(self._schema, cols)
                cols = {n: [] for n in names}
                count = 0
        if count:
            yield Table.from_columns(self._schema, cols)


class LibSvmSource(BoundedSource):
    """LibSVM/SVMlight text: ``label idx:val idx:val ...`` with 1-based or
    0-based indices; produces (label DOUBLE, features SPARSE_VECTOR)."""

    def __init__(self, path: str, n_features: Optional[int] = None, zero_based: bool = False):
        self.path = path
        self.n_features = n_features
        self.zero_based = zero_based
        self._schema = Schema(["label", "features"], [DataTypes.DOUBLE, DataTypes.SPARSE_VECTOR])

    def schema(self) -> Schema:
        return self._schema

    def read(self) -> Table:
        native = _native_lib()
        if native is not None:
            labels, vecs = native.read_libsvm(self.path, self.n_features, self.zero_based)
            return Table.from_columns(self._schema, {"label": labels, "features": vecs})
        labels: List[float] = []
        vecs: List = []
        max_idx = -1
        for label, idx, val in _iter_libsvm_rows(self.path, self.zero_based):
            labels.append(label)
            if idx.size:
                max_idx = max(max_idx, int(idx.max()))
            vecs.append((idx, val))
        dim = self.n_features if self.n_features is not None else max_idx + 1
        sparse = [SparseVector(dim, i, v) for i, v in vecs]
        return Table.from_columns(self._schema, {"label": labels, "features": sparse})

    def read_chunks(self, max_rows: int) -> Iterator[Table]:
        """Stream the file as chunks of at most ``max_rows`` rows, via the
        same parser as ``read()``'s pure-Python path (:func:`_iter_libsvm_rows`).

        Requires ``n_features``: the global dimension cannot be inferred
        without a full pass, and out-of-core training must know the model
        width up front (Criteo-style hashed feature spaces fix it anyway).
        """
        if max_rows <= 0:
            raise ValueError("max_rows must be positive")
        if self.n_features is None:
            raise ValueError(
                "chunked LibSVM reads require n_features (the global feature "
                "dimension cannot be inferred without materializing the file)"
            )
        dim = self.n_features
        native = _native_lib()
        if native is not None and native.streaming_available():
            from flink_ml_tpu.ops.batch import CsrRows

            for labels, indptr, indices, values in native.iter_libsvm_chunks(
                self.path, dim, self.zero_based, max_rows
            ):
                # the pure path's SparseVector constructor rejects indices
                # beyond the declared size at parse time; match it
                if indices.size and int(indices.max()) >= dim:
                    raise ValueError(
                        f"{self.path}: feature index {int(indices.max())} out "
                        f"of range for declared size {dim}"
                    )
                # CSR-backed column: zero per-row Python between the C++
                # parser and the vectorized minibatch packer
                rows = CsrRows(dim, indptr, indices, values)
                yield Table.from_columns(
                    self._schema, {"label": labels, "features": rows}
                )
            return
        labels: List[float] = []
        vecs: List[SparseVector] = []
        for label, idx, val in _iter_libsvm_rows(self.path, self.zero_based):
            labels.append(label)
            vecs.append(SparseVector(dim, idx, val))
            if len(labels) == max_rows:
                yield Table.from_columns(
                    self._schema, {"label": labels, "features": vecs}
                )
                labels, vecs = [], []
        if labels:
            yield Table.from_columns(
                self._schema, {"label": labels, "features": vecs}
            )


class ShardedSource(BoundedSource):
    """A bounded source over an ordered list of file shards.

    The analog of the reference reading a directory of part-files as one
    partitioned DataSet: ``read()`` concatenates all shards (only for
    datasets that fit), ``read_chunks`` streams shard after shard so host
    residency stays bounded by one chunk regardless of total size.

    ``ShardedSource.glob(pattern, make_source)`` builds one from a filename
    pattern, sorted for a deterministic row order.
    """

    def __init__(self, sources: Sequence[BoundedSource]):
        if not sources:
            raise ValueError("ShardedSource needs at least one shard")
        schemas = {
            (tuple(s.schema().field_names), tuple(s.schema().field_types))
            for s in sources
        }
        if len(schemas) > 1:
            raise ValueError(f"shard schemas differ: {schemas}")
        self.sources = list(sources)

    def schema(self) -> Schema:
        return self.sources[0].schema()

    def read(self) -> Table:
        return Table.concat([s.read() for s in self.sources])

    def read_chunks(self, max_rows: int) -> Iterator[Table]:
        for source in self.sources:
            yield from source.read_chunks(max_rows)

    @staticmethod
    def glob(pattern: str, make_source: Callable[[str], BoundedSource]) -> "ShardedSource":
        import glob as _glob

        paths = sorted(_glob.glob(pattern))
        if not paths:
            raise FileNotFoundError(f"no files match {pattern!r}")
        return ShardedSource([make_source(p) for p in paths])


class ChunkedTable:
    """A lazy, source-backed table: the out-of-core input to Estimator.fit.

    Wraps a :class:`BoundedSource` plus a chunk-row cap.  Training drivers
    iterate ``chunks()`` (each chunk a bounded materialized Table) and never
    hold more than ~two chunks at once (one being packed, one in flight to
    the device).  ``materialize()`` exists for small-data escape hatches and
    tests — production out-of-core paths must not call it.

    ``spill=True`` lets multi-epoch trainers write packed binary blocks to
    local disk on the first epoch and stream those on later epochs instead
    of re-parsing text (lib/out_of_core.BlockSpill) — one packed copy of
    the dataset on disk buys near-device-rate epochs after the first.
    """

    is_chunked = True

    def __init__(self, source: BoundedSource, chunk_rows: int, spill: bool = False):
        if chunk_rows <= 0:
            raise ValueError("chunk_rows must be positive")
        self.source = source
        self.chunk_rows = int(chunk_rows)
        self.spill = bool(spill)

    @property
    def schema(self) -> Schema:
        return self.source.schema()

    def chunks(self) -> Iterator[Table]:
        if not obs.enabled():
            return self.source.read_chunks(self.chunk_rows)
        return self._counted_chunks()

    def _counted_chunks(self) -> Iterator[Table]:
        for t in self.source.read_chunks(self.chunk_rows):
            obs.counter_add("source.chunks_parsed")
            obs.counter_add("source.rows_parsed", t.num_rows())
            yield t

    def materialize(self) -> Table:
        return self.source.read()

    def __repr__(self) -> str:
        return f"ChunkedTable({type(self.source).__name__}, chunk_rows={self.chunk_rows})"


class TransformedChunkedTable:
    """A ChunkedTable viewed through a Transformer — the lazy forward edge of
    a multi-stage out-of-core pipeline (``Pipeline.fit`` over chunked input).

    Each ``chunks()`` iteration replays the base source and maps the stage's
    ``transform1`` over every chunk, so host residency stays one chunk and
    multi-epoch consumers (trainer drivers) see a re-iterable stream.  With
    ``spill`` on, the *downstream trainer* spills post-transform packed
    blocks, so later epochs skip both the parse and the transform.
    """

    is_chunked = True

    def __init__(self, base, stage):
        self.base = base
        self.stage = stage
        self.chunk_rows = base.chunk_rows
        self.spill = getattr(base, "spill", False)
        self._schema: Optional[Schema] = None

    @property
    def schema(self) -> Schema:
        # the output schema is data-dependent (OutputColsHelper merge), so it
        # is probed by transforming one chunk — once per fit, cached
        if self._schema is None:
            chunks = self.chunks()
            try:
                first = next(iter(chunks), None)
            finally:
                chunks.close()  # release the base source's file handle now
            if first is None:
                raise ValueError("cannot infer schema of an empty chunked table")
            self._schema = first.schema
        return self._schema

    def chunks(self) -> Iterator[Table]:
        # one streamed-transform implementation: the stage's own
        # transform_chunks (the streamed-inference path) is the per-chunk loop
        return self.stage.transform_chunks(self.base)

    def materialize(self) -> Table:
        return self.stage.transform1(self.base.materialize())

    def __repr__(self) -> str:
        return f"TransformedChunkedTable({self.base!r} -> {type(self.stage).__name__})"


class UnboundedSource:
    """A source of timestamped records, consumed by the streaming driver.

    ``stream()`` yields ``(event_time_ms, row_tuple)`` in event-time order per
    producer (the driver handles windowing + watermarks).

    ``stream_chunks()`` is the optional COLUMNAR batch protocol: yield
    ``(ts_array, {col_name: column})`` blocks whose timestamps are
    non-decreasing within and across blocks (vector columns may be
    matrix-backed ``(n, d)`` arrays).  A source that implements it feeds the
    streaming driver's vectorized span path — zero per-record Python on
    ingest.  Return ``None`` (the default) when the source cannot guarantee
    time order; the driver then falls back to the per-record merge loop,
    which handles out-of-order arrival via watermarks/lateness.
    """

    def stream(self) -> Iterator[Tuple[int, Tuple]]:  # pragma: no cover - interface
        raise NotImplementedError

    def schema(self) -> Schema:  # pragma: no cover - interface
        raise NotImplementedError

    def stream_chunks(self, max_rows: int = 8192):
        return None


def columnize_rows(rows: Sequence[Tuple], schema: Schema) -> dict:
    """Row tuples -> columnar dict per the Table column conventions
    (dense-vector columns stack into one matrix when widths agree)."""
    from flink_ml_tpu.ops.vector import DenseVector

    names = schema.field_names
    is_vec = [DataTypes.is_vector(t) for t in schema.field_types]
    if not rows:
        return {n: [] for n in names}
    out = {}
    for n, vec, col in zip(names, is_vec, zip(*rows)):
        if not vec:
            out[n] = np.asarray(col)
            continue
        if col and all(type(v) is DenseVector for v in col):
            try:
                arr = np.asarray([v.values for v in col])
            except ValueError:  # ragged widths refuse to stack
                out[n] = list(col)
                continue
            if arr.ndim == 2:
                out[n] = arr
                continue
        out[n] = list(col)
    return out


def chunk_row_iter(ts, cols, schema: Schema) -> Iterator[Tuple[int, Tuple]]:
    """Decode one columnar chunk back to ``(ts, row_tuple)`` records — the
    per-record fallback view of the chunk protocol."""
    from flink_ml_tpu.ops.vector import DenseVector

    names = schema.field_names
    is_vec = [DataTypes.is_vector(t) for t in schema.field_types]
    mats = []
    for n, vec in zip(names, is_vec):
        col = cols[n]
        if vec and isinstance(col, np.ndarray) and col.ndim == 2:
            mats.append(("mat", col))
        else:
            mats.append(("col", col))
    for i in range(len(ts)):
        row = tuple(
            DenseVector(c[i]) if kind == "mat" else c[i] for kind, c in mats
        )
        yield int(ts[i]), row


class GeneratorSource(UnboundedSource):
    """Wraps a generator function into an unbounded source.

    ``gen`` is called with no args and must yield ``(event_time_ms, row)``.
    A ``linear_timestamps`` helper covers the reference's LinearTimestamp
    assigner (IncrementalLearningSkeleton.java:144-158): record i gets time
    ``i * interval_ms``.

    ``time_ordered=True`` declares the generator yields non-decreasing
    timestamps, unlocking ``stream_chunks`` (batched columnar ingest); the
    driver validates the claim and fails loudly on violation.  NOTE the
    latency trade-off: the chunk view buffers ``chunk_rows`` records before
    the driver sees them, so a LIVE source that trickles records should
    either set ``chunk_rows`` to roughly its expected rows-per-window or
    leave ``time_ordered=False`` (the per-record merge loop fires windows
    at record granularity).  Bounded replays (``linear_timestamps``) have
    no liveness, so buffering costs nothing.
    """

    def __init__(self, gen: Callable[[], Iterator[Tuple[int, Tuple]]], schema: Schema,
                 time_ordered: bool = False, chunk_rows: int = 8192):
        if chunk_rows <= 0:
            raise ValueError("chunk_rows must be positive")
        self._gen = gen
        self._schema = schema
        self._time_ordered = time_ordered
        self.chunk_rows = int(chunk_rows)

    def stream(self) -> Iterator[Tuple[int, Tuple]]:
        return self._gen()

    def schema(self) -> Schema:
        return self._schema

    def stream_chunks(self, max_rows: Optional[int] = None):
        if not self._time_ordered:
            return None
        step = int(max_rows) if max_rows else self.chunk_rows

        def chunks():
            ts_buf: List[int] = []
            rows_buf: List[Tuple] = []
            for ts, row in self._gen():
                ts_buf.append(ts)
                rows_buf.append(tuple(row))
                if len(ts_buf) >= step:
                    yield (np.asarray(ts_buf, np.int64),
                           columnize_rows(rows_buf, self._schema))
                    ts_buf, rows_buf = [], []
            if ts_buf:
                yield (np.asarray(ts_buf, np.int64),
                       columnize_rows(rows_buf, self._schema))

        return chunks()

    @staticmethod
    def linear_timestamps(rows: Sequence[Tuple], interval_ms: int, schema: Schema) -> "GeneratorSource":
        def gen():
            for i, row in enumerate(rows):
                yield i * interval_ms, tuple(row)

        return GeneratorSource(gen, schema, time_ordered=True)


class ColumnarUnboundedSource(UnboundedSource):
    """Time-ordered unbounded source backed by columnar arrays — the
    zero-per-record ingest path for the streaming driver's vectorized span
    processing.  ``columns`` maps schema field names to equal-length
    columns; dense-vector columns may be ``(n, d)`` matrices (zero-copy all
    the way into the window update's ``features_dense``)."""

    def __init__(self, timestamps, columns: dict, schema: Schema,
                 chunk_rows: int = 8192):
        ts = np.asarray(timestamps, np.int64)
        if ts.ndim != 1:
            raise ValueError("timestamps must be 1-D")
        if np.any(np.diff(ts) < 0):
            raise ValueError(
                "ColumnarUnboundedSource requires non-decreasing timestamps "
                "(use a per-record UnboundedSource for out-of-order streams)"
            )
        for name in schema.field_names:
            if name not in columns:
                raise ValueError(f"missing column {name!r}")
            if len(columns[name]) != len(ts):
                raise ValueError(
                    f"column {name!r} length {len(columns[name])} != "
                    f"{len(ts)} timestamps"
                )
        if chunk_rows <= 0:
            raise ValueError("chunk_rows must be positive")
        self._ts = ts
        self._cols = {n: columns[n] for n in schema.field_names}
        self._schema = schema
        self.chunk_rows = int(chunk_rows)

    def schema(self) -> Schema:
        return self._schema

    def stream_chunks(self, max_rows: Optional[int] = None):
        step = int(max_rows) if max_rows else self.chunk_rows

        def chunks():
            for a in range(0, len(self._ts), step):
                b = a + step
                yield (self._ts[a:b],
                       {n: c[a:b] for n, c in self._cols.items()})

        return chunks()

    def stream(self) -> Iterator[Tuple[int, Tuple]]:
        for ts, cols in self.stream_chunks():
            yield from chunk_row_iter(ts, cols, self._schema)


class QueueUnboundedSource(UnboundedSource):
    """Live queue-fed chunk source — the unbounded stream a PROCESS feeds
    while a consumer (the streaming driver, a continuous-learning loop)
    trains from it concurrently.

    ``feed(cols)`` enqueues one time-ordered chunk, auto-timestamped on a
    fixed ``interval_ms`` grid continuing from the previous feed
    (``feed_chunk(ts, cols)`` takes explicit timestamps); ``close()``
    ends the stream.  A consumer blocked between feeds parks on the
    queue — zero CPU — which is what makes this the label-stream shape
    for serving-adjacent training loops.  One-shot, single-consumer.
    """

    def __init__(self, schema: Schema, interval_ms: int = 50):
        import queue

        if interval_ms <= 0:
            raise ValueError("interval_ms must be positive")
        self._schema = schema
        self._interval_ms = int(interval_ms)
        self._q: "queue.Queue" = queue.Queue()
        self._next_ts = 0

    def feed(self, cols: dict) -> None:
        """Enqueue one chunk, timestamped after everything fed so far."""
        n = len(next(iter(cols.values())))
        ts = self._next_ts + np.arange(n, dtype=np.int64) * self._interval_ms
        self.feed_chunk(ts, cols)

    def feed_chunk(self, ts, cols: dict) -> None:
        """Enqueue one chunk with explicit (non-decreasing) timestamps."""
        ts = np.asarray(ts, np.int64)
        if len(ts) == 0:
            return
        if int(ts[0]) < self._next_ts or np.any(np.diff(ts) < 0):
            raise ValueError(
                "fed timestamps must be non-decreasing across feeds "
                "(the chunk protocol's time-order contract)"
            )
        self._next_ts = int(ts[-1]) + self._interval_ms
        self._q.put((ts, cols))

    def close(self) -> None:
        """End the stream: the consumer's iterator finishes after
        draining everything fed before the close."""
        self._q.put(None)

    def schema(self) -> Schema:
        return self._schema

    def stream_chunks(self, max_rows: Optional[int] = None):
        def chunks():
            while True:
                item = self._q.get()
                if item is None:
                    return
                ts, cols = item
                if max_rows is None:
                    yield ts, cols
                    continue
                step = int(max_rows)
                for a in range(0, len(ts), step):
                    b = a + step
                    yield ts[a:b], {k: v[a:b] for k, v in cols.items()}

        return chunks()

    def stream(self) -> Iterator[Tuple[int, Tuple]]:
        for ts, cols in self.stream_chunks():
            yield from chunk_row_iter(ts, cols, self._schema)


# -- helpers -----------------------------------------------------------------


def _native_lib():
    try:
        from flink_ml_tpu import native

        return native if native.available() else None
    except Exception:
        return None


def _iter_csv_rows(path: str, delimiter: str, skip_header: bool, arity: int):
    """The one pure-Python CSV row stream: ``read()`` (native-loader
    fallback) and ``read_chunks`` both consume it, so the materialized and
    streamed row sequences are the same parser's output by construction."""
    with open(path, newline="") as f:
        reader = csv.reader(f, delimiter=delimiter)
        for i, row in enumerate(reader):
            if skip_header and i == 0:
                continue
            if not row:
                continue
            if len(row) != arity:
                raise ValueError(
                    f"{path}: row {i} has {len(row)} fields, schema expects {arity}"
                )
            yield row


def _iter_libsvm_rows(path: str, zero_based: bool):
    """The one pure-Python LibSVM row stream (``label idx:val ...`` with
    ``#`` comments): yields ``(label, indices, values)``; shared by
    ``read()``'s fallback and ``read_chunks``."""
    offset = 0 if zero_based else 1
    with open(path) as f:
        for line in f:
            line = line.split("#", 1)[0].strip()
            if not line:
                continue
            parts = line.split()
            idx = np.array(
                [int(p.split(":", 1)[0]) - offset for p in parts[1:]],
                dtype=np.int64,
            )
            val = np.array([float(p.split(":", 1)[1]) for p in parts[1:]])
            yield float(parts[0]), idx, val


def _read_csv_cells(path: str, delimiter: str, skip_header: bool, arity: int):
    native = _native_lib()
    if native is not None:
        rows = native.read_csv(path, delimiter, skip_header, arity)
        if rows is not None:
            return rows
        # None: input not representable in the native transport (control
        # bytes inside quoted cells) — parse it with the pure reader below
    return list(_iter_csv_rows(path, delimiter, skip_header, arity))


def _parse_cell(cell: str, typ: str):
    cell = cell.strip()
    if typ == DataTypes.STRING:
        return cell
    if cell == "" or cell.lower() == "null":
        return None if typ == DataTypes.STRING else _null_numeric(typ)
    if DataTypes.is_vector(typ):
        return parse_vector(cell)
    if typ == DataTypes.BOOLEAN:
        return cell.lower() in ("true", "1")
    if typ in (DataTypes.INT, DataTypes.LONG):
        return int(cell)
    return float(cell)


def _null_numeric(typ: str):
    return np.nan if typ in (DataTypes.DOUBLE, DataTypes.FLOAT) else 0

def _atomic_np_save(path: str, arr) -> None:
    """Raw .npy write with tmp-file + rename atomicity (shared by the
    packed BlockSpill and the parsed ChunkSpillCache)."""
    tmp = path + ".tmp"
    with open(tmp, "wb") as f:  # file handle: np.save can't rename it
        np.save(f, arr)
    os.replace(tmp, path)


class ChunkSpillCache:
    """Binary replay cache of PARSED source chunks — one text parse total.

    Fit paths with a layout pre-pass (the multi-process shape/count scans,
    the KMeans reservoir init) used to
    read the text source twice before the packed :class:`BlockSpill` took
    over: once to scan, once to pack.  Out-of-core means every pass is a
    full disk/network read — never pay two.  Wrapping the chunked table in
    this cache records each parsed chunk's columns as raw ``.npy`` during
    the FIRST full iteration (the scan), then replays memory-mapped binary
    for every later iteration — the pack pass reads pages, not text.

    Cacheable columns: numeric/bool/string ndarrays, matrix-backed
    dense-vector columns, and CSR-backed sparse columns (``CsrRows``).  A
    chunk with any other column shape (per-row ``SparseVector`` objects,
    ragged widths) disables the cache for the whole stream — consumers
    just re-parse, correctness unaffected.  A partial iteration (sampled
    ``estimate_nnz_pad``, schema peeks) leaves the cache incomplete and is
    re-recorded by the next full pass.

    Disk transiently holds this raw copy alongside the packed BlockSpill;
    both live in per-fit temporary directories (:func:`chunk_cache`), and
    nested caches are suppressed so at most ONE raw copy exists per fit.
    """

    is_chunked = True

    def __init__(self, base, directory: str):
        import os

        self.base = base
        self.chunk_rows = base.chunk_rows
        self.spill = getattr(base, "spill", False)
        self.directory = directory
        os.makedirs(directory, exist_ok=True)
        self._complete = False
        self._disabled = False
        self._chunks: list = []  # per chunk: (schema, [(name, descriptor)])

    @property
    def schema(self):
        return self.base.schema

    def materialize(self):
        return self.base.materialize()

    def chunks(self):
        if self._complete:
            return self._replay()
        if self._disabled:
            return self.base.chunks()
        return self._record()

    def _path(self, i: int, j: int) -> str:
        import os

        return os.path.join(self.directory, f"chunk-{i:06d}-{j:02d}.npy")

    def _record(self):
        # descriptors accumulate LOCALLY and publish to self._chunks only
        # when the base iterator is exhausted: an abandoned partial
        # recording generator (sampled pre-scans, schema peeks) that is
        # later resumed — or a second interleaved chunks() iteration —
        # must never splice its pass's metadata into another pass's replay
        # sequence
        chunks: list = []
        base_iter = self.base.chunks()
        i = 0
        for t in base_iter:
            with obs.phase("spill.record_chunk"):
                descs = self._try_save(t, i)
            if descs is None:
                # uncacheable column shape: disable and keep serving the
                # rest of this pass straight from the same base iterator
                # (chunks already consumed cannot be re-read mid-pass)
                self._disabled = True
                obs.counter_add("spill.cache_disabled")
                yield t
                yield from base_iter
                return
            chunks.append((t.schema, descs))
            obs.counter_add("spill.chunks_recorded")
            i += 1
            yield t
        self._chunks = chunks
        self._complete = True

    def _try_save(self, t: Table, i: int):
        """Per-chunk column descriptors, or None when any column shape is
        uncacheable."""
        from flink_ml_tpu.ops.batch import CsrRows

        descs = []
        j = 0
        for name in t.schema.field_names:
            col = t.col(name)
            if isinstance(col, CsrRows):
                paths = []
                for arr in (col.indptr, col.indices, col.values):
                    p = self._path(i, j)
                    _atomic_np_save(p, np.ascontiguousarray(arr))
                    paths.append(p)
                    j += 1
                descs.append((name, ("csr", col.dim, paths)))
            elif isinstance(col, np.ndarray) and col.dtype != object:
                p = self._path(i, j)
                _atomic_np_save(p, np.ascontiguousarray(col))
                j += 1
                descs.append((name, ("arr", p)))
            elif (
                isinstance(col, np.ndarray) and col.dtype == object
                and len(col) and all(isinstance(x, str) for x in col)
            ):
                # string columns (categorical CSV) promote to fixed-width
                # unicode — npy-serializable, replayed as '<U' arrays that
                # downstream stringify/indexing consume unchanged
                p = self._path(i, j)
                _atomic_np_save(p, np.asarray(col, dtype=str))
                j += 1
                descs.append((name, ("arr", p)))
            else:
                return None
        return descs

    def _replay(self):
        from flink_ml_tpu.ops.batch import CsrRows

        for schema, descs in self._chunks:
            with obs.phase("spill.replay_chunk"):
                cols = {}
                for name, d in descs:
                    if d[0] == "csr":
                        _, dim, paths = d
                        indptr, indices, values = (
                            np.load(p, mmap_mode="r") for p in paths
                        )
                        cols[name] = CsrRows(dim, indptr, indices, values)
                    else:
                        cols[name] = np.load(d[1], mmap_mode="r")
                table = Table.from_columns(schema, cols)
            obs.counter_add("spill.chunks_replayed")
            yield table


def _has_cache_below(table) -> bool:
    """True when the table's base chain already bottoms out in a
    ChunkSpillCache — nesting a second cache would hold another full
    binary copy of the dataset on disk for a transform-replay saving that
    rarely justifies it (the text parse is already amortized)."""
    seen: set = set()
    t = table
    while t is not None and id(t) not in seen:
        if isinstance(t, ChunkSpillCache):
            return True
        seen.add(id(t))
        t = getattr(t, "base", None)
    return False


@contextlib.contextmanager
def chunk_cache(table, enabled: bool = True):
    """Scope a :class:`ChunkSpillCache` over a chunked table for one fit;
    a no-op when ``enabled`` is false, the table is not chunked (or not
    spill-enabled — single-pass fits have nothing to amortize), or a cache
    already exists below it (:func:`_has_cache_below`)."""
    import shutil
    import tempfile

    if (
        not enabled
        or not getattr(table, "is_chunked", False)
        or not getattr(table, "spill", False)
        or _has_cache_below(table)
    ):
        yield table
        return
    directory = tempfile.mkdtemp(prefix="fmt_chunkcache_")
    try:
        yield ChunkSpillCache(table, directory)
    finally:
        shutil.rmtree(directory, ignore_errors=True)
