"""ctypes bindings for the native ingestion library (loader.cpp).

The shared library is built lazily on first use (``make`` in this directory,
redone whenever the recorded digest of its sources differs) and the table
sources fall back to pure Python when it is unavailable —
``FLINK_ML_TPU_NO_NATIVE=1`` forces the fallback.  API consumed by
``flink_ml_tpu.table.sources._native_lib``:

  available() -> bool
  read_csv(path, delimiter, skip_header, arity) -> list[list[str]] | None
      (None = input not representable in the native transport — control
      bytes inside quoted cells — caller must fall back to the pure parser)
  read_libsvm(path, n_features, zero_based) -> (labels ndarray, CsrRows)
  count_ids(ids, dim) -> int64 ndarray | None  (a threaded bincount)

Streaming (bounded memory — the out-of-core path):

  iter_csv_doubles(path, delimiter, skip_header, arity, max_rows)
      -> yields (rows, arity) float64 ndarrays; raises NativeFallback on the
      first non-numeric cell with .rows_delivered so the caller can resume
      the pure parser from that row
  iter_libsvm_chunks(path, n_features, zero_based, max_rows)
      -> yields raw CSR chunks (labels, indptr, indices, values)
"""

from __future__ import annotations

import ctypes
import hashlib
import os
import subprocess
import threading
from typing import List, Optional

import numpy as np

_DIR = os.path.dirname(os.path.abspath(__file__))
_SO = os.path.join(_DIR, "libflinkmltpu.so")
_DIGEST = _SO + ".digest"

_lock = threading.Lock()
_lib = None
_tried = False


def _source_digest() -> str:
    h = hashlib.sha256()
    for name in ("loader.cpp", "Makefile"):
        with open(os.path.join(_DIR, name), "rb") as f:
            h.update(f.read())
    return h.hexdigest()


def _load():
    global _lib, _tried
    with _lock:
        if _tried:
            return _lib
        _tried = True
        if os.environ.get("FLINK_ML_TPU_NO_NATIVE"):
            return None
        # rebuild whenever the .so was not built from THESE sources: the
        # digest of loader.cpp + Makefile it was built from is recorded
        # beside it.  Not by mtime — a copied or exported tree does not
        # preserve mtimes, and an ignored .so can ride along with sources
        # it no longer matches.  make -B for the same reason (make's own
        # staleness test is the mtime one).
        want = _source_digest()
        try:
            with open(_DIGEST) as f:
                have = f.read().strip()
        except OSError:
            have = None
        if not os.path.exists(_SO) or have != want:
            try:
                subprocess.run(
                    ["make", "-B", "-C", _DIR],
                    check=True,
                    capture_output=True,
                    timeout=120,
                )
                with open(_DIGEST, "w") as f:
                    f.write(want + "\n")
            except (OSError, subprocess.SubprocessError):
                # no compiler / read-only install: a prebuilt .so keeps
                # serving, a missing one selects the pure-Python parsers
                if not os.path.exists(_SO):
                    return None
        try:
            lib = ctypes.CDLL(_SO)
        except OSError:
            return None
        lib.fml_read_csv.restype = ctypes.POINTER(ctypes.c_char)
        lib.fml_read_csv.argtypes = [
            ctypes.c_char_p, ctypes.c_char, ctypes.c_int,
            ctypes.POINTER(ctypes.c_int64),
        ]
        lib.fml_read_libsvm.restype = ctypes.c_int
        lib.fml_read_libsvm.argtypes = [
            ctypes.c_char_p, ctypes.c_int,
            ctypes.POINTER(ctypes.POINTER(ctypes.c_double)),
            ctypes.POINTER(ctypes.POINTER(ctypes.c_int64)),
            ctypes.POINTER(ctypes.POINTER(ctypes.c_int64)),
            ctypes.POINTER(ctypes.POINTER(ctypes.c_double)),
            ctypes.POINTER(ctypes.c_int64),
            ctypes.POINTER(ctypes.c_int64),
            ctypes.POINTER(ctypes.c_int64),
        ]
        lib.fml_free.restype = None
        lib.fml_free.argtypes = [ctypes.c_void_p]
        # the streaming symbols arrived later: a stale prebuilt .so (no
        # compiler to rebuild) must keep the whole-file fast paths working
        # and only lose streaming, not all native acceleration
        try:
            lib.fml_open_libsvm_stream.restype = ctypes.c_void_p
            lib.fml_open_libsvm_stream.argtypes = [ctypes.c_char_p, ctypes.c_int]
            lib.fml_next_libsvm_chunk.restype = ctypes.c_int64
            lib.fml_next_libsvm_chunk.argtypes = [
                ctypes.c_void_p, ctypes.c_int64,
                ctypes.POINTER(ctypes.POINTER(ctypes.c_double)),
                ctypes.POINTER(ctypes.POINTER(ctypes.c_int64)),
                ctypes.POINTER(ctypes.POINTER(ctypes.c_int64)),
                ctypes.POINTER(ctypes.POINTER(ctypes.c_double)),
                ctypes.POINTER(ctypes.c_int64),
                ctypes.POINTER(ctypes.c_int64),
            ]
            lib.fml_close_libsvm_stream.restype = None
            lib.fml_close_libsvm_stream.argtypes = [ctypes.c_void_p]
            lib.fml_open_csv_stream.restype = ctypes.c_void_p
            lib.fml_open_csv_stream.argtypes = [
                ctypes.c_char_p, ctypes.c_char, ctypes.c_int,
            ]
            lib.fml_next_csv_doubles.restype = ctypes.c_int64
            lib.fml_next_csv_doubles.argtypes = [
                ctypes.c_void_p, ctypes.c_int64, ctypes.c_int64,
                ctypes.POINTER(ctypes.POINTER(ctypes.c_double)),
            ]
            lib.fml_close_csv_stream.restype = None
            lib.fml_close_csv_stream.argtypes = [ctypes.c_void_p]
            lib._fml_streaming = True
        except AttributeError:
            lib._fml_streaming = False
        try:  # likewise the count
            lib.fml_count_ids.restype = ctypes.c_int64
            lib.fml_count_ids.argtypes = [
                ctypes.c_void_p, ctypes.c_int64, ctypes.c_int64,
                ctypes.c_void_p,
            ]
            lib._fml_count = True
        except AttributeError:
            lib._fml_count = False
        _lib = lib
        return _lib


def streaming_available() -> bool:
    lib = _load()
    return lib is not None and getattr(lib, "_fml_streaming", False)


class NativeFallback(Exception):
    """The native numeric-CSV stream hit a non-numeric cell; the caller must
    continue with the pure parser, skipping ``rows_delivered`` rows."""

    def __init__(self, rows_delivered: int):
        super().__init__(f"non-numeric cell after {rows_delivered} rows")
        self.rows_delivered = rows_delivered


def available() -> bool:
    return _load() is not None


def count_ids(ids: np.ndarray, dim: int) -> Optional[np.ndarray]:
    """``np.bincount(ids, minlength=dim)`` of int32 ids, all in ``[0,
    dim)``, counted in chunks on the machine's cores (the native call holds
    no GIL; ``np.bincount`` holds it, so threads do not share its work).
    None where the library or its count is not available, or the ids are
    not int32 in one piece of memory: the caller counts them itself."""
    lib = _load()
    if (lib is None or not getattr(lib, "_fml_count", False)
            or ids.dtype != np.int32 or not ids.flags.c_contiguous):
        return None
    from concurrent.futures import ThreadPoolExecutor

    n = len(ids)
    threads = max(1, min(os.cpu_count() or 1, n >> 20))
    cuts = np.linspace(0, n, threads + 1).astype(np.int64)

    def count(t):
        counts = np.zeros(dim, np.int64)
        lo, hi = int(cuts[t]), int(cuts[t + 1])
        bad = lib.fml_count_ids(ids[lo:hi].ctypes.data, hi - lo, dim,
                                counts.ctypes.data)
        if bad >= 0:
            raise ValueError(f"feature id {int(ids[lo + bad])} out of range "
                             f"[0, {dim})")
        return counts

    with ThreadPoolExecutor(threads) as pool:
        parts = list(pool.map(count, range(threads)))
    total = parts[0]
    for part in parts[1:]:
        total += part
    return total


def read_csv(path: str, delimiter: str, skip_header: bool, arity: int):
    """Parse via the native loader; None means 'fall back to pure Python'
    (the file contains the transport's control bytes in quoted cells)."""
    lib = _load()
    out_len = ctypes.c_int64(0)
    buf = lib.fml_read_csv(
        path.encode(), delimiter.encode()[:1], 1 if skip_header else 0,
        ctypes.byref(out_len),
    )
    if not buf:
        if out_len.value == -2:
            return None
        raise IOError(f"cannot read {path}")
    try:
        text = ctypes.string_at(buf, out_len.value).decode("utf-8", "replace")
    finally:
        lib.fml_free(buf)
    rows = []
    for i, line in enumerate(text.split("\x1e")):
        if line == "" and i > 0:
            continue  # trailing terminator
        cells = line.split("\x1f")
        if cells == [""]:
            continue  # blank line in the file
        if len(cells) != arity:
            raise ValueError(
                f"{path}: row {i} has {len(cells)} fields, schema expects {arity}"
            )
        rows.append(cells)
    return rows


def read_libsvm(path: str, n_features: Optional[int], zero_based: bool):
    """Whole-file LibSVM parse -> (labels, CsrRows column).

    The CSR column IS the fast representation (lazy SparseVector row views
    for row-level consumers, contiguous arrays for the vectorized packer) —
    no per-row object construction on load.
    """
    from flink_ml_tpu.ops.batch import CsrRows

    lib = _load()
    labels_p = ctypes.POINTER(ctypes.c_double)()
    indptr_p = ctypes.POINTER(ctypes.c_int64)()
    indices_p = ctypes.POINTER(ctypes.c_int64)()
    values_p = ctypes.POINTER(ctypes.c_double)()
    n_rows = ctypes.c_int64(0)
    nnz = ctypes.c_int64(0)
    max_idx = ctypes.c_int64(0)
    rc = lib.fml_read_libsvm(
        path.encode(), 1 if zero_based else 0,
        ctypes.byref(labels_p), ctypes.byref(indptr_p),
        ctypes.byref(indices_p), ctypes.byref(values_p),
        ctypes.byref(n_rows), ctypes.byref(nnz), ctypes.byref(max_idx),
    )
    if rc == -1:
        raise IOError(f"cannot read {path}")
    if rc != 0:
        raise ValueError(f"{path}: malformed libsvm input")
    try:
        nr, nz = n_rows.value, nnz.value
        labels = np.ctypeslib.as_array(labels_p, shape=(max(nr, 1),))[:nr].copy()
        indptr = np.ctypeslib.as_array(indptr_p, shape=(nr + 1,)).copy()
        indices = np.ctypeslib.as_array(indices_p, shape=(max(nz, 1),))[:nz].copy()
        values = np.ctypeslib.as_array(values_p, shape=(max(nz, 1),))[:nz].copy()
    finally:
        lib.fml_free(labels_p)
        lib.fml_free(indptr_p)
        lib.fml_free(indices_p)
        lib.fml_free(values_p)

    dim = n_features if n_features is not None else int(max_idx.value) + 1
    if n_features is not None and nz and int(indices.max()) >= dim:
        raise ValueError(
            f"{path}: feature index {int(indices.max())} out of range for "
            f"declared size {dim}"
        )
    return labels, CsrRows(dim, indptr, indices, values)


def iter_csv_doubles(path: str, delimiter: str, skip_header: bool,
                     arity: int, max_rows: int):
    """Stream an all-numeric CSV as ``(rows, arity)`` float64 chunks.

    On the first non-numeric cell, raises :class:`NativeFallback` carrying
    how many rows were already yielded — the caller resumes the pure parser
    from there (rows consumed by the failed native call re-parse cleanly
    because the fallback re-reads the file).
    """
    lib = _load()
    handle = lib.fml_open_csv_stream(
        path.encode(), delimiter.encode()[:1], 1 if skip_header else 0
    )
    if not handle:
        raise IOError(f"cannot read {path}")
    delivered = 0
    try:
        while True:
            out = ctypes.POINTER(ctypes.c_double)()
            n = lib.fml_next_csv_doubles(handle, max_rows, arity,
                                         ctypes.byref(out))
            if n == -2:
                raise NativeFallback(delivered)
            if n == -1:
                raise MemoryError(f"native CSV chunk alloc failed for {path}")
            if n == 0:
                lib.fml_free(out)  # the EOF call still allocated its buffer
                return
            try:
                chunk = np.ctypeslib.as_array(
                    out, shape=(int(n), arity)
                ).copy()
            finally:
                lib.fml_free(out)
            delivered += int(n)
            yield chunk
    finally:
        lib.fml_close_csv_stream(handle)


def iter_libsvm_chunks(path: str, n_features: int, zero_based: bool,
                       max_rows: int):
    """Stream a LibSVM file as raw CSR chunks
    ``(labels, indptr, indices, values)`` — callers wrap them (CsrRows)
    without any per-row Python."""
    lib = _load()
    handle = lib.fml_open_libsvm_stream(path.encode(), 1 if zero_based else 0)
    if not handle:
        raise IOError(f"cannot read {path}")
    try:
        while True:
            labels_p = ctypes.POINTER(ctypes.c_double)()
            indptr_p = ctypes.POINTER(ctypes.c_int64)()
            indices_p = ctypes.POINTER(ctypes.c_int64)()
            values_p = ctypes.POINTER(ctypes.c_double)()
            nnz = ctypes.c_int64(0)
            max_idx = ctypes.c_int64(0)
            n = lib.fml_next_libsvm_chunk(
                handle, max_rows,
                ctypes.byref(labels_p), ctypes.byref(indptr_p),
                ctypes.byref(indices_p), ctypes.byref(values_p),
                ctypes.byref(nnz), ctypes.byref(max_idx),
            )
            if n == -2:
                raise ValueError(f"{path}: malformed libsvm input")
            if n == -1:
                raise MemoryError(f"native libsvm chunk alloc failed for {path}")
            if n == 0:
                # the EOF call still allocated its (empty) buffers
                for p in (labels_p, indptr_p, indices_p, values_p):
                    lib.fml_free(p)
                return
            try:
                nr, nz = int(n), int(nnz.value)
                labels = np.ctypeslib.as_array(labels_p, shape=(nr,)).copy()
                indptr = np.ctypeslib.as_array(indptr_p, shape=(nr + 1,)).copy()
                indices = np.ctypeslib.as_array(
                    indices_p, shape=(max(nz, 1),)
                )[:nz].copy()
                values = np.ctypeslib.as_array(
                    values_p, shape=(max(nz, 1),)
                )[:nz].copy()
            finally:
                lib.fml_free(labels_p)
                lib.fml_free(indptr_p)
                lib.fml_free(indices_p)
                lib.fml_free(values_p)
            yield labels, indptr, indices, values
    finally:
        lib.fml_close_libsvm_stream(handle)
