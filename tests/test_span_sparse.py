"""The spans, scopes and counters of the SPARSE fit route (PR 27), beside
``tests/test_span.py``'s of the dense one:

* one warm ``LogisticRegression.fit`` of a ``CsrRows`` column observes every
  span of the fit path exactly once; the pack lookup and the zero start lie
  inside ``fit.prepare`` (``phase.pack_sparse`` only on the first fit), and
  ``fit.wall``'s children leave no more unnamed than on the dense route;
* ``train.sparse_fits``, ``train.sparse_entries`` (stored entries x epochs)
  and ``train.sparse_slots`` (padded width x steps x epochs) count beside
  ``train.fused_runs``; since PR 28 the table's uniform rows take the
  row-regular step (``train.sparse_ell_fits``; a step's padded width is
  ``width x mb``), and a warm fit observes the spans the parent's did and
  no other;
* the sparse train program is ``jit_bundled`` too, on either step layout,
  and its step carries ``fmt.train.sparse.forward`` and
  ``fmt.train.sparse.backward``;
* the pack's order check, block by block, says what the whole-column check
  said, and lets equal neighbours inside a row stand.
"""

import re

import jax.numpy as jnp
import numpy as np
import pytest

from flink_ml_tpu import obs
from flink_ml_tpu.lib import LogisticRegression, common
from flink_ml_tpu.ops.batch import CsrRows
from flink_ml_tpu.table.schema import DataTypes, Schema
from flink_ml_tpu.table.table import Table
from flink_ml_tpu.utils.environment import MLEnvironmentFactory

SCHEMA = Schema.of(("features", DataTypes.SPARSE_VECTOR), ("label", "double"))
#: fit.wall's direct children: the dense route's, letter for letter
CHILDREN = ("fit.prepare", "slab_pool.lookup", "train.place_params",
            "train.dispatch", "train.sync", "train.demux", "train.health",
            "fit.finish", "fit.report")
MISS_ONLY = ("slab_pool.build", "place.h2d", "phase.pack_sparse",
             "phase.pack_sparse/pack_csr")
SPARSE_SCOPES = {"fmt.train", "fmt.train.sparse.forward",
                 "fmt.train.sparse.backward", "fmt.train.grad", "fmt.train.psum",
                 "fmt.train.update", "fmt.train.bundle"}
#: segment-CSR's four random-access operations, inside forward and backward
#: (PR 33); the row-regular step has one gather and one scatter, unnamed
SEGMENT_SCOPES = {"fmt.train.sparse.take_weights", "fmt.train.sparse.row_sum",
                  "fmt.train.sparse.take_error", "fmt.train.sparse.scatter"}
ROWS, WIDTH, DIM, BATCH, EPOCHS = 2048, 7, 300, 512, 2


@pytest.fixture(autouse=True)
def _isolated(tmp_path, monkeypatch):
    import flink_ml_tpu.obs.report as report_mod

    monkeypatch.setenv("FMT_OBS_REPORTS", str(tmp_path / "reports"))
    monkeypatch.setenv("FMT_TRACE_DIR", str(tmp_path / "traces"))
    obs.disable()
    obs.reset()
    report_mod._PREV_FIT_SNAPSHOT = {"counters": {}, "timings": {}}
    yield
    obs.disable()
    obs.reset()
    report_mod._PREV_FIT_SNAPSHOT = {"counters": {}, "timings": {}}


def _table(seed=3):
    rng = np.random.RandomState(seed)
    indices = np.sort(rng.randint(0, DIM, (ROWS, WIDTH)).astype(np.int32),
                      axis=1)  # a collision inside a row stays two entries
    values = np.full(ROWS * WIDTH, 1.0 / np.sqrt(WIDTH), np.float32)
    indptr = np.arange(ROWS + 1, dtype=np.int64) * WIDTH
    y = (rng.rand(ROWS) < 0.3).astype(np.float64)
    column = CsrRows(DIM, indptr, indices.reshape(-1), values)
    return Table.from_columns(SCHEMA, {"features": column, "label": y})


def _logreg():
    return (LogisticRegression().set_vector_col("features")
            .set_label_col("label").set_prediction_col("pred")
            .set_num_features(DIM).set_global_batch_size(BATCH)
            .set_max_iter(EPOCHS))


def _snapshot():
    snap = obs.registry().snapshot()
    return snap["timings"], snap["counters"]


def test_a_warm_sparse_fit_observes_every_span_once_and_counts_its_entries():
    obs.enable()
    table = _table()
    _logreg().fit(table)  # packs, places, compiles
    first, counted = _snapshot()
    for name in CHILDREN + ("fit.wall",) + MISS_ONLY[:1] + MISS_ONLY[2:]:
        assert first[name]["count"] == 1, name
    assert first["place.h2d"]["count"] == 2  # two leaves: ints and floats
    # the pack runs inside fit.prepare, no longer after it
    assert first["fit.prepare"]["total_s"] >= \
        first["phase.pack_sparse"]["total_s"] > 0
    n_dev = len(MLEnvironmentFactory.get_default().get_mesh().devices.flat)
    steps = -(-ROWS // BATCH)
    # rows of one width: the row-regular step, WIDTH x mb slots a block
    block = WIDTH * (BATCH // n_dev)
    assert counted["train.sparse_fits"] == counted["train.fused_runs"] == 1
    assert counted["train.sparse_ell_fits"] == 1
    assert "train.sparse_ell_declined" not in counted
    assert counted["train.sparse_entries"] == ROWS * WIDTH * EPOCHS
    assert counted["train.sparse_slots"] == block * n_dev * steps * EPOCHS
    assert counted["train.onepass_fits"] == 0

    _logreg().fit(table)  # warm: the cached pack, a pool hit
    warm, counted = _snapshot()
    delta = {k: (v["count"] - first.get(k, {"count": 0})["count"],
                 v["total_s"] - first.get(k, {"total_s": 0.0})["total_s"])
             for k, v in warm.items()}
    for name in CHILDREN + ("fit.wall",):
        assert delta[name][0] == 1, name
    for name in MISS_ONLY:
        assert delta[name][0] == 0, name
    # the spans the parent's warm fit observed, and no other: the layout's
    # choice lies inside the cached pack, so a warm fit scans no row
    assert {k for k, (count, _s) in delta.items() if count} == \
        set(CHILDREN + ("fit.wall",))
    wall, children = delta["fit.wall"][1], sum(delta[c][1] for c in CHILDREN)
    assert wall >= children > 0
    # what no child names: dispatch, sync and the rest are spans, so the
    # fit's own share is small beside them
    assert wall - children < 0.25 * wall
    assert counted["slab_pool.hits"] == counted["slab_pool.misses"] == 1
    assert counted["train.sparse_fits"] == counted["train.sparse_ell_fits"] == 2
    assert counted["train.sparse_entries"] == 2 * ROWS * WIDTH * EPOCHS


def test_a_repeated_sparse_fit_returns_the_same_bytes():
    table = _table()
    a, b = _logreg().fit(table), _logreg().fit(table)
    assert np.array_equal(np.asarray(a.coefficients()),
                          np.asarray(b.coefficients()))
    assert a.intercept() == b.intercept()
    assert list(a.train_losses_) == list(b.train_losses_)


@pytest.mark.parametrize("layout", ["segment_csr", "row_regular"])
def test_the_sparse_train_program_carries_its_scopes_and_its_name(layout):
    mesh = MLEnvironmentFactory.get_default().get_mesh()
    n_dev = len(mesh.devices.flat)
    mb, nnz_pad = 64, 512
    if layout == "segment_csr":
        stack = common.SparseMinibatchStack(
            ints=np.zeros((2 * n_dev, 2, nnz_pad), np.int32),
            floats=np.zeros((2 * n_dev, nnz_pad + 2 * mb), np.float32),
            steps=2, mb=mb, nnz_pad=nnz_pad, dim=DIM)
    else:
        stack = common.EllMinibatchStack(
            ints=np.zeros((2 * n_dev, WIDTH, mb), np.int32),
            floats=np.zeros((2 * n_dev, WIDTH + 2, mb), np.float32),
            steps=2, mb=mb, width=WIDTH, dim=DIM)
    fn = common.make_sparse_glm_train_fn(
        "logistic", mesh, stack, 0.125, 0.0, 3, 0.0)
    batch = (jnp.asarray(stack.ints), jnp.asarray(stack.floats))
    assert fn.bundle_fetch and fn.loss_hist_len == 3
    params = (jnp.zeros((DIM,), jnp.float32), jnp.zeros((), jnp.float32))
    (program,) = [c.cell_contents for c in fn.__closure__
                  if hasattr(c.cell_contents, "lower")]
    lowered = program.lower(params, batch)
    assert lowered.as_text().startswith("module @jit_bundled")
    scopes = SPARSE_SCOPES | (SEGMENT_SCOPES if layout == "segment_csr"
                              else set())
    assert set(re.findall(r"fmt\.[a-z_.]+",
                          lowered.as_text(debug_info=True))) == scopes
    compiled = lowered.compile().as_text()
    assert scopes <= set(re.findall(r"fmt\.[a-z_.]+", compiled))


def _whole_column_check(indptr, indices):
    """The check the blockwise one replaced, with equal neighbours allowed."""
    total = int(indptr[-1])
    if total < 2:
        return False
    same_row = np.ones(total - 1, dtype=bool)
    ends = indptr[1:-1] - 1
    same_row[ends[(ends >= 0) & (ends < total - 1)]] = False
    return bool(np.any((np.diff(indices.astype(np.int64)) < 0) & same_row))


def _csr(rng, rows, dim, sort, empty_share=0.2):
    counts = rng.randint(0, 6, rows) * (rng.rand(rows) > empty_share)
    indptr = np.concatenate([[0], np.cumsum(counts)]).astype(np.int64)
    parts = [rng.randint(0, dim, c) for c in counts]
    if sort:
        parts = [np.sort(p) for p in parts]
    return indptr, (np.concatenate(parts) if parts else
                    np.zeros(0)).astype(np.int32)


@pytest.mark.parametrize("block", [1, 2, 5, 64, 1 << 24])
@pytest.mark.parametrize("sort", [True, False], ids=["sorted", "file-order"])
def test_the_blockwise_order_check_says_what_the_whole_column_check_said(
        block, sort, monkeypatch):
    monkeypatch.setattr(common, "_ORDER_CHECK_BLOCK", block)
    rng = np.random.RandomState(11)
    for rows, dim in ((1, 4), (3, 2), (40, 5), (200, 50)):
        indptr, indices = _csr(rng, rows, dim, sort)
        total = int(indptr[-1])
        got = common._csr_rows_out_of_order(indptr, indices, total)
        assert got == _whole_column_check(indptr, indices), (rows, dim)
        if sort:
            assert got is False  # equal neighbours inside a row stand


def test_a_row_that_falls_at_a_block_edge_is_found(monkeypatch):
    monkeypatch.setattr(common, "_ORDER_CHECK_BLOCK", 4)
    indptr = np.array([0, 4, 8], np.int64)
    # the fall (9 -> 1) is the pair (4, 5): the first pair of block two
    falling = np.array([1, 2, 3, 4, 9, 1, 2, 3], np.int32)
    assert common._csr_rows_out_of_order(indptr, falling, 8) is True
    # the same fall across the row boundary (pair (3, 4)) is no fall
    crossing = np.array([1, 2, 3, 9, 1, 2, 3, 4], np.int32)
    assert common._csr_rows_out_of_order(indptr, crossing, 8) is False
