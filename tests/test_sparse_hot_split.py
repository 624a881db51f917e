"""The plain sparse route's frequency split (PR 30): the pack that lays it,
the step that reads it, the rule that engages it and what it counts.

On the CPU the pack neither counts nor splits (the rule's costs are a TPU's:
``common._hot_split_measured``), so every test here that wants the split
says, in the test, that the costs were measured; the step's two Pallas calls
then run on the interpreter.  ``_HOT_K`` is cut to 256 where a table of a few
hundred features has to have a cold part.
"""

import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from flink_ml_tpu import obs
from flink_ml_tpu.lib import LinearRegression, LogisticRegression, common
from flink_ml_tpu.ops import pallas_kernels
from flink_ml_tpu.ops.batch import CsrRows
from flink_ml_tpu.table.schema import DataTypes, Schema
from flink_ml_tpu.table.table import Table
from flink_ml_tpu.utils.environment import MLEnvironmentFactory

SCHEMA = Schema.of(("features", DataTypes.SPARSE_VECTOR), ("label", "double"))


def _skewed(rows, dim, width, seed, ragged=False, duplicate_in_row=None,
            zipf=1.4):
    """CSR parts with a click log's skew: four entries in five fall on a
    few features by a power law, the fifth anywhere in ``dim``; values off
    every bfloat16 point."""
    rng = np.random.RandomState(seed)
    counts = rng.randint(1, width + 1, rows) if ragged else \
        np.full(rows, width)
    indptr = np.concatenate([[0], np.cumsum(counts)]).astype(np.int64)
    ids = np.where(rng.rand(indptr[-1]) < 0.8,
                   (rng.zipf(zipf, indptr[-1]) - 1) * 7919 % dim,
                   rng.randint(0, dim, indptr[-1])).astype(np.int32)
    if duplicate_in_row is not None:
        lo = indptr[duplicate_in_row]
        ids[lo + 1] = ids[lo]
    values = (rng.randn(indptr[-1]) * 1.2345678).astype(np.float32)
    y = (rng.rand(rows) < 0.35).astype(np.float64)
    return indptr, ids, values, y


TABLES = {
    # name: (rows, dim, width, ragged, duplicate_in_row, hot k)
    "uniform_width": (480, 3000, 5, False, None, 256),
    "ragged": (480, 3000, 6, True, None, 256),
    "duplicate_id": (480, 3000, 5, False, 7, 256),
    "dim_under_k": (480, 200, 5, False, None, 256),   # an empty cold list
    "last_short_step": (403, 3000, 5, True, None, 256),
    # the table as the program lays it, 128 x 128: over 16384 features seen
    "whole_hot_table": (25000, 200000, 4, False, None, 16384),
}


@pytest.fixture
def measured(monkeypatch):
    """The split's costs count as measured here (as on a TPU)."""
    monkeypatch.setattr(common, "_hot_split_measured", lambda: True)


def _pack(name, n_dev, batch, monkeypatch, split):
    rows, dim, width, ragged, dup, k = TABLES[name]
    indptr, ids, values, y = _skewed(rows, dim, width, 13, ragged, dup)
    monkeypatch.setattr(common, "_HOT_K", k)
    monkeypatch.setattr(common, "_hot_split_measured", lambda: split)
    monkeypatch.setattr(common, "_hot_split_wins", lambda *a: True)
    stack = common.pack_sparse_minibatches(
        CsrRows(dim, indptr, ids, values), y, n_dev, batch, dim=dim,
        row_regular=True)
    assert isinstance(stack, common.EllMinibatchStack)
    assert (stack.hot_ids is not None) == split
    return stack, (indptr, ids, values, y)


def _dense(stack, n_dev):
    """The table a stack holds, rows in table order: (rows, dim) float64."""
    blocks = len(stack.ints)
    out = np.zeros((blocks, stack.mb, stack.dim))
    rows = np.broadcast_to(np.arange(stack.mb), (stack.width, stack.mb))
    for g in range(blocks):
        ids = stack.ints[g]
        if stack.hot_ids is not None:
            ids = stack.hot_ids[0][ids]
        np.add.at(out[g], (rows, ids), stack.floats[g, :stack.width])
        if stack.hot_ids is not None:
            kept = stack.cold_ints[g, 1] < stack.mb
            np.add.at(out[g], (stack.cold_ints[g, 1, kept],
                               stack.cold_ints[g, 0, kept]),
                      stack.cold_vals[g, kept])
    # block g = device k, local step s; table order is step-major
    out = out.reshape(n_dev, stack.steps, stack.mb, stack.dim)
    return out.transpose(1, 0, 2, 3).reshape(-1, stack.dim)[:stack.n_rows]


@pytest.mark.parametrize("n_dev", [1, 2])
@pytest.mark.parametrize("name", sorted(TABLES))
def test_the_split_pack_holds_every_entry_once_and_restores_the_table(
        name, n_dev, monkeypatch):
    batch = 25000 if name == "whole_hot_table" else 128
    split, parts = _pack(name, n_dev, batch, monkeypatch, True)
    plain, _ = _pack(name, n_dev, batch, monkeypatch, False)
    indptr, ids, values, _y = parts
    k = TABLES[name][5]
    # the leaves keep their shapes; the cold list is segment-COO, row-major
    assert split.ints.shape == plain.ints.shape
    assert split.floats.shape == plain.floats.shape
    assert split.hot_ids.shape == (n_dev, k)
    assert split.ints.min() >= 0 and split.ints.max() < k
    assert split.cold_pad % 512 == 0 and split.cold_pad >= 512
    assert split.cold_ints.shape == (len(split.ints), 2, split.cold_pad)
    assert split.cold_vals.shape == (len(split.ints), split.cold_pad)
    assert (np.diff(split.cold_ints[:, 1, :].astype(np.int64)) >= 0).all()
    # hot ids: the most frequent features, ties to the lower id
    counts = np.bincount(ids, minlength=split.dim)
    want = np.argsort(-counts, kind="stable")[:k]
    assert np.array_equal(split.hot_ids[0, :len(want)], want)
    assert (split.hot_ids == split.hot_ids[0]).all()
    # every stored entry in exactly one part
    hot_held = int(np.count_nonzero(split.floats[:, :split.width]))
    cold_held = int((split.cold_ints[:, 1] < split.mb).sum())
    assert hot_held == split.n_hot_entries
    assert hot_held + cold_held == split.n_entries == len(ids)
    is_hot = np.isin(ids, want)
    assert split.n_hot_entries == int(is_hot.sum())
    if name == "dim_under_k":
        assert cold_held == 0
    else:
        assert 0 < cold_held < len(ids)
    # and the two parts together are the table
    if name != "whole_hot_table":  # (25000 x 200000 is not laid dense)
        table = np.zeros((split.n_rows, split.dim))
        np.add.at(table, (np.repeat(np.arange(split.n_rows),
                                    np.diff(indptr)), ids), values)
        np.testing.assert_array_equal(_dense(split, n_dev), table)
        np.testing.assert_array_equal(_dense(plain, n_dev), table)
    assert split.step_slots == split.width * split.mb + split.cold_pad


@pytest.mark.parametrize("with_intercept", [True, False],
                         ids=["intercept", "no_intercept"])
@pytest.mark.parametrize("kind", ["logistic", "squared"])
@pytest.mark.parametrize("name", sorted(TABLES))
def test_the_split_step_equals_the_row_regular_step(
        name, kind, with_intercept, monkeypatch):
    """One minibatch gradient, split and unsplit, every step of the table."""
    batch = 25000 if name == "whole_hot_table" else 128
    split, _ = _pack(name, 1, batch, monkeypatch, True)
    plain, _ = _pack(name, 1, batch, monkeypatch, False)
    dim = split.dim
    rng = np.random.RandomState(3)
    params = (jnp.asarray(0.1 * rng.randn(dim), jnp.float32),
              jnp.asarray(0.3, jnp.float32))
    step_plain = common.make_ell_mb_grad_step(
        kind, plain.mb, plain.width, dim, with_intercept)
    step_split = common.make_hot_ell_grad_step(
        kind, split.mb, split.width, dim, with_intercept, interpret=True)
    batch = tuple(jnp.asarray(a) for a in split.batch)
    for g in range(len(plain.ints)):
        (gw_p, gb_p), loss_p, w_p = step_plain(
            params, (jnp.asarray(plain.ints[g]), jnp.asarray(plain.floats[g])))
        (gw_s, gb_s), loss_s, w_s = step_split(params, batch, g)
        assert gw_s.dtype == jnp.float32 and gw_s.shape == (dim,)
        # float32 sums in another order: to 1e-6 of the largest sum, a
        # step of 128 rows (1e-5 where a step sums 25000)
        tol = 1e-5 if name == "whole_hot_table" else 1e-6
        for got, want in ((gw_s, gw_p), (gb_s, gb_p), (loss_s, loss_p)):
            scale = max(1.0, float(jnp.max(jnp.abs(want))))
            assert float(jnp.max(jnp.abs(got - want))) <= tol * scale
        assert float(w_s) == float(w_p)
        if not with_intercept:
            assert float(gb_s) == 0.0


def _fit(stack, kind, with_intercept=True, epochs=3):
    mesh = MLEnvironmentFactory.get_default().get_mesh()
    start = (jnp.zeros((stack.dim,), jnp.float32),
             jnp.zeros((), jnp.float32))
    return common.train_glm_sparse(start, stack, kind, mesh, 0.5, epochs,
                                   with_intercept=with_intercept)


def _mesh_devices():
    return len(MLEnvironmentFactory.get_default().get_mesh().devices.flat)


@pytest.mark.parametrize("kind,with_intercept", [
    ("logistic", True), ("logistic", False), ("squared", True)])
@pytest.mark.parametrize("name", ["ragged", "dim_under_k", "last_short_step"])
def test_a_three_epoch_split_fit_equals_the_row_regular_fit(
        name, kind, with_intercept, monkeypatch):
    n_dev = _mesh_devices()
    split, _ = _pack(name, n_dev, 16 * n_dev, monkeypatch, True)
    plain, _ = _pack(name, n_dev, 16 * n_dev, monkeypatch, False)
    a = _fit(split, kind, with_intercept)
    b = _fit(plain, kind, with_intercept)
    np.testing.assert_allclose(a.params[0], b.params[0], rtol=1e-6, atol=1e-6)
    np.testing.assert_allclose(a.params[1], b.params[1], rtol=1e-6, atol=1e-6)
    np.testing.assert_allclose(a.losses, b.losses, rtol=1e-6, atol=1e-6)
    assert a.epochs == b.epochs == 3
    # and a repeated split fit returns the same bytes
    again = _fit(split, kind, with_intercept)
    assert np.asarray(again.params[0]).tobytes() == \
        np.asarray(a.params[0]).tobytes()


def test_one_piece_of_three_is_caught(monkeypatch):
    """The bfloat16 control: with the float32's first piece alone in both
    products the fit leaves the float32 fit by far more than rounding."""
    n_dev = _mesh_devices()
    split, _ = _pack("ragged", n_dev, 16 * n_dev, monkeypatch, True)
    plain, _ = _pack("ragged", n_dev, 16 * n_dev, monkeypatch, False)
    want = np.asarray(_fit(plain, "logistic").params[0])

    def gap(result):
        return np.linalg.norm(np.asarray(result.params[0]) - want) \
            / np.linalg.norm(want)

    assert gap(_fit(split, "logistic")) < 1e-6
    three = pallas_kernels._f32_pieces

    def first_piece_only(x):
        top = three(x)[0]
        return [top, jnp.zeros_like(top), jnp.zeros_like(top)]

    common._EPOCH_STEP_CACHE.clear()
    jax.clear_caches()
    monkeypatch.setattr(pallas_kernels, "_f32_pieces", first_piece_only)
    try:
        assert gap(_fit(split, "logistic")) > 1e-5
    finally:
        common._EPOCH_STEP_CACHE.clear()
        jax.clear_caches()


def test_the_three_pieces_sum_to_the_float32():
    x = jnp.asarray(np.random.RandomState(0).randn(4096) * 1e3, jnp.float32)
    pieces = pallas_kernels._f32_pieces(x)
    for p in pieces:  # each a bfloat16 value
        assert np.array_equal(np.asarray(p.astype(jnp.bfloat16)
                                         .astype(jnp.float32)), np.asarray(p))
    assert np.array_equal(np.asarray(pieces[0] + pieces[1] + pieces[2]),
                          np.asarray(x))


# -- the rule and its counters -------------------------------------------------


@pytest.mark.parametrize("hot_share,wins", [
    (0.909, True),    # the cell's table at 16384 features
    (0.848, True),    # at 4096
    (0.70, True), (0.66, False),
    (0.6, False),     # faster split by 4%: no room
    (0.3, False), (0.016, False),   # a table hashed without skew
])
def test_the_rule_by_the_hot_share(hot_share, wins):
    slots = 39 * 32768
    assert common._hot_split_wins(hot_share, slots, slots) is wins


def test_a_ragged_table_pays_the_hot_lookup_on_every_slot():
    # half the slots hold entries: the cold list is half as long for the
    # same share, and the split wins from a lower share
    slots = 39 * 32768
    assert common._hot_split_wins(0.5, slots, slots // 2)
    assert not common._hot_split_wins(0.5, slots, slots)


def _uniform(rows, dim, width, seed):
    rng = np.random.RandomState(seed)
    ids = rng.randint(0, dim, rows * width).astype(np.int32)
    indptr = np.arange(rows + 1, dtype=np.int64) * width
    values = rng.randn(rows * width).astype(np.float32)
    y = (rng.rand(rows) < 0.4).astype(np.float64)
    return indptr, ids, values, y


def test_a_skewed_table_engages_and_a_uniform_one_packs_as_the_parent(
        measured, monkeypatch):
    monkeypatch.setattr(common, "_HOT_K", 256)
    for parts, dim, engages in ((_skewed(400, 5000, 5, 3, zipf=2.5), 5000, True),
                                (_uniform(400, 5000, 5, 3), 5000, False)):
        indptr, ids, values, y = parts
        column = CsrRows(dim, indptr, ids, values)
        stack = common.pack_sparse_minibatches(
            column, y, 2, 64, dim=dim, row_regular=True)
        assert (stack.hot_ids is not None) == engages
        assert stack.hot_declined == (not engages)
        if engages:
            assert 0.68 < stack.n_hot_entries / stack.n_entries < 1.0
            continue
        # declined: the parent's leaves, byte for byte
        monkeypatch.setattr(common, "_hot_split_measured", lambda: False)
        parent = common.pack_sparse_minibatches(
            column, y, 2, 64, dim=dim, row_regular=True)
        monkeypatch.setattr(common, "_hot_split_measured", lambda: True)
        assert not parent.hot_declined and parent.hot_ids is None
        assert stack.ints.tobytes() == parent.ints.tobytes()
        assert stack.floats.tobytes() == parent.floats.tobytes()
        assert stack.batch[0] is stack.ints and len(stack.batch) == 2
        assert stack.step_slots == parent.step_slots


def test_off_the_chip_the_pack_neither_counts_nor_splits(monkeypatch):
    assert common._hot_split_measured() is False  # the suite runs on the CPU
    monkeypatch.setattr(common, "_hot_features", lambda *a: 1 / 0)
    indptr, ids, values, y = _skewed(200, 300, 5, 4)
    stack = common.pack_sparse_minibatches(
        CsrRows(300, indptr, ids, values), y, 1, 64, dim=300,
        row_regular=True)
    assert stack.hot_ids is None and not stack.hot_declined


@pytest.fixture
def counters(tmp_path, monkeypatch):
    monkeypatch.setenv("FMT_OBS_REPORTS", str(tmp_path / "reports"))
    obs.reset()
    obs.enable()
    yield lambda: obs.registry().snapshot()["counters"]
    obs.disable()
    obs.reset()


def _estimator(cls, dim, batch, epochs):
    return (cls().set_vector_col("features").set_label_col("label")
            .set_prediction_col("pred").set_num_features(dim)
            .set_global_batch_size(batch).set_learning_rate(0.5)
            .set_max_iter(epochs))


@pytest.mark.parametrize("cls", [LogisticRegression, LinearRegression])
def test_an_estimator_fit_takes_the_split_and_counts_it(
        cls, measured, counters, monkeypatch):
    monkeypatch.setattr(common, "_HOT_K", 256)
    n_dev, epochs, dim = _mesh_devices(), 2, 5000
    indptr, ids, values, y = _skewed(500, dim, 5, 5, zipf=2.5)
    table = Table.from_columns(SCHEMA, {
        "features": CsrRows(dim, indptr, ids, values), "label": y})
    model = _estimator(cls, dim, 8 * n_dev, epochs).fit(table)
    (stack,) = table._pack_cache.values()
    assert stack.hot_ids is not None
    counted = counters()
    blocks = len(stack.ints)
    assert counted["train.sparse_fits"] == counted["train.sparse_ell_fits"] \
        == counted["train.sparse_hot_fits"] == 1
    assert "train.sparse_hot_declined" not in counted
    assert counted["train.sparse_hot_entries"] == stack.n_hot_entries * epochs
    assert counted["train.sparse_entries"] == len(ids) * epochs
    assert 0.68 < counted["train.sparse_hot_entries"] \
        / counted["train.sparse_entries"] < 1.0
    assert counted["train.sparse_slots"] == \
        (5 * 8 + stack.cold_pad) * blocks * epochs
    assert counted["train.pallas_interpreted"] == 1  # on the CPU, and said
    # the unsplit fit of the same table gives the same model (the pool
    # holds placed leaves by the table's content: a process does not change
    # its platform between two fits, a test does)
    from flink_ml_tpu.table import slab_pool

    slab_pool.pool().clear()
    monkeypatch.setattr(common, "_hot_split_measured", lambda: False)
    other = Table.from_columns(SCHEMA, {
        "features": CsrRows(dim, indptr, ids, values), "label": y})
    plain = _estimator(cls, dim, 8 * n_dev, epochs).fit(other)
    np.testing.assert_allclose(model.coefficients(), plain.coefficients(),
                               rtol=1e-6, atol=1e-6)
    counted = counters()
    assert counted["train.sparse_fits"] == 2
    assert counted["train.sparse_hot_fits"] == 1


def test_a_declined_fit_counts_itself_and_runs_the_unsplit_step(
        measured, counters, monkeypatch):
    monkeypatch.setattr(common, "_HOT_K", 256)
    n_dev, dim = _mesh_devices(), 5000
    indptr, ids, values, y = _uniform(500, dim, 5, 6)
    table = Table.from_columns(SCHEMA, {
        "features": CsrRows(dim, indptr, ids, values), "label": y})
    _estimator(LogisticRegression, dim, 8 * n_dev, 1).fit(table)
    counted = counters()
    assert counted["train.sparse_fits"] == counted["train.sparse_ell_fits"] == 1
    assert counted["train.sparse_hot_fits"] == 0  # there from the first fit
    assert counted["train.sparse_hot_declined"] == 1
    assert "train.sparse_hot_entries" not in counted
    assert "train.pallas_interpreted" not in counted
    assert counted["train.sparse_slots"] == 5 * 8 * n_dev * -(-500 // (8 * n_dev))


def test_the_split_program_is_jit_bundled_and_carries_the_hot_scope(
        monkeypatch):
    mesh = MLEnvironmentFactory.get_default().get_mesh()
    n_dev = len(mesh.devices.flat)
    stack, _ = _pack("uniform_width", n_dev, 8 * n_dev, monkeypatch, True)
    fn = common.make_sparse_glm_train_fn(
        "logistic", mesh, stack, 0.125, 0.0, 3, 0.0)
    assert fn.bundle_fetch and fn.loss_hist_len == 3
    assert fn.pallas_interpret is True
    params = (jnp.zeros((stack.dim,), jnp.float32),
              jnp.zeros((), jnp.float32))
    (program,) = [c.cell_contents for c in fn.__closure__
                  if hasattr(c.cell_contents, "lower")]
    lowered = program.lower(params, tuple(jnp.asarray(a)
                                          for a in stack.batch))
    assert lowered.as_text().startswith("module @jit_bundled")
    scopes = set(re.findall(r"fmt\.[a-z_.]+",
                            lowered.as_text(debug_info=True)))
    # the cold list runs segment-CSR's four operations, under their names
    assert scopes == {"fmt.train", "fmt.train.sparse.forward",
                      "fmt.train.sparse.backward", "fmt.train.sparse.hot",
                      "fmt.train.sparse.take_weights",
                      "fmt.train.sparse.row_sum",
                      "fmt.train.sparse.take_error",
                      "fmt.train.sparse.scatter",
                      "fmt.train.grad", "fmt.train.update",
                      "fmt.train.bundle"}
    assert "fmt.train.sparse.hot" in lowered.compile().as_text()
