"""The plain sparse route's frequency split (PR 30): the pack that lays it
(since PR 36 a step's rows in the order of their cold width, the cold
entries plane by plane, the planes' cuts as data), the step that reads it,
the rule that engages it and what it counts.

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
    # rows 3 and 130 hold no hot entry at all: every plane of the cold list
    "all_cold_rows": (480, 3000, 5, False, None, 256),
    "all_cold_rows_ragged": (403, 3000, 6, True, None, 256),
    # the second step of 128 rows holds hot entries only
    "a_step_without_cold": (480, 3000, 5, False, None, 256),
    # the table as the program lays it, 128 x 128: over 16384 features seen
    "whole_hot_table": (25000, 200000, 4, False, None, 16384),
}


def _table(name):
    """The CSR parts of one of ``TABLES`` (and the ids counted hot in it)."""
    rows, dim, width, ragged, dup, k = TABLES[name]
    indptr, ids, values, y = _skewed(rows, dim, width, 13, ragged, dup)
    seen = np.bincount(ids, minlength=dim)
    hot = np.argsort(-seen, kind="stable")[:k]
    if name.startswith("all_cold_rows"):
        # ids no row holds, from the top: at one entry each they lose the
        # tie for the last hot places to the lower ids
        rare = np.flatnonzero(seen == 0)
        for r in (3, 130):
            n = indptr[r + 1] - indptr[r]
            ids[indptr[r]:indptr[r + 1]] = rare[-(r + n):][:n]
    elif name == "a_step_without_cold":
        ids[indptr[128]:indptr[256]] = hot[:5].tolist() * 128
    return indptr, ids, values, y


@pytest.fixture
def measured(monkeypatch):
    """The split's costs count as measured here (as on a TPU)."""
    monkeypatch.setattr(common, "_hot_split_measured", lambda: True)


def _pack(name, n_dev, batch, monkeypatch, split):
    dim, k = TABLES[name][1], TABLES[name][5]
    indptr, ids, values, y = _table(name)
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
    """The table a stack holds, rows in table order: (rows, dim) float64.
    A split stack's through each step's order of rows (the row at each
    place) and the cold planes' starts and lengths."""
    blocks = len(stack.ints)
    out = np.zeros((blocks, stack.mb, stack.dim))
    for g in range(blocks):
        ids = stack.ints[g]
        order = np.arange(stack.mb)
        if stack.hot_ids is not None:
            ids = stack.hot_ids[0][ids]
            order = stack.order[g]
        rows = np.broadcast_to(order, (stack.width, stack.mb))
        np.add.at(out[g], (rows, ids), stack.floats[g, :stack.width])
        if stack.hot_ids is not None:
            for start, length in stack.cold_cuts[g].T:
                plane = slice(start, start + length)
                np.add.at(out[g], (order[:length], stack.cold_idx[g, plane]),
                          stack.cold_vals[g, plane])
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
    # the leaves keep their shapes; the cold list lies plane by plane, its
    # length an odd multiple of 512, the planes' starts and lengths beside it
    blocks = len(split.ints)
    assert split.ints.shape == plain.ints.shape
    assert split.floats.shape == plain.floats.shape
    assert split.hot_ids.shape == (n_dev, k)
    assert split.ints.min() >= 0 and split.ints.max() < k
    assert split.cold_slots % 1024 == 512
    assert split.cold_idx.shape == split.cold_vals.shape == \
        (blocks, split.cold_slots)
    assert split.cold_cuts.shape == (blocks, 2, split.width)
    assert split.cold_cuts.dtype == split.cold_idx.dtype == np.int32
    starts, lengths = split.cold_cuts[:, 0], split.cold_cuts[:, 1]
    # a plane holds the first places, no more than the plane before it, and
    # starts where that one ends: no pad between planes
    assert (lengths >= 0).all() and (lengths <= split.mb).all()
    assert (np.diff(lengths, axis=1) <= 0).all()
    assert (starts[:, 0] == 0).all()
    assert np.array_equal(starts[:, 1:], np.cumsum(lengths, axis=1)[:, :-1])
    held = lengths.sum(axis=1)
    assert held.max() <= split.cold_slots < held.max() + 1024
    for g in range(blocks):  # past the entries: id 0 at value 0.0
        assert not split.cold_idx[g, held[g]:].any()
        assert not split.cold_vals[g, held[g]:].any()
    # a step's order is a permutation of its places, its rows in
    # descending order of cold width, the pad rows of a short step last
    assert np.array_equal(np.sort(split.order, axis=1),
                          np.tile(np.arange(split.mb), (blocks, 1)))
    weights = split.floats[:, split.width + 1]
    assert (np.diff(weights, axis=1) <= 0).all()
    # hot ids: the most frequent features, ties to the lower id
    counts = np.bincount(ids, minlength=split.dim)
    want = np.argsort(-counts, kind="stable")[:k]
    assert np.array_equal(split.hot_ids[0, :len(want)], want)
    assert (split.hot_ids == split.hot_ids[0]).all()
    # every stored entry in exactly one part
    hot_held = int(np.count_nonzero(split.floats[:, :split.width]))
    cold_held = int(held.sum())
    assert cold_held == int(np.count_nonzero(split.cold_vals))
    assert hot_held == split.n_hot_entries
    assert hot_held + cold_held == split.n_entries == len(ids)
    is_hot = np.isin(ids, want)
    assert split.n_hot_entries == int(is_hot.sum())
    if name == "dim_under_k":
        assert cold_held == 0 and split.cold_slots == 512
    else:
        assert 0 < cold_held < len(ids)
    if name.startswith("all_cold_rows"):
        # a row that is all cold leaves no value in the coded planes and
        # has a place in every cold plane up to its own width
        row_width = int(indptr[4] - indptr[3])
        (place,) = np.flatnonzero(split.order[0] == 3)
        assert lengths[0, row_width - 1] > place
        assert not split.floats[0, :split.width, place].any()
    if name == "a_step_without_cold" and n_dev == 1:
        assert held[1] == 0 and not lengths[1].any()
        assert np.array_equal(split.order[1], np.arange(split.mb))
    # and the two parts together are the table
    if name != "whole_hot_table":  # (25000 x 200000 is not laid dense)
        table = np.zeros((split.n_rows, split.dim))
        np.add.at(table, (np.repeat(np.arange(split.n_rows),
                                    np.diff(indptr)), ids), values)
        np.testing.assert_array_equal(_dense(split, n_dev), table)
        np.testing.assert_array_equal(_dense(plain, n_dev), table)
    assert split.step_slots == split.width * split.mb + split.cold_slots


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
    ("logistic", True), ("logistic", False), ("squared", True),
    ("squared", False)])
@pytest.mark.parametrize("name", ["ragged", "dim_under_k", "last_short_step",
                                  "all_cold_rows_ragged",
                                  "a_step_without_cold"])
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
    (0.82, True),     # thinned by a tenth: x3.51 on the chip (PR 36)
    (0.70, True), (0.66, True),   # under the threshold of PR 30's cold list
    (0.55, True),     # thinned by four tenths: x1.84
    (0.35, True), (0.31, True),
    (0.28, False),    # thinned by seven tenths: x1.25, the rule's room
    (0.1, False),     # break-even by the costs
    (0.016, False),   # a table hashed without skew
])
def test_the_rule_by_the_hot_share(hot_share, wins):
    slots = 39 * 32768
    assert common._hot_split_wins(hot_share, slots, slots) is wins


def test_the_rule_reckons_a_cold_slot_at_two_random_accesses():
    """The constant is PR 36's reading of the cold list laid plane by
    plane: about a row-regular slot's two accesses, not segment-COO's
    four."""
    assert common._COLD_SLOT_NS == 13.8
    assert 0.9 < common._COLD_SLOT_NS / common._ELL_SLOT_NS < 1.1
    assert common._HOT_SPLIT_ROOM == 0.8 and common._HOT_SLOT_NS == 1.5


@pytest.mark.parametrize("hot_share,wins", [
    (0.821, True),    # the ragged cell's table at 16384 features (PR 43)
    (0.664, True),    # at 1024
    (0.40, True),
    (0.30, False),    # the rule's room on the classes' slots
    (0.1, False)])
def test_the_rule_on_the_ragged_cells_classed_slots(hot_share, wins):
    """The classes' slots a step (4,069,888 at seed 3405000003) against
    the fullest step's entries (about 4.2 M): the split engages from a hot
    share of 0.33."""
    assert common._hot_split_wins(hot_share, 4_069_888, 4_200_000) is wins


@pytest.mark.parametrize("native_count", [True, False],
                         ids=["threads", "one_thread"])
def test_the_count_on_threads_gives_the_one_thread_count(native_count,
                                                         monkeypatch):
    """The native count (chunks on the machine's cores) and numpy's,
    block by block, pick the same hot ids and the same share."""
    from flink_ml_tpu import native

    monkeypatch.setattr(common, "_HOT_K", 256)
    monkeypatch.setattr(common, "_ORDER_CHECK_BLOCK", 1 << 16)
    dim = 5000
    _indptr, ids, _values, _y = _skewed(40000, dim, 9, 7, ragged=True)
    want = np.bincount(ids, minlength=dim)
    if native_count:
        counted = native.count_ids(ids, dim)
        if not native.available():
            assert counted is None
            pytest.skip("no native library here")
        assert np.array_equal(counted, want)
        with pytest.raises(ValueError, match="out of range"):
            native.count_ids(np.array([3, dim], np.int32), dim)
        assert native.count_ids(ids.astype(np.int64), dim) is None
    else:
        monkeypatch.setattr(native, "count_ids", lambda *a: None)
    hot, share = common._hot_features(ids, dim)
    top = np.argsort(-want, kind="stable")[:256]
    assert np.array_equal(hot, top)
    assert share == want[top].sum() / len(ids)


def test_a_ragged_table_pays_the_hot_lookup_on_every_slot():
    # half the slots hold entries: the cold list is half as long for the
    # same share, and the split wins from a lower share
    slots = 39 * 32768
    assert common._hot_split_wins(0.2, slots, slots // 2)
    assert not common._hot_split_wins(0.2, slots, slots)


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
            assert 0.3 < stack.n_hot_entries / stack.n_entries < 1.0
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
    assert 0.3 < counted["train.sparse_hot_entries"] \
        / counted["train.sparse_entries"] < 1.0
    assert counted["train.sparse_slots"] == \
        (5 * 8 + stack.cold_slots) * blocks * epochs
    # the mechanism's counter: the cold list's slots walked, which over
    # the steps are the fullest step's cold entries rounded by padded_nnz
    held = stack.cold_cuts[:, 1].sum(axis=1)
    assert counted["train.sparse_cold_slots"] == \
        stack.cold_slots * blocks * epochs
    assert counted["train.sparse_cold_slots"] // (blocks * epochs) == \
        common.padded_nnz(int(held.max()), 512)
    assert held.sum() == stack.n_entries - stack.n_hot_entries
    # and the hot kernels' slots: every plane of every step, pads included
    assert counted["train.sparse_hot_slots"] == 5 * 8 * blocks * epochs \
        == stack.hot_slots * epochs
    assert counted["train.sparse_hot_entries"] <= \
        counted["train.sparse_hot_slots"]
    # and the pack's gauges, said once in its own phase
    gauges = obs.registry().snapshot()["gauges"]
    assert gauges["pack_sparse.cold_step_slots"] == stack.cold_slots
    assert gauges["pack_sparse.cold_planes"] == \
        np.count_nonzero(stack.cold_cuts[:, 1].max(axis=0))
    assert 1 <= gauges["pack_sparse.cold_planes"] <= 5
    assert gauges["pack_sparse.ell_step_slots"] == 5 * 8
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
    assert "train.sparse_cold_slots" not in counted
    assert "train.sparse_hot_slots" not in counted
    assert "pack_sparse.cold_step_slots" not in \
        obs.registry().snapshot()["gauges"]
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
    # the cold list's two random-access operations under their names; no
    # sum by row id and no take of the error, which segment-CSR names
    assert scopes == {"fmt.train", "fmt.train.sparse.forward",
                      "fmt.train.sparse.backward", "fmt.train.sparse.hot",
                      "fmt.train.sparse.take_weights",
                      "fmt.train.sparse.scatter",
                      "fmt.train.grad", "fmt.train.psum", "fmt.train.update",
                      "fmt.train.bundle"}
    assert "fmt.train.sparse.hot" in lowered.compile().as_text()
