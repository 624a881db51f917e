"""The plain sparse route on RAGGED tables (PR 33, PR 34): rows whose stored
entry counts differ, made by the benchmark's generator
(``chipbench/data_ragged.py``) and held against its plain reference
(``chipbench/references/csr_glm_sgd.py``).

* ``LogisticRegression.fit`` of a CSR column on the default route agrees with
  the reference on each of the layout rule's three outcomes: a table whose
  widths pass ``_ELL_MAX_SLOT_RATIO`` at one width (row-regular, one class),
  one that fails it at one width and passes it with a step's rows ordered by
  width and laid in a few width classes (row-regular, classed: PR 34), and
  one that fails it either way (segment-CSR, ``ell_declined``); the
  reference's bfloat16 control fails the same tolerances;
* one ragged table laid all three ways by hand gives the same loss and
  gradient within float32 rounding;
* the classed pack holds every stored entry in exactly one slot, a step's
  order of rows both ways beside labels and weights in the table's order,
  one shape for every step, an odd multiple of 512 slots, and the same
  classes for the same seed; a table of
  one width packs as the parent packed it, byte for byte, behind the
  parent's cache key;
* the rule's inputs are on the stack, in the pack's gauges and in
  ``train.sparse_ell_slots_reckoned`` / ``train.sparse_ell_classes``, by the
  widths;
* a fit through the classed layout on the suite's 1-D mesh of CPU devices
  matches the fit on one device;
* segment-CSR's four random-access operations carry their scopes in the
  step's jaxpr; the split step's cold list (laid plane by plane since PR
  36) and the classed step carry the two that remain;
* the classed layout split by frequency (PR 43, forced here as on a TPU,
  ``_HOT_K`` cut to 256): every stored entry in exactly one slot of the hot
  blocks or the cold list, restored through both orders of rows, the
  blocks' schedule and the planes' cuts; its step against the unsplit
  classed step, both losses, with and without intercept; its fit against
  the plain reference and the unsplit fit, the same bytes again; a table
  whose hot share fails the rule keeps the parent's leaves and cache key
  and counts ``train.sparse_hot_declined``; the counters.
"""

import os
import re
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from chipbench import data_ragged, references  # noqa: E402
from flink_ml_tpu import obs  # noqa: E402
from flink_ml_tpu.lib import (  # noqa: E402
    LinearRegression, LogisticRegression, common)
from flink_ml_tpu.ops.batch import CsrRows  # noqa: E402
from flink_ml_tpu.table.schema import DataTypes, Schema  # noqa: E402
from flink_ml_tpu.table.table import Table  # noqa: E402
from flink_ml_tpu.utils.environment import MLEnvironmentFactory  # noqa: E402

SCHEMA = Schema.of(("features", DataTypes.SPARSE_VECTOR), ("label", "double"))
#: a device's step is 512 rows on the suite's eight devices: four lane blocks
ROWS, DIM, BATCH, EPOCHS, LR, REG = 12000, 4000, 4096, 2, 0.5, 1e-4
#: the configuration's laws (``chipbench/configs/url_ragged_lr.json``) ...
RAGGED = {"days": 11, "width_mean_day0": 110.1, "width_growth": 0.10,
          "width_sigma": 0.30, "width_min": 24, "width_max": 512,
          "real_features": 64, "real_share": 0.5, "zipf_exponent": 1.1,
          "vocabulary_day0": 0.2, "label_noise": 0.5, "positive_share": 0.3333}
#: ... the same with widths that hardly differ: one width passes the rule
EVEN = dict(RAGGED, width_sigma=0.02, width_growth=0.0, width_max=128)
#: ... and with widths so spread that every step's widest lane block is 512
#: wide over rows of 24 to 60: the classes fail the rule too
SPIKY = dict(RAGGED, width_sigma=1.5, width_mean_day0=60)
TABLES = {"ragged": RAGGED, "even": EVEN, "spiky": SPIKY}
#: what the pack lays for each on the suite's mesh: the rule's three outcomes
OUTCOME = {"ragged": "classed", "even": "one_width", "spiky": "declined"}
SEGMENT_SCOPES = {"fmt.train.sparse.take_weights", "fmt.train.sparse.row_sum",
                  "fmt.train.sparse.take_error", "fmt.train.sparse.scatter"}
#: The program and the reference add the same float32 products in another
#: order (a sorted segment sum or a sum over the entries' axis against an
#: unsorted scatter-add): both read 1e-7 here.  A step computed in bfloat16
#: reads 2e-4 and 1e-6: each tolerance sits a decade or more over the first
#: and under the second.
COEF_TOL, LOSS_TOL = 1e-5, 4e-7


@pytest.fixture(autouse=True)
def _isolated(tmp_path, monkeypatch):
    monkeypatch.setenv("FMT_OBS_REPORTS", str(tmp_path / "reports"))
    monkeypatch.setenv("FMT_TRACE_DIR", str(tmp_path / "traces"))
    obs.disable()
    obs.reset()
    yield
    obs.disable()
    obs.reset()


def _rows(name, seed=2**31 + 33):
    return data_ragged.make_rows(TABLES[name], ROWS, DIM, seed)


def _table(indptr, indices, values, y):
    column = CsrRows(DIM, indptr, indices, values)
    return Table.from_columns(SCHEMA, {"features": column,
                                       "label": y.astype(np.float64)})


def _logreg():
    return (LogisticRegression().set_vector_col("features")
            .set_label_col("label").set_prediction_col("pred")
            .set_num_features(DIM).set_global_batch_size(BATCH)
            .set_max_iter(EPOCHS).set_learning_rate(LR).set_reg(REG)
            .set_tol(0.0))


def _answer(model):
    return {"coef": np.asarray(model.coefficients(), np.float64),
            "intercept": float(model.intercept()),
            "losses": np.asarray(model.train_losses_, np.float64)}


def _n_dev():
    return len(MLEnvironmentFactory.get_default().get_mesh().devices.flat)


@pytest.mark.parametrize("name", sorted(TABLES))
def test_a_ragged_fit_agrees_with_the_plain_reference_on_either_layout(name):
    obs.enable()
    indptr, indices, values, y = _rows(name)
    widths = np.diff(indptr)
    assert widths.min() < widths.max()  # ragged, all of them
    got = _answer(_logreg().fit(_table(indptr, indices, values, y)))
    counted = obs.registry().snapshot()["counters"]
    assert counted["train.sparse_fits"] == 1
    declined = OUTCOME[name] == "declined"
    assert counted["train.sparse_ell_fits"] == int(not declined)
    assert counted.get("train.sparse_ell_declined", 0) == int(declined)
    classes = counted["train.sparse_ell_classes"]
    assert {"declined": classes == 0, "one_width": classes == 1,
            "classed": classes > 1}[OUTCOME[name]], classes
    reference = references.load("csr_glm_sgd")
    table = reference.Table(indptr, indices, values, y, DIM, BATCH)
    gaps = reference.gaps(got, table.fit(LR, REG, EPOCHS))
    assert gaps["coef_gap"] < COEF_TOL and gaps["loss_gap"] < LOSS_TOL, gaps
    # the precision below the one stated fails at least one of the two
    control = reference.gaps(table.fit(LR, REG, EPOCHS, precision="bf16"),
                             table.fit(LR, REG, EPOCHS))
    assert control["coef_gap"] > COEF_TOL or control["loss_gap"] > LOSS_TOL
    assert control["coef_gap"] > 10 * gaps["coef_gap"]


def _pack(name, n_dev=None, **kwargs):
    indptr, indices, values, y = _rows(name)
    return common.pack_sparse_minibatches(
        CsrRows(DIM, indptr, indices, values), y, n_dev or _n_dev(), BATCH,
        dim=DIM, **kwargs)


def _fit_stack(stack, mesh):
    start = (jnp.zeros((DIM,), jnp.float32), jnp.zeros((), jnp.float32))
    r = common.train_glm_sparse(start, stack, "logistic", mesh, LR, EPOCHS,
                                reg=REG)
    return (np.asarray(r.params[0], np.float64), float(r.params[1]),
            np.asarray(r.losses, np.float64))


def _three_layouts(monkeypatch):
    """The "ragged" table as segment-CSR, in width classes (the pack's own
    choice) and, the rule lifted here only, side by side at the widest."""
    csr, classed = _pack("ragged"), _pack("ragged", row_regular=True)
    monkeypatch.setattr(common, "_ELL_MAX_SLOT_RATIO", 1e9)
    one_width = _pack("ragged", row_regular=True)
    assert type(csr) is common.SparseMinibatchStack
    assert type(classed) is common.ClassedEllMinibatchStack
    assert type(one_width) is common.EllMinibatchStack
    assert csr.n_entries == classed.n_entries == one_width.n_entries
    return csr, classed, one_width


def test_one_ragged_table_laid_three_ways_gives_the_same_sums(monkeypatch):
    mesh = MLEnvironmentFactory.get_default().get_mesh()
    csr, classed, one_width = _three_layouts(monkeypatch)
    assert classed.row_regular and classed.hot_ids is None
    assert classed.ell_classes > 1 == one_width.ell_classes > csr.ell_classes
    assert one_width.width == int(np.diff(_rows("ragged")[0]).max())
    w_a, b_a, l_a = _fit_stack(csr, mesh)
    for stack in (classed, one_width):
        w_b, b_b, l_b = _fit_stack(stack, mesh)
        # float32 rounding of sums taken in another order, nothing more
        assert np.linalg.norm(w_a - w_b) / np.linalg.norm(w_a) < 1e-6
        assert abs(b_a - b_b) < 1e-6
        assert np.allclose(l_a, l_b, rtol=1e-6)


@pytest.mark.parametrize("kind", ["logistic", "squared"])
def test_a_classed_step_gives_the_segment_csr_steps_loss_and_gradient(
        kind, monkeypatch):
    """One step of each layout from the same weights: the squared loss is
    half the weighted sum of squared (score - label), so it holds the scores
    themselves to rounding, the gradient the error's way back."""
    csr, classed, one_width = _three_layouts(monkeypatch)
    rng = np.random.default_rng(34)
    params = (jnp.asarray(rng.normal(0, 0.3, DIM), jnp.float32),
              jnp.asarray(0.25, jnp.float32))
    block = len(csr.ints) - 1  # the last device's last step: pad rows too
    outs = []
    for stack in (csr, classed, one_width):
        _key, step = stack.grad_step(kind)
        (g_w, g_b), loss_sum, w_sum = jax.jit(step)(
            params, tuple(jnp.asarray(leaf[block]) for leaf in stack.batch))
        outs.append((np.asarray(g_w, np.float64), float(g_b),
                     float(loss_sum), float(w_sum)))
    (g_a, b_a, l_a, n_a) = outs[0]
    assert 0 < n_a < csr.mb  # a short step
    for g_b_, b_b, l_b, n_b in outs[1:]:
        assert n_b == n_a
        assert np.linalg.norm(g_a - g_b_) / np.linalg.norm(g_a) < 1e-6
        assert b_b == pytest.approx(b_a, rel=1e-5, abs=1e-5)
        assert l_b == pytest.approx(l_a, rel=1e-6)


def _device_steps(indptr, n_dev, mb, steps):
    """Row bounds of every device step, in the leaves' order (device-major:
    block ``k * steps + s`` is device ``k``'s step ``s``)."""
    n = len(indptr) - 1
    for k in range(n_dev):
        for s in range(steps):
            lo = min(s * n_dev * mb + k * mb, n)
            yield k * steps + s, lo, min(lo + mb, n)


def test_the_classed_pack_holds_every_entry_once_in_its_rows_own_order():
    indptr, indices, values, y = _rows("ragged")
    widths = np.diff(indptr)
    n_dev = _n_dev()
    stack = _pack("ragged", row_regular=True)
    mb, slots = stack.mb, stack.slots
    assert type(stack) is common.ClassedEllMinibatchStack
    # whole lane blocks, widest first, every place in one class
    rows_c = np.array([r for r, _w in stack.classes])
    width_c = np.array([w for _r, w in stack.classes])
    assert rows_c.sum() == mb and not (rows_c % 128).any()
    assert (np.diff(width_c) < 0).all() and width_c.min() >= 1
    # one shape for every step; an odd multiple of 512 slots, a tail of pads
    assert stack.ints.shape == (n_dev * stack.steps, slots + 2 * mb)
    assert stack.floats.shape == (n_dev * stack.steps, slots + 2 * mb)
    assert stack.ints.dtype == np.int32 and stack.floats.dtype == np.float32
    classed = int((rows_c * width_c).sum())
    assert slots % 512 == 0 and (slots // 512) % 2 == 1
    assert 0 <= slots - classed < 1024 and stack.ell_step_slots == classed
    assert stack.step_slots == slots
    assert not stack.ints[:, classed:slots].any()
    assert not stack.floats[:, classed:slots].any()
    seen = 0
    for block, lo, hi in _device_steps(indptr, n_dev, mb, stack.steps):
        m = hi - lo
        # the step's order both ways: rows by descending width, stable,
        # the pad rows of a short step after them where they stood
        order = stack.ints[block, slots : slots + mb]
        place_of = stack.ints[block, slots + mb :]
        assert np.array_equal(order[:m],
                              np.argsort(-widths[lo:hi], kind="stable"))
        assert np.array_equal(order[m:], np.arange(m, mb))
        assert np.array_equal(place_of[order], np.arange(mb))
        # labels and row weights in the table's order, as segment-CSR's
        labels = stack.floats[block, slots : slots + mb]
        weights = stack.floats[block, slots + mb :]
        assert np.array_equal(labels[:m], y[lo:hi].astype(np.float32))
        assert not labels[m:].any()
        assert np.array_equal(weights, (np.arange(mb) < m).astype(np.float32))
        at = place = 0
        for rows, width in stack.classes:
            ids = stack.ints[block, at : at + rows * width].reshape(
                width, rows)
            vals = stack.floats[block, at : at + rows * width].reshape(
                width, rows)
            for p in range(place, min(place + rows, m)):
                row = lo + order[p]
                e0, e1 = indptr[row], indptr[row + 1]
                assert e1 - e0 <= width
                # the row's entries in their stored order, then pads
                assert np.array_equal(ids[: e1 - e0, p - place],
                                      indices[e0:e1])
                assert np.array_equal(vals[: e1 - e0, p - place],
                                      values[e0:e1].astype(np.float32))
                assert not ids[e1 - e0 :, p - place].any()
                assert not vals[e1 - e0 :, p - place].any()
                seen += e1 - e0
            assert not vals[:, max(0, m - place) :].any()  # pad rows
            at += rows * width
            place += rows
    assert seen == indptr[-1] == stack.n_entries
    assert np.count_nonzero(stack.floats[:, :slots]) == indptr[-1]


def test_the_same_seed_gives_the_same_classes_and_leaves():
    first, again = (_pack("ragged", row_regular=True) for _ in range(2))
    assert first.classes == again.classes and first.slots == again.slots
    assert first.ints.tobytes() == again.ints.tobytes()
    assert first.floats.tobytes() == again.floats.tobytes()
    assert first.grad_step("logistic")[0] == again.grad_step("logistic")[0]
    # the classes are the widths', not the values': a table that keeps this
    # one's widths and has every other value of its own cuts the same
    indptr, indices, values, y = _rows("ragged")
    other = common.pack_sparse_minibatches(
        CsrRows(DIM, indptr, (indices[::-1] % DIM).copy(), values[::-1].copy()),
        1.0 - y, _n_dev(), BATCH, dim=DIM, row_regular=True)
    assert other.classes == first.classes
    assert other.ints.tobytes() != first.ints.tobytes()
    # and another seed's widths cut their own
    indptr2, indices2, values2, y2 = _rows("ragged", seed=2**31 + 34)
    another = common.pack_sparse_minibatches(
        CsrRows(DIM, indptr2, indices2, values2), y2, _n_dev(), BATCH,
        dim=DIM, row_regular=True)
    assert type(another) is common.ClassedEllMinibatchStack
    assert another.classes != first.classes


def _parents_bounds(indptr, n_dev, mb, steps):
    return [(lo, hi, int(indptr[lo]), int(indptr[hi])) if hi > lo
            else (lo, lo, 0, 0)
            for _b, lo, hi in sorted(_device_steps(indptr, n_dev, mb, steps))]


@pytest.mark.parametrize("name", ["even", "criteo_like"])
def test_a_table_of_one_width_packs_as_the_parent_packed_it(name):
    """Byte for byte ``_pack_ell`` called as the parent's pack calls it, and
    behind the parent's cache key: the program is the parent's."""
    if name == "even":
        indptr, indices, values, y = _rows("even")
    else:  # every row 39 wide, as Criteo's
        rng = np.random.default_rng(39)
        indptr = 39 * np.arange(ROWS + 1, dtype=np.int64)
        indices = rng.integers(0, DIM, 39 * ROWS).astype(np.int32)
        values = np.full(39 * ROWS, 39 ** -0.5, np.float32)
        y = rng.integers(0, 2, ROWS).astype(np.float64)
    column = CsrRows(DIM, indptr, indices, values)
    n_dev = _n_dev()
    stack = common.pack_sparse_minibatches(column, y, n_dev, BATCH, dim=DIM,
                                           row_regular=True)
    assert type(stack) is common.EllMinibatchStack and stack.hot_ids is None
    counts = np.diff(indptr)
    width, mb, steps = int(counts.max()), BATCH // n_dev, -(-ROWS // BATCH)
    want = common._pack_ell(
        column, y, _parents_bounds(indptr, n_dev, mb, steps), counts, width,
        mb, steps, DIM)
    assert (stack.steps, stack.mb, stack.width, stack.dim) == \
        (want.steps, want.mb, want.width, want.dim) == (steps, mb, width, DIM)
    assert stack.ints.tobytes() == want.ints.tobytes()
    assert stack.floats.tobytes() == want.floats.tobytes()
    assert stack.ints.shape == (n_dev * steps, width, mb)
    key, _step = stack.grad_step("logistic")
    assert key == ("sparse-ell", mb, width, DIM)
    assert stack.ell_classes == 1 and stack.ell_step_slots == mb * width


@pytest.mark.parametrize("name", sorted(TABLES))
def test_the_rules_inputs_are_kept_said_and_counted(name):
    obs.enable()
    indptr, indices, values, y = _rows(name)
    widths = np.diff(indptr)
    n_dev, steps = _n_dev(), -(-ROWS // BATCH)
    mb = BATCH // n_dev
    stack = _pack(name, row_regular=True)
    # the fullest device step, rounded up to an odd multiple of the pack's 512
    fullest = max(int(indptr[hi] - indptr[lo])
                  for _b, lo, hi in _device_steps(indptr, n_dev, mb, steps))
    nnz_pad = (-(-fullest // 512) | 1) * 512
    one_width = mb * int(widths.max())
    outcome = OUTCOME[name]
    assert (one_width <= common._ELL_MAX_SLOT_RATIO * nnz_pad) == \
        (outcome == "one_width")
    gauges = obs.registry().snapshot()["gauges"]
    assert gauges["pack_sparse.widest_row"] == widths.max()
    assert gauges["pack_sparse.mean_row"] == pytest.approx(widths.mean())
    assert gauges["pack_sparse.csr_step_slots"] == nnz_pad
    # what the rule reckoned: one width where that passes, else the classes'
    reckoned = gauges["pack_sparse.ell_step_slots"]
    assert reckoned == stack.ell_step_slots
    assert (reckoned <= common._ELL_MAX_SLOT_RATIO * nnz_pad) == \
        (outcome != "declined") == stack.row_regular
    if outcome == "one_width":
        assert reckoned == one_width == stack.step_slots
        assert gauges["pack_sparse.ell_classes"] == 1 == stack.ell_classes
    else:
        assert gauges["pack_sparse.ell_classes"] > 1
        assert nnz_pad < reckoned < one_width
    if outcome == "classed":
        assert gauges["pack_sparse.ell_classes"] == stack.ell_classes
        assert reckoned == sum(r * w for r, w in stack.classes)
        assert 0 <= stack.step_slots - reckoned < 1024
    if outcome == "declined":
        assert stack.ell_declined and stack.ell_classes == 0
        assert stack.widest_row == int(widths.max())
        assert stack.nnz_pad == nnz_pad == stack.step_slots
    # a pack that was not asked reckons nothing
    plain = _pack(name)
    assert plain.widest_row == 0 == plain.ell_step_slots
    assert not plain.ell_declined and plain.ell_classes == 0

    mesh = MLEnvironmentFactory.get_default().get_mesh()
    for s in (stack, plain):
        _fit_stack(s, mesh)
    counted = obs.registry().snapshot()["counters"]
    blocks = n_dev * steps * EPOCHS
    assert counted["train.sparse_fits"] == 2
    assert counted["train.sparse_ell_fits"] == int(outcome != "declined")
    assert counted.get("train.sparse_ell_declined", 0) == \
        int(outcome == "declined")
    assert counted["train.sparse_ell_classes"] == stack.ell_classes
    assert counted["train.sparse_ell_slots_reckoned"] == \
        reckoned * blocks  # the asked pack's fit alone
    assert counted["train.sparse_slots"] == \
        (stack.step_slots + plain.step_slots) * blocks


def test_a_classed_fit_on_the_mesh_matches_the_fit_on_one_device():
    from flink_ml_tpu.parallel.mesh import create_mesh

    mesh = MLEnvironmentFactory.get_default().get_mesh()
    n_dev = len(mesh.devices.flat)
    if n_dev < 2:
        pytest.skip("needs the suite's CPU devices")
    many = _pack("ragged", row_regular=True)
    one = _pack("ragged", n_dev=1, row_regular=True)
    for stack, devices in ((many, n_dev), (one, 1)):
        assert type(stack) is common.ClassedEllMinibatchStack
        assert stack.mb == BATCH // devices
        assert len(stack.ints) == devices * stack.steps
    assert one.ell_classes > many.ell_classes  # 32 lane blocks to cut, not 4
    w_a, b_a, l_a = _fit_stack(many, mesh)
    w_b, b_b, l_b = _fit_stack(one, create_mesh({"data": 1}, jax.devices()[:1]))
    assert np.linalg.norm(w_a - w_b) / np.linalg.norm(w_a) < 1e-6
    assert abs(b_a - b_b) < 1e-6
    assert np.allclose(l_a, l_b, rtol=1e-6)


@pytest.mark.parametrize("nnz_max,floor,expected", [
    (1, 0, 512), (512, 0, 512), (513, 0, 1536), (1024, 0, 1536),
    (1025, 0, 1536), (1537, 0, 2560), (3_970_100, 0, 3_970_560),
    (3_970_600, 0, 3_971_584),  # 7756 blocks of 512: one more, to 7757
    # a floor that processes or chunks agreed on stands, as ever
    (513, 1024, 1024), (513, 512, 1024), (1500, 4096, 4096), (1, 512, 512)])
def test_a_steps_padded_width_is_an_odd_multiple_where_nothing_fixes_it(
        nnz_max, floor, expected):
    assert common.padded_nnz(nnz_max, 512, floor) == expected
    if not floor:
        assert (expected // 512) % 2 == 1 and 0 <= expected - nnz_max < 1024


def _lowered(step, params, xs):
    return jax.jit(step).lower(params, *xs).as_text(debug_info=True)


def test_the_classed_step_carries_the_two_scopes_that_remain():
    classes, mb = ((128, 7), (256, 3)), 384
    slots = common.padded_nnz(128 * 7 + 256 * 3, 512)
    fn = common.make_classed_ell_grad_step("logistic", mb, classes, slots,
                                           DIM)
    params = (jnp.zeros((DIM,), jnp.float32), jnp.zeros((), jnp.float32))
    text = _lowered(fn, params, ((jnp.zeros((slots + 2 * mb,), jnp.int32),
                                  jnp.zeros((slots + 2 * mb,), jnp.float32)),))
    scopes = set(re.findall(r"fmt\.[a-z_.]+", text))
    assert scopes == {"fmt.train.sparse.forward", "fmt.train.sparse.backward",
                      "fmt.train.sparse.take_weights",
                      "fmt.train.sparse.scatter"}
    assert "/fmt.train.sparse.forward/fmt.train.sparse.take_weights/mul" \
        in text
    assert "/fmt.train.sparse.backward/fmt.train.sparse.scatter/scatter-add" \
        in text
    # one take and one scatter over all the slots, no row ids; the two
    # other takes put mb scores into the table's order and mb errors back
    gathers = re.findall(r'"stablehlo\.gather"\(.*-> tensor<(\d+)xf32>', text)
    assert gathers.count(str(slots)) == 1 and set(gathers) == {
        str(slots), str(mb)}
    assert text.count('"stablehlo.scatter"(') == 1


@pytest.mark.parametrize("step", ["segment_csr", "split_cold_list"])
def test_the_random_access_operations_carry_their_scopes(step):
    mb, nnz_pad, width, k = 128, 512, 4, common._HOT_K
    params = (jnp.zeros((DIM,), jnp.float32), jnp.zeros((), jnp.float32))
    if step == "segment_csr":
        fn = common.make_sparse_mb_grad_step("logistic", mb, nnz_pad, DIM)
        xs = ((jnp.zeros((2, nnz_pad), jnp.int32),
               jnp.zeros((nnz_pad + 2 * mb,), jnp.float32)),)
    else:
        fn = common.make_hot_ell_grad_step("logistic", mb, width, DIM,
                                           interpret=True)
        xs = ((jnp.zeros((1, width, mb), jnp.int32),
               jnp.zeros((1, width + 2, mb), jnp.float32),
               jnp.zeros((1, nnz_pad), jnp.int32),
               jnp.zeros((1, nnz_pad), jnp.float32),
               jnp.zeros((1, 2, width), jnp.int32),
               jnp.zeros((1, k), jnp.int32)), jnp.int32(0))
    text = _lowered(fn, params, xs)
    scopes = set(re.findall(r"fmt\.[a-z_.]+", text))
    # segment-CSR runs four; the split step's cold list, laid plane by
    # plane since PR 36, the two that a row-regular layout leaves
    split = step == "split_cold_list"
    assert (SEGMENT_SCOPES <= scopes) == (not split)
    assert ("fmt.train.sparse.hot" in scopes) == split
    assert ("fmt.train.sparse.row_sum" in scopes) == (not split)
    assert ("fmt.train.sparse.take_error" in scopes) == (not split)
    # each inside its half of the step, with the operation it names
    paths = ["forward/fmt.train.sparse.take_weights/mul",
             "backward/fmt.train.sparse.scatter/scatter-add"]
    if not split:
        paths += ["forward/fmt.train.sparse.row_sum/scatter-add",
                  "backward/fmt.train.sparse.take_error/mul"]
    for path in paths:
        assert "/fmt.train.sparse." + path in text, path


# -- the classed layout split by frequency (PR 43) -----------------------------

#: features looked up by comparison on these 4000-feature tables: two rows of
#: the hot table, so that a table has a cold part
HOT_K = 256


@pytest.fixture
def split(monkeypatch):
    """The split's costs count as measured (as on a TPU), K cut to 256."""
    monkeypatch.setattr(common, "_HOT_K", HOT_K)
    monkeypatch.setattr(common, "_hot_split_measured", lambda: True)


def _entries(stack, n_dev):
    """The table a split classed stack holds, as (row, feature, value)
    triplets sorted by row and feature: every slot of the hot blocks read
    through its step's schedule and hot order, every slot of the cold list
    through the planes' cuts and the cold order."""
    out = []
    nb, planes, tile = stack.hot_codes.shape[1:]
    for g in range(len(stack.ints)):
        k, s = divmod(g, stack.steps)
        lo = s * n_dev * stack.mb + k * stack.mb  # the step's first row
        hot_order, cold_order = stack.ints[g, 0], stack.ints[g, 2]
        sched = stack.hot_sched[g]
        for b in np.flatnonzero(sched[0] == np.arange(nb)):
            tile_at = sched[1, b] * tile + np.arange(tile)
            inside = tile_at < stack.mb
            for p in range(planes):
                vals = stack.hot_vals[g, b, p][inside]
                held = vals != 0
                rows = hot_order[tile_at[inside][held]]
                ids = stack.hot_ids[0][stack.hot_codes[g, b, p][inside][held]]
                out.append(np.stack([lo + rows, ids, vals[held]], axis=1))
        for start, length in stack.cold_cuts[g].T:
            plane = slice(start, start + length)
            out.append(np.stack([lo + cold_order[:length],
                                 stack.cold_idx[g, plane],
                                 stack.cold_vals[g, plane]], axis=1))
    triplets = np.concatenate(out)
    return triplets[np.lexsort((triplets[:, 1], triplets[:, 0]))]


@pytest.mark.parametrize("n_dev", [1, 0], ids=["one_device", "mesh"])
def test_the_classed_split_holds_every_entry_once_through_both_orders(
        n_dev, split):
    indptr, indices, values, y = _rows("ragged")
    widths = np.diff(indptr)
    stack = _pack("ragged", n_dev=n_dev or None, row_regular=True)
    n_dev = n_dev or _n_dev()
    assert type(stack) is common.ClassedEllMinibatchStack
    assert stack.hot_ids is not None and not stack.hot_declined
    assert stack.ell_classes > 1 and stack.row_regular
    mb, steps, blocks = stack.mb, stack.steps, len(stack.ints)
    nb, planes, tile = stack.hot_codes.shape[1:]
    assert blocks == n_dev * steps
    # the leaves' shapes: blocks of eight planes of one row tile, their
    # count a multiple of 8; the cold list an odd multiple of 512 slots,
    # its planes a multiple of 128
    assert planes == common._HOT_BLOCK_PLANES == 8
    assert tile == min(1024, mb) and tile % 128 == 0 and nb % 8 == 0
    assert stack.hot_codes.shape == stack.hot_vals.shape == \
        (blocks, nb, planes, tile)
    assert stack.hot_sched.shape == (blocks, 2, nb)
    assert stack.ints.shape == (blocks, 4, mb)
    assert stack.floats.shape == (blocks, 2, mb)
    assert stack.cold_slots % 1024 == 512
    assert stack.cold_cuts.shape[:2] == (blocks, 2)
    assert stack.cold_cuts.shape[2] % 128 == 0
    assert stack.hot_ids.shape == (n_dev, HOT_K)
    for leaf in (stack.hot_codes, stack.hot_sched, stack.cold_idx,
                 stack.cold_cuts, stack.ints):
        assert leaf.dtype == np.int32
    assert 0 <= stack.hot_codes.min() and stack.hot_codes.max() < HOT_K
    # the hot ids: the most frequent features, ties to the lower id
    want = np.argsort(-np.bincount(indices, minlength=DIM),
                      kind="stable")[:HOT_K]
    assert np.array_equal(stack.hot_ids[0], want)
    is_hot = np.isin(indices, want)
    hot_before = np.concatenate([[0], np.cumsum(is_hot)])[indptr]
    walked = 0
    for block, lo, hi in _device_steps(indptr, n_dev, mb, steps):
        m = hi - lo
        hot_w = np.diff(hot_before[lo : hi + 1])
        cold_w = widths[lo:hi] - hot_w
        # each part's order both ways: rows by descending width in it,
        # stable, the pad rows of a short step after them where they stood
        for part, w in ((0, hot_w), (2, cold_w)):
            order = stack.ints[block, part]
            assert np.array_equal(order[:m], np.argsort(-w, kind="stable"))
            assert np.array_equal(order[m:], np.arange(m, mb))
            assert np.array_equal(stack.ints[block, part + 1][order],
                                  np.arange(mb))
        # labels and row weights in the table's order
        assert np.array_equal(stack.floats[block, 0, :m],
                              y[lo:hi].astype(np.float32))
        assert np.array_equal(stack.floats[block, 1],
                              (np.arange(mb) < m).astype(np.float32))
        # the schedule: each row tile's blocks in a run, as many as its
        # widest row fills (at least one), then pads that name the last
        # block and tile again; past the blocks, no value
        sched = stack.hot_sched[block]
        tiles = -(-mb // tile)
        widest = np.zeros(tiles, np.int64)
        if m:
            widest[: -(-m // tile)] = hot_w[
                stack.ints[block, 0, np.arange(0, m, tile)]]
        need = np.maximum(1, -(-widest // planes))
        used = int(need.sum())
        assert np.array_equal(sched[0, :used], np.arange(used))
        assert (sched[0, used:] == used - 1).all()
        assert np.array_equal(sched[1, :used],
                              np.repeat(np.arange(tiles), need))
        assert (sched[1, used:] == tiles - 1).all()
        assert not stack.hot_vals[block, used:].any()
        walked += int(need[widest > 0].sum()) * planes * tile
        # the cold planes: a plane holds the first places and starts where
        # the one before it ends
        starts, lengths = stack.cold_cuts[block]
        assert np.array_equal(lengths, [np.count_nonzero(cold_w > j)
                                        for j in range(len(lengths))])
        assert np.array_equal(starts[1:], np.cumsum(lengths)[:-1])
        assert not stack.cold_vals[block, lengths.sum():].any()
    # every stored entry in exactly one slot, and nothing else held
    held = _entries(stack, n_dev)
    rows = np.repeat(np.arange(ROWS), widths)
    order = np.lexsort((indices, rows))
    assert len(held) == len(indices) == stack.n_entries
    assert np.array_equal(held[:, 0], rows[order])
    assert np.array_equal(held[:, 1], indices[order])
    assert np.array_equal(held[:, 2].astype(np.float32),
                          values[order].astype(np.float32))
    assert stack.n_hot_entries == int(is_hot.sum())
    assert np.count_nonzero(stack.hot_vals) == stack.n_hot_entries
    assert np.count_nonzero(stack.cold_vals) == \
        stack.n_entries - stack.n_hot_entries
    # what the kernels walk: the blocks of tiles that hold a hot entry,
    # pads of a block included
    assert stack.hot_slots == walked
    assert stack.n_hot_entries <= stack.hot_slots
    assert stack.step_slots == nb * planes * tile + stack.cold_slots


def _classed_pair(monkeypatch, n_dev=None, rows=ROWS):
    """The "ragged" table's first ``rows`` rows laid classed, unsplit and
    split."""
    indptr, indices, values, y = _rows("ragged")
    column = CsrRows(DIM, indptr[: rows + 1], indices[: indptr[rows]],
                     values[: indptr[rows]])

    def pack():
        return common.pack_sparse_minibatches(
            column, y[:rows], n_dev or _n_dev(), BATCH, dim=DIM,
            row_regular=True)

    plain = pack()
    monkeypatch.setattr(common, "_HOT_K", HOT_K)
    monkeypatch.setattr(common, "_hot_split_measured", lambda: True)
    split = pack()
    assert plain.hot_ids is None and split.hot_ids is not None
    assert plain.classes == split.classes
    return plain, split


@pytest.mark.parametrize("with_intercept", [True, False],
                         ids=["intercept", "no_intercept"])
@pytest.mark.parametrize("kind", ["logistic", "squared"])
def test_a_classed_split_step_gives_the_classed_steps_loss_and_gradient(
        kind, with_intercept, monkeypatch):
    """Every step of the table from the same weights, split and unsplit:
    the squared loss holds the scores themselves to rounding."""
    plain, split = _classed_pair(monkeypatch)
    _same_steps(plain, split, kind, with_intercept)


def test_a_classed_split_step_over_row_tiles_gives_the_classed_step(
        monkeypatch):
    """On one device a step is four row tiles of 1024 places, and the last
    step's 2808 rows leave its last tile empty: the kernels' blocks change
    tile, and a tile with no row zeroes its scores."""
    plain, split = _classed_pair(monkeypatch, n_dev=1, rows=11000)
    assert split.hot_codes.shape[-1] == 1024 and split.mb == 4096
    last = split.hot_sched[-1]
    assert last[1].max() == 3 and split.ints[-1, 1].max() == 4095
    assert split.floats[-1, 1].sum() == 11000 - 2 * 4096
    _same_steps(plain, split, "squared", True)


def _same_steps(plain, split, kind, with_intercept):
    rng = np.random.default_rng(43)
    params = (jnp.asarray(rng.normal(0, 0.3, DIM), jnp.float32),
              jnp.asarray(0.25, jnp.float32))
    _key, step_plain = plain.grad_step(kind, with_intercept)
    _key, step_split = split.grad_step(kind, with_intercept)
    assert step_split.pallas_interpret is True  # on the CPU, and said
    batch = tuple(jnp.asarray(a) for a in split.batch)
    step_split = jax.jit(step_split)
    for block in range(len(plain.ints)):
        (g_a, b_a), l_a, n_a = step_plain(
            params, tuple(jnp.asarray(leaf[block]) for leaf in plain.batch))
        (g_b, b_b), l_b, n_b = step_split(params, batch, jnp.int32(block))
        assert g_b.dtype == jnp.float32 and g_b.shape == (DIM,)
        assert float(n_b) == float(n_a)
        g_a, g_b = np.asarray(g_a, np.float64), np.asarray(g_b, np.float64)
        assert np.linalg.norm(g_a - g_b) / np.linalg.norm(g_a) < 1e-6
        assert float(b_b) == pytest.approx(float(b_a), rel=1e-5, abs=1e-5)
        assert float(l_b) == pytest.approx(float(l_a), rel=1e-6)
        if not with_intercept:
            assert float(b_b) == 0.0


def test_a_split_classed_fit_agrees_with_the_plain_reference(split):
    obs.enable()
    indptr, indices, values, y = _rows("ragged")
    table = _table(indptr, indices, values, y)
    got = _answer(_logreg().fit(table))
    counted = obs.registry().snapshot()["counters"]
    assert counted["train.sparse_fits"] == counted["train.sparse_hot_fits"] \
        == counted["train.sparse_ell_fits"] == 1
    assert counted["train.sparse_ell_classes"] > 1
    reference = references.load("csr_glm_sgd")
    plain = reference.Table(indptr, indices, values, y, DIM, BATCH)
    gaps = reference.gaps(got, plain.fit(LR, REG, EPOCHS))
    assert gaps["coef_gap"] < COEF_TOL and gaps["loss_gap"] < LOSS_TOL, gaps
    # the precision below the one stated fails at least one of the two
    control = reference.gaps(plain.fit(LR, REG, EPOCHS, precision="bf16"),
                             plain.fit(LR, REG, EPOCHS))
    assert control["coef_gap"] > COEF_TOL or control["loss_gap"] > LOSS_TOL
    # and a repeated fit of the same table returns the same bytes
    again = _answer(_logreg().fit(table))
    assert again["coef"].tobytes() == got["coef"].tobytes()
    assert again["losses"].tobytes() == got["losses"].tobytes()


@pytest.mark.parametrize("kind,with_intercept", [
    ("logistic", True), ("logistic", False), ("squared", True),
    ("squared", False)])
def test_a_split_classed_fit_equals_the_unsplit_classed_fit(
        kind, with_intercept, monkeypatch):
    plain, split = _classed_pair(monkeypatch)
    mesh = MLEnvironmentFactory.get_default().get_mesh()
    start = (jnp.zeros((DIM,), jnp.float32), jnp.zeros((), jnp.float32))
    fits = [common.train_glm_sparse(start, s, kind, mesh, LR, EPOCHS,
                                    reg=REG, with_intercept=with_intercept)
            for s in (plain, split, split)]
    a, b, again = ([np.asarray(r.params[0], np.float64), float(r.params[1]),
                    np.asarray(r.losses, np.float64)] for r in fits)
    assert np.linalg.norm(a[0] - b[0]) / np.linalg.norm(a[0]) < 1e-6
    assert abs(a[1] - b[1]) < 1e-6
    assert np.allclose(a[2], b[2], rtol=1e-6)
    assert b[0].tobytes() == again[0].tobytes()
    assert b[2].tobytes() == again[2].tobytes()


def _uniform_ids(indptr, seed=43):
    """The "ragged" table's widths with ids drawn uniformly, distinct in a
    row: 256 of 4000 features hold about 6% of its entries."""
    rng = np.random.default_rng(seed)
    return np.concatenate([
        np.sort(rng.choice(DIM, w, replace=False)) for w in np.diff(indptr)
    ]).astype(np.int32)


def test_a_classed_table_whose_hot_share_fails_the_rule_packs_as_the_parent(
        split, monkeypatch):
    """On one device, where a step's 32 lane blocks cut into classes that
    walk 1.06 slots for one (on the suite's eight, four blocks walk 1.47,
    and the hot lookup's cheap slots win even a share of 7%)."""
    from flink_ml_tpu.parallel.mesh import create_mesh

    obs.enable()
    indptr, _indices, values, y = _rows("ragged")
    column = CsrRows(DIM, indptr, _uniform_ids(indptr), values)
    stack = common.pack_sparse_minibatches(column, y, 1, BATCH, dim=DIM,
                                           row_regular=True)
    assert type(stack) is common.ClassedEllMinibatchStack
    assert stack.hot_ids is None and stack.hot_declined
    _ids, share = common._hot_features(column.indices, DIM)
    fullest = int(np.diff(indptr[np.minimum(
        np.arange(0, ROWS + BATCH, BATCH), ROWS)]).max())
    assert share < 0.1
    assert not common._hot_split_wins(share, stack.ell_step_slots, fullest)
    # the parent's leaves and cache key, byte for byte
    monkeypatch.setattr(common, "_hot_split_measured", lambda: False)
    parent = common.pack_sparse_minibatches(column, y, 1, BATCH, dim=DIM,
                                            row_regular=True)
    assert not parent.hot_declined and parent.hot_ids is None
    assert stack.ints.tobytes() == parent.ints.tobytes()
    assert stack.floats.tobytes() == parent.floats.tobytes()
    assert stack.grad_step("logistic")[0] == parent.grad_step("logistic")[0]
    assert stack.grad_step("logistic")[0][0] == "sparse-ell-classed"
    assert len(stack.batch) == 2 and stack.batch[0] is stack.ints
    assert stack.step_slots == parent.step_slots and stack.cold_slots == 0
    _fit_stack(stack, create_mesh({"data": 1}, jax.devices()[:1]))
    counted = obs.registry().snapshot()["counters"]
    assert counted["train.sparse_hot_declined"] == 1
    assert counted["train.sparse_hot_fits"] == 0
    for name in ("train.sparse_hot_entries", "train.sparse_cold_slots",
                 "train.sparse_hot_slots"):
        assert name not in counted


@pytest.mark.parametrize("cls", [LogisticRegression, LinearRegression])
def test_an_estimator_fit_takes_the_classed_split_and_counts_it(cls, split):
    obs.enable()
    indptr, indices, values, y = _rows("ragged")
    table = _table(indptr, indices, values, y)
    (cls().set_vector_col("features").set_label_col("label")
     .set_prediction_col("pred").set_num_features(DIM)
     .set_global_batch_size(BATCH).set_max_iter(EPOCHS)
     .set_learning_rate(LR).fit(table))
    (stack,) = table._pack_cache.values()
    assert type(stack) is common.ClassedEllMinibatchStack
    assert stack.hot_ids is not None
    counted = obs.registry().snapshot()["counters"]
    blocks = len(stack.ints)
    assert counted["train.sparse_fits"] == counted["train.sparse_ell_fits"] \
        == counted["train.sparse_hot_fits"] == 1
    assert "train.sparse_hot_declined" not in counted
    assert counted["train.sparse_ell_classes"] == stack.ell_classes > 1
    assert counted["train.sparse_entries"] == len(indices) * EPOCHS
    assert counted["train.sparse_hot_entries"] == \
        stack.n_hot_entries * EPOCHS
    assert 0.5 < counted["train.sparse_hot_entries"] \
        / counted["train.sparse_entries"] < 1.0
    assert counted["train.sparse_cold_slots"] == \
        stack.cold_slots * blocks * EPOCHS
    # the hot kernels' slots: the live blocks', at least the hot entries
    assert counted["train.sparse_hot_slots"] == stack.hot_slots * EPOCHS
    assert counted["train.sparse_hot_entries"] <= \
        counted["train.sparse_hot_slots"]
    assert counted["train.sparse_slots"] == \
        stack.step_slots * blocks * EPOCHS
    assert counted["train.sparse_ell_slots_reckoned"] == \
        stack.ell_step_slots * blocks * EPOCHS
    assert counted["train.pallas_interpreted"] == 1  # on the CPU, and said
    gauges = obs.registry().snapshot()["gauges"]
    assert gauges["pack_sparse.cold_step_slots"] == stack.cold_slots
    assert gauges["pack_sparse.cold_planes"] == \
        np.count_nonzero(stack.cold_cuts[:, 1].max(axis=0))
    assert gauges["pack_sparse.ell_classes"] == stack.ell_classes


def test_the_split_classed_step_carries_the_hot_scope_and_no_unrolled_planes(
        split):
    stack = _pack("ragged", row_regular=True)
    key, step = stack.grad_step("logistic")
    assert key[0] == "sparse-ell-classed-hot"
    params = (jnp.zeros((DIM,), jnp.float32), jnp.zeros((), jnp.float32))
    text = _lowered(step, params, (tuple(jnp.asarray(a) for a in stack.batch),
                                   jnp.int32(0)))
    scopes = set(re.findall(r"fmt\.[a-z_.]+", text))
    assert scopes == {"fmt.train.sparse.forward", "fmt.train.sparse.backward",
                      "fmt.train.sparse.hot", "fmt.train.sparse.take_weights",
                      "fmt.train.sparse.scatter", "fmt.train.sparse.orders"}
    # the planes in two loops: ONE slice of the products and ONE write of
    # the error into the cold buffer in all of the program's text (the
    # kernels' calls are pinned compiled for the chip: test_pallas_aot.py)
    assert text.count("stablehlo.while") >= 2
    mb, buffer = stack.mb, stack.cold_slots + stack.mb
    assert len(re.findall(rf"stablehlo.dynamic_slice.*-> tensor<{mb}xf32>",
                          text)) == 1
    assert len(re.findall(rf"stablehlo.dynamic_update_slice.*"
                          rf"-> tensor<{buffer}xf32>", text)) == 1
