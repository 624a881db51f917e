"""The plain sparse route on RAGGED tables (PR 33): rows whose stored entry
counts differ, made by the benchmark's generator (``chipbench/data_ragged.py``)
and held against its plain reference (``chipbench/references/csr_glm_sgd.py``).

* ``LogisticRegression.fit`` of a CSR column on the default route agrees with
  the reference on both sides of the layout rule: a table whose widths fail
  ``_ELL_MAX_SLOT_RATIO`` (segment-CSR, ``ell_declined``) and one whose
  widths pass it (row-regular); the reference's bfloat16 control fails the
  same tolerances;
* one ragged table laid both ways by hand gives the same sums within float32
  rounding;
* the rule's inputs are on the stack, in the pack's gauges and in
  ``train.sparse_ell_slots_reckoned``, by the widths;
* segment-CSR's four random-access operations carry their scopes in the
  step's jaxpr, on the segment-CSR step and on the split step's cold list.
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
from flink_ml_tpu.lib import LogisticRegression, common  # noqa: E402
from flink_ml_tpu.ops.batch import CsrRows  # noqa: E402
from flink_ml_tpu.table.schema import DataTypes, Schema  # noqa: E402
from flink_ml_tpu.table.table import Table  # noqa: E402
from flink_ml_tpu.utils.environment import MLEnvironmentFactory  # noqa: E402

SCHEMA = Schema.of(("features", DataTypes.SPARSE_VECTOR), ("label", "double"))
ROWS, DIM, BATCH, EPOCHS, LR, REG = 3000, 4000, 512, 2, 0.5, 1e-4
#: the configuration's laws (``chipbench/configs/url_ragged_lr.json``) ...
RAGGED = {"days": 11, "width_mean_day0": 110.1, "width_growth": 0.10,
          "width_sigma": 0.30, "width_min": 24, "width_max": 512,
          "real_features": 64, "real_share": 0.5, "zipf_exponent": 1.1,
          "vocabulary_day0": 0.2, "label_noise": 0.5, "positive_share": 0.3333}
#: ... and the same with widths that hardly differ: the rule's other side
EVEN = dict(RAGGED, width_sigma=0.02, width_growth=0.0, width_max=128)
TABLES = {"ragged": RAGGED, "even": EVEN}
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
    assert widths.min() < widths.max()  # ragged, both of them
    got = _answer(_logreg().fit(_table(indptr, indices, values, y)))
    counted = obs.registry().snapshot()["counters"]
    assert counted["train.sparse_fits"] == 1
    declined = name == "ragged"
    assert counted["train.sparse_ell_fits"] == int(not declined)
    assert counted.get("train.sparse_ell_declined", 0) == int(declined)
    reference = references.load("csr_glm_sgd")
    table = reference.Table(indptr, indices, values, y, DIM, BATCH)
    gaps = reference.gaps(got, table.fit(LR, REG, EPOCHS))
    assert gaps["coef_gap"] < COEF_TOL and gaps["loss_gap"] < LOSS_TOL, gaps
    # the precision below the one stated fails at least one of the two
    control = reference.gaps(table.fit(LR, REG, EPOCHS, precision="bf16"),
                             table.fit(LR, REG, EPOCHS))
    assert control["coef_gap"] > COEF_TOL or control["loss_gap"] > LOSS_TOL
    assert control["coef_gap"] > 10 * gaps["coef_gap"]


def _fit_stack(stack, mesh):
    start = (jnp.zeros((DIM,), jnp.float32), jnp.zeros((), jnp.float32))
    r = common.train_glm_sparse(start, stack, "logistic", mesh, LR, EPOCHS,
                                reg=REG)
    return (np.asarray(r.params[0], np.float64), float(r.params[1]),
            np.asarray(r.losses, np.float64))


def test_one_ragged_table_laid_both_ways_gives_the_same_sums(monkeypatch):
    indptr, indices, values, y = _rows("ragged")
    mesh = MLEnvironmentFactory.get_default().get_mesh()
    column = CsrRows(DIM, indptr, indices, values)

    def pack():
        return common.pack_sparse_minibatches(
            column, y, _n_dev(), BATCH, dim=DIM, row_regular=True)

    csr = pack()
    assert not csr.row_regular and csr.ell_declined
    # the rule lifted, here only: the same rows side by side at the widest
    monkeypatch.setattr(common, "_ELL_MAX_SLOT_RATIO", 1e9)
    ell = pack()
    assert ell.row_regular and ell.hot_ids is None
    assert ell.width == csr.widest_row == int(np.diff(indptr).max())
    assert ell.n_entries == csr.n_entries == int(indptr[-1])
    (w_a, b_a, l_a), (w_b, b_b, l_b) = _fit_stack(csr, mesh), \
        _fit_stack(ell, mesh)
    # float32 rounding of sums taken in another order, nothing more
    assert np.linalg.norm(w_a - w_b) / np.linalg.norm(w_a) < 1e-6
    assert abs(b_a - b_b) < 1e-6
    assert np.allclose(l_a, l_b, rtol=1e-6)


@pytest.mark.parametrize("name", sorted(TABLES))
def test_the_rules_inputs_are_kept_said_and_counted(name):
    obs.enable()
    indptr, indices, values, y = _rows(name)
    widths = np.diff(indptr)
    n_dev, steps = _n_dev(), -(-ROWS // BATCH)
    mb = BATCH // n_dev
    stack = common.pack_sparse_minibatches(
        CsrRows(DIM, indptr, indices, values), y, n_dev, BATCH, dim=DIM,
        row_regular=True)
    # the fullest device step, rounded up to an odd multiple of the pack's 512
    starts = np.minimum(mb * np.arange(n_dev * steps + 1), ROWS)
    fullest = int((indptr[starts[1:]] - indptr[starts[:-1]]).max())
    nnz_pad = (-(-fullest // 512) | 1) * 512
    passes = mb * int(widths.max()) <= common._ELL_MAX_SLOT_RATIO * nnz_pad
    assert passes == (name == "even") == stack.row_regular
    assert stack.ell_step_slots == mb * int(widths.max())
    if not passes:
        assert stack.widest_row == int(widths.max())
        assert stack.nnz_pad == nnz_pad == stack.step_slots
    gauges = obs.registry().snapshot()["gauges"]
    assert gauges["pack_sparse.widest_row"] == widths.max()
    assert gauges["pack_sparse.mean_row"] == pytest.approx(widths.mean())
    assert gauges["pack_sparse.ell_step_slots"] == mb * widths.max()
    assert gauges["pack_sparse.csr_step_slots"] == nnz_pad
    # a pack that was not asked reckons nothing
    plain = common.pack_sparse_minibatches(
        CsrRows(DIM, indptr, indices, values), y, n_dev, BATCH, dim=DIM)
    assert plain.widest_row == 0 == plain.ell_step_slots
    assert not plain.ell_declined

    mesh = MLEnvironmentFactory.get_default().get_mesh()
    for s in (stack, plain):
        _fit_stack(s, mesh)
    counted = obs.registry().snapshot()["counters"]
    blocks = n_dev * steps * EPOCHS
    assert counted["train.sparse_ell_slots_reckoned"] == \
        mb * int(widths.max()) * blocks  # the asked pack's fit alone
    assert counted["train.sparse_slots"] == \
        (stack.step_slots + plain.step_slots) * blocks
    ratio = mb * int(widths.max()) / stack.step_slots
    assert (ratio > common._ELL_MAX_SLOT_RATIO) == (name == "ragged")


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


@pytest.mark.parametrize("step", ["segment_csr", "split_cold_list"])
def test_the_four_random_access_operations_carry_their_scopes(step):
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
               jnp.zeros((1, 2, nnz_pad), jnp.int32),
               jnp.zeros((1, nnz_pad), jnp.float32),
               jnp.zeros((1, k), jnp.int32)), jnp.int32(0))
    text = _lowered(fn, params, xs)
    scopes = set(re.findall(r"fmt\.[a-z_.]+", text))
    assert SEGMENT_SCOPES <= scopes
    assert ("fmt.train.sparse.hot" in scopes) == (step == "split_cold_list")
    # each inside its half of the step, with the operation it names
    for path in ("forward/fmt.train.sparse.take_weights/mul",
                 "forward/fmt.train.sparse.row_sum/scatter-add",
                 "backward/fmt.train.sparse.take_error/mul",
                 "backward/fmt.train.sparse.scatter/scatter-add"):
        assert "/fmt.train.sparse." + path in text, path
