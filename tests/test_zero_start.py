"""A warm fit launches one program (PR 38).

Every GLM route starts from zero weights made on the host
(``lib/glm.py:_zero_start``); :func:`_run_fused_train` places a replicated
zero start once for a program that frees none of its params
(``_place_start``) and copies a caller's device arrays only for a train fn
whose ``donates_params`` says the program frees them; the bundled program
every estimator fit runs donates nothing.  So a warm re-fit makes no
``jnp.zeros``, no ``jnp.copy`` and no ``device_put`` and compiles nothing,
``train.param_copies`` reads 0 on every bundled fit and 2 on an unbundled
donating one, a donating program never gets the shared start, and the host
start gives the same program, on the same values, as an explicit
``jnp.zeros`` start: coefficients and loss history bit for bit.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from flink_ml_tpu import obs
from flink_ml_tpu.lib import LogisticRegression, common
from flink_ml_tpu.lib.classification import _log_loss_grads
from flink_ml_tpu.lib.clustering import KMeans
from flink_ml_tpu.lib.glm import _zero_start
from flink_ml_tpu.obs import flight
from flink_ml_tpu.ops.batch import CsrRows
from flink_ml_tpu.parallel.mesh import create_mesh, replicate
from flink_ml_tpu.table import slab_pool
from flink_ml_tpu.table.schema import DataTypes, Schema
from flink_ml_tpu.table.table import Table
from flink_ml_tpu.utils.environment import MLEnvironmentFactory

DIM, ROWS, BATCH = 12, 1024, 256


@pytest.fixture(autouse=True)
def _isolated(tmp_path, monkeypatch):
    import flink_ml_tpu.obs.report as report_mod

    monkeypatch.setenv("FMT_OBS_REPORTS", str(tmp_path / "reports"))
    obs.disable()
    obs.reset()
    flight.reset()
    slab_pool.reset_pool()
    report_mod._PREV_FIT_SNAPSHOT = {"counters": {}, "timings": {}}
    yield
    obs.disable()
    obs.reset()
    flight.reset()
    report_mod._PREV_FIT_SNAPSHOT = {"counters": {}, "timings": {}}


def _mesh():
    return MLEnvironmentFactory.get_default().get_mesh()


def _dense(seed=38):
    rng = np.random.RandomState(seed)
    X = rng.randn(ROWS, DIM).astype(np.float32)
    y = (X @ rng.randn(DIM) > 0).astype(np.float64)
    return X, y


def _csr(seed=38, width=5):
    """Rows of one width: the pack lays them row-regular on a 1-D mesh."""
    rng = np.random.RandomState(seed)
    indices = np.concatenate([np.sort(rng.choice(DIM, width, replace=False))
                              for _ in range(ROWS)]).astype(np.int32)
    values = rng.randn(ROWS * width).astype(np.float32)
    indptr = np.arange(0, ROWS * width + 1, width, dtype=np.int64)
    y = (rng.rand(ROWS) < 0.4).astype(np.float64)
    return CsrRows(DIM, indptr, indices, values), y


def _table(kind):
    if kind == "sparse_row_regular":
        rows, y = _csr()
        return Table.from_columns(
            Schema.of(("features", DataTypes.SPARSE_VECTOR),
                      ("label", "double")), {"features": rows, "label": y})
    X, y = _dense()
    if kind == "kmeans":
        return Table.from_columns(
            Schema.of(("features", DataTypes.DENSE_VECTOR)), {"features": X})
    return Table.from_columns(
        Schema.of(("features", DataTypes.DENSE_VECTOR), ("label", "double")),
        {"features": X, "label": y})


def _estimator(kind, lr):
    if kind == "kmeans":
        return (KMeans().set_vector_col("features").set_prediction_col("c")
                .set_k(4).set_max_iter(3).set_seed(int(lr * 1000)))
    return (LogisticRegression().set_vector_col("features")
            .set_label_col("label").set_prediction_col("pred")
            .set_learning_rate(lr).set_global_batch_size(BATCH)
            .set_max_iter(3))


def _counters():
    return obs.registry().snapshot()["counters"]


def _compiled():
    """The programs compiled since the flight ring was last reset."""
    return [e["program"] for e in flight.events() if e["kind"] == "compile"]


# -- the counter -----------------------------------------------------------------


@pytest.mark.parametrize("kind", ["dense", "sparse_row_regular", "kmeans"])
def test_a_bundled_fit_copies_no_parameter(kind):
    obs.enable()
    table = _table(kind)
    _estimator(kind, 0.381).fit(table)
    _estimator(kind, 0.381).fit(table)
    counted = _counters()
    assert counted["train.fused_runs"] == 2
    assert counted["train.param_copies"] == 0
    if kind == "sparse_row_regular":
        assert counted["train.sparse_ell_fits"] == 2


def test_an_unbundled_donating_fn_still_copies_and_refits():
    obs.enable()
    mesh = _mesh()
    X, y = _dense()
    stack = common.pack_minibatches(X, y, len(mesh.devices.ravel()), BATCH)
    fn = common.make_glm_train_fn(_log_loss_grads(True), mesh, 0.382, 0.0,
                                  3, 0.0)
    assert fn.donates_params is True
    assert common.make_glm_train_fn(_log_loss_grads(True), mesh, 0.382, 0.0,
                                    3, 0.0, bundle=True).donates_params \
        is False
    start = replicate(mesh, (jnp.zeros((DIM,), jnp.float32),
                             jnp.zeros((), jnp.float32)))
    batch = common._combined_view(stack)
    first = common._run_fused_train(fn, start, batch, mesh,
                                    n_rows=stack.n_rows)
    assert _counters()["train.param_copies"] == 2
    # the program trained on copies: the caller's arrays are alive and zero
    np.testing.assert_array_equal(np.asarray(start[0]), np.zeros(DIM))
    again = common._run_fused_train(fn, start, batch, mesh,
                                    n_rows=stack.n_rows)
    assert _counters()["train.param_copies"] == 4
    np.testing.assert_array_equal(first.params[0], again.params[0])
    assert first.losses == again.losses


# -- the same program on the same values -------------------------------------------


def _run(route, start):
    X, y = _dense()
    if route == "dense":
        mesh = _mesh()
        stack = common.pack_minibatches(X, y, len(mesh.devices.ravel()),
                                        BATCH)
        return common.train_glm(start, stack, _log_loss_grads(True), mesh,
                                learning_rate=0.383, max_iter=4)
    if route == "placer_2d":
        mesh = create_mesh({"data": 2, "model": 4}, jax.devices()[:8])
        stack = common.pack_minibatches(X, y, 2, BATCH)
        return common.train_glm_dense_2d(start, stack, "logistic", mesh,
                                         learning_rate=0.383, max_iter=4)
    mesh = _mesh()
    rows, y = _csr()
    sstack = common.pack_sparse_minibatches(
        rows, y, len(mesh.devices.ravel()), BATCH, dim=DIM,
        row_regular=route == "sparse_row_regular")
    assert isinstance(sstack, common.EllMinibatchStack) \
        == (route == "sparse_row_regular")
    return common.train_glm_sparse(start, sstack, "logistic", mesh,
                                   learning_rate=0.383, max_iter=4)


@pytest.mark.parametrize("route", ["dense", "sparse_row_regular",
                                   "segment_csr", "placer_2d"])
def test_the_host_start_is_the_device_zeros_start_bit_for_bit(route):
    obs.enable()
    host = _run(route, _zero_start(DIM))
    assert _compiled(), "the first start compiles its route's program"
    device = (jnp.zeros((DIM,), jnp.float32), jnp.zeros((), jnp.float32))
    flight.reset()
    explicit = _run(route, device)
    np.testing.assert_array_equal(host.params[0], explicit.params[0])
    np.testing.assert_array_equal(np.asarray(host.params[1]),
                                  np.asarray(explicit.params[1]))
    assert host.losses == explicit.losses and host.epochs == explicit.epochs
    # the same compiled program took both starts: nothing compiled anew
    # but the copy the donating 2-D program makes of a device start
    assert set(_compiled()) <= {"jit(copy)"}, _compiled()


# -- a warm re-fit -------------------------------------------------------------------


@pytest.mark.parametrize("kind", ["dense", "sparse_row_regular"])
def test_a_warm_refit_makes_no_zeros_or_copy_and_compiles_nothing(
        kind, monkeypatch):
    obs.enable()
    table = _table(kind)
    _estimator(kind, 0.384).fit(table)
    calls = []

    def spy(name, real):
        def called(*args, **kwargs):
            calls.append(name)
            return real(*args, **kwargs)
        return called

    monkeypatch.setattr(jnp, "zeros", spy("zeros", jnp.zeros))
    monkeypatch.setattr(jnp, "copy", spy("copy", jnp.copy))
    # the start was placed by the first fit: nothing crosses to the device
    monkeypatch.setattr(jax, "device_put", spy("device_put", jax.device_put))
    flight.reset()
    _estimator(kind, 0.384).fit(table)
    assert calls == []
    assert _compiled() == []
    warm = obs.load_reports()[-1]["metrics"]
    assert not [k for k in warm["timings"] if k.startswith("compile.")]
    assert warm["counters"]["train.fused_runs"] == 1
    assert "train.param_copies" not in warm["counters"]  # added 0
    assert _counters()["train.param_copies"] == 0


# -- the start placed once --------------------------------------------------------------


def _bundled_and_unbundled(mesh, lr):
    grad = _log_loss_grads(True)
    return (common.make_glm_train_fn(grad, mesh, lr, 0.0, 3, 0.0, bundle=True),
            common.make_glm_train_fn(grad, mesh, lr, 0.0, 3, 0.0))


def test_a_zero_start_is_placed_once_for_a_program_that_reads_it():
    mesh = _mesh()
    X, y = _dense()
    stack = common.pack_minibatches(X, y, len(mesh.devices.ravel()), BATCH)
    bundled, _ = _bundled_and_unbundled(mesh, 0.385)
    batch = common._combined_view(stack)
    first = common._place_start(mesh, _zero_start(DIM))
    again = common._place_start(mesh, _zero_start(DIM))
    assert first[0] is again[0] and first[1] is again[1]
    assert first[0].sharding == replicate(mesh, np.zeros(DIM))[0].sharding
    r1 = common._run_fused_train(bundled, _zero_start(DIM), batch, mesh,
                                 n_rows=stack.n_rows)
    r2 = common._run_fused_train(bundled, _zero_start(DIM), batch, mesh,
                                 n_rows=stack.n_rows)
    np.testing.assert_array_equal(r1.params[0], r2.params[0])
    # the program read the placed start and freed none of it
    np.testing.assert_array_equal(np.asarray(first[0]), np.zeros(DIM))
    assert not first[0].is_deleted() and not first[1].is_deleted()


@pytest.mark.parametrize("start", ["ones", "negative_zero"])
def test_a_start_that_is_not_zero_bytes_is_placed_anew(start):
    mesh = _mesh()
    w = np.ones(DIM, np.float32) if start == "ones" \
        else np.full(DIM, -0.0, np.float32)
    first = common._place_start(mesh, (w, np.float32(0)))
    again = common._place_start(mesh, (w, np.float32(0)))
    assert first[0] is not again[0]
    np.testing.assert_array_equal(np.asarray(first[0]).view(np.uint32),
                                  w.view(np.uint32))
    assert first[1] is again[1]  # the intercept's zero is placed once


def test_a_donating_program_never_gets_the_placed_zero_start():
    mesh = _mesh()
    X, y = _dense()
    stack = common.pack_minibatches(X, y, len(mesh.devices.ravel()), BATCH)
    bundled, donating = _bundled_and_unbundled(mesh, 0.386)
    batch = common._combined_view(stack)
    shared = common._place_start(mesh, _zero_start(DIM))
    runs = [common._run_fused_train(fn, _zero_start(DIM), batch, mesh,
                                    n_rows=stack.n_rows)
            for fn in (donating, donating, bundled)]
    # the donating program freed fresh placements, never the shared start
    assert not shared[0].is_deleted() and not shared[1].is_deleted()
    for r in runs[1:]:
        np.testing.assert_array_equal(r.params[0], runs[0].params[0])
