"""The dense fit data-parallel over 1, 2 and 4 devices (PR 37): the public
``LogisticRegression`` on the environment's mesh against both plain
references, the pack's one-copy layout against the three-copy layout it
replaced, the placement's device-major slices, and what the fit counts and
names of its collectives.  CPU, small sizes, 4 of the 8 virtual devices."""

import os
import sys

import jax
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from chipbench import references  # noqa: E402
from flink_ml_tpu import obs  # noqa: E402
from flink_ml_tpu.lib import LogisticRegression, common  # noqa: E402
from flink_ml_tpu.parallel.mesh import (  # noqa: E402
    create_mesh,
    shard_batch,
    shard_batch_prefetched,
)
from flink_ml_tpu.table import slab_pool  # noqa: E402
from flink_ml_tpu.table.schema import DataTypes, Schema  # noqa: E402
from flink_ml_tpu.table.table import Table  # noqa: E402
from flink_ml_tpu.utils.environment import MLEnvironmentFactory  # noqa: E402

ROWS, DIM, BATCH, EPOCHS, LR, REG = 5000, 24, 512, 3, 0.2, 1e-4
DEVICES = [1, 2, 4]


def _rows(seed=11, rows=ROWS, dim=DIM):
    rng = np.random.default_rng(seed)
    X = (rng.standard_normal((rows, dim)) * 2 + 0.5).astype(np.float32)
    y = (X @ rng.standard_normal(dim) + rng.standard_normal(rows)
         > 1.0).astype(np.float32)
    return X, y


@pytest.fixture
def mesh_of(monkeypatch):
    """Sets the default environment's mesh the public way, and puts the
    one that stood back."""
    env = MLEnvironmentFactory.get_default()
    before = env.get_mesh()

    def set_mesh(n_dev):
        mesh = create_mesh({"data": n_dev}, jax.devices()[:n_dev])
        env.set_mesh(mesh)
        return mesh

    yield set_mesh
    env.set_mesh(before)
    slab_pool.pool().clear()


@pytest.fixture
def counted():
    was_on = obs.enabled()
    obs.enable()
    obs.reset()
    yield lambda: dict(obs.registry().snapshot()["counters"])
    if not was_on:
        obs.disable()


def _fit(X, y):
    table = Table.from_columns(
        Schema.of(("features", DataTypes.DENSE_VECTOR), ("label", "double")),
        {"features": X, "label": y})
    model = (LogisticRegression().set_vector_col("features")
             .set_label_col("label").set_prediction_col("pred")
             .set_learning_rate(LR).set_reg(REG).set_global_batch_size(BATCH)
             .set_max_iter(EPOCHS).set_tol(0.0).set_with_intercept(True)
             .fit(table))
    return {"coef": np.asarray(model.coefficients(), np.float64),
            "intercept": float(model.intercept()),
            "losses": np.asarray(model.train_losses_, np.float64)}


@pytest.mark.parametrize("n_dev", DEVICES)
def test_the_public_fit_over_the_mesh_agrees_with_both_references(
        n_dev, mesh_of, counted):
    """Float32 storage and products on every side; what may differ is the
    ORDER of the float32 additions of a sum over the batch (a device's
    rows, then the devices' partial sums, against one sum).  Read here: 0
    exactly between the program and the reference laid over as many
    devices; 2.7e-8 (2 devices) and 3.2e-8 (4) of the coefficients' norm
    between either and ``glm_sgd`` on one device, 0 on every loss; held to
    2e-6.  The same products in bfloat16 (the references' own control)
    read 3.1e-4 on the coefficients: 150 times the limit."""
    X, y = _rows()
    mesh_of(n_dev)
    got = _fit(X, y)
    plain = references.load("glm_sgd")
    over = references.load("glm_sgd_over_chips")
    one = plain.Table(X, y, BATCH).fit(LR, REG, EPOCHS)
    laid = over.Table(X, y, BATCH, chips=n_dev)
    ref = laid.fit(LR, REG, EPOCHS)
    # every chunk's batch axis lies over n_dev devices, a quarter each
    for xs, yc, mask in laid.chunks:
        assert len(xs.sharding.device_set) == n_dev
        assert xs.addressable_shards[0].data.shape == \
            (xs.shape[0], BATCH // n_dev, DIM)
        assert yc.sharding == mask.sharding
    for a, b in ((got, ref), (got, one), (ref, one)):
        gaps = over.gaps(a, b)
        assert gaps["coef_gap"] < 2e-6 and gaps["loss_gap"] < 2e-6, gaps
    assert over.gaps is plain.gaps and over.CONTROLS is plain.CONTROLS
    assert over.NUMBERS == plain.NUMBERS
    for label, variant in over.CONTROLS.items():
        if "precision" in variant:
            bad = over.Table(X, y, BATCH, chips=n_dev, **variant).fit(
                LR, REG, EPOCHS)
        else:
            bad = laid.fit(LR, REG, EPOCHS, **variant)
        worse = over.gaps(bad, ref)
        assert worse["coef_gap"] > 1e-4, (label, worse)
        # and the one-device reference's variant is the same other answer
        if "fault" in variant:
            same = plain.Table(X, y, BATCH).fit(LR, REG, EPOCHS, **variant)
            assert np.allclose(bad["coef"], same["coef"], rtol=0,
                               atol=2e-6 * np.abs(ref["coef"]).max()), label
    # what the fit counted, from shapes alone
    steps = -(-ROWS // BATCH)
    counters = counted()
    assert counters["train.fused_runs"] == 1
    assert counters["train.data_shards"] == n_dev
    assert counters["train.psum_calls"] == 4 * steps * EPOCHS
    assert counters["train.psum_bytes"] == (DIM + 3) * 4 * steps * EPOCHS
    assert counters["place.devices"] == n_dev
    assert counters["train.onepass_fits"] == 0  # never on the CPU


def test_the_reference_over_chips_refuses_what_it_cannot_lay():
    over = references.load("glm_sgd_over_chips")
    X, y = _rows(rows=600)
    with pytest.raises(SystemExit):
        over.Table(X, y, BATCH, chips=len(jax.devices()) + 1)
    with pytest.raises(SystemExit):
        over.Table(X, y, 510, chips=4)  # a batch that does not divide
    source = open(os.path.join(ROOT, "chipbench", "references",
                               "glm_sgd_over_chips.py")).read()
    assert "flink_ml_tpu" not in source.split('"""', 2)[2]  # code, not doc
    assert "shard_map" not in source.split('"""', 2)[2]
    assert "psum" not in source.split('"""', 2)[2]


def _three_copies(X, y, n_dev, batch, dtype=np.float32, min_steps=0):
    """The layout as ``pack_minibatches`` built it until PR 37: a padded
    copy, a transposed copy, and ``_combined_view``'s concatenated one."""
    n, d = X.shape
    if batch <= 0:
        batch = max(n, n_dev)
    mb = max(1, -(-batch // n_dev))
    steps = max(max(1, -(-n // (mb * n_dev))), int(min_steps))
    n_pad = steps * mb * n_dev
    Xp = np.zeros((n_pad, d), dtype)
    yp, wp = np.zeros((n_pad,), dtype), np.zeros((n_pad,), dtype)
    Xp[:n], yp[:n], wp[:n] = X, y, 1.0
    Xp = Xp.reshape(steps, n_dev, mb, d).transpose(1, 0, 2, 3).reshape(
        n_dev * steps, mb, d)
    yp = yp.reshape(steps, n_dev, mb).transpose(1, 0, 2).reshape(
        n_dev * steps, mb)
    wp = wp.reshape(steps, n_dev, mb).transpose(1, 0, 2).reshape(
        n_dev * steps, mb)
    return np.concatenate([Xp, yp[..., None], wp[..., None]], axis=2), \
        steps, mb


@pytest.mark.parametrize("n_dev", DEVICES)
@pytest.mark.parametrize("rows,batch,min_steps", [
    (1000, 96, 0), (1000, 0, 0), (37, 64, 3), (4096, 512, 0), (5, 8, 0)],
    ids=["ragged-last-step", "full-batch", "floored-steps", "whole-steps",
         "fewer-rows-than-a-step"])
def test_the_one_copy_pack_lays_the_bytes_the_three_copies_laid(
        n_dev, rows, batch, min_steps):
    X, y = _rows(seed=rows, rows=rows, dim=7)
    old, steps, mb = _three_copies(X, y, n_dev, batch, min_steps=min_steps)
    stack = common.pack_minibatches(X, y.astype(np.float64), n_dev, batch,
                                    min_steps=min_steps)
    assert (stack.steps, stack.mb, stack.n_rows) == (steps, mb, rows)
    slab = common._combined_view(stack)
    assert slab is stack.combined and slab.flags.c_contiguous  # no copy
    assert slab.dtype == np.float32 and slab.tobytes() == old.tobytes()
    # x, y, w are views of the one array
    for view, cols in ((stack.x, slice(0, 7)), (stack.y, 7), (stack.w, 8)):
        assert np.shares_memory(view, slab)
        assert np.array_equal(view, old[..., cols])
    assert common._combined_view(stack) is slab  # the same array every call
    # the step -> rows mapping: global step s takes rows [s*G, (s+1)*G) in
    # table order, device k the k-th mb-slice of it
    for s in range(steps):
        for k in range(n_dev):
            lo = min((s * n_dev + k) * mb, rows)
            hi = min(lo + mb, rows)
            assert np.array_equal(stack.x[k * steps + s, :hi - lo], X[lo:hi])
            assert stack.w[k * steps + s].sum() == hi - lo


def test_a_large_table_is_laid_over_threads_to_the_same_bytes(monkeypatch):
    X, y = _rows(seed=5, rows=60_000, dim=40)
    monkeypatch.setattr(common, "_PACK_BYTES_A_THREAD_SHIFT", 16)
    many = common.pack_minibatches(X, y, 4, 4096)
    monkeypatch.setattr(common, "_PACK_BYTES_A_THREAD_SHIFT", 62)
    one = common.pack_minibatches(X, y, 4, 4096)
    assert many.combined.tobytes() == one.combined.tobytes() \
        == _three_copies(X, y, 4, 4096)[0].tobytes()


@pytest.mark.parametrize("n_dev", DEVICES)
def test_the_placement_sends_every_device_the_slices_of_its_own_block(
        n_dev, counted):
    X, y = _rows(seed=3, rows=40_000, dim=30)
    mesh = create_mesh({"data": n_dev}, jax.devices()[:n_dev])
    slab = common._combined_view(common.pack_minibatches(X, y, n_dev, 2048))
    placed = shard_batch_prefetched(mesh, slab, chunk_bytes=1 << 19,
                                    min_bytes=1 << 19)
    plain = shard_batch(mesh, slab)
    assert placed.sharding == plain.sharding
    for a, b in zip(placed.addressable_shards, plain.addressable_shards):
        assert a.device == b.device and a.index == b.index
        assert np.array_equal(np.asarray(a.data), np.asarray(b.data))
    # one span a device's slice: slices x devices of them
    steps = slab.shape[0] // n_dev
    a_step = slab.nbytes // slab.shape[0]
    slices = -(-steps // max(1, (1 << 19) // (a_step * n_dev)))
    spans = obs.registry().snapshot()["timings"]["place.h2d"]["count"]
    assert spans == slices * n_dev


@pytest.mark.parametrize("n_dev", [1, 4])
def test_the_steps_collectives_stand_under_a_scope_of_their_own(n_dev):
    from flink_ml_tpu.lib.classification import _log_loss_grads

    mesh = create_mesh({"data": n_dev}, jax.devices()[:n_dev])
    grads = _log_loss_grads(True)
    X, y = _rows(rows=1024, dim=8)
    slab = shard_batch(mesh, common._combined_view(
        common.pack_minibatches(X, y, n_dev, 256)))
    params = (np.zeros(8, np.float32), np.float32(0))
    fused = common.make_glm_train_fn(grads, mesh, 0.1, 0.0, 2, 0.0)
    epoch = common.make_glm_epoch_step(grads, mesh, 0.1, 0.0)
    for fn, batch in ((fused, slab), (epoch, (slab[..., :-2], slab[..., -2],
                                              slab[..., -1]))):
        jitted = getattr(fn, "_fn", fn)  # the epoch step bounds its dispatch
        text = jitted.lower(params, batch).as_text(debug_info=True)
        assert "fmt.train.grad/fmt.train.psum" in text
