"""The dense GLM gradient kernel (``ops/pallas_kernels.py:glm_grad``): its
numbers on the interpreter against the XLA grad fns, the fused fit that holds
it against the fused fit that does not, the rule that selects it, and what
building it costs.  The estimator's own CPU path keeps the XLA step, so the
kernel and the step are called directly here."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from flink_ml_tpu import obs
from flink_ml_tpu.lib import common
from flink_ml_tpu.lib.classification import _log_loss_grads
from flink_ml_tpu.lib.regression import _squared_loss_grads
from flink_ml_tpu.ops import pallas_kernels
from flink_ml_tpu.ops.pallas_kernels import (glm_grad, glm_grad_tile,
                                             glm_grad_schedule)
from flink_ml_tpu.parallel.mesh import default_mesh

GRAD_FNS = {"logistic": _log_loss_grads, "squared": _squared_loss_grads}


def slab_of(steps=3, rows=256, d=28, seed=0, padded=0):
    """A dense combined slab (features, label, weight a row) as the pack
    makes it; the last ``padded`` rows of the last minibatch are padding:
    zeros at weight 0."""
    rng = np.random.RandomState(seed)
    slab = rng.randn(steps, rows, d + 2).astype(np.float32)
    slab[..., -2] = slab[..., -2] > 0
    slab[..., -1] = rng.rand(steps, rows) > 0.1  # some zero weights
    if padded:
        slab[-1, -padded:, :] = 0.0
    wts = (rng.randn(d) / np.sqrt(d)).astype(np.float32)
    return jnp.asarray(slab), jnp.asarray(wts), jnp.float32(0.3)


def xla_sums(kind, slab, step, wts, b):
    mb = slab[step]
    (g_w, g_b), loss, w_sum = GRAD_FNS[kind](True)(
        (wts, b), mb[:, :-2], mb[:, -2], mb[:, -1])
    return g_w, g_b, loss, w_sum


class TestGlmGradKernel:
    @pytest.mark.parametrize("kind", ["logistic", "squared"])
    @pytest.mark.parametrize("d", [2000, 784, 28, 37])
    def test_matches_the_xla_grad_fn(self, kind, d):
        """The cells' widths, a narrow one, and one that fills neither
        sublane groups nor lane chunks."""
        slab, wts, b = slab_of(rows=128 if d > 100 else 256, d=d)
        for step in (0, 2):
            gw, gb, loss, wsum = glm_grad(slab, jnp.int32(step), wts, b,
                                          kind=kind, interpret=True)
            rgw, rgb, rloss, rwsum = xla_sums(kind, slab, step, wts, b)
            scale = float(jnp.max(jnp.abs(rgw)))
            assert float(jnp.max(jnp.abs(gw - rgw))) <= 2e-6 * scale
            np.testing.assert_allclose(gb, rgb, rtol=1e-5, atol=2e-5)
            np.testing.assert_allclose(loss, rloss, rtol=1e-5)
            assert float(wsum) == float(rwsum)

    def test_padded_rows_of_the_last_minibatch_are_neutral(self):
        slab, wts, b = slab_of(padded=100)
        got = glm_grad(slab, jnp.int32(2), wts, b, interpret=True)
        # the same minibatch with its padding filled with rows at weight 0
        filled = slab.at[2, -100:, :-1].set(7.0)
        again = glm_grad(filled, jnp.int32(2), wts, b, interpret=True)
        for a, c in zip(got, again):
            np.testing.assert_array_equal(a, c)
        assert float(got[3]) == float(jnp.sum(slab[2, :, -1]))

    def test_two_calls_return_the_same_bytes(self):
        slab, wts, b = slab_of(d=37)
        one = glm_grad(slab, jnp.int32(1), wts, b, interpret=True)
        two = glm_grad(slab, jnp.int32(1), wts, b, interpret=True)
        for a, c in zip(one, two):
            assert np.asarray(a).tobytes() == np.asarray(c).tobytes()

    def test_row_tiles_agree(self):
        """Several row tiles a minibatch against one: the accumulators
        carry across the grid."""
        slab, wts, b = slab_of(rows=512)
        a = glm_grad(slab, jnp.int32(0), wts, b, tile_rows=128,
                     interpret=True)
        c = glm_grad(slab, jnp.int32(0), wts, b, tile_rows=512,
                     interpret=True)
        np.testing.assert_allclose(a[0], c[0], rtol=1e-5, atol=1e-5)
        np.testing.assert_allclose(a[2], c[2], rtol=1e-6)

    def test_no_tile_is_a_clear_error(self):
        # rows that do not fill lane chunks; a width over the VMEM budget
        with pytest.raises(ValueError, match="no row tile"):
            slab, wts, b = slab_of(rows=100)
            glm_grad(slab, jnp.int32(0), wts, b, interpret=True)
        wide = jnp.zeros((1, 128, 8194), jnp.float32)
        with pytest.raises(ValueError, match="VMEM"):
            glm_grad(wide, jnp.int32(0), jnp.zeros((8192,), jnp.float32),
                     jnp.float32(0.0), interpret=True)


def zero_signless(a):
    """The bytes of ``a`` with every exact zero made +0.0."""
    return (np.asarray(a) + 0.0).tobytes()


class TestTrailingTilesAreSkipped:
    """The kernel reads a minibatch's row tiles up to its last row of
    nonzero weight (``glm_grad_schedule``, at least one) and passes over the
    rest: its sums are those of a read of every tile, byte for byte up to
    the sign of an exact zero."""

    ROWS, TILE = 512, 128

    @pytest.mark.parametrize("kind", ["logistic", "squared"])
    @pytest.mark.parametrize("filled,fill,tiles", [
        (512, 0.0, 4),   # every row: nothing to skip
        (1, 0.0, 1),     # one row
        (128, 0.0, 1),   # exactly one tile
        (129, 0.0, 2),   # one row past a tile
        (0, 0.0, 1),     # no row: a minibatch of padding reads one tile
        (200, 7.0, 2),   # padding at weight 0 over rows of 7.0
    ], ids=["every-row", "one-row", "one-tile", "one-past-a-tile",
            "no-row", "weight-0-over-7"])
    def test_skipping_returns_the_bytes_of_reading_every_tile(
            self, kind, filled, fill, tiles):
        slab, wts, b = slab_of(steps=2, rows=self.ROWS, d=37, seed=filled)
        slab = slab.at[:, :, -1].set(1.0)
        slab = slab.at[-1, filled:, :].set(fill).at[-1, filled:, -1].set(0.0)
        schedule = glm_grad_schedule(slab, self.TILE)
        assert schedule.dtype == jnp.int32
        assert schedule.tolist() == [[0, self.ROWS // self.TILE], [1, tiles]]
        for step in (0, 1):
            skipping = glm_grad(slab, schedule[step], wts, b, kind=kind,
                                tile_rows=self.TILE, interpret=True)
            every = glm_grad(slab, jnp.int32(step), wts, b, kind=kind,
                             tile_rows=self.TILE, interpret=True)
            for a, c in zip(skipping, every):
                assert zero_signless(a) == zero_signless(c)
        assert float(skipping[3]) == float(filled)

    @pytest.mark.parametrize("n,steps,tile,last", [
        (400_000, 13, 512, 14),       # epsilon_lr: 6,784 rows in the last
        (2_025_000, 62, 1024, 26),    # mnist8m_lr: 26,152 rows in the last
    ], ids=["epsilon", "mnist8m"])
    def test_the_count_by_hand_at_the_cells_geometries(self, n, steps, tile,
                                                       last):
        """The pack's weight row alone (three columns a row: the count reads
        nothing else) at a cell's minibatch of 32,768 rows."""
        mb = 32768
        slab = np.zeros((steps, mb, 3), np.float32)
        weights = np.zeros(steps * mb, np.float32)
        weights[:n] = 1.0
        slab[..., -1] = weights.reshape(steps, mb)
        per_mb = mb // tile
        counted = np.asarray(glm_grad_schedule(jnp.asarray(slab), tile))[:, 1]
        assert counted.tolist() == [per_mb] * (steps - 1) + [last]
        read, skipped = common.onepass_tile_counts(n, 1, steps, mb, tile)
        assert (read, skipped) == (int(counted.sum()), per_mb - last)

    def test_the_count_by_hand_over_four_chips(self):
        """mnist8m whole over four chips: only the fourth chip's last
        minibatch (6,304 rows) ends in padding, 7 tiles of 32 read."""
        read, skipped = common.onepass_tile_counts(8_100_000, 4, 62, 32768,
                                                   1024)
        assert (read, skipped) == (4 * 62 * 32 - 25, 25)
        # a shard of padding alone reads one tile
        assert common.onepass_tile_counts(300, 4, 1, 256, 128) == (5, 3)


class TestRowTileArithmetic:
    @pytest.mark.parametrize("rows,d,tile", [
        (32768, 2000, 512),    # epsilon_lr
        (32768, 784, 1024),    # mnist8m_lr
        (32768, 28, 2048),     # narrow: the cap
        (4096, 2000, 512),
        (1280, 784, 1280),     # the longest divisor, not a power of two
        (32768, 4000, 128),    # the shortest tile still fits
        (32768, 4096, 0),      # too wide for it: the XLA step
        (1000, 28, 0),         # rows that do not fill lane chunks
        (0, 28, 0),
    ])
    def test_tile_by_shape(self, rows, d, tile):
        assert glm_grad_tile(rows, d) == tile
        if tile:
            assert rows % tile == 0 and tile % 128 == 0


class _Device:
    def __init__(self, platform):
        self.platform = platform


class _Mesh:
    def __init__(self, platform="tpu", axis_names=("data",)):
        self.axis_names = axis_names
        self.devices = np.array([_Device(platform)], dtype=object)


class _Layout:
    def __init__(self, major_to_minor, tiling=((8, 128),)):
        self.major_to_minor, self.tiling = major_to_minor, tiling


class _Slab:
    """What the rule reads of a placed slab."""

    def __init__(self, shape=(13, 32768, 2002), dtype=jnp.float32,
                 major_to_minor=(0, 2, 1), tiling=((8, 128),)):
        self.shape, self.ndim, self.dtype = shape, len(shape), dtype
        self.format = type("Format", (), {})()
        self.format.layout = (None if major_to_minor is None
                              else _Layout(major_to_minor, tiling))


@pytest.fixture
def counters():
    obs.enable()
    obs.reset()
    try:
        yield lambda: obs.registry().snapshot()["counters"]
    finally:
        obs.disable()
        obs.reset()


class TestSelectionRule:
    """What takes the kernel, what keeps the XLA step, and which of those
    are counted: all from what the code observes."""

    GRAD = _log_loss_grads(True)

    @pytest.mark.parametrize("shape,rows", [
        ((13, 32768, 2002), 512), ((62, 32768, 786), 1024),
        ((7, 4096, 30), 2048)])
    def test_a_rows_minor_slab_on_a_tpu_takes_the_kernel(
            self, counters, shape, rows):
        assert common._onepass_rows(self.GRAD, _Mesh(), _Slab(shape)) == rows
        assert "train.onepass_declined" not in counters()

    @pytest.mark.parametrize("mesh,grad_fn", [
        (_Mesh("cpu"), GRAD),
        (_Mesh("tpu", ("data", "model")), GRAD),
        (_Mesh(), lambda p, x, y, w: None),  # no glm_kind: not a dense GLM
    ], ids=["cpu", "2-D mesh", "foreign grad fn"])
    def test_what_is_not_eligible_is_not_counted(self, counters, mesh,
                                                 grad_fn):
        assert common._onepass_rows(grad_fn, mesh, _Slab()) == 0
        assert "train.onepass_declined" not in counters()

    @pytest.mark.parametrize("slab", [
        _Slab(major_to_minor=(0, 1, 2)),           # features minor
        _Slab((8, 32768, 2002), major_to_minor=(2, 0, 1)),  # steps next
        _Slab(major_to_minor=None),                # layout not reported
        _Slab(tiling=((4, 128),)),
        _Slab(dtype=jnp.float64),
        _Slab((13, 32768, 4098)),                  # no tile fits VMEM
        _Slab((13, 1000, 2002)),                   # rows off the lanes
        np.zeros((3, 128, 6), np.float32),         # a host array: no layout
    ], ids=["features-minor", "steps-on-sublanes", "no-layout", "tiling",
            "float64", "too-wide", "ragged-rows", "host-array"])
    def test_an_eligible_fit_that_keeps_xla_is_counted(self, counters, slab):
        assert common._onepass_rows(self.GRAD, _Mesh(), slab) == 0
        assert counters()["train.onepass_declined"] == 1

    def test_the_kernels_module_is_imported_early_only_where_it_may_run(
            self, monkeypatch):
        """About a second of host, started on a thread before the slab's
        placement: only for an eligible fit, only once a process."""
        import sys
        import threading

        started = []
        monkeypatch.setattr(threading.Thread, "start",
                            lambda self: started.append(self.name))
        # as in a process that has not used a kernel yet
        monkeypatch.delitem(sys.modules, common._KERNELS_MODULE)
        common._import_kernels_early(self.GRAD, _Mesh("cpu"))
        common._import_kernels_early(lambda p, x, y, w: None, _Mesh())
        assert started == []
        common._import_kernels_early(self.GRAD, _Mesh())
        assert started == ["fmt-kernels-import"]
        monkeypatch.setitem(sys.modules, common._KERNELS_MODULE,
                            pallas_kernels)
        common._import_kernels_early(self.GRAD, _Mesh())
        assert len(started) == 1

    def test_the_estimator_on_the_cpu_keeps_the_xla_step(self, counters):
        from flink_ml_tpu.lib import LogisticRegression
        from flink_ml_tpu.table.schema import DataTypes, Schema
        from flink_ml_tpu.table.table import Table

        rng = np.random.RandomState(1)
        X = rng.randn(1024, 6).astype(np.float32)
        y = (X @ rng.randn(6) > 0).astype(np.float64)
        table = Table.from_columns(
            Schema.of(("features", DataTypes.DENSE_VECTOR),
                      ("label", "double")), {"features": X, "label": y})
        (LogisticRegression().set_vector_col("features")
         .set_label_col("label").set_prediction_col("pred")
         .set_global_batch_size(1024).set_max_iter(3).fit(table))
        c = counters()
        assert c["train.fused_runs"] == 1
        assert c["train.onepass_fits"] == 0  # there, for a reader to find
        assert "train.pallas_interpreted" not in c
        assert "train.onepass_declined" not in c


def fused_fit(grad_fn, onepass_rows, slab_host, n_rows, d, max_iter=5,
              mesh=None):
    mesh = mesh or default_mesh()
    fn = common.make_glm_train_fn(grad_fn, mesh, 0.2, 0.01, max_iter, 0.0,
                                  bundle=True, onepass_rows=onepass_rows)
    p0 = (jnp.zeros((d,), jnp.float32), jnp.zeros((), jnp.float32))
    return common._run_fused_train(fn, p0, slab_host, mesh, n_rows=n_rows)


class TestTheStepInsideTheFusedFit:
    """``make_glm_train_fn(onepass_rows=...)``: the scan over step numbers,
    the kernel a step, the psums, update and bundle where they were."""

    @staticmethod
    def stack(d=37, seed=2):
        rng = np.random.RandomState(seed)
        n_dev = jax.device_count()
        n = 128 * n_dev * 3 - 40  # the last minibatches end in padding
        X = rng.randn(n, d).astype(np.float32)
        y = (X @ rng.randn(d) > 0).astype(np.float64)
        stack = common.pack_minibatches(X, y, n_dev, 128 * n_dev)
        return common._combined_view(stack), n

    @pytest.mark.parametrize("kind", ["logistic", "squared"])
    @pytest.mark.parametrize("with_intercept", [True, False])
    def test_matches_the_xla_fit(self, kind, with_intercept):
        slab, n = self.stack()
        grad_fn = GRAD_FNS[kind](with_intercept)
        xla = fused_fit(grad_fn, 0, slab, n, 37)
        one = fused_fit(grad_fn, 128, slab, n, 37)
        np.testing.assert_allclose(one.params[0], xla.params[0],
                                   rtol=0, atol=2e-6)
        np.testing.assert_allclose(one.params[1], xla.params[1],
                                   rtol=0, atol=2e-6)
        np.testing.assert_allclose(one.losses, xla.losses, rtol=2e-6)
        assert one.epochs == xla.epochs == 5
        if not with_intercept:
            assert float(one.params[1]) == 0.0

    def test_a_repeated_fit_returns_the_same_bytes(self):
        slab, n = self.stack(d=28)
        a = fused_fit(_log_loss_grads(True), 128, slab, n, 28)
        b = fused_fit(_log_loss_grads(True), 128, slab, n, 28)
        assert a.params[0].tobytes() == b.params[0].tobytes()
        assert a.losses == b.losses

    def test_the_fit_is_counted_and_so_is_the_interpreter(self, counters):
        slab, n = self.stack(d=28)
        fused_fit(_log_loss_grads(True), 128, slab, n, 28, max_iter=2)
        fused_fit(_log_loss_grads(True), 0, slab, n, 28, max_iter=2)
        c = counters()
        assert c["train.fused_runs"] == 2
        assert c["train.onepass_fits"] == 1
        assert c["train.pallas_interpreted"] == 1

    @staticmethod
    def skipping_stack(n_dev, n, d=12, batch=4096, seed=3):
        """A pack whose last step ends in whole row tiles of padding."""
        rng = np.random.RandomState(seed)
        X = rng.randn(n, d).astype(np.float32)
        y = (X @ rng.randn(d) > 0).astype(np.float64)
        return common._combined_view(
            common.pack_minibatches(X, y, n_dev, batch))

    def test_the_tiles_read_and_skipped_are_counted(self, counters):
        """Eight shards of 512 rows, two steps, 7,492 rows: the last step's
        seventh shard holds 324 rows (3 tiles of 128 read, 1 passed over),
        its eighth none (1 read, 3 passed over); the 14 full minibatches
        read all 4 of theirs.  Two epochs; the XLA step counts 0 and 0."""
        n_dev = jax.device_count()
        assert n_dev == 8
        n = 2 * 4096 - 700
        slab = self.skipping_stack(n_dev, n)
        grad_fn = _log_loss_grads(True)
        one = fused_fit(grad_fn, 128, slab, n, 12, max_iter=2)
        c = counters()
        assert c["train.onepass_tiles"] == 2 * (14 * 4 + 3 + 1)
        assert c["train.onepass_tiles_skipped"] == 2 * (1 + 3)
        obs.reset()
        xla = fused_fit(grad_fn, 0, slab, n, 12, max_iter=2)
        c = counters()
        assert c["train.onepass_tiles"] == 0
        assert c["train.onepass_tiles_skipped"] == 0
        np.testing.assert_allclose(one.params[0], xla.params[0], rtol=0,
                                   atol=2e-6)
        np.testing.assert_allclose(one.losses, xla.losses, rtol=2e-6)

    def test_four_shards_with_padding_in_the_last_alone_match_one_device(
            self):
        """2,048 rows a step over four shards of 512, 3,796 rows: the last
        step's fourth shard alone ends in padding (212 rows, 2 of its 4
        tiles read), and the fit matches the same table's on one device."""
        from jax.sharding import Mesh

        n, d = 2 * 2048 - 300, 12
        fits = []
        for n_dev in (4, 1):
            mesh = Mesh(np.array(jax.devices()[:n_dev]), ("data",))
            slab = self.skipping_stack(n_dev, n, d=d, batch=2048)
            assert slab.shape == (2 * n_dev, 2048 // n_dev, d + 2)
            fits.append(fused_fit(_log_loss_grads(True), 128, slab, n, d,
                                  max_iter=3, mesh=mesh))
        four, one = fits
        np.testing.assert_allclose(four.params[0], one.params[0], rtol=0,
                                   atol=2e-6)
        np.testing.assert_allclose(four.params[1], one.params[1], rtol=0,
                                   atol=2e-6)
        np.testing.assert_allclose(four.losses, one.losses, rtol=2e-6)
        assert four.epochs == one.epochs == 3

    def test_the_program_keeps_its_name(self):
        """``jit_bundled``: three readers of the benchmark match on it."""
        mesh = default_mesh()
        slab, _n = self.stack(d=28)
        fn = common.make_glm_train_fn(_log_loss_grads(True), mesh, 0.3, 0.0,
                                      2, 0.0, bundle=True, onepass_rows=128)
        p0 = (jnp.zeros((28,), jnp.float32), jnp.zeros((), jnp.float32))
        jitted = fn.__closure__[0].cell_contents
        assert "jit_bundled" in jitted.lower(
            p0, jnp.asarray(slab)).as_text()[:200]


class TestBuildCost:
    """The set-up budget as a test: what is built for a fit is the same size
    whatever the steps an epoch and the rows a minibatch, so tracing,
    lowering, the executable and its cache entry are too."""

    @staticmethod
    def lowered_text(steps, rows, d):
        def epoch(slab, wts, b):
            def step(params, i):
                gw, gb, loss, _w = glm_grad(slab, i, *params)
                return (params[0] - gw, params[1] - gb), loss

            return jax.lax.scan(step, (wts, b), glm_grad_schedule(
                slab, glm_grad_tile(rows, d)))

        args = (jax.ShapeDtypeStruct((steps, rows, d + 2), jnp.float32),
                jax.ShapeDtypeStruct((d,), jnp.float32),
                jax.ShapeDtypeStruct((), jnp.float32))
        # lowered for the chip (Mosaic), with no chip and no TPU client
        return jax.jit(epoch).trace(*args).lower(
            lowering_platforms=("tpu",)).as_text()

    @pytest.mark.parametrize("d", [2000, 784])
    def test_the_lowered_program_does_not_grow_with_steps_or_rows(self, d):
        base = self.lowered_text(13, 32768, d)
        assert "tpu_custom_call" in base
        assert len(self.lowered_text(62, 32768, d)) <= 1.03 * len(base)
        assert len(base) <= 1.03 * len(self.lowered_text(13, 4096, d))


class TestLoweringIsChosenByPlatform:
    """tpu compiles with Mosaic or raises, cpu interprets, anything else
    raises — nothing selects the interpreter silently."""

    def test_cpu_interprets_and_says_so(self):
        assert pallas_kernels.launch_interpreted() is True
        fn = common.make_glm_train_fn(_log_loss_grads(True), default_mesh(),
                                      0.4, 0.0, 2, 0.0, bundle=True,
                                      onepass_rows=128)
        assert fn.onepass is True
        assert fn.pallas_interpret is True

    def test_tpu_compiles_with_mosaic(self, monkeypatch):
        monkeypatch.setattr(jax, "devices", lambda *a: [_Device("tpu")])
        assert pallas_kernels.launch_interpreted() is False

    def test_unknown_platform_raises(self, monkeypatch):
        monkeypatch.setattr(jax, "devices",
                            lambda *a: [_Device("some_plugin")])
        with pytest.raises(RuntimeError, match="some_plugin"):
            pallas_kernels.launch_interpreted()
        with pytest.raises(RuntimeError, match="some_plugin"):
            common.make_glm_train_fn(_log_loss_grads(True), default_mesh(),
                                     0.5, 0.0, 2, 0.0, bundle=True,
                                     onepass_rows=128)
        with pytest.raises(RuntimeError, match="some_plugin"):
            pallas_kernels.serve_chain(["glm_score"], [True], 4)
