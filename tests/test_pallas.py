"""Pallas kernel numerics (interpret mode on the CPU test mesh) and
integration as a drop-in GradFn in the training harness."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from flink_ml_tpu.ops import pallas_kernels
from flink_ml_tpu.ops.pallas_kernels import glm_grad, make_pallas_grad_fn


def data(n=300, d=28, seed=0):
    rng = np.random.RandomState(seed)
    x = jnp.asarray(rng.randn(n, d), jnp.float32)
    y = jnp.asarray((rng.randn(n) > 0), jnp.float32)
    w = jnp.asarray((rng.rand(n) > 0.1), jnp.float32)  # some zero weights
    wts = jnp.asarray(rng.randn(d), jnp.float32)
    b = jnp.asarray(0.3, jnp.float32)
    return x, y, w, wts, b


class TestGlmGradKernel:
    @pytest.mark.parametrize("kind", ["logistic", "squared"])
    def test_matches_jnp_reference(self, kind):
        x, y, w, wts, b = data()
        gw, gb, loss, wsum = glm_grad(x, y, w, wts, b, kind=kind, interpret=True)
        logits = x @ wts + b
        if kind == "logistic":
            err = (jax.nn.sigmoid(logits) - y) * w
            ref_loss = jnp.sum(w * (jnp.logaddexp(0.0, logits) - y * logits))
        else:
            err = (logits - y) * w
            ref_loss = 0.5 * jnp.sum(err * (logits - y))
        np.testing.assert_allclose(gw, x.T @ err, rtol=2e-4, atol=2e-4)
        np.testing.assert_allclose(gb, err.sum(), rtol=1e-4, atol=1e-4)
        np.testing.assert_allclose(loss, ref_loss, rtol=1e-4)
        np.testing.assert_allclose(wsum, w.sum(), rtol=1e-6)

    def test_row_padding_is_neutral(self):
        """n not a multiple of the tile: padded rows must contribute nothing."""
        x, y, w, wts, b = data(n=130)
        gw_a, *_ = glm_grad(x, y, w, wts, b, interpret=True, tile_rows=64)
        gw_b, *_ = glm_grad(x, y, w, wts, b, interpret=True, tile_rows=512)
        np.testing.assert_allclose(gw_a, gw_b, rtol=1e-5, atol=1e-5)

    def test_wide_d_tile_shrinks_to_vmem_budget(self):
        x, y, w, wts, b = data(n=64, d=3000)
        gw, *_ = glm_grad(x, y, w, wts, b, interpret=True)
        logits = x @ wts + b
        err = (jax.nn.sigmoid(logits) - y) * w
        np.testing.assert_allclose(gw, x.T @ err, rtol=2e-3, atol=2e-3)


class TestPallasGradFnIntegration:
    def test_grad_fn_contract(self):
        """make_pallas_grad_fn satisfies the GradFn contract numerically."""
        x, y, w, wts, b = data()
        grad_fn = make_pallas_grad_fn("logistic", with_intercept=True)
        (g_w, g_b), loss, wsum = grad_fn((wts, b), x, y, w)
        logits = x @ wts + b
        err = (jax.nn.sigmoid(logits) - y) * w
        np.testing.assert_allclose(g_w, x.T @ err, rtol=2e-4, atol=2e-4)
        np.testing.assert_allclose(g_b, err.sum(), rtol=1e-4, atol=1e-4)

        no_b = make_pallas_grad_fn("logistic", with_intercept=False)
        (_, g_b0), *_ = no_b((wts, b), x, y, w)
        assert float(g_b0) == 0.0

    def test_trains_through_harness(self):
        """make_pallas_grad_fn drops into train_glm and converges — runs in
        the CPU CI suite via interpret mode (the grad fn declares
        shard_map_check_vma=False there; strict vma with Mosaic, which
        chip_smoke.py covers)."""
        from flink_ml_tpu.lib.common import pack_minibatches, train_glm
        from flink_ml_tpu.parallel.mesh import default_mesh

        rng = np.random.RandomState(1)
        X = rng.randn(160, 4)
        true_w = np.array([1.0, -2.0, 0.5, 0.0])
        y = ((X @ true_w) > 0).astype(np.float64)
        mesh = default_mesh()
        stack = pack_minibatches(X, y, jax.device_count())
        grad_fn = make_pallas_grad_fn("logistic", with_intercept=True)
        result = train_glm(
            (jnp.zeros((4,), jnp.float32), jnp.zeros((), jnp.float32)),
            stack, grad_fn, mesh, learning_rate=0.5, max_iter=60,
        )
        w, b = result.params
        preds = (X @ w + b) > 0
        assert np.mean(preds == y) > 0.9

    def test_trains_through_listener_path(self):
        """The listener/checkpoint epoch path (make_glm_epoch_step ->
        make_data_parallel_step) must also honor the grad fn's vma
        declaration (r4 review finding)."""
        from flink_ml_tpu.iteration.listener import IterationListener
        from flink_ml_tpu.lib.common import pack_minibatches, train_glm
        from flink_ml_tpu.parallel.mesh import default_mesh

        class Counter(IterationListener):
            epochs = 0

            def on_epoch_watermark_incremented(self, epoch, context):
                self.epochs += 1

        rng = np.random.RandomState(3)
        X = rng.randn(128, 4)
        y = ((X @ np.array([1.0, -2.0, 0.5, 0.0])) > 0).astype(np.float64)
        listener = Counter()
        result = train_glm(
            (jnp.zeros((4,), jnp.float32), jnp.zeros((), jnp.float32)),
            pack_minibatches(X, y, jax.device_count()),
            make_pallas_grad_fn("logistic", with_intercept=True),
            default_mesh(), learning_rate=0.5, max_iter=15,
            listeners=[listener],
        )
        assert listener.epochs == result.epochs == 15
        w, b = result.params
        assert np.mean(((X @ w + b) > 0) == y) > 0.9

    def test_matches_jnp_grad_fn_through_harness(self):
        """The pallas-backed fused fit matches the jnp grad fn's fit."""
        from flink_ml_tpu.lib.classification import _log_loss_grads
        from flink_ml_tpu.lib.common import pack_minibatches, train_glm
        from flink_ml_tpu.parallel.mesh import default_mesh

        rng = np.random.RandomState(2)
        X = rng.randn(128, 6)
        y = ((X @ rng.randn(6)) > 0).astype(np.float64)
        mesh = default_mesh()
        stack = pack_minibatches(X, y, jax.device_count(), global_batch_size=32)
        p0 = (jnp.zeros((6,), jnp.float32), jnp.zeros((), jnp.float32))
        rp = train_glm((jnp.copy(p0[0]), jnp.copy(p0[1])), stack,
                       make_pallas_grad_fn("logistic", with_intercept=True),
                       mesh, learning_rate=0.5, max_iter=10)
        rj = train_glm((jnp.copy(p0[0]), jnp.copy(p0[1])), stack,
                       _log_loss_grads(True), mesh,
                       learning_rate=0.5, max_iter=10)
        np.testing.assert_allclose(rp.params[0], rj.params[0],
                                   rtol=5e-4, atol=5e-5)
        np.testing.assert_allclose(rp.params[1], rj.params[1],
                                   rtol=5e-4, atol=5e-5)


class TestLoweringIsChosenByPlatform:
    """tpu compiles with Mosaic or raises, cpu interprets, anything else
    raises — nothing selects the interpreter silently."""

    class _Device:
        def __init__(self, platform):
            self.platform = platform

    def test_cpu_interprets_and_says_so(self):
        assert pallas_kernels.launch_interpreted() is True
        grad_fn = make_pallas_grad_fn("logistic", with_intercept=True)
        assert grad_fn.pallas_interpret is True
        assert grad_fn.shard_map_check_vma is False

    def test_tpu_compiles_with_mosaic(self, monkeypatch):
        monkeypatch.setattr(jax, "devices",
                            lambda *a: [self._Device("tpu")])
        assert pallas_kernels.launch_interpreted() is False

    def test_unknown_platform_raises(self, monkeypatch):
        monkeypatch.setattr(jax, "devices",
                            lambda *a: [self._Device("some_plugin")])
        with pytest.raises(RuntimeError, match="some_plugin"):
            pallas_kernels.launch_interpreted()
        with pytest.raises(RuntimeError, match="some_plugin"):
            make_pallas_grad_fn("logistic", with_intercept=True)
        with pytest.raises(RuntimeError, match="some_plugin"):
            pallas_kernels.serve_chain(["glm_score"], [True], 4)

    def test_interpreted_fit_is_counted(self):
        from flink_ml_tpu import obs
        from flink_ml_tpu.lib.common import pack_minibatches, train_glm
        from flink_ml_tpu.parallel.mesh import default_mesh

        x, y, *_ = data(n=64, d=4)
        stack = pack_minibatches(np.asarray(x), np.asarray(y, np.float64),
                                 jax.device_count())
        obs.enable()
        obs.reset()
        try:
            train_glm(
                (jnp.zeros((4,), jnp.float32), jnp.zeros((), jnp.float32)),
                stack, make_pallas_grad_fn("logistic", with_intercept=True),
                default_mesh(), learning_rate=0.5, max_iter=2,
            )
            c = obs.registry().snapshot()["counters"]
            assert c.get("train.pallas_interpreted", 0) == 1, c
        finally:
            obs.disable()
            obs.reset()

    def test_too_wide_for_vmem_is_a_clear_error(self):
        # the (d_pad, 1) weight/gradient blocks alone overflow the budget
        with pytest.raises(ValueError, match="VMEM"):
            glm_grad(*data(n=8, d=8192), interpret=True)
