"""Pallas-fused serving chain (ops/pallas_kernels.serve_chain) — kernel
parity, the planner's Pallas hot path, low-precision inference, and the
bundled/donated train-step dispatch (ISSUE 17).

Every kernel test here runs in INTERPRET mode on the CPU mesh — the
serve-chain kernel deliberately avoids the vma plumbing that gates the
older grad kernels, so no environment skip applies.  The contract under
test: the Pallas path returns bit-identical discrete predictions and
quarantine side-tables to the XLA fused path, affine stages bit-exact,
scores inside float tolerance; anything ineligible (csr, kNN, int8) falls
back to the XLA program and counts a ``fused.pallas_fallbacks``.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from flink_ml_tpu import obs
from flink_ml_tpu.api.pipeline import Pipeline
from flink_ml_tpu.common import fused
from flink_ml_tpu.lib import Knn, LogisticRegression
from flink_ml_tpu.lib.feature import MinMaxScaler, StandardScaler
from flink_ml_tpu.ops.pallas_kernels import SERVE_CHAIN_OPS, serve_chain
from flink_ml_tpu.parallel.mesh import default_mesh
from flink_ml_tpu.serve import quarantine
from flink_ml_tpu.table.schema import DataTypes, Schema
from flink_ml_tpu.table.table import Table
from flink_ml_tpu.utils.environment import MLEnvironmentFactory

N, D = 1024, 6
D_PAD = 128  # serve_chain pads the lane axis to the 128 multiple
SCHEMA = Schema.of(("features", DataTypes.DENSE_VECTOR), ("label", "double"))


@pytest.fixture
def dense_table():
    rng = np.random.RandomState(7)
    X = (2.0 * rng.randn(N, D) + 1.0).astype(np.float32)
    w = rng.randn(D).astype(np.float32)
    y = ((X - 1.0) @ w > 0).astype(np.float64)
    return Table.from_columns(SCHEMA, {"features": X, "label": y})


@pytest.fixture
def obs_on():
    obs.enable()
    obs.reset()
    yield
    obs.reset()
    obs.disable()


@pytest.fixture
def batch_size():
    env = MLEnvironmentFactory.get_default()
    old = env.default_batch_size
    env.default_batch_size = 256
    yield 256
    env.default_batch_size = old


def _pad(X):
    out = np.zeros((X.shape[0], D_PAD), np.float32)
    out[:, : X.shape[1]] = X
    return out


def _stage_params(rng, kinds, d):
    params = []
    for kind in kinds:
        if kind == "glm_score":
            params.append((rng.randn(d).astype(np.float32),
                           np.float32(rng.randn())))
        else:
            params.append((rng.randn(d).astype(np.float32),
                           rng.randn(d).astype(np.float32)))
    return params


def _ref_chain(kinds, fetch, X, params):
    """The chain as ONE jitted XLA program, padded exactly like the kernel
    (zero pads are exact through every stage), outputs sliced like the
    caller.  Jitted, not eager numpy: the parity contract is kernel == XLA
    elementwise, and compiled XLA fuses ``h * a + b`` into an FMA that a
    separate mul/add rounds differently."""
    padded = []
    for kind, (pa, pb) in zip(kinds, params):
        if kind == "glm_score":
            w = np.zeros((D_PAD, 1), np.float32)
            w[: pa.size, 0] = pa
            padded.append((w, np.float32(pb)))
        else:
            a = np.zeros((D_PAD,), np.float32)
            a[: pa.size] = pa
            b = np.zeros((D_PAD,), np.float32)
            b[: pb.size] = pb
            padded.append((a, b))

    @jax.jit
    def chain(h, stage_params):
        outs = []
        for kind, (pa, pb), keep in zip(kinds, stage_params, fetch):
            if kind == "glm_score":
                h = h @ pa + pb
            else:
                h = (h - pa) * pb if kind == "affine_sub_mul" else h * pa + pb
            if keep:
                outs.append(h)
        return outs

    return [np.asarray(o) for o in chain(jnp.asarray(_pad(X)), padded)]


class TestServeChainKernel:
    @pytest.mark.parametrize("kind", SERVE_CHAIN_OPS)
    def test_single_stage_matches_reference(self, kind):
        rng = np.random.RandomState(3)
        X = rng.randn(256, D).astype(np.float32)
        params = _stage_params(rng, [kind], D)
        fn = serve_chain([kind], [True], D)
        (got,) = fn(jnp.asarray(_pad(X)), tuple(map(jnp.asarray, params[0])))
        (ref,) = _ref_chain([kind], [True], X, params)
        got = np.asarray(got)
        if kind == "glm_score":
            np.testing.assert_allclose(got[:, 0], ref[:, 0],
                                       rtol=1e-5, atol=1e-6)
        else:
            # affine stages are bit-exact: same elementwise f32 ops
            np.testing.assert_array_equal(got, ref)

    def test_three_stage_chain_matches_reference(self):
        rng = np.random.RandomState(4)
        X = rng.randn(512, D).astype(np.float32)
        kinds = ["affine_sub_mul", "affine_mul_add", "glm_score"]
        fetch = [True, True, True]
        params = _stage_params(rng, kinds, D)
        fn = serve_chain(kinds, fetch, D)
        got = fn(jnp.asarray(_pad(X)),
                 *[tuple(map(jnp.asarray, p)) for p in params])
        refs = _ref_chain(kinds, fetch, X, params)
        np.testing.assert_array_equal(np.asarray(got[0]), refs[0])
        np.testing.assert_array_equal(np.asarray(got[1]), refs[1])
        np.testing.assert_allclose(np.asarray(got[2])[:, 0], refs[2][:, 0],
                                   rtol=1e-5, atol=1e-6)

    def test_zero_padding_is_exact(self):
        """Pad lanes [d:] stay exactly zero through affine stages — the
        guarantee that lets the planner slice [:, :d] without a mask."""
        rng = np.random.RandomState(5)
        X = rng.randn(64, D).astype(np.float32)
        kinds = ["affine_sub_mul", "affine_mul_add"]
        params = _stage_params(rng, kinds, D)
        fn = serve_chain(kinds, [True, True], D)
        got = fn(jnp.asarray(_pad(X)),
                 *[tuple(map(jnp.asarray, p)) for p in params])
        for o in got:
            assert not np.asarray(o)[:, D:].any()

    @pytest.mark.parametrize("n", [1, 5, 7, 96, 250, 1000])
    def test_ragged_row_counts(self, n):
        """Bisection slices and tails hit row counts with gcd(n, tile) < 8;
        the kernel pads rows to a legal tile and slices back."""
        rng = np.random.RandomState(n)
        X = rng.randn(n, D).astype(np.float32)
        kinds = ["affine_sub_mul", "glm_score"]
        params = _stage_params(rng, kinds, D)
        fn = serve_chain(kinds, [False, True], D)
        (got,) = fn(jnp.asarray(_pad(X)),
                    *[tuple(map(jnp.asarray, p)) for p in params])
        (ref,) = _ref_chain(kinds, [False, True], X, params)
        assert got.shape[0] == n
        np.testing.assert_allclose(np.asarray(got)[:, 0], ref[:, 0],
                                   rtol=1e-5, atol=1e-6)

    def test_masked_variant_flags_and_zeroes_adversarial_rows(self):
        """NaN, +/-Inf rows mask to 0 and are zeroed before the chain;
        denormal (tiny but finite) rows stay servable and exact."""
        rng = np.random.RandomState(6)
        X = rng.randn(40, D).astype(np.float32)
        X[3, 0] = np.nan
        X[11, 2] = np.inf
        X[17, 5] = -np.inf
        X[23] = np.float32(1e-42)  # denormal: finite, must NOT quarantine
        kinds = ["affine_sub_mul", "glm_score"]
        params = _stage_params(rng, kinds, D)
        fn = serve_chain(kinds, [False, True], D, masked=True)
        mask, score = fn(jnp.asarray(_pad(X)),
                         *[tuple(map(jnp.asarray, p)) for p in params])
        mask = np.asarray(mask)[:, 0] > 0
        bad = {3, 11, 17}
        assert set(np.nonzero(~mask)[0]) == bad
        assert mask[23]
        Xz = X.copy()
        Xz[list(bad)] = 0.0
        (ref,) = _ref_chain(kinds, [False, True], Xz, params)
        np.testing.assert_allclose(np.asarray(score)[:, 0], ref[:, 0],
                                   rtol=1e-5, atol=1e-6)

    @pytest.mark.parametrize("width", [1, 2, 4, 8])
    def test_shard_map_parity_across_mesh_widths(self, width):
        """The collective-free kernel composes inside shard_map row
        sharding: any mesh width returns the width-1 answer bitwise."""
        from jax.sharding import PartitionSpec as P

        from flink_ml_tpu.parallel.collectives import shard_map

        rng = np.random.RandomState(8)
        X = rng.randn(256, D).astype(np.float32)
        kinds = ["affine_sub_mul", "affine_mul_add", "glm_score"]
        params = _stage_params(rng, kinds, D)
        fn = serve_chain(kinds, [False, False, True], D)
        jp = [tuple(map(jnp.asarray, p)) for p in params]
        (base,) = fn(jnp.asarray(_pad(X)), *jp)
        mesh = default_mesh(devices=jax.devices()[:width])
        flat = [a for p in jp for a in p]

        def local(x, *margs):
            pairs = [tuple(margs[i : i + 2]) for i in range(0, len(margs), 2)]
            (out,) = fn(x, *pairs)
            return out

        sharded = shard_map(
            local, mesh,
            in_specs=(P("data"),) + (P(),) * len(flat),
            out_specs=P("data"),
            # the serving plane's rule (FusedRun._apply_fn): collective-
            # free programs run with the varying-axes check off
            check_vma=False,
        )
        got = sharded(jnp.asarray(_pad(X)), *flat)
        np.testing.assert_array_equal(np.asarray(got), np.asarray(base))

    def test_rejects_unknown_op(self):
        with pytest.raises(ValueError):
            serve_chain(["affine_sub_mul", "relu"], [True, True], D)


def _transform(model, table, monkeypatch, *, pallas, precision="f32"):
    monkeypatch.setenv("FMT_FUSE_TRANSFORM", "1")
    monkeypatch.setenv("FMT_SERVE_PALLAS", "1" if pallas else "0")
    monkeypatch.setenv("FMT_SERVE_PRECISION", precision)
    (out,) = model.transform(table)
    return out


def _lr_pipeline(dense_table, max_iter=3, lr=0.5):
    return Pipeline([
        StandardScaler().set_selected_col("features"),
        MinMaxScaler().set_selected_col("features"),
        LogisticRegression().set_vector_col("features")
        .set_label_col("label").set_prediction_col("pred")
        .set_prediction_detail_col("proba").set_max_iter(max_iter)
        .set_learning_rate(lr),
    ]).fit(dense_table)


class TestPallasServePath:
    def test_pipeline_parity_and_one_kernel_per_dispatch(
            self, dense_table, obs_on, batch_size, monkeypatch):
        """The acceptance shape: with FMT_SERVE_PALLAS=1 every fused
        dispatch is exactly ONE Pallas launch, predictions bit-identical
        to the XLA chain, floats inside tolerance, zero fallbacks."""
        model = _lr_pipeline(dense_table)
        xla = _transform(model, dense_table, monkeypatch, pallas=False)
        obs.reset()
        pal = _transform(model, dense_table, monkeypatch, pallas=True)
        c = obs.registry().snapshot()["counters"]
        assert c.get("fused.pallas_dispatches") == \
            c.get("pipeline.fused_dispatches") == -(-N // batch_size)
        assert "fused.pallas_fallbacks" not in c
        # on the CPU harness every one of them ran interpreted, and says so
        assert c.get("fused.pallas_interpreted") == \
            c.get("fused.pallas_dispatches")
        np.testing.assert_array_equal(
            np.asarray(xla.col("pred")), np.asarray(pal.col("pred")))
        np.testing.assert_allclose(
            np.asarray(xla.col("proba"), dtype=np.float64),
            np.asarray(pal.col("proba"), dtype=np.float64),
            rtol=1e-5, atol=1e-7)
        np.testing.assert_array_equal(
            np.asarray(xla.features_dense("features")),
            np.asarray(pal.features_dense("features")))

    def test_quarantine_side_table_parity(self, dense_table, obs_on,
                                          batch_size, monkeypatch):
        """The deferred in-kernel scan yields the SAME side-table (rows,
        reasons) and the same survivors as the XLA path's host scan."""
        X = np.asarray(dense_table.features_dense("features")).copy()
        for r, c in ((3, 0), (257, 2), (511, 5), (900, 1)):
            X[r, c] = np.nan if r % 2 else np.inf
        bad = Table.from_columns(SCHEMA, {
            "features": X, "label": dense_table.col("label")})
        model = _lr_pipeline(dense_table)

        def run(pallas):
            quarantine.reset()
            out = _transform(model, bad, monkeypatch, pallas=pallas)
            qt = quarantine.quarantine_table("StandardScalerModel")
            rows = sorted(int(r) for r in qt.col(quarantine.QUARANTINE_ROW_COL))
            reasons = set(qt.col(quarantine.QUARANTINE_REASON_COL))
            quarantine.reset()
            return out, rows, reasons

        xla, xrows, xreasons = run(False)
        pal, prows, preasons = run(True)
        assert prows == xrows == [3, 257, 511, 900]
        assert preasons == xreasons == {"nan_inf"}
        assert pal.num_rows() == xla.num_rows() == N - 4
        np.testing.assert_array_equal(
            np.asarray(xla.col("pred")), np.asarray(pal.col("pred")))

    def test_ineligible_plan_falls_back_and_counts(self, dense_table,
                                                   obs_on, monkeypatch):
        """kNN's kernel has no pallas_op: the knob stays honored by
        falling back to the XLA program (identical output) and counting
        a fused.pallas_fallbacks so --check can flag a degraded fleet."""
        model = Pipeline([
            StandardScaler().set_selected_col("features"),
            Knn().set_vector_col("features").set_label_col("label")
            .set_k(3).set_prediction_col("p"),
        ]).fit(dense_table)
        off = _transform(model, dense_table, monkeypatch, pallas=False)
        obs.reset()
        on = _transform(model, dense_table, monkeypatch, pallas=True)
        c = obs.registry().snapshot()["counters"]
        assert c.get("fused.pallas_fallbacks", 0) >= 1
        assert "fused.pallas_dispatches" not in c
        np.testing.assert_array_equal(
            np.asarray(off.col("p")), np.asarray(on.col("p")))

    def test_compile_ledger_records_pallas_prefix(self, dense_table, obs_on,
                                                  tmp_path, monkeypatch):
        from flink_ml_tpu.obs import trace

        monkeypatch.setenv("FMT_OBS_REPORTS", str(tmp_path / "reports"))
        trace.reset()
        fused.reset_compile_keys()
        model = _lr_pipeline(dense_table)
        _transform(model, dense_table, monkeypatch, pallas=True)
        import json

        with open(trace.compile_ledger_path()) as f:
            kernels = [json.loads(line)["kernel"] for line in f]
        assert any(k.startswith("pallas:") for k in kernels)
        trace.reset()


def _margin_table(model, table, monkeypatch, band=0.02):
    """Rows whose f32 probability clears the decision boundary by more
    than the documented low-precision tolerance band — the set on which
    discrete predictions are CONTRACTUALLY bit-identical (a row sitting
    inside the band may legitimately flip under quantization)."""
    f32 = _transform(model, table, monkeypatch, pallas=False)
    proba = np.asarray(f32.col("proba"), dtype=np.float64)
    keep = np.abs(proba - 0.5) > band
    # the strong fixture fit separates the classes well — most rows clear
    # the band, so the parity check below has real coverage
    assert keep.sum() > N * 0.85
    return table.filter_rows(keep)


class TestServePrecision:
    def test_bf16_discrete_parity(self, dense_table, obs_on, batch_size,
                                  monkeypatch):
        model = _lr_pipeline(dense_table, max_iter=50, lr=5.0)
        eval_t = _margin_table(model, dense_table, monkeypatch)
        f32 = _transform(model, eval_t, monkeypatch, pallas=False)
        obs.reset()
        bf16 = _transform(model, eval_t, monkeypatch, pallas=False,
                          precision="bf16")
        assert obs.registry().snapshot()["gauges"]["serve.precision"] == 16
        np.testing.assert_array_equal(
            np.asarray(f32.col("pred")), np.asarray(bf16.col("pred")))
        np.testing.assert_allclose(
            np.asarray(f32.col("proba"), dtype=np.float64),
            np.asarray(bf16.col("proba"), dtype=np.float64),
            rtol=2e-2, atol=2e-2)

    def test_bf16_rides_the_pallas_kernel(self, dense_table, obs_on,
                                          batch_size, monkeypatch):
        model = _lr_pipeline(dense_table, max_iter=50, lr=5.0)
        eval_t = _margin_table(model, dense_table, monkeypatch)
        f32 = _transform(model, eval_t, monkeypatch, pallas=True)
        obs.reset()
        bf16 = _transform(model, eval_t, monkeypatch, pallas=True,
                          precision="bf16")
        c = obs.registry().snapshot()["counters"]
        assert c.get("fused.pallas_dispatches") == \
            -(-eval_t.num_rows() // batch_size)
        np.testing.assert_array_equal(
            np.asarray(f32.col("pred")), np.asarray(bf16.col("pred")))

    def test_int8_discrete_parity_forces_xla(self, dense_table, obs_on,
                                             batch_size, monkeypatch):
        """int8 can't represent NaN: the planner keeps the XLA program
        (host-side validation) even with the Pallas knob on."""
        model = _lr_pipeline(dense_table, max_iter=50, lr=5.0)
        eval_t = _margin_table(model, dense_table, monkeypatch)
        f32 = _transform(model, eval_t, monkeypatch, pallas=False)
        obs.reset()
        i8 = _transform(model, eval_t, monkeypatch, pallas=True,
                        precision="int8")
        snap = obs.registry().snapshot()
        assert snap["gauges"]["serve.precision"] == 8
        assert "fused.pallas_dispatches" not in snap["counters"]
        assert snap["counters"].get("fused.pallas_fallbacks", 0) >= 1
        np.testing.assert_array_equal(
            np.asarray(f32.col("pred")), np.asarray(i8.col("pred")))
        np.testing.assert_allclose(
            np.asarray(f32.col("proba"), dtype=np.float64),
            np.asarray(i8.col("proba"), dtype=np.float64),
            rtol=5e-2, atol=5e-2)


class TestBundledTrainDispatch:
    def _fit_ingredients(self):
        from flink_ml_tpu.lib import common as C
        from flink_ml_tpu.lib.classification import _log_loss_grads

        rng = np.random.RandomState(0)
        X = rng.randn(N, D).astype(np.float32)
        w = rng.randn(D)
        y = (X @ w > 0).astype(np.float32)
        stack = C.pack_minibatches(X, y, 1, 128)
        return C, _log_loss_grads(True), stack

    @pytest.mark.parametrize("width", [1, 4, 8])
    def test_bundled_fetch_bitwise_parity(self, width):
        """The single-buffer fetch program returns bit-identical params,
        losses, epochs, and delta to the 4-tuple + fetch_flat path."""
        C, grad_fn, stack = self._fit_ingredients()
        mesh = default_mesh(devices=jax.devices()[:width])
        init = (np.zeros(D), np.zeros(()))
        batch = C._combined_view(stack)
        plain = C._run_fused_train(
            C.make_glm_train_fn(grad_fn, mesh, 0.5, 0.0, 12, 0.0),
            init, batch, mesh, n_rows=N)
        bund = C._run_fused_train(
            C.make_glm_train_fn(grad_fn, mesh, 0.5, 0.0, 12, 0.0,
                                bundle=True),
            init, batch, mesh, n_rows=N)
        for a, b in zip(jax.tree_util.tree_leaves(plain.params),
                        jax.tree_util.tree_leaves(bund.params)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        assert plain.epochs == bund.epochs
        assert plain.losses == bund.losses
        assert plain.final_delta == bund.final_delta

    def test_direct_caller_keeps_tuple_contract(self):
        """diagnose_perf and the graft entry unpack the raw 4-tuple: the
        default (unbundled) build must keep returning it."""
        from flink_ml_tpu.parallel.mesh import replicate, shard_batch

        C, grad_fn, stack = self._fit_ingredients()
        mesh = default_mesh(devices=jax.devices()[:1])
        fn = C.make_glm_train_fn(grad_fn, mesh, 0.5, 0.0, 3, 0.0)
        out = fn(replicate(mesh, (jnp.zeros(D), jnp.zeros(()))),
                 shard_batch(mesh, C._combined_view(stack)))
        assert isinstance(out, tuple) and len(out) == 4
        assert not getattr(fn, "bundle_fetch", False)
