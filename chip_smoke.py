"""chip_smoke.py — the quickest proof that the system still starts on the chip.

Drives the main path once, in ONE process, through the entry points a user
calls (``Pipeline``, ``StandardScaler``, ``LogisticRegression``,
``PipelineModel.load`` via ``ModelServer(path=...)``): fit -> transform ->
serve at the full width of the HIGGS-shaped dense logistic regression
(2,000,000 x 28 f32 train rows, globalBatchSize 32768, 5 epochs; weights
start at zero, data from a seed), then the two Pallas kernels compiled with
Mosaic at widths 28 and 512 against the XLA formulations they replace.

Every phase is ASSERTED: the first failure raises, the process exits
non-zero and no result line is printed.  It refuses to run on anything but a
TPU.  At exit every counter behind which a failure could hide (fallbacks,
retries, bisections, interpreted kernels, warm-start degrades) must be zero.

Run it from the repo root, through the chip tool:

    python chip_smoke.py                      # one process per chip
    python chip_smoke.py && python chip_smoke.py   # cache proof: the second
                                              # run adds no cache file

It writes only under ``chiprun_out/chip_smoke/`` (RunReports, traces, flight
dumps, the saved model and its ``warm_aot/``) and the compile cache
(``JAX_COMPILATION_CACHE_DIR``, else ``<repo>/.jax_cache``).  The LAST line
of stdout is the verdict the driver parses, one JSON object with exactly two
keys: ``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``
— the device as JAX reports it.  The line before it, prefixed
``chip_smoke: summary``, is the long record: each phase's seconds
(compile-bearing and steady apart), the asserted counters, the cache
directory and its entry counts, ``"claim": null``.  The seconds are SMOKE
TIMINGS of one run — not benchmark metrics; the chip benchmark is ROADMAP S0.
"""

import json
import os
import shutil
import sys
import time
from concurrent.futures import ThreadPoolExecutor

ROOT = os.path.dirname(os.path.abspath(__file__))

#: the full size (the driver's run); the tier-1 test shrinks it on CPU
FULL = {
    "n_train": 2_000_000, "n_test": 500_000, "dim": 28,
    "batch": 32768, "epochs": 5,
    "requests": 64, "max_request_rows": 256, "big_request_rows": 4096,
    # 7 steps of 16384: a slab of 8 steps would lie steps-on-sublanes on
    # the chip and keep the XLA step (lib/common.py:_onepass_rows)
    "wide_dim": 512, "wide_train": 114_688, "wide_batch": 16384,
}

#: counters that must be ZERO at exit: each is a way a run "works" without
#: the device having done the work
MUST_BE_ZERO = (
    "fused.pallas_fallbacks", "fused.pallas_interpreted",
    "train.pallas_interpreted", "pipeline.plan_fallback_batches",
    "serve.fallbacks", "serve.dispatch_failures", "serving.failed_requests",
    "serving.shed", "pressure.ooms", "pressure.bisections",
    "fault.retries", "fault.giveups", "warmstart.save_failures",
    "warmstart.degraded",
)


def _counters():
    from flink_ml_tpu import obs

    return obs.registry().snapshot()["counters"]


def _cache_entries(cache_dir):
    if not cache_dir or not os.path.isdir(cache_dir):
        return 0
    return sum(len(files) for _r, _d, files in os.walk(cache_dir))


def _auc(y, score):
    import numpy as np

    order = np.argsort(score, kind="mergesort")
    ranks = np.empty(len(score), dtype=np.float64)
    ranks[order] = np.arange(1, len(score) + 1)
    pos = y > 0.5
    n_pos, n_neg = int(pos.sum()), int((~pos).sum())
    return float((ranks[pos].sum() - n_pos * (n_pos + 1) / 2)
                 / (n_pos * n_neg))


def _table(X, y=None):
    from flink_ml_tpu.table.schema import DataTypes, Schema
    from flink_ml_tpu.table.table import Table

    if y is None:
        return Table.from_columns(
            Schema.of(("features", DataTypes.DENSE_VECTOR)), {"features": X})
    return Table.from_columns(
        Schema.of(("features", DataTypes.DENSE_VECTOR), ("label", "double")),
        {"features": X, "label": y})


def _logreg(sizes, lr=1.0):
    from flink_ml_tpu.lib import LogisticRegression

    return (LogisticRegression().set_vector_col("features")
            .set_label_col("label").set_prediction_col("pred")
            .set_prediction_detail_col("proba").set_learning_rate(lr)
            .set_global_batch_size(sizes["batch"])
            .set_max_iter(sizes["epochs"]))


def _data(n, dim, seed):
    import numpy as np

    rng = np.random.RandomState(seed)
    X = (1.5 * rng.randn(n, dim) + 0.5).astype(np.float32)
    w = (rng.randn(dim) / np.sqrt(dim)).astype(np.float32)
    y = (((X - 0.5) @ w + 0.25 * rng.randn(n).astype(np.float32)) > 0
         ).astype(np.float32)
    return X, y


def _numpy_reference(X, y, sizes, lr=1.0):
    """The same pipeline in numpy: standardize (sample std, as
    StandardScaler), then the identical minibatch SGD (mean gradient per
    global batch, f32)."""
    import numpy as np

    mean = X.mean(axis=0, dtype=np.float64)
    std = X.std(axis=0, ddof=1, dtype=np.float64)
    shift = mean.astype(np.float32)
    scale = (1.0 / np.where(std > 0, std, 1.0)).astype(np.float32)
    Xs = (X - shift) * scale
    w = np.zeros(X.shape[1], np.float32)
    b = np.float32(0.0)
    step = np.float32(lr)
    for _ in range(sizes["epochs"]):
        for lo in range(0, len(y), sizes["batch"]):
            xb, yb = Xs[lo:lo + sizes["batch"]], y[lo:lo + sizes["batch"]]
            err = 1.0 / (1.0 + np.exp(-(xb @ w + b))) - yb
            w -= step * (xb.T @ err) / np.float32(len(yb))
            b -= step * err.mean()
    return lambda Q: ((Q - shift) * scale) @ w + b


# -- phases -------------------------------------------------------------------


def phase_fit(ctx):
    """Pipeline.fit at full width, on every local chip; then the warm fit."""
    import jax
    import numpy as np

    from flink_ml_tpu.api.pipeline import Pipeline
    from flink_ml_tpu.lib.feature import StandardScaler
    from flink_ml_tpu.table import slab_pool

    sizes = ctx["sizes"]
    n, q = sizes["n_train"], sizes["n_test"]
    X, y = _data(n + q, sizes["dim"], seed=0)
    ctx["X_test"], ctx["y_test"] = X[n:], y[n:]
    train = _table(X[:n], y[:n])

    t0 = time.perf_counter()
    model = Pipeline([
        StandardScaler().set_selected_col("features"), _logreg(sizes),
    ]).fit(train)
    cold_s = time.perf_counter() - t0  # pack + H2D + compile + 5 epochs
    ctx["model"] = model
    lr_model = model.stages[-1]
    losses = [float(x) for x in lr_model.train_losses_]
    assert len(losses) == sizes["epochs"] and np.all(np.isfinite(losses)), \
        losses
    assert all(b < a for a, b in zip(losses, losses[1:])), \
        f"loss history not decreasing: {losses}"
    c = _counters()
    assert c.get("train.fused_runs", 0) >= 1, c

    # the placed training slab: spread over every local chip, none holding
    # the whole batch
    n_dev = jax.device_count()
    slabs = [leaf for key, value in slab_pool.pool().items()
             if key[0] == "table"
             for leaf in jax.tree_util.tree_leaves(value)
             if isinstance(leaf, jax.Array)]
    assert slabs, "no placed slab in the pool after the fit"
    for slab in slabs:
        shards = slab.addressable_shards
        devices = {s.device for s in shards}
        assert len(devices) == n_dev, (len(devices), n_dev)
        assert all(d.platform == ctx["platform"] for d in devices), devices
        if n_dev > 1:
            assert all(s.data.shape[0] * n_dev == slab.shape[0]
                       for s in shards), [s.data.shape for s in shards]

    # quality: held-out AUC within 0.005 of the same pipeline in numpy
    ref_score = _numpy_reference(X[:n], y[:n], sizes)(ctx["X_test"])
    ctx["auc_reference"] = _auc(ctx["y_test"], ref_score)

    # warm fit: the LR stage alone, twice on ONE Table — the second must hit
    # the pooled slab and reproduce the coefficients bit for bit
    first = _logreg(sizes).fit(train)
    hits0 = slab_pool.pool().counters()[0]
    t0 = time.perf_counter()
    second = _logreg(sizes).fit(train)
    warm_s = time.perf_counter() - t0
    assert slab_pool.pool().counters()[0] - hits0 >= 1, "warm fit missed"
    assert np.array_equal(first.coefficients(), second.coefficients())
    assert first.intercept() == second.intercept()
    ctx["coefficients"] = [float(x) for x in lr_model.coefficients()]
    return {"compile_s": cold_s, "steady_s": warm_s,
            "final_loss": losses[-1], "slab_devices": n_dev}


def phase_transform(ctx):
    """PipelineModel.transform of the held-out rows: one fused program,
    sharded over the mesh when there is more than one chip."""
    import jax
    import numpy as np

    from flink_ml_tpu import obs

    queries = _table(ctx["X_test"])
    ctx["queries"] = queries
    c0 = _counters()
    t0 = time.perf_counter()
    (out,) = ctx["model"].transform(queries)
    proba = np.asarray(out.col("proba"), dtype=np.float64)
    cold_s = time.perf_counter() - t0
    t0 = time.perf_counter()
    (again,) = ctx["model"].transform(queries)
    pred = np.asarray(again.col("pred"))
    steady_s = time.perf_counter() - t0
    c = _counters()
    assert c.get("pipeline.fused_dispatches", 0) \
        > c0.get("pipeline.fused_dispatches", 0), c
    n_dev = jax.device_count()
    gauges = obs.registry().snapshot()["gauges"]
    assert gauges.get("fused.mesh_devices") == n_dev, gauges
    if n_dev > 1:
        assert c.get("fused.shard_map_dispatches", 0) >= 1, c
    assert out.num_rows() == len(ctx["y_test"]) and np.all(np.isfinite(proba))
    assert np.array_equal(pred, np.asarray(out.col("pred")))
    auc = _auc(ctx["y_test"], proba)
    assert abs(auc - ctx["auc_reference"]) < 0.005, \
        (auc, ctx["auc_reference"])
    ctx["ref_pred"] = pred
    ctx["ref_proba"] = proba
    return {"compile_s": cold_s, "steady_s": steady_s, "auc": auc,
            "auc_reference": ctx["auc_reference"]}


def phase_serve(ctx, tag="xla"):
    """save -> ModelServer(path) -> concurrent small requests + one big one,
    every answer equal to the direct transform; then a SECOND server on the
    same path, so the warm-start store's hit path runs too."""
    import numpy as np

    from flink_ml_tpu.serving import ModelServer

    sizes = ctx["sizes"]
    queries = ctx["queries"]
    path = os.path.join(ctx["out"], "model_" + tag)
    shutil.rmtree(path, ignore_errors=True)
    ctx["model"].save(path)
    rng = np.random.RandomState(11)
    n_q = queries.num_rows()
    rows = rng.randint(1, sizes["max_request_rows"] + 1,
                       size=sizes["requests"])
    spans = [(int(lo), int(lo + k)) for lo, k in
             zip(rng.randint(0, n_q - sizes["max_request_rows"],
                             size=len(rows)), rows)]
    spans.append((0, min(sizes["big_request_rows"], n_q)))

    def check(server, lo, hi):
        res = server.predict(queries.slice_rows(lo, hi), timeout=600)
        assert res.num_rows == hi - lo and res.num_quarantined == 0
        assert np.array_equal(np.asarray(res.table.col("pred")),
                              ctx["ref_pred"][lo:hi]), (lo, hi)
        np.testing.assert_allclose(
            np.asarray(res.table.col("proba"), dtype=np.float64),
            ctx["ref_proba"][lo:hi], rtol=0, atol=1e-5)

    def traffic(server):
        t0 = time.perf_counter()
        with ThreadPoolExecutor(max_workers=4) as pool:
            for f in [pool.submit(check, server, lo, hi)
                      for lo, hi in spans]:
                f.result()
        return time.perf_counter() - t0

    t0 = time.perf_counter()
    server = ModelServer(path=path, warmup=queries.slice_rows(0, 8))
    deploy_s = time.perf_counter() - t0  # load + warm ladder compiles
    try:
        # the first pass still compiles the buckets the warm ladder does
        # not walk (coalesced batches past its top rung, the big request);
        # the second is the steady one
        first_pass_s = traffic(server)
        traffic_s = traffic(server)
        stats = server.stats()
    finally:
        server.shutdown()
    assert stats.get("serving.failed_requests", 0) == 0, stats
    assert stats.get("serving.shed", 0) == 0, stats
    assert stats["serving.requests"] == 2 * len(spans), stats

    hits0 = _counters().get("warmstart.hits", 0)
    t0 = time.perf_counter()
    second = ModelServer(path=path, warmup=queries.slice_rows(0, 8))
    redeploy_s = time.perf_counter() - t0
    try:
        for lo, hi in spans[:8] + spans[-1:]:
            check(second, lo, hi)
    finally:
        second.shutdown()
    assert _counters().get("warmstart.hits", 0) - hits0 >= 1, \
        "second ModelServer on the same path never hit the warm store"
    return {"compile_s": deploy_s + first_pass_s, "steady_s": traffic_s,
            "deploy_s": deploy_s, "warm_redeploy_s": redeploy_s,
            "requests": 2 * len(spans)}


def phase_serve_pallas(ctx):
    """The same server traffic with the serving chain lowered to the Pallas
    kernel — compiled with Mosaic, never interpreted."""
    d0 = _counters().get("fused.pallas_dispatches", 0)
    os.environ["FMT_SERVE_PALLAS"] = "1"
    try:
        out = phase_serve(ctx, tag="pallas")
    finally:
        del os.environ["FMT_SERVE_PALLAS"]
    dispatches = _counters().get("fused.pallas_dispatches", 0) - d0
    assert dispatches >= 1, "FMT_SERVE_PALLAS=1 dispatched no Pallas kernel"
    out["pallas_dispatches"] = dispatches
    return out


def _timed(fn, reps=20):
    """(first-call seconds, steady seconds per call), each ended by
    block_until_ready."""
    import jax

    t0 = time.perf_counter()
    jax.block_until_ready(fn())
    first = time.perf_counter() - t0
    t0 = time.perf_counter()
    for _ in range(reps):
        out = fn()
    jax.block_until_ready(out)
    return first, (time.perf_counter() - t0) / reps


def phase_kernels(ctx):
    """glm_grad and serve_chain compiled for real (interpret=False) at widths
    28 and 512, each against the XLA formulation it replaces; glm_grad also
    inside the fused training program on the mesh (strict check_vma), chosen
    there by the program's own rule from the placed slab; lloyd_sums inside
    a KMeans fit, chosen by the estimator's rule from the table; and
    serve_chain through the FusedRun path, raw and masked, f32 and bf16."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from flink_ml_tpu.api.pipeline import Pipeline
    from flink_ml_tpu.lib import common
    from flink_ml_tpu.lib.classification import _log_loss_grads
    from flink_ml_tpu.lib.feature import StandardScaler
    from flink_ml_tpu.ops.pallas_kernels import glm_grad, launch_interpreted
    from flink_ml_tpu.parallel.mesh import shard_batch_prefetched
    from flink_ml_tpu.utils.environment import MLEnvironmentFactory

    sizes = ctx["sizes"]
    interpret = launch_interpreted()
    assert not interpret, "kernel phase needs a TPU"
    out = {"compile_s": 0.0, "steady_s": 0.0, "smoke_timings_ms": {}}
    grad_fn = _log_loss_grads(True)
    xla_grad = jax.jit(grad_fn)

    # 1. the bare minibatch gradient, read out of a slab of three
    # minibatches where it lies (a slab of these shapes lies rows-minor on
    # the chip, which the kernel's view of it needs: asserted)
    for n, d in ((sizes["wide_batch"], sizes["wide_dim"]),
                 (sizes["batch"], sizes["dim"])):
        X, y = _data(3 * n, d, seed=3)
        slab = jax.device_put(np.concatenate(
            [X, y[:, None], np.ones((3 * n, 1), np.float32)],
            axis=1).reshape(3, n, d + 2))
        layout = slab.format.layout
        assert tuple(layout.major_to_minor) == (0, 2, 1), layout
        wts = jnp.asarray(np.random.RandomState(4).randn(d) * 0.1,
                          jnp.float32)
        b = jnp.float32(0.1)
        step = jnp.int32(1)
        x, yj, w = slab[1, :, :d], slab[1, :, d], slab[1, :, d + 1]
        first, steady = _timed(lambda: glm_grad(
            slab, step, wts, b, kind="logistic", interpret=False))
        _, xla_steady = _timed(lambda: xla_grad((wts, b), x, yj, w))
        gw, gb, loss, wsum = glm_grad(slab, step, wts, b, kind="logistic",
                                      interpret=False)
        (rgw, rgb), rloss, rwsum = xla_grad((wts, b), x, yj, w)
        scale = float(jnp.max(jnp.abs(rgw)))
        assert float(jnp.max(jnp.abs(gw - rgw))) <= 1e-5 * scale, (n, d)
        np.testing.assert_allclose(float(gb), float(rgb), rtol=1e-5,
                                   atol=1e-3)
        np.testing.assert_allclose(float(loss), float(rloss), rtol=1e-5)
        assert float(wsum) == float(rwsum) == n
        out["compile_s"] += first
        out["steady_s"] += steady
        out["smoke_timings_ms"][f"glm_grad_{n}x{d}"] = {
            "pallas": round(steady * 1e3, 3),
            "xla": round(xla_steady * 1e3, 3)}

    # 2. the same kernel inside the fused fit, on the default mesh — the
    # strict-check_vma configuration only a TPU selects — picked by the
    # program's own rule from the slab as placed; against the fit that
    # keeps the XLA step
    mesh = MLEnvironmentFactory.get_default().get_mesh()
    n_dev = jax.device_count()
    d = sizes["wide_dim"]
    Xw, yw = _data(sizes["wide_train"], d, seed=5)
    stack = common.pack_minibatches(Xw, yw.astype(np.float64), n_dev,
                                    sizes["wide_batch"])
    placed = shard_batch_prefetched(mesh, common._combined_view(stack))
    rows = common._onepass_rows(grad_fn, mesh, placed)
    assert rows > 0, (placed.shape, placed.format)
    p0 = (jnp.zeros((d,), jnp.float32), jnp.zeros((), jnp.float32))
    before = _counters()
    fits = {}
    t0 = time.perf_counter()
    fits["pallas"] = common.train_glm(
        p0, stack, grad_fn, mesh, learning_rate=0.2, max_iter=3,
        device_batch=placed)
    fits["xla"] = common._run_fused_train(
        common.make_glm_train_fn(grad_fn, mesh, 0.2, 0.0, 3, 0.0,
                                 bundle=True),
        p0, placed, mesh, batch_preplaced=True, n_rows=stack.n_rows)
    out["compile_s"] += time.perf_counter() - t0
    after = _counters()
    assert after["train.onepass_fits"] - before.get(
        "train.onepass_fits", 0) == 1, after
    np.testing.assert_allclose(fits["pallas"].params[0],
                               fits["xla"].params[0], rtol=0, atol=1e-5)
    np.testing.assert_allclose(fits["pallas"].losses, fits["xla"].losses,
                               rtol=1e-5)

    # 3. the Lloyd iteration's one-read kernel: a KMeans fit of the same
    # 512-wide rows takes it by the estimator's own rule (strict check_vma
    # on the default mesh); against the XLA tiles from the same init
    from flink_ml_tpu.lib import clustering

    k, iters = 16, 2
    before = _counters()
    t0 = time.perf_counter()
    model = (clustering.KMeans().set_vector_col("features")
             .set_prediction_col("cluster").set_k(k).set_max_iter(iters)
             .set_seed(1)).fit(_table(Xw))
    after = _counters()
    assert after["train.kmeans_onepass_fits"] - before.get(
        "train.kmeans_onepass_fits", 0) == 1, after
    assert after.get("train.kmeans_onepass_declined", 0) == before.get(
        "train.kmeans_onepass_declined", 0), after
    trail = model.train_centroids_
    tiles = common._run_fused_train(
        clustering.make_kmeans_train_fn(mesh, k, iters, 0.0),
        (jnp.asarray(trail[0]), jnp.zeros_like(trail)),
        (Xw, np.ones((len(Xw),), np.float32)), mesh, n_rows=len(Xw))
    out["compile_s"] += time.perf_counter() - t0
    # a row within rounding of two centroids may go either way: the norm
    want = np.asarray(tiles.params[0], np.float64)
    gap = np.linalg.norm(model.centroids() - want) / np.linalg.norm(want)
    assert gap <= 1e-3, gap
    np.testing.assert_allclose(model.train_costs_, tiles.losses, rtol=1e-5)

    # 4. serve_chain through the product path: a 512-wide scaler -> LR
    # pipeline, FMT_SERVE_PALLAS on against off, raw (quarantine off) and
    # masked (the NaN/Inf scan deferred into the kernel, one bad row
    # planted), f32 and bf16 placement, 4096-row bucket and a 1-row one
    wide = Pipeline([
        StandardScaler().set_selected_col("features"),
        _logreg({**sizes, "batch": sizes["wide_batch"], "epochs": 2},
                lr=0.2),
    ]).fit(_table(Xw, yw))
    big = Xw[:sizes["big_request_rows"]].copy()
    bad = big.copy()
    bad[7, 3] = np.nan

    def serve(X, **env):
        os.environ.update(env)
        try:
            t0 = time.perf_counter()
            (res,) = wide.transform(_table(X))
            cols = (np.asarray(res.col("pred")),
                    np.asarray(res.col("proba"), dtype=np.float64))
            return cols, time.perf_counter() - t0
        finally:
            for k in env:
                del os.environ[k]

    for precision in ("f32", "bf16"):
        for label, X, quarantine in (("raw", big, "0"), ("masked", bad, "1"),
                                     ("masked_1row", big[:1], "1")):
            env = {"FMT_SERVE_PRECISION": precision,
                   "FMT_SERVE_QUARANTINE": quarantine}
            d0 = _counters().get("fused.pallas_dispatches", 0)
            (p_pred, p_proba), first = serve(X, FMT_SERVE_PALLAS="1", **env)
            (_, _), steady = serve(X, FMT_SERVE_PALLAS="1", **env)
            assert _counters()["fused.pallas_dispatches"] - d0 == 2
            (x_pred, x_proba), _ = serve(X, FMT_SERVE_PALLAS="0", **env)
            (_, _), xla_steady = serve(X, FMT_SERVE_PALLAS="0", **env)
            want_rows = len(X) - (1 if label == "masked" else 0)
            assert len(p_pred) == len(x_pred) == want_rows, (label, len(p_pred))
            np.testing.assert_allclose(p_proba, x_proba, rtol=0, atol=1e-5)
            margin = np.abs(x_proba - 0.5) > 1e-4
            assert np.array_equal(p_pred[margin], x_pred[margin])
            out["compile_s"] += first
            out["steady_s"] += steady
            out["smoke_timings_ms"][
                f"serve_chain_{len(X)}x{d}_{label}_{precision}"] = {
                "pallas": round(steady * 1e3, 3),
                "xla": round(xla_steady * 1e3, 3)}
    return out


def phase_nothing_hid(ctx):
    """Every counter a failure could hide behind is zero; no breaker left
    its closed state."""
    from flink_ml_tpu import obs
    from flink_ml_tpu.serve.breaker import breaker_states

    snap = obs.registry().snapshot()
    hidden = {k: snap["counters"][k] for k in MUST_BE_ZERO
              if snap["counters"].get(k, 0)}
    assert not hidden, f"the run hid failures: {hidden}"
    tripped = {k: v for k, v in breaker_states().items() if v > 0}
    assert not tripped, f"breakers left closed state: {tripped}"
    ctx["counters"] = {k: snap["counters"].get(k, 0) for k in MUST_BE_ZERO + (
        "train.fused_runs", "train.param_copies", "train.onepass_fits",
        "train.onepass_declined", "train.onepass_tiles",
        "train.onepass_tiles_skipped",
        "train.sparse_ell_fits", "train.sparse_ell_declined",
        "train.sparse_ell_classes",
        "train.sparse_slots", "train.sparse_ell_slots_reckoned",
        "train.sparse_hot_fits", "train.sparse_hot_entries",
        "train.sparse_hot_declined", "train.sparse_cold_slots",
        "train.sparse_hot_slots",
        "train.kmeans_fits", "train.kmeans_row_iters",
        "train.kmeans_onepass_fits", "train.kmeans_onepass_declined",
        "slab_pool.hits", "pipeline.fused_dispatches",
        "fused.shard_map_dispatches", "fused.pallas_dispatches",
        "warmstart.hits", "warmstart.saves", "serving.requests",
        "compile.cache_hits", "compile.cache_misses")}
    # beside them what the whole run spent building programs, in seconds:
    # traced, lowered, in the backend (compiles AND cache reads), reading
    ctx["counters"].update({
        k: round(snap["timings"].get(k, {}).get("total_s", 0.0), 4)
        for k in ("compile.trace", "compile.lower", "compile.backend",
                  "compile.cache_read")})
    return {"compile_s": 0.0, "steady_s": 0.0}


#: the chip run; the tier-1 test runs the first three at a tiny size on CPU
PHASES = (("fit", phase_fit), ("transform", phase_transform),
          ("serve", phase_serve), ("serve_pallas", phase_serve_pallas),
          ("kernels", phase_kernels), ("nothing_hid", phase_nothing_hid))


def run(sizes, phases, out_dir, platform="tpu"):
    """Run ``phases`` in order on ``platform`` (anything else found is an
    error); returns the summary dict.  The first failed assertion
    propagates."""
    import jax

    device = jax.devices()[0]
    if device.platform != platform:
        raise SystemExit(
            f"chip_smoke: needs platform {platform!r}, JAX found "
            f"{device.platform!r} ({device.device_kind}); refusing to run")
    os.environ["FMT_OBS_REPORTS"] = os.path.join(out_dir, "reports")
    os.environ["FMT_TRACE_DIR"] = os.path.join(out_dir, "traces")
    os.environ["FMT_FLIGHT_DIR"] = os.path.join(out_dir, "flight")

    import jaxlib

    from flink_ml_tpu import native, obs
    from flink_ml_tpu.utils import compile_cache

    # only now: run alone, without the package, the import above fails and
    # nothing is left behind
    os.makedirs(out_dir, exist_ok=True)
    try:
        import libtpu
        libtpu_version = getattr(libtpu, "__version__", "unknown")
    except ImportError:
        libtpu_version = None
    cache_dir = compile_cache.cache_dir()
    entries_before = _cache_entries(cache_dir)
    info = {
        "platform": device.platform, "kind": device.device_kind,
        "count": len(jax.devices()),
    }
    print("chip_smoke:", json.dumps({
        **info, "jax": jax.__version__, "jaxlib": jaxlib.__version__,
        "libtpu": libtpu_version, "jax_enable_x64": jax.config.jax_enable_x64,
        "compile_cache_dir": cache_dir, "cache_entries": entries_before,
        "native_ingest": native.available(),
    }), flush=True)

    obs.enable()
    obs.reset()
    ctx = {"sizes": sizes, "out": out_dir, "platform": platform}
    summary = {}
    for name, phase in phases:
        t0 = time.perf_counter()
        result = phase(ctx)
        result = {k: round(v, 4) if isinstance(v, float) else v
                  for k, v in result.items()}
        result["ok"] = True
        result["wall_s"] = round(time.perf_counter() - t0, 3)
        summary[name] = result
        print(f"chip_smoke: phase {name} ok "
              f"(smoke timings, not benchmark metrics): "
              f"{json.dumps(result)}", flush=True)
    entries_after = _cache_entries(cache_dir)
    print(f"chip_smoke: compile cache {cache_dir}: {entries_before} entries "
          f"before, {entries_after} after", flush=True)
    return {
        "ok": True,
        "device": info,
        "phases": summary,
        "compile_s": round(sum(p["compile_s"] for p in summary.values()), 3),
        "steady_s": round(sum(p["steady_s"] for p in summary.values()), 3),
        "counters": ctx.get("counters", {}),
        "coefficients": ctx.get("coefficients"),
        "cache": {"dir": cache_dir, "entries_before": entries_before,
                  "entries_after": entries_after},
        "timings": "smoke timings of one run, not benchmark metrics",
        "claim": None,
    }


def verdict(summary):
    """The last stdout line: exactly ``ok`` and ``device``, nothing else —
    the driver rejects any other key."""
    device = summary["device"]
    return {"ok": bool(summary["ok"]),
            "device": {"platform": str(device["platform"]),
                       "kind": str(device["kind"]),
                       "count": int(device["count"])}}


def main():
    summary = run(FULL, PHASES,
                  os.path.join(ROOT, "chiprun_out", "chip_smoke"))
    print("chip_smoke: summary", json.dumps(summary), flush=True)
    print(json.dumps(verdict(summary)), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
