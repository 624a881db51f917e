"""Full benchmark matrix — every BASELINE.json config plus the Criteo-shaped
sparse path (the north-star workload).

Each workload prints ONE JSON line:
  {"metric", "value", "unit", "vs_baseline", ...extras}

Two CPU baselines are measured per training workload:
  * ``per_record``  — the reference-shaped hot loop (one row at a time
    through numpy, SubUpdate.map / ModelMapperAdapter.map shape,
    examples-batch/.../LinearRegression.java:215-231) — labeled, not used
    for the headline ratio;
  * ``vectorized``  — an honest numpy minibatch SGD / Lloyd / brute-force
    implementation of the SAME algorithm (full-batch vector math on the
    host CPU).  ``vs_baseline`` is measured against THIS.

AUC/RMSE parity against the vectorized baseline is measured on held-out
rows and recorded as ``auc_parity``/``rmse_parity`` in each GLM record
(north star: >=4x at identical AUC, BASELINE.json) — recorded, not
asserted, so a parity miss still emits a (self-incriminating) record
instead of crashing the bench sweep.  (``chip_smoke.py`` is where the same
check is an assertion.)

Every record names the device it ran on (``platform`` / ``device_kind`` /
``device_count``).  A ``*_frac`` of a hardware peak is emitted only for a
device in :data:`DEVICE_PEAKS`; on any other device the record says
``peak: "not in DEVICE_PEAKS"`` instead — a CPU run is never divided by a
TPU's bandwidth.

Device throughput is read from the drivers' own StepMetrics (fit is run
once to compile, then re-run; the second run's metrics are steady-state).

Usage: python bench_all.py [workload ...]   (default: all)
Workloads: logreg kmeans linreg knn online sparse
"""

from __future__ import annotations

import json
import os
import sys
import tempfile
import time

import numpy as np

# ---------------------------------------------------------------- utilities


def _auc(y: np.ndarray, scores: np.ndarray) -> float:
    """Rank-based AUC (Mann-Whitney)."""
    order = np.argsort(scores, kind="mergesort")
    ranks = np.empty(len(scores))
    ranks[order] = np.arange(1, len(scores) + 1)
    pos = y == 1
    n1 = int(pos.sum())
    n0 = len(y) - n1
    if n1 == 0 or n0 == 0:
        return 0.5
    return float((ranks[pos].sum() - n1 * (n1 + 1) / 2) / (n1 * n0))


def _sigmoid(z):
    return 1.0 / (1.0 + np.exp(-z))


def _emit(record: dict) -> dict:
    # name the device the record was measured on (workers that pin
    # themselves to CPU stamp their own "backend" and keep it)
    record = {**_device_stamp(), **record}
    print(json.dumps(record))
    # durable telemetry (ISSUE 1): every bench record also lands in
    # reports/runs.jsonl as a RunReport (git SHA, device topology, the
    # registry snapshot with compile/steady splits) — a no-op when obs is
    # off, so importing bench_all for its helpers stays side-effect-free
    from flink_ml_tpu import obs

    obs.bench_report(record)
    return record


def _n_chips() -> int:
    import jax

    return jax.device_count()


def _steady_fit_sps(fit, sweeps: int = 3) -> tuple:
    """Warmup (compile + pack), then the MEDIAN steady rate over ``sweeps``
    fits — one sweep on a shared host is not a robust record."""
    fit()  # warmup: compile + pack
    rates = []
    for _ in range(sweeps):
        model = fit()
        s = model.train_metrics_.summary(skip_warmup=0)
        rates.append(s["samples_per_sec"])
    return float(np.median(rates)), model


# ------------------------------------------------------- numpy CPU baselines


def _np_sgd_glm(X, y, lr, batch, epochs, kind, time_budget_s=8.0):
    """Vectorized numpy minibatch SGD — the honest CPU baseline.  Identical
    update rule to the framework (mean gradient per global batch), SAME dtype
    as the device path (f32 data halves the CPU's memory traffic — the
    strongest sensible baseline).  Returns (w, b, rows_per_sec); stops early
    on the time budget and reports the measured rate (the trajectory for
    parity always runs >= 1 full epoch)."""
    n, d = X.shape
    w = np.zeros(d, dtype=X.dtype)
    b = X.dtype.type(0.0)
    lr = X.dtype.type(lr)
    t0 = time.perf_counter()
    rows_done = 0
    for _ in range(epochs):
        for lo in range(0, n, batch):
            xb = X[lo:lo + batch]
            yb = y[lo:lo + batch]
            z = xb @ w + b
            err = (_sigmoid(z) - yb) if kind == "logistic" else (z - yb)
            w -= lr * (xb.T @ err) / len(yb)
            b -= lr * err.mean()
            rows_done += len(yb)
        if time.perf_counter() - t0 > time_budget_s:
            break
    return w, b, rows_done / (time.perf_counter() - t0)


def _np_per_record_glm(X, y, lr, batch, kind, budget_rows=20_000):
    """The reference-shaped per-record loop (one row at a time)."""
    d = X.shape[1]
    w = np.zeros(d)
    b = 0.0
    lr_r = lr / batch
    n = min(budget_rows, len(y))
    t0 = time.perf_counter()
    for i in range(n):
        xi = X[i]
        z = xi @ w + b
        err = (_sigmoid(z) - y[i]) if kind == "logistic" else (z - y[i])
        w -= lr_r * err * xi
        b -= lr_r * err
    return n / (time.perf_counter() - t0)


# ------------------------------------------------------------------ workloads


#: published peaks per chip, keyed by ``jax.devices()[0].device_kind`` —
#: the denominators of every ``*_frac`` field.  Source: Google Cloud
#: documentation, "TPU v5e" (197 TFLOP/s bf16, 819 GB/s HBM, 16 GB).  A
#: device that is not here gets no ``*_frac`` field (:func:`_peak_fields`).
DEVICE_PEAKS = {
    "TPU v5 lite": {"bf16_tflops": 197.0, "hbm_gbps": 819.0, "hbm_gb": 16.0},
}


def _device_stamp() -> dict:
    """The device a record was measured on, as JAX reports it."""
    import jax

    device = jax.devices()[0]
    return {"platform": device.platform, "device_kind": device.device_kind,
            "device_count": jax.device_count()}


def _peak_fields(name: str, achieved: float, peak_key: str) -> dict:
    """``{name: achieved / peak}`` for a device in :data:`DEVICE_PEAKS`;
    otherwise no fraction at all, and a field that says why."""
    import jax

    peaks = DEVICE_PEAKS.get(jax.devices()[0].device_kind)
    if peaks is None:
        return {"peak": "not in DEVICE_PEAKS"}
    return {name: round(achieved / peaks[peak_key], 4)}


def _glm_decompose(fit_at_epochs, epochs, n_train, row_bytes, t_short):
    """Separate the fixed per-call cost (dispatch + sync + fetch) from
    per-epoch device time via a two-point slope: steady wall at E (``t_short``,
    already measured by the caller) and 5E epochs, both on resident data.
    Returns a dict of decomposition fields.

    The steady wall is ``latency + E * epoch_time``; the slope isolates the
    device-only rate from whatever one dispatch costs on this host.
    """
    long_walls, _ = fit_at_epochs(5 * epochs, sweeps=3)
    t_long = float(np.median(long_walls))
    per_epoch = max((t_long - t_short) / (4 * epochs), 1e-9)
    latency = max(t_short - epochs * per_epoch, 0.0)
    dev_sps = n_train / per_epoch
    gbps = dev_sps * row_bytes / 1e9
    return {
        "device_only_sps": round(dev_sps, 1),
        "per_epoch_ms": round(per_epoch * 1e3, 3),
        "call_latency_ms": round(latency * 1e3, 1),
        "device_hbm_gbps": round(gbps, 1),
        **_peak_fields("device_hbm_frac", gbps, "hbm_gbps"),
    }


def _bench_glm(kind, n_rows, n_features, epochs, batch, lr, seed):
    """Shared dense-GLM bench body: matrix-backed f32 columns, resident-data
    steady state (the CPU baseline's data sits in RAM; the device analog is
    data sitting in HBM — the one-time transfer is reported inside
    first_fit_s), slope decomposition, parity vs the vectorized baseline."""
    from flink_ml_tpu.lib import LinearRegression, LogisticRegression
    from flink_ml_tpu.table.schema import DataTypes, Schema
    from flink_ml_tpu.table.table import Table

    rng = np.random.RandomState(seed)
    X = rng.randn(n_rows, n_features).astype(np.float32)
    true_w = (rng.randn(n_features) / np.sqrt(n_features)).astype(np.float32)
    if kind == "logistic":
        y = ((X @ true_w + 0.17 * rng.randn(n_rows).astype(np.float32)) > 0
             ).astype(np.float32)
    else:
        y = (X @ true_w + 0.1 * rng.randn(n_rows).astype(np.float32)
             ).astype(np.float32)
    n_train = int(0.8 * n_rows)
    schema = Schema.of(("features", DataTypes.DENSE_VECTOR), ("label", "double"))
    t = Table.from_columns(
        schema, {"features": X[:n_train], "label": y[:n_train]}
    )
    est_cls = LogisticRegression if kind == "logistic" else LinearRegression

    def fit_at_epochs(n_epochs, sweeps=1):
        def fit():
            return (
                est_cls().set_vector_col("features")
                .set_label_col("label").set_prediction_col("pred")
                .set_learning_rate(lr).set_global_batch_size(batch)
                .set_max_iter(n_epochs).fit(t)
            )

        fit()  # warmup: compile (+ pack/place on first call; cached after)
        walls = []
        for _ in range(sweeps):
            t0 = time.perf_counter()
            model = fit()
            walls.append(time.perf_counter() - t0)
        return walls, model

    # median of >=3 steady sweeps, with the sample spread reported
    # alongside
    t0 = time.perf_counter()
    walls, model = fit_at_epochs(epochs, sweeps=3)
    steady_wall = float(np.median(walls))
    first_fit_s = time.perf_counter() - t0 - sum(walls)  # compile+pack+h2d
    device_sps = n_train * model.train_epochs_ / steady_wall

    decomp = _glm_decompose(fit_at_epochs, epochs, n_train,
                            row_bytes=(n_features + 2) * 4,
                            t_short=steady_wall)

    def _call_ms(m):
        steps = getattr(m.train_metrics_, "steps", [])
        return round(float(np.median(
            [s.get("call_latency_ms", 0.0) for s in steps])), 1) \
            if steps else None

    per_record_sps = _np_per_record_glm(
        X[:n_train], y[:n_train], lr, batch, kind
    )
    w_np, b_np, vec_sps = _np_sgd_glm(
        X[:n_train], y[:n_train], lr, batch, epochs, kind
    )

    Xq, yq = X[n_train:], y[n_train:]
    record = {
        "metric": f"{est_cls.__name__}.fit samples/sec/chip",
        "value": round(device_sps / _n_chips(), 1),
        "unit": "samples/sec/chip",
        "vs_baseline": round(device_sps / vec_sps, 2),
        "vs_per_record": round(device_sps / per_record_sps, 2),
        "baseline_vectorized_sps": round(vec_sps, 1),
        "baseline_per_record_sps": round(per_record_sps, 1),
        **decomp,
        "steady_wall_s": round(steady_wall, 3),
        "sweep_walls_s": [round(w, 3) for w in walls],
        "first_fit_s": round(first_fit_s, 1),
        "call_latency_ms": _call_ms(model),
        "shape": f"{n_train}x{n_features} f32 batch={batch} epochs={epochs}",
    }
    if kind == "logistic":
        qt = Table.from_columns(
            Schema.of(("features", DataTypes.DENSE_VECTOR)), {"features": Xq}
        )
        auc_tpu = _auc(yq, model.predict_proba(qt))
        auc_np = _auc(yq, _sigmoid(Xq @ w_np + b_np))
        record.update({
            "auc_tpu": round(auc_tpu, 4),
            "auc_baseline": round(auc_np, 4),
            "auc_parity": bool(abs(auc_tpu - auc_np) < 0.005),
        })
    else:
        rmse_tpu = float(np.sqrt(np.mean(
            (Xq @ model.coefficients() + model.intercept() - yq) ** 2)))
        rmse_np = float(np.sqrt(np.mean((Xq @ w_np + b_np - yq) ** 2)))
        record.update({
            "rmse_tpu": round(rmse_tpu, 4),
            "rmse_baseline": round(rmse_np, 4),
            "rmse_parity": bool(abs(rmse_tpu - rmse_np) < 0.01),
        })
    return _emit(record)


def bench_logreg(n_rows=2_500_000, n_features=28, epochs=50, batch=32768):
    """LogisticRegression.fit, HIGGS-shaped (BASELINE configs[0]).

    HIGGS is 11M x 28; this cell trains on 2M rows (sizing it to the
    dataset is ROADMAP R1).

    batch=32768, lr=1.0: a 4x batch over the earlier (8192, lr 0.5) config
    with the lr doubled (square-root scaling — held-out AUC was measured
    identical, 0.9906, at both configs on a 625k sweep; the bench records
    auc_parity vs the same-config CPU baseline).  Fewer, larger steps were
    chosen to cut per-step overhead; the size of that effect on the chip is
    to be re-measured (ROADMAP S0/S4).  The CPU baseline runs the identical
    config, so vs_baseline stays honest.
    """
    return _bench_glm("logistic", n_rows, n_features, epochs, batch,
                      lr=1.0, seed=0)


def bench_logreg_wide(n_rows=156_250, n_features=512, epochs=50, batch=16384):
    """Wide dense LogisticRegression — the bandwidth-utilization probe: at
    512 features each epoch streams ~0.5 GB through the MXU-feedable
    (16384, 512) @ (512,) matvec, so the per-epoch slope measures achieved
    HBM bandwidth rather than per-step overhead."""
    return _bench_glm("logistic", n_rows, n_features, epochs, batch,
                      lr=0.2, seed=7)


def bench_linreg(n_rows=500_000, n_features=90, epochs=50, batch=8192):
    """LinearRegression.fit, YearPredictionMSD-shaped (BASELINE configs[2])."""
    return _bench_glm("squared", n_rows, n_features, epochs, batch,
                      lr=0.1, seed=1)


def _kmeans_decompose(X, cents, epochs=10):
    """Device-time decomposition of one Lloyd epoch (VERDICT r4 #8): the
    distance matmul's share and MFU, the argmin/min add-on, and the
    segment-sum (scatter) share — measured as slopes between E and 3E
    fused-scan runs on resident data, so the fixed per-call cost cancels
    like the GLM decomposition's."""
    import jax
    import jax.numpy as jnp

    x = jnp.asarray(X)
    c0 = jnp.asarray(cents)
    k = c0.shape[0]
    n, d = X.shape
    x2 = jnp.sum(x * x, axis=1)

    def full_epoch(c, _):
        d2 = x2[:, None] - 2.0 * (x @ c.T) + jnp.sum(c * c, axis=1)
        assign = jnp.argmin(d2, axis=1)
        cost = jnp.sum(jnp.maximum(jnp.min(d2, axis=1), 0.0))
        sums = jax.ops.segment_sum(x, assign, num_segments=k)
        counts = jax.ops.segment_sum(
            jnp.ones((n,), jnp.float32), assign, num_segments=k
        )
        new_c = jnp.where(
            counts[:, None] > 0, sums / jnp.maximum(counts[:, None], 1.0), c
        )
        return new_c, cost

    def mm_epoch(c, _):
        g = x @ c.T  # the MXU term alone
        # nudge the carry so XLA cannot hoist the matmul out of the scan
        return c + 1e-12 * jnp.mean(g), jnp.sum(g)

    def assign_epoch(c, _):
        d2 = x2[:, None] - 2.0 * (x @ c.T) + jnp.sum(c * c, axis=1)
        m = jnp.min(d2, axis=1)
        a = jnp.argmin(d2, axis=1)
        return c + 1e-12 * (jnp.mean(m) + jnp.mean(a)), jnp.sum(m)

    def slope_epoch_s(body):
        def run(n_ep):
            f = jax.jit(
                lambda c: jax.lax.scan(body, c, None, length=n_ep)[0]
            )
            r = f(c0)
            jax.block_until_ready(r)
            t0 = time.perf_counter()
            r = f(c0)
            jax.block_until_ready(r)
            return time.perf_counter() - t0

        t1 = run(epochs)
        t3 = run(3 * epochs)
        return max((t3 - t1) / (2 * epochs), 1e-9)

    t_full = slope_epoch_s(full_epoch)
    t_mm = slope_epoch_s(mm_epoch)
    t_assign = slope_epoch_s(assign_epoch)
    mm_tflops = 2.0 * n * d * k / t_mm / 1e12
    return {
        "device_epoch_ms": round(t_full * 1e3, 2),
        "device_only_sps": round(n / t_full, 1),
        "matmul_frac": round(t_mm / t_full, 3),
        "argmin_extra_frac": round((t_assign - t_mm) / t_full, 3),
        "segment_frac": round((t_full - t_assign) / t_full, 3),
        "matmul_tflops": round(mm_tflops, 1),
        # against the bf16 MXU peak; the distances run f32
        **_peak_fields("mfu_vs_bf16_peak", mm_tflops, "bf16_tflops"),
    }


def bench_kmeans(n_rows=500_000, n_features=64, k=100, epochs=10):
    """KMeans k=100 (BASELINE configs[1])."""
    from flink_ml_tpu.lib.clustering import KMeans
    from flink_ml_tpu.table.schema import DataTypes, Schema
    from flink_ml_tpu.table.table import Table

    rng = np.random.RandomState(2)
    centers = 10.0 * rng.randn(k, n_features).astype(np.float32)
    X = (centers[rng.randint(k, size=n_rows)] +
         rng.randn(n_rows, n_features).astype(np.float32))
    schema = Schema.of(("features", DataTypes.DENSE_VECTOR),)
    t = Table.from_columns(schema, {"features": X})

    def fit():
        return (
            KMeans().set_vector_col("features").set_k(k)
            .set_max_iter(epochs).set_prediction_col("c").set_seed(0).fit(t)
        )

    device_sps, model = _steady_fit_sps(fit)

    # vectorized numpy baseline: one FULL Lloyd epoch — assignment, one
    # preallocated sums/counts accumulation across chunks, and the centroid
    # divide — then cost parity against the device result from the same
    # centroids (identical work per epoch on both sides).
    c = model.centroids().astype(np.float32)
    chunk = 8192
    sums = np.zeros((k, n_features), np.float32)
    counts = np.zeros((k,), np.float32)
    cost_np = 0.0
    c2 = (c * c).sum(1)
    t0 = time.perf_counter()
    for lo in range(0, n_rows, chunk):
        xb = X[lo:lo + chunk]
        d2 = (xb * xb).sum(1)[:, None] - 2.0 * xb @ c.T + c2
        assign = np.argmin(d2, axis=1)
        cost_np += float(np.maximum(d2[np.arange(len(xb)), assign], 0.0).sum())
        np.add.at(sums, assign, xb)
        np.add.at(counts, assign, 1.0)
    np.divide(sums, np.maximum(counts[:, None], 1.0), out=sums)
    vec_sps = n_rows / (time.perf_counter() - t0)

    # parity: the device's final-epoch cost vs the numpy cost of assigning
    # to those same centroids (the device cost is recorded pre-update, so
    # compare within a loose relative band)
    cost_dev = model.train_cost_
    cost_parity = bool(
        abs(cost_np - cost_dev) / max(cost_np, 1e-9) < 0.05
    )

    return _emit({
        "metric": "KMeans.fit samples/sec/chip (k=100)",
        "value": round(device_sps / _n_chips(), 1),
        "unit": "samples/sec/chip",
        "vs_baseline": round(device_sps / vec_sps, 2),
        "baseline_vectorized_sps": round(vec_sps, 1),
        "train_cost": round(cost_dev, 1),
        "baseline_cost": round(cost_np, 1),
        "cost_parity": cost_parity,
        **_kmeans_decompose(X, c),
        "shape": f"{n_rows}x{n_features} f32 k={k} epochs={epochs}",
    })


def bench_knn(n_train=60_000, n_query=10_000, n_features=784, k=5, n_classes=10):
    """Knn Model.transform batch inference, MNIST-shaped (BASELINE configs[3])."""
    from flink_ml_tpu.lib.knn import Knn
    from flink_ml_tpu.table.schema import DataTypes, Schema
    from flink_ml_tpu.table.table import Table
    rng = np.random.RandomState(3)
    prototypes = rng.randn(n_classes, n_features).astype(np.float32)
    labels = rng.randint(n_classes, size=n_train)
    X = prototypes[labels] + 0.8 * rng.randn(n_train, n_features).astype(np.float32)
    qlabels = rng.randint(n_classes, size=n_query)
    Q = prototypes[qlabels] + 0.8 * rng.randn(n_query, n_features).astype(np.float32)

    schema = Schema.of(("features", DataTypes.DENSE_VECTOR), ("label", "double"))
    t = Table.from_columns(
        schema, {"features": X, "label": labels.astype(np.float64)}
    )
    qt = Table.from_columns(
        Schema.of(("features", DataTypes.DENSE_VECTOR)), {"features": Q}
    )
    model = (Knn().set_vector_col("features").set_label_col("label")
             .set_prediction_col("pred").set_k(k).fit(t))

    model.transform(qt)  # warmup: compile + model packing
    t_walls = []
    for _ in range(3):  # median-of-3 (shared-host variance)
        t0 = time.perf_counter()
        (out,) = model.transform(qt)
        t_walls.append(time.perf_counter() - t0)
    device_rps = n_query / float(np.median(t_walls))
    acc = float(np.mean(np.asarray(out.col("pred")) == qlabels))

    # roofline decomposition (VERDICT r3 weak #4): device-only rate on
    # resident inputs, the distance matmul's achieved FLOP/s, and the
    # top_k/vote share.  The transform wall above also pays the per-call
    # query transfer (~31 MB), so the split shows which wall the workload
    # actually sits against.
    import jax
    import jax.numpy as jnp

    from flink_ml_tpu.lib.knn import _knn_apply
    from flink_ml_tpu.parallel.mesh import create_mesh

    mapper = model._mapper_cache  # packed + device-resident by the warmup
    xt, yt, chunk = mapper._xt, mapper._yt, mapper._chunk
    # single-CHIP roofline by construction: both the full apply and the
    # matmul-only probe run on one device, so t_full/t_mm are comparable
    # and MFU is against the one-chip peak (no row-multiple padding needed)
    mesh1 = create_mesh({"data": 1}, jax.devices()[:1])
    apply_fn = _knn_apply(mesh1, k, chunk, n_classes)
    xq = jnp.asarray(Q)

    def timed(fn, *args):
        best = 1e9
        out = fn(*args)
        np.asarray(out)  # sync
        for _ in range(3):
            t0 = time.perf_counter()
            out = fn(*args)
            np.asarray(out.ravel()[0])
            best = min(best, time.perf_counter() - t0)
        return best, out

    t_full, _ = timed(apply_fn, xq, xt, yt)

    @jax.jit
    def dist_only(xq, xt):
        # same chunked distance matmuls, per-row min instead of top-k merge
        n_chunks = xt.shape[0] // chunk
        xq2 = jnp.sum(xq * xq, axis=1, keepdims=True)

        def scan_chunk(best, i):
            xc = jax.lax.dynamic_slice_in_dim(xt, i * chunk, chunk)
            d = xq2 - 2.0 * (xq @ xc.T) + jnp.sum(xc * xc, axis=1)
            return jnp.minimum(best, jnp.min(d, axis=1)), None

        best, _ = jax.lax.scan(
            scan_chunk, jnp.full((xq.shape[0],), jnp.inf, xq.dtype),
            jnp.arange(n_chunks),
        )
        return best

    t_mm, _ = timed(dist_only, xq, xt)
    flops = 2.0 * n_query * xt.shape[0] * n_features  # the x @ c.T term
    mm_tflops = flops / t_mm / 1e12
    device_only_rps = n_query / t_full
    topk_frac = max(0.0, (t_full - t_mm) / t_full)

    # bf16Distances opt-in (matmul-bound workload): same apply with the
    # cross term in bf16/f32-accum; accuracy checked on these queries
    apply_bf16 = _knn_apply(mesh1, k, chunk, n_classes, True)
    t_bf16, out_bf16 = timed(apply_bf16, xq, xt, yt)
    out_bf16 = np.asarray(out_bf16)
    classes = mapper._classes
    acc_bf16 = float(np.mean(
        classes[out_bf16[:, 0].astype(np.int64)] == qlabels
    ))

    # numpy brute-force baseline: >=5k queries, chunked f32 distance matrix
    # + argpartition top-k + vote — the same algorithm, honest host shape
    n_sub = min(5000, n_query)
    t0 = time.perf_counter()
    x2 = (X * X).sum(1)
    agree = 0
    for i in range(0, n_sub, 500):
        qb = Q[i:i + 500]
        d2 = (qb * qb).sum(1)[:, None] - 2.0 * qb @ X.T + x2
        idx = np.argpartition(d2, k, axis=1)[:, :k]
        votes = np.take(labels, idx)
        pred = np.array([np.bincount(v, minlength=n_classes).argmax()
                         for v in votes])
        agree += int((pred == qlabels[i:i + 500]).sum())
    vec_rps = n_sub / (time.perf_counter() - t0)
    acc_np = agree / n_sub

    return _emit({
        "metric": "Knn.transform rows/sec/chip",
        "value": round(device_rps / _n_chips(), 1),
        "unit": "rows/sec/chip",
        "vs_baseline": round(device_rps / vec_rps, 2),
        "baseline_vectorized_rps": round(vec_rps, 1),
        "device_only_rps": round(device_only_rps, 1),
        "matmul_tflops": round(mm_tflops, 1),
        # against the bf16 MXU peak; the distances run f32
        **_peak_fields("mfu_vs_bf16_peak", mm_tflops, "bf16_tflops"),
        "topk_vote_frac": round(topk_frac, 3),
        "device_only_rps_bf16": round(n_query / t_bf16, 1),
        "accuracy_bf16": round(acc_bf16, 4),
        "accuracy": round(acc, 4),
        "baseline_accuracy": round(acc_np, 4),
        "shape": f"train {n_train}x{n_features}, query {n_query}, k={k}",
    })


def bench_online(n_rows=100_000, n_features=28, rows_per_window=1000):
    """Online LogisticRegression, streaming mini-batch (BASELINE configs[4]).

    The source is columnar (ColumnarUnboundedSource): the driver's
    vectorized span path ingests with zero per-record Python — the
    realistic shape for a production feed (a NIC/DMA delivers buffers, not
    Python tuples).  The CPU baseline stays the reference's per-record
    SGD."""
    from flink_ml_tpu.lib.online import OnlineLogisticRegression
    from flink_ml_tpu.table.schema import DataTypes, Schema
    from flink_ml_tpu.table.sources import ColumnarUnboundedSource

    rng = np.random.RandomState(4)
    X = rng.randn(n_rows, n_features)
    true_w = rng.randn(n_features)
    y = ((X @ true_w) > 0).astype(np.float64)
    schema = Schema.of(("features", DataTypes.DENSE_VECTOR), ("label", "double"))
    window_ms = 1000
    interval = window_ms // rows_per_window
    ts = np.arange(n_rows, dtype=np.int64) * interval

    def run():
        source = ColumnarUnboundedSource(
            ts, {"features": X, "label": y}, schema
        )
        est = (OnlineLogisticRegression().set_vector_col("features")
               .set_label_col("label").set_prediction_col("p")
               .set_learning_rate(0.5).set_window_ms(window_ms))
        return est.fit_unbounded(source)

    run()  # warmup: compile
    runs = []
    for _ in range(3):  # median-of-3 (shared-host variance)
        model, result = run()
        runs.append((result.metrics.summary(skip_warmup=1), model, result))
    # one consistent record: every reported stat comes from the median run
    s, model, result = runs[
        int(np.argsort([r[0]["samples_per_sec"] for r in runs])[1])
    ]
    windows_per_sec = s["steady_steps"] / s["total_seconds"]
    per_record_sps = _np_per_record_glm(X, y, 0.5, rows_per_window, "logistic")
    # columnar-fed CPU baseline (ADVICE r4): the same window-minibatch
    # update rule on vectorized numpy, so the headline ratio's ingest-format
    # change is disclosed with a same-shape comparison alongside it.  The
    # run is a FULL single pass (no time budget): with aligned timestamps a
    # window is exactly a batch, so this is also the quality-parity
    # reference trajectory (VERDICT r4 #8 — every other workload asserts
    # parity; the streaming one now does too).
    w_cpu, b_cpu, vec_cpu_sps = _np_sgd_glm(
        X.astype(np.float32), y.astype(np.float32), 0.5, rows_per_window,
        1, "logistic", time_budget_s=1e9,
    )
    w_dev = np.asarray(model.coefficients(), dtype=np.float32)
    b_dev = np.float32(model.intercept())
    pred_dev = (X.astype(np.float32) @ w_dev + b_dev) > 0
    pred_cpu = (X.astype(np.float32) @ w_cpu + b_cpu) > 0
    parity_agreement = float(np.mean(pred_dev == pred_cpu))
    auc_dev = _auc(y, X.astype(np.float32) @ w_dev + b_dev)
    auc_cpu = _auc(y, X.astype(np.float32) @ w_cpu + b_cpu)

    # host/device split: the same driver + packing with a NO-OP update
    # isolates the host-side cost (merge, windowing, Table packing); the
    # difference to the real run is the device-dispatch share per window.
    from flink_ml_tpu.iteration.unbounded import StreamingDriver

    source = ColumnarUnboundedSource(ts, {"features": X, "label": y}, schema)
    t0 = time.perf_counter()
    host_only = StreamingDriver(window_ms=window_ms).run(
        None, source, lambda state, table, epoch: state
    )
    host_wall = time.perf_counter() - t0
    host_rps = n_rows / host_wall

    # VERDICT r4 #2: checkpointing must stay on the vectorized span path.
    # Driver-overhead measure: the same no-op driver with a snapshot EVERY
    # window (the worst case; pure host cost — columnar payload + npz
    # write, no device state to fetch).
    import shutil
    import tempfile as _tf

    from flink_ml_tpu.iteration.checkpoint import CheckpointConfig

    ck_dir = _tf.mkdtemp(prefix="bench_online_ck_")
    try:
        source = ColumnarUnboundedSource(
            ts, {"features": X, "label": y}, schema
        )
        t0 = time.perf_counter()
        StreamingDriver(window_ms=window_ms).run(
            None, source, lambda state, table, epoch: state,
            checkpoint=CheckpointConfig(directory=ck_dir, every_n_epochs=1),
        )
        host_ckpt_wall = time.perf_counter() - t0
    finally:
        shutil.rmtree(ck_dir, ignore_errors=True)
    host_ckpt_rps = n_rows / host_ckpt_wall

    # end-to-end with checkpointing ELIGIBLE at every window: snapshots
    # are asynchronous (background writer, at most one in flight — Flink's
    # async checkpoint model), so the driver thread only builds columnar
    # payloads; the device-state fetch and npz write overlap the stream.
    # Warmed like the headline run (compile excluded), median-of-3.
    def run_wall(with_ckpt):
        ck_dir = _tf.mkdtemp(prefix="bench_online_ck2_") if with_ckpt else None
        try:
            src2 = ColumnarUnboundedSource(
                ts, {"features": X, "label": y}, schema
            )
            est2 = (OnlineLogisticRegression().set_vector_col("features")
                    .set_label_col("label").set_prediction_col("p")
                    .set_learning_rate(0.5).set_window_ms(window_ms))
            cfg = (
                CheckpointConfig(
                    directory=ck_dir, every_n_epochs=1, keep=10**6
                )
                if with_ckpt else None
            )
            _, res2 = est2.fit_unbounded(src2, checkpoint=cfg)
            # steady-state window throughput (the headline's own measure):
            # snapshot payload-build + submit land in the window timings;
            # the background write overlaps the stream.  The one-time final
            # drain/model fetch is shutdown cost, not stream throughput.
            rps = res2.metrics.summary(skip_warmup=1)["samples_per_sec"]
            written = len(
                [f for f in os.listdir(ck_dir) if f.endswith(".npz")]
            ) if with_ckpt else 0
        finally:
            if ck_dir is not None:
                shutil.rmtree(ck_dir, ignore_errors=True)
        return rps, written

    run_wall(True)  # warmup (jit caches shared with the headline run)
    e2e_base_rps = sorted(run_wall(False)[0] for _ in range(3))[1]
    ck_runs = sorted(run_wall(True) for _ in range(3))
    e2e_ckpt_rps, n_snapshots = ck_runs[1]
    real_wall = s["total_seconds"]
    device_ms_per_window = max(
        (real_wall - host_wall * (s["steady_steps"] / max(host_only.windows_fired, 1)))
        / max(s["steady_steps"], 1) * 1e3,
        0.0,
    )

    return _emit({
        "metric": "OnlineLogisticRegression windows/sec",
        "value": round(windows_per_sec, 2),
        "unit": "windows/sec",
        "vs_baseline": round(s["samples_per_sec"] / per_record_sps, 2),
        "vs_baseline_note": (
            "vectorized columnar ingest vs per-record CPU baseline "
            "(the reference's streaming shape); see vs_vectorized_cpu "
            "for the same-ingest-shape comparison"
        ),
        "vectorized_cpu_rows_per_sec": round(vec_cpu_sps, 1),
        "vs_vectorized_cpu": round(s["samples_per_sec"] / vec_cpu_sps, 2),
        "parity_agreement": round(parity_agreement, 4),
        "auc_tpu": round(auc_dev, 4),
        "auc_baseline": round(auc_cpu, 4),
        "auc_parity": bool(abs(auc_dev - auc_cpu) < 0.002),
        "rows_per_sec": round(s["samples_per_sec"], 1),
        "host_only_rows_per_sec": round(host_rps, 1),
        # durable-path parity (VERDICT r4 #2): snapshot-every-window no-op
        # driver vs the plain no-op driver (pure host overhead), and
        # end-to-end with a Flink-style 1 s checkpoint interval
        "host_only_ckpt_rows_per_sec": round(host_ckpt_rps, 1),
        "driver_ckpt_ratio": round(host_ckpt_rps / host_rps, 3),
        "rows_per_sec_ckpt": round(e2e_ckpt_rps, 1),
        "rows_per_sec_nockpt": round(e2e_base_rps, 1),
        "ckpt_ratio": round(e2e_ckpt_rps / e2e_base_rps, 3),
        "ckpt_snapshots_written": n_snapshots,
        "host_frac": round(min(host_wall / max(real_wall, 1e-9), 1.0), 3),
        "device_dispatch_ms_per_window": round(device_ms_per_window, 2),
        "windows_fired": result.windows_fired,
        "shape": f"{n_rows}x{n_features}, {rows_per_window} rows/window",
    })


def bench_sparse(n_rows=100_000, dim=1_000_000, nnz=39, epochs=40, batch=8192):
    """Criteo-shaped sparse LogisticRegression — the north-star workload:
    hashed features at >=1M dim through the native LibSVM loader and the
    fused segment-CSR training path (lib/common.py make_sparse_glm_train_fn).
    """
    from flink_ml_tpu.lib import LogisticRegression
    from flink_ml_tpu.table.sources import LibSvmSource

    # synthetic LibSVM file: power-law-ish hashed indices, ~nnz per row
    path = bench_sparse_file(n_rows, dim, nnz)

    t0 = time.perf_counter()
    table = LibSvmSource(path, n_features=dim, zero_based=True).read()
    load_s = time.perf_counter() - t0

    def fit(hot=0, mode="auto"):
        return (
            LogisticRegression().set_vector_col("features")
            .set_label_col("label").set_prediction_col("pred")
            .set_num_features(dim).set_learning_rate(0.5)
            .set_global_batch_size(batch).set_max_iter(epochs)
            .set_num_hot_features(hot).set_hot_slab_mode(mode).fit(table)
        )

    plain_sps, model = _steady_fit_sps(fit)
    # hot/cold split (lib/common.HotColdStack): the generator's frequency
    # head is features [0, 50k) — stream them via a dense bf16 MXU slab.
    hot_k = 50176  # 512-aligned cover of the frequency head
    # THE HEADLINE is the SCALABLE formulation (VERDICT r4 #1): slabs
    # densify in-program per minibatch, HBM holds O(nnz) — the only
    # variant that exists at shapes where rows x hot_k x 2B cannot fit
    # (see bench_sparse_scale).  The resident-slab variant (fastest while
    # it fits) is reported alongside.
    stream_sps, stream_model = _steady_fit_sps(lambda: fit(hot_k, "stream"))
    resident_sps, _ = _steady_fit_sps(lambda: fit(hot_k, "resident"))
    device_sps = stream_sps
    # behavioral parity between the formulations (binary values are exact
    # in bf16; only summation grouping differs): prediction agreement
    head = table.slice_rows(0, min(20_000, n_rows))
    (pa,) = model.transform(head)
    (pb,) = stream_model.transform(head)
    agree = float(np.mean(
        np.asarray(pa.col("pred")) == np.asarray(pb.col("pred"))
    ))

    # vectorized numpy sparse SGD baseline: CSR array slices, reduceat
    # forward + add.at scatter — the honest host-CPU formulation with its
    # data ALREADY in CSR arrays (the fastest fair in-RAM condition; no
    # object iteration inside the timed loop)
    from flink_ml_tpu.ops.batch import CsrRows

    vecs = table.col("features")
    if not isinstance(vecs, CsrRows):
        vecs = CsrRows.from_vectors(list(vecs), dim=dim)
    y = np.asarray(table.col("label"), dtype=np.float64)
    n_base = min(n_rows, 4 * batch)
    w_np = np.zeros(dim)
    b_np = 0.0
    t0 = time.perf_counter()
    for lo in range(0, n_base, batch):
        hi = min(lo + batch, n_base)
        e0, e1 = int(vecs.indptr[lo]), int(vecs.indptr[hi])
        yb = y[lo:hi]
        flat_idx = vecs.indices[e0:e1]
        flat_val = vecs.values[e0:e1]
        counts = np.diff(vecs.indptr[lo : hi + 1])
        bounds = vecs.indptr[lo:hi] - e0
        z = np.add.reduceat(flat_val * w_np[flat_idx], bounds) + b_np
        err = _sigmoid(z) - yb
        np.add.at(
            w_np, flat_idx,
            (-0.5 / (hi - lo)) * np.repeat(err, counts) * flat_val,
        )
        b_np -= 0.5 * err.mean()
    vec_sps = n_base / (time.perf_counter() - t0)

    return _emit({
        "metric": "Sparse LogisticRegression.fit samples/sec/chip (Criteo-shaped)",
        "value": round(device_sps / _n_chips(), 1),
        "unit": "samples/sec/chip",
        "vs_baseline": round(device_sps / vec_sps, 2),
        "formulation": "hotcold-stream (in-program densify, O(nnz) HBM)",
        "plain_sps": round(plain_sps, 1),
        "hotcold_stream_sps": round(stream_sps, 1),
        "hotcold_resident_sps": round(resident_sps, 1),
        "resident_vs_baseline": round(resident_sps / vec_sps, 2),
        "stream_vs_plain": round(stream_sps / plain_sps, 2),
        "hot_k": hot_k,
        "pred_agreement": round(agree, 4),
        "nnz_per_sec": round(device_sps * nnz, 1),
        "dim": dim,
        "native_load_rows_per_sec": round(n_rows / load_s, 1),
        "shape": f"{n_rows} rows, {dim} features, ~{nnz} nnz/row, "
                 f"batch={batch} epochs={epochs}",
    })


def bench_sparse_scale(n_rows=1_000_000, dim=1_000_000, nnz=39, epochs=4,
                       batch=8192):
    """The Criteo-direction scale point (VERDICT r4 #1): 1M rows x 1M dim,
    where the resident-slab formulation is IMPOSSIBLE (rows x hot_k x 2B
    ~= 100 GB against 16 GB of HBM) — only the streamed in-program-densify
    hot/cold formulation and the plain segment-CSR path exist.  Data
    (packed entries, ~12 B/nnz) stays HBM-resident like every other
    in-memory headline row; the CPU baseline is the same strengthened CSR
    SGD at the same shape."""
    from flink_ml_tpu.lib import LogisticRegression
    from flink_ml_tpu.ops.batch import CsrRows
    from flink_ml_tpu.table.sources import LibSvmSource

    path = bench_sparse_file(n_rows, dim, nnz)
    t0 = time.perf_counter()
    table = LibSvmSource(path, n_features=dim, zero_based=True).read()
    load_s = time.perf_counter() - t0
    hot_k = 50176

    def fit(mode="stream", hot=hot_k):
        return (
            LogisticRegression().set_vector_col("features")
            .set_label_col("label").set_prediction_col("pred")
            .set_num_features(dim).set_learning_rate(0.5)
            .set_global_batch_size(batch).set_max_iter(epochs)
            .set_num_hot_features(hot).set_hot_slab_mode(mode).fit(table)
        )

    stream_sps, _ = _steady_fit_sps(lambda: fit("stream"))
    plain_sps, _ = _steady_fit_sps(lambda: fit(hot=0))

    # strengthened CSR CPU baseline at the same shape (data in RAM as CSR
    # arrays; reduceat forward + add.at scatter)
    vecs = table.col("features")
    if not isinstance(vecs, CsrRows):
        vecs = CsrRows.from_vectors(list(vecs), dim=dim)
    y = np.asarray(table.col("label"), dtype=np.float64)
    n_base = min(n_rows, 8 * batch)
    w_np = np.zeros(dim)
    b_np = 0.0
    t0 = time.perf_counter()
    for lo in range(0, n_base, batch):
        hi = min(lo + batch, n_base)
        e0, e1 = int(vecs.indptr[lo]), int(vecs.indptr[hi])
        yb = y[lo:hi]
        flat_idx = vecs.indices[e0:e1]
        flat_val = vecs.values[e0:e1]
        counts = np.diff(vecs.indptr[lo : hi + 1])
        bounds = vecs.indptr[lo:hi] - e0
        z = np.add.reduceat(flat_val * w_np[flat_idx], bounds) + b_np
        err = _sigmoid(z) - yb
        np.add.at(
            w_np, flat_idx,
            (-0.5 / (hi - lo)) * np.repeat(err, counts) * flat_val,
        )
        b_np -= 0.5 * err.mean()
    vec_sps = n_base / (time.perf_counter() - t0)

    slab_gb = n_rows * hot_k * 2 / 1e9
    return _emit({
        "metric": "Sparse LR samples/sec/chip at scale (resident slab impossible)",
        "value": round(stream_sps / _n_chips(), 1),
        "unit": "samples/sec/chip",
        "vs_baseline": round(stream_sps / vec_sps, 2),
        "formulation": "hotcold-stream (in-program densify, O(nnz) HBM)",
        "plain_sps": round(plain_sps, 1),
        "stream_vs_plain": round(stream_sps / plain_sps, 2),
        "resident_slab_would_need_gb": round(slab_gb, 1),
        "hot_k": hot_k,
        "native_load_rows_per_sec": round(n_rows / load_s, 1),
        "shape": f"{n_rows} rows, {dim} features, ~{nnz} nnz/row, "
                 f"batch={batch} epochs={epochs}",
    })


def bench_pipeline_file(n_rows, vocab_sizes, seed=11):
    """Synthetic categorical CSV (Criteo-shaped head): one string column
    per vocabulary, zipf-ish frequency within each, plus a label derived
    from per-value weights.  Cached under the bench temp dir."""
    import hashlib

    key = hashlib.md5(
        f"{n_rows}-{vocab_sizes}-{seed}".encode()
    ).hexdigest()[:12]
    path = os.path.join(
        tempfile.gettempdir(), f"bench_pipe_{key}.csv"
    )
    if os.path.exists(path):
        return path
    rng = np.random.RandomState(seed)
    cols = []
    score = np.zeros(n_rows)
    for vs in vocab_sizes:
        # zipf-ish draw over the vocabulary
        r = rng.zipf(1.3, size=n_rows) - 1
        v = np.minimum(r, vs - 1).astype(np.int64)
        w = rng.randn(vs) * 0.6
        score += w[v]
        cols.append(v)
    y = (score + 0.3 * rng.randn(n_rows) > 0).astype(np.int64)
    tmp = path + ".tmp"
    with open(tmp, "w") as f:
        for i in range(n_rows):
            f.write(
                ",".join(f"k{c[i]}" for c in cols) + f",{y[i]}\n"
            )
    os.replace(tmp, path)
    return path


def bench_pipeline(n_rows=300_000,
                   vocab_sizes=(100_000, 20_000, 5_000, 1_000, 200, 50, 10,
                                4),
                   epochs=10, batch=8192, chunk_rows=32_768):
    """The Criteo pipeline AS a pipeline (VERDICT r4 #5): chunked
    categorical CSV -> StringIndexer -> OneHotEncoder (one offset-stacked
    CsrRows column) -> sparse hot/cold LogisticRegression, end-to-end.
    This is the workload the reference's entire colname vocabulary +
    merge-rule design exists to serve (HasSelectedCol.java:33-47,
    OutputColsHelper.java:32-52).

    The baseline is the vectorized-numpy equivalent of the SAME chain:
    np.unique factorize per column + offset-stacked CSR build + the
    strengthened CSR SGD.  Both sides report end-to-end rows/s plus the
    head (encode) / train split.
    """
    from flink_ml_tpu.api.pipeline import Pipeline
    from flink_ml_tpu.lib import (
        LogisticRegression,
        OneHotEncoder,
        StringIndexer,
    )
    from flink_ml_tpu.table.schema import DataTypes, Schema
    from flink_ml_tpu.table.sources import ChunkedTable, CsvSource

    path = bench_pipeline_file(n_rows, tuple(vocab_sizes))
    cat_cols = [f"c{i}" for i in range(len(vocab_sizes))]
    schema = Schema.of(
        *[(c, DataTypes.STRING) for c in cat_cols],
        ("label", DataTypes.DOUBLE),
    )

    def make_pipeline():
        return Pipeline([
            StringIndexer().set_selected_cols(cat_cols),
            OneHotEncoder().set_selected_cols(cat_cols)
            .set_output_col("features"),
            LogisticRegression().set_vector_col("features")
            .set_label_col("label").set_prediction_col("pred")
            .set_prediction_detail_col("prob")
            .set_learning_rate(0.5).set_global_batch_size(batch)
            .set_max_iter(epochs)
            .set_num_hot_features(2048).set_hot_slab_mode("stream"),
        ])

    def chunked():
        return ChunkedTable(
            CsvSource(path, schema), chunk_rows, spill=True
        )

    # end-to-end: CSV parse + two head fits + sparse LR fit, all chunked
    make_pipeline().fit(chunked())  # warmup: compile
    t0 = time.perf_counter()
    pm = make_pipeline().fit(chunked())
    e2e_wall = time.perf_counter() - t0
    e2e_rps = n_rows / e2e_wall

    # head/train split: the manual chain IS Pipeline.fit's sequence
    # (Pipeline.java:80-94) — time the stages separately once
    table = chunked()
    t0 = time.perf_counter()
    si = StringIndexer().set_selected_cols(cat_cols).fit(table)
    t_index = time.perf_counter() - t0
    from flink_ml_tpu.table.sources import TransformedChunkedTable

    indexed = TransformedChunkedTable(table, si)
    t0 = time.perf_counter()
    enc = (OneHotEncoder().set_selected_cols(cat_cols)
           .set_output_col("features").fit(indexed))
    t_encode = time.perf_counter() - t0
    encoded = TransformedChunkedTable(indexed, enc)
    t0 = time.perf_counter()
    (LogisticRegression().set_vector_col("features")
     .set_label_col("label").set_prediction_col("pred")
     .set_learning_rate(0.5).set_global_batch_size(batch)
     .set_max_iter(epochs).set_num_hot_features(2048)
     .set_hot_slab_mode("stream").fit(encoded))
    t_train = time.perf_counter() - t0

    # vectorized-numpy equivalent of the same chain
    raw_cols = [[] for _ in cat_cols]
    ys = []
    with open(path) as f:
        for line in f:
            parts = line.rstrip("\n").split(",")
            for j in range(len(cat_cols)):
                raw_cols[j].append(parts[j])
            ys.append(float(parts[-1]))
    y = np.asarray(ys)
    t0 = time.perf_counter()
    offsets = [0]
    idx_cols = []
    for vals in raw_cols:
        arr = np.asarray(vals)
        uniq, inv = np.unique(arr, return_inverse=True)
        idx_cols.append(inv + offsets[-1])
        offsets.append(offsets[-1] + len(uniq))
    dim = offsets[-1]
    flat_idx_all = np.stack(idx_cols, axis=1).reshape(-1)
    k = len(cat_cols)
    np_encode_s = time.perf_counter() - t0
    w_np = np.zeros(dim)
    b_np = 0.0
    n_base = min(n_rows, 8 * batch)
    t0 = time.perf_counter()
    for lo in range(0, n_base, batch):
        hi = min(lo + batch, n_base)
        yb = y[lo:hi]
        flat_idx = flat_idx_all[lo * k : hi * k]
        z = w_np[flat_idx].reshape(-1, k).sum(axis=1) + b_np
        err = _sigmoid(z) - yb
        np.add.at(
            w_np, flat_idx, (-0.5 / (hi - lo)) * np.repeat(err, k)
        )
        b_np -= 0.5 * err.mean()
    np_rate = n_base / (time.perf_counter() - t0)
    np_train_s = n_rows * epochs / np_rate
    np_e2e_rps = n_rows / (np_encode_s + np_train_s)

    # quality: AUC of the pipeline's scores on the head of the file
    from flink_ml_tpu.lib.encoding import binary_auc

    head_n = min(50_000, n_rows)
    head = CsvSource(path, schema).read().slice_rows(0, head_n)
    (scored,) = pm.transform(head)
    auc = binary_auc(
        np.asarray(head.col("label"), dtype=np.float64),
        np.asarray(scored.col("prob"), dtype=np.float64),
    )

    return _emit({
        "metric": "Categorical pipeline end-to-end rows/sec (CSV -> "
                  "StringIndexer -> OneHotEncoder -> sparse LR)",
        "value": round(e2e_rps, 1),
        "unit": "rows/sec",
        "vs_baseline": round(e2e_rps / np_e2e_rps, 2),
        "e2e_wall_s": round(e2e_wall, 2),
        "head_index_s": round(t_index, 2),
        "head_encode_s": round(t_encode, 2),
        "train_s": round(t_train, 2),
        "baseline_encode_s": round(np_encode_s, 2),
        "baseline_train_s_est": round(np_train_s, 2),
        "baseline_e2e_rows_per_sec": round(np_e2e_rps, 1),
        "encoded_dim": int(dim),
        "auc_head": round(float(auc), 4),
        "shape": f"{n_rows} rows x {len(cat_cols)} cat cols, "
                 f"dim~{dim}, batch={batch} epochs={epochs}",
    })


def bench_sparse_ooc(n_rows=100_000, dim=1_000_000, nnz=39, epochs=10,
                     batch=8192, chunk_rows=16_384):
    """Larger-than-RAM variant of the Criteo-shaped workload: the same
    LibSVM file trained through the out-of-core path (lib/out_of_core.py)
    with host residency capped at ``chunk_rows`` rows (~1/6 of the dataset)
    — chunks re-parse from disk every epoch and prefetch host->device while
    the previous chunk trains.  ``vs_in_memory`` is the throughput ratio
    against the fully-resident fused fit of the identical program (the
    streaming overhead the chunked feed pays for unbounded scale).
    """
    from flink_ml_tpu.lib import LogisticRegression
    from flink_ml_tpu.table.sources import ChunkedTable, LibSvmSource

    path = bench_sparse_file(n_rows, dim, nnz)
    source = LibSvmSource(path, n_features=dim, zero_based=True)

    def est():
        return (
            LogisticRegression().set_vector_col("features")
            .set_label_col("label").set_prediction_col("pred")
            .set_num_features(dim).set_learning_rate(0.5)
            .set_global_batch_size(batch).set_max_iter(epochs)
        )

    # in-memory reference run (same epochs) for the overhead ratio
    table = source.read()
    mem_sps, mem_model = _steady_fit_sps(lambda: est().fit(table))

    # Decomposition by algebra on two spill runs (both warmed, both paying
    # the epoch-1 parse + spill write): wall_2 = first + steady,
    # wall_N = first + (N-1)*steady.  The steady epochs stream binary spill
    # and pay the per-epoch host->device re-transfer the out-of-core
    # contract requires (in-memory transfers once and stays resident).
    est().set_max_iter(1).fit(ChunkedTable(source, chunk_rows))  # warm compile
    t0 = time.perf_counter()
    est().set_max_iter(2).fit(ChunkedTable(source, chunk_rows, spill=True))
    wall_2 = time.perf_counter() - t0

    chunked = ChunkedTable(source, chunk_rows=chunk_rows, spill=True)
    t0 = time.perf_counter()
    model = est().fit(chunked)
    wall = time.perf_counter() - t0
    ooc_sps = n_rows * epochs / wall
    steady_epoch_s = max(wall - wall_2, 1e-9) / max(epochs - 2, 1)
    first_epoch_s = max(wall_2 - steady_epoch_s, 0.0)
    # bytes a steady epoch moves host->device: segment-CSR ints + floats,
    # sized with the SAME estimator the fit uses (includes its safety pad);
    # each global step transfers one group per data-parallel device
    from flink_ml_tpu.lib.out_of_core import estimate_nnz_pad

    mb_per_dev = -(-batch // _n_chips())
    nnz_pad = estimate_nnz_pad(
        ChunkedTable(source, chunk_rows), "features", mb_per_dev, _n_chips()
    )
    blocks = -(-n_rows // batch)
    epoch_bytes = blocks * _n_chips() * (
        2 * nnz_pad * 4 + (nnz_pad + 2 * mb_per_dev) * 4
    )

    drift = float(np.max(np.abs(model.coefficients() - mem_model.coefficients())))
    return _emit({
        "metric": "Out-of-core sparse LogisticRegression.fit samples/sec/chip",
        "value": round(ooc_sps / _n_chips(), 1),
        "unit": "samples/sec/chip",
        "vs_in_memory": round(ooc_sps / mem_sps, 3),
        "host_cap_rows": chunk_rows,
        "bit_match_in_memory": bool(drift == 0.0),
        "first_epoch_s": round(first_epoch_s, 2),
        "steady_epoch_s": round(steady_epoch_s, 3),
        "steady_epoch_mb": round(epoch_bytes / 1e6, 1),
        "steady_stream_mb_per_s": round(epoch_bytes / 1e6 / steady_epoch_s, 1),
        "shape": f"{n_rows} rows, {dim} features, ~{nnz} nnz/row, "
                 f"batch={batch} epochs={epochs} chunk_rows={chunk_rows}",
    })


def bench_warm_fit(n_rows=200_000, n_features=28, epochs=5, batch=16384):
    """Repeated-fit sweep over ONE table (ISSUE 2): cold vs warm call
    latency and slab-pool hit counts.

    Three fits of the same table — fit 1 cold (pack + place + compile),
    fit 2 warm at the same learning rate (slab pool + program cache hits),
    fit 3 at a VARIED learning rate (new compiled program, but the placed
    batch still comes from the pool — the hyperparameter-sweep shape the
    pool exists for).  An uncached fit (``FMT_SLAB_POOL=0`` semantics via a
    cleared pool + fresh table) provides the AUC-parity reference.

    The emitted ``warm_over_cold`` ratio (fit 2 wall / fit 1 wall, lower is
    better) is the machine-robust number BASELINE.json gates: a broken pool
    drags it toward 1.0 regardless of host speed.
    """
    from flink_ml_tpu.lib import LogisticRegression
    from flink_ml_tpu.table import slab_pool
    from flink_ml_tpu.table.schema import DataTypes, Schema
    from flink_ml_tpu.table.table import Table

    rng = np.random.RandomState(11)
    X = rng.randn(n_rows, n_features).astype(np.float32)
    true_w = (rng.randn(n_features) / np.sqrt(n_features)).astype(np.float32)
    y = ((X @ true_w + 0.17 * rng.randn(n_rows).astype(np.float32)) > 0
         ).astype(np.float32)
    n_train = int(0.8 * n_rows)
    schema = Schema.of(("features", DataTypes.DENSE_VECTOR),
                       ("label", "double"))
    t = Table.from_columns(
        schema, {"features": X[:n_train], "label": y[:n_train]}
    )

    def fit(table, lr):
        t0 = time.perf_counter()
        model = (
            LogisticRegression().set_vector_col("features")
            .set_label_col("label").set_prediction_col("pred")
            .set_learning_rate(lr).set_global_batch_size(batch)
            .set_max_iter(epochs).fit(table)
        )
        return model, time.perf_counter() - t0

    # a genuinely cold first fit: empty pool, and an lr no earlier workload
    # in this process has compiled (the epoch-step cache keys on lr)
    slab_pool.reset_pool()
    pool = slab_pool.pool()
    lrs = [0.517, 0.517, 0.2585]  # fit 3 varies the rate (sweep shape)
    walls, models, fit_hits = [], [], []
    for lr in lrs:
        h0 = pool.hits
        model, wall = fit(t, lr)
        walls.append(wall)
        models.append(model)
        fit_hits.append(pool.hits - h0)
    cold_ms, warm_ms, sweep_ms = (w * 1e3 for w in walls)

    # uncached reference: fresh pool AND fresh (content-distinct) table —
    # the full pack+place path, for AUC parity vs the pooled fits
    slab_pool.reset_pool()
    t_fresh = Table.from_columns(
        schema, {"features": X[:n_train].copy(), "label": y[:n_train].copy()}
    )
    uncached_model, uncached_wall = fit(t_fresh, lrs[1])
    slab_pool.reset_pool()

    Xq, yq = X[n_train:], y[n_train:]
    qt = Table.from_columns(
        Schema.of(("features", DataTypes.DENSE_VECTOR)), {"features": Xq}
    )
    auc_warm = _auc(yq, models[1].predict_proba(qt))
    auc_uncached = _auc(yq, uncached_model.predict_proba(qt))
    return _emit({
        "metric": "LogisticRegression.repeated_fit warm_over_cold",
        "value": round(walls[1] / walls[0], 4),
        "unit": "ratio (lower is better)",
        "cold_fit_ms": round(cold_ms, 1),
        "warm_fit_ms": round(warm_ms, 1),
        "sweep_fit_ms": round(sweep_ms, 1),  # varied lr: pool hit, recompile
        "uncached_fit_ms": round(uncached_wall * 1e3, 1),
        "pool_hits_per_fit": fit_hits,
        "pool_hits": pool.hits, "pool_misses": pool.misses,
        "pool_evictions": pool.evictions,
        "warm_hits_pool": bool(fit_hits[1] > 0 and fit_hits[2] > 0),
        "auc_warm": round(auc_warm, 4),
        "auc_uncached": round(auc_uncached, 4),
        "auc_parity": bool(abs(auc_warm - auc_uncached) < 1e-6),
        "shape": f"{n_train}x{n_features} f32 batch={batch} epochs={epochs} "
                 f"x3 fits (lr varied on fit 3)",
    })


def bench_serve_fused(n_rows=200_000, n_features=16, batch=4096, sweeps=3):
    """Staged vs fused pipeline inference (ISSUE 6): a 3-stage serving
    chain (StandardScaler -> MinMaxScaler -> LogisticRegression score)
    transformed with ``FMT_FUSE_TRANSFORM`` off (the per-stage path: one
    dispatch + 2 host<->device hops per stage per batch) and on (one fused
    dispatch per batch, columns device-resident across stages).

    The emitted ``fused_over_staged`` ratio (fused wall / staged wall,
    lower is better) is the machine-robust number BASELINE.json gates:
    dispatch count per batch is 1 vs 3 by construction (asserted via the
    ``pipeline.fused_dispatches`` counter), so a broken planner drags the
    ratio toward 1.0 on any host.  Exact discrete-prediction parity vs the
    staged path is asserted, not just recorded — a fused plan that serves
    different labels is a bug, never a data point.
    """
    import warnings

    from flink_ml_tpu import obs
    from flink_ml_tpu.api.pipeline import Pipeline
    from flink_ml_tpu.lib import LogisticRegression
    from flink_ml_tpu.lib.feature import MinMaxScaler, StandardScaler
    from flink_ml_tpu.table.schema import DataTypes, Schema
    from flink_ml_tpu.table.table import Table
    from flink_ml_tpu.utils.environment import MLEnvironmentFactory

    rng = np.random.RandomState(13)
    X = (2.0 * rng.randn(n_rows, n_features) + 3.0).astype(np.float32)
    true_w = (rng.randn(n_features) / np.sqrt(n_features)).astype(np.float32)
    y = ((X - 3.0) @ true_w > 0).astype(np.float64)
    t = Table.from_columns(
        Schema.of(("features", DataTypes.DENSE_VECTOR), ("label", "double")),
        {"features": X, "label": y},
    )
    model = Pipeline([
        StandardScaler().set_selected_col("features"),
        MinMaxScaler().set_selected_col("features"),
        LogisticRegression().set_vector_col("features")
        .set_label_col("label").set_prediction_col("pred")
        .set_prediction_detail_col("proba")
        .set_learning_rate(0.5).set_max_iter(5),
    ]).fit(t)

    env = MLEnvironmentFactory.get_default()
    old_bs, env.default_batch_size = env.default_batch_size, batch
    old_knob = os.environ.get("FMT_FUSE_TRANSFORM")

    def timed(fuse: bool):
        os.environ["FMT_FUSE_TRANSFORM"] = "1" if fuse else "0"
        model.transform(t)  # warmup: compile every per-batch bucket
        walls = []
        for _ in range(sweeps):
            t0 = time.perf_counter()
            (out,) = model.transform(t)
            walls.append(time.perf_counter() - t0)
        return float(np.median(walls)), out

    try:
        with warnings.catch_warnings():
            warnings.simplefilter("ignore", UserWarning)
            staged_s, staged_out = timed(False)
            obs.reset()
            fused_s, fused_out = timed(True)
            counters = obs.registry().snapshot()["counters"]
            # dispatch-cost satellite (ISSUE 15): the same fused sweep
            # with buffer donation off — the delta is the HBM-residency
            # cost donation removes (CPU ignores donation, so there the
            # two arms are the same program and the ratio reads ~1.0)
            old_donate = os.environ.get("FMT_FUSE_DONATE")
            os.environ["FMT_FUSE_DONATE"] = "0"
            try:
                nodonate_s, _ = timed(True)
            finally:
                if old_donate is None:
                    os.environ.pop("FMT_FUSE_DONATE", None)
                else:
                    os.environ["FMT_FUSE_DONATE"] = old_donate
        import jax

        donation_active = jax.default_backend() != "cpu"
        n_batches = -(-n_rows // batch)
        # (sweeps + warmup) transforms x one dispatch per batch per run
        dispatches_per_transform = (
            counters.get("pipeline.fused_dispatches", 0) / (sweeps + 1)
        )
        assert dispatches_per_transform == n_batches, (
            dispatches_per_transform, n_batches)
        pred_parity = bool(np.array_equal(
            np.asarray(staged_out.col("pred")),
            np.asarray(fused_out.col("pred")),
        ))
        assert pred_parity, "fused discrete predictions diverge from staged"
        proba_err = float(np.max(np.abs(
            np.asarray(staged_out.col("proba"))
            - np.asarray(fused_out.col("proba"))
        )))
    finally:
        env.default_batch_size = old_bs
        if old_knob is None:
            os.environ.pop("FMT_FUSE_TRANSFORM", None)
        else:
            os.environ["FMT_FUSE_TRANSFORM"] = old_knob

    return _emit({
        "metric": "PipelineModel.transform fused_over_staged",
        "value": round(fused_s / staged_s, 4),
        "unit": "ratio (lower is better)",
        "staged_ms": round(staged_s * 1e3, 1),
        "fused_ms": round(fused_s * 1e3, 1),
        "staged_rows_per_sec": round(n_rows / staged_s, 1),
        "fused_rows_per_sec": round(n_rows / fused_s, 1),
        "dispatches_per_batch_staged": 3,
        "dispatches_per_batch_fused": 1,
        "pred_parity": pred_parity,
        "proba_max_abs_err": proba_err,
        "donation_active": donation_active,
        "fused_nodonate_ms": round(nodonate_s * 1e3, 1),
        "donate_over_nodonate": round(fused_s / nodonate_s, 4),
        "shape": f"{n_rows}x{n_features} f32, 3 stages "
                 f"(scaler->scaler->LR score), batch={batch}, "
                 f"{n_batches} batches, median of {sweeps}",
    })


def bench_serve_pallas(n_rows=200_000, n_features=16, batch=4096, sweeps=3):
    """Pallas serving kernel + low-precision inference legs (ISSUE 17).

    Two gated ratios against the same XLA fused baseline:

    - ``fused_pallas_over_xla``: the 3-stage chain served through ONE
      ``serve_chain`` Pallas launch per batch (``FMT_SERVE_PALLAS=1``) vs
      the XLA fused program.  One-kernel-per-dispatch is asserted via
      ``fused.pallas_dispatches == pipeline.fused_dispatches``; discrete
      predictions must be bit-identical.  On CPU the kernel runs in
      interpret mode (an emulation, not the TPU lowering), so the CPU gate
      bounds overhead; on TPU the single HBM pass is the win.
    - ``quantized_over_f32``: the same chain at ``FMT_SERVE_PRECISION=
      bf16`` (half the batch-placement bytes) vs f32.  Discrete parity is
      asserted on margin rows — rows whose f32 probability clears 0.5 by
      more than the documented bf16 tolerance band; a quantization bug
      flips predictions far from the boundary and fails the assert.

    A side (untimed) probe injects NaN/Inf rows and asserts the deferred
    in-kernel quarantine scan yields the SAME side-table rows/reasons and
    surviving predictions as the XLA path's host scan.
    """
    import warnings

    from flink_ml_tpu import obs
    from flink_ml_tpu.api.pipeline import Pipeline
    from flink_ml_tpu.lib import LogisticRegression
    from flink_ml_tpu.lib.feature import MinMaxScaler, StandardScaler
    from flink_ml_tpu.serve import quarantine
    from flink_ml_tpu.table.schema import DataTypes, Schema
    from flink_ml_tpu.table.table import Table
    from flink_ml_tpu.utils.environment import MLEnvironmentFactory

    rng = np.random.RandomState(17)
    X = (2.0 * rng.randn(n_rows, n_features) + 3.0).astype(np.float32)
    true_w = (rng.randn(n_features) / np.sqrt(n_features)).astype(np.float32)
    y = ((X - 3.0) @ true_w > 0).astype(np.float64)
    schema = Schema.of(("features", DataTypes.DENSE_VECTOR),
                       ("label", "double"))
    t = Table.from_columns(schema, {"features": X, "label": y})
    model = Pipeline([
        StandardScaler().set_selected_col("features"),
        MinMaxScaler().set_selected_col("features"),
        LogisticRegression().set_vector_col("features")
        .set_label_col("label").set_prediction_col("pred")
        .set_prediction_detail_col("proba")
        .set_learning_rate(2.0).set_max_iter(30),
    ]).fit(t)

    env = MLEnvironmentFactory.get_default()
    old_bs, env.default_batch_size = env.default_batch_size, batch
    old_env = {k: os.environ.get(k) for k in
               ("FMT_FUSE_TRANSFORM", "FMT_SERVE_PALLAS",
                "FMT_SERVE_PRECISION")}

    def arm(pallas, precision="f32"):
        os.environ["FMT_FUSE_TRANSFORM"] = "1"
        os.environ["FMT_SERVE_PALLAS"] = "1" if pallas else "0"
        os.environ["FMT_SERVE_PRECISION"] = precision
        return pallas, precision

    def timed(table, pallas, precision="f32"):
        arm(pallas, precision)
        model.transform(table)  # warmup: compile every per-batch bucket
        walls = []
        for _ in range(sweeps):
            t0 = time.perf_counter()
            (out,) = model.transform(table)
            walls.append(time.perf_counter() - t0)
        return float(np.median(walls)), out

    try:
        with warnings.catch_warnings():
            warnings.simplefilter("ignore", UserWarning)
            # margin eval set: rows whose f32 probability clears the
            # boundary by > the bf16 tolerance band — discrete parity is
            # contractual there (boundary rows may legitimately flip)
            arm(False)
            (full,) = model.transform(t)
            proba = np.asarray(full.col("proba"), dtype=np.float64)
            eval_t = t.filter_rows(np.abs(proba - 0.5) > 0.02)
            n_eval = eval_t.num_rows()
            assert n_eval > n_rows * 0.8, n_eval  # fit separates classes

            xla_s, xla_out = timed(eval_t, False)
            obs.reset()
            pallas_s, pallas_out = timed(eval_t, True)
            counters = obs.registry().snapshot()["counters"]
            obs.reset()
            bf16_s, bf16_out = timed(eval_t, False, "bf16")
            gauges = obs.registry().snapshot()["gauges"]

            # one Pallas launch per fused dispatch, zero fallbacks
            assert counters.get("fused.pallas_dispatches", 0) == \
                counters.get("pipeline.fused_dispatches", -1), counters
            assert "fused.pallas_fallbacks" not in counters, counters
            n_batches = -(-n_eval // batch)
            assert counters["fused.pallas_dispatches"] == \
                (sweeps + 1) * n_batches, counters
            assert gauges.get("serve.precision") == 16, gauges

            pallas_pred_parity = bool(np.array_equal(
                np.asarray(xla_out.col("pred")),
                np.asarray(pallas_out.col("pred"))))
            assert pallas_pred_parity, \
                "pallas discrete predictions diverge from XLA"
            quant_pred_parity = bool(np.array_equal(
                np.asarray(xla_out.col("pred")),
                np.asarray(bf16_out.col("pred"))))
            assert quant_pred_parity, \
                "bf16 discrete predictions diverge from f32 on margin rows"
            pallas_proba_err = float(np.max(np.abs(
                np.asarray(xla_out.col("proba"))
                - np.asarray(pallas_out.col("proba")))))
            quant_proba_err = float(np.max(np.abs(
                np.asarray(xla_out.col("proba"))
                - np.asarray(bf16_out.col("proba")))))

            # quarantine parity probe (untimed): the deferred in-kernel
            # scan must match the host scan's side-table exactly
            Xq = np.asarray(
                t.slice_rows(0, 4096).features_dense("features")).copy()
            Xq[7, 0] = np.nan
            Xq[513, 3] = np.inf
            Xq[4000, 9] = -np.inf
            bad_t = Table.from_columns(schema, {
                "features": Xq, "label": y[:4096]})

            def q_probe(pallas):
                arm(pallas)
                quarantine.reset()
                (out,) = model.transform(bad_t)
                qt = quarantine.quarantine_table("StandardScalerModel")
                rows = sorted(int(r) for r in
                              qt.col(quarantine.QUARANTINE_ROW_COL))
                reasons = sorted(set(
                    qt.col(quarantine.QUARANTINE_REASON_COL)))
                quarantine.reset()
                return rows, reasons, np.asarray(out.col("pred"))

            x_rows, x_reasons, x_preds = q_probe(False)
            p_rows, p_reasons, p_preds = q_probe(True)
            quarantine_parity = bool(
                x_rows == p_rows == [7, 513, 4000]
                and x_reasons == p_reasons
                and np.array_equal(x_preds, p_preds))
            assert quarantine_parity, (x_rows, p_rows)
    finally:
        env.default_batch_size = old_bs
        for k, v in old_env.items():
            if v is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = v

    import jax

    interpret = jax.default_backend() != "tpu"
    shape = (f"{n_eval}x{n_features} f32 margin rows, 3 stages "
             f"(scaler->scaler->LR score), batch={batch}, "
             f"{-(-n_eval // batch)} batches, median of {sweeps}")
    pallas_rec = _emit({
        "metric": "PipelineModel.transform fused_pallas_over_xla",
        "value": round(pallas_s / xla_s, 4),
        "unit": "ratio (lower is better)",
        "xla_ms": round(xla_s * 1e3, 1),
        "pallas_ms": round(pallas_s * 1e3, 1),
        "interpret_mode": interpret,
        "pred_parity": pallas_pred_parity,
        "proba_max_abs_err": pallas_proba_err,
        "quarantine_parity": quarantine_parity,
        "kernel_launches_per_dispatch": 1,
        "shape": shape,
    })
    quant_rec = _emit({
        "metric": "PipelineModel.transform quantized_over_f32",
        "value": round(bf16_s / xla_s, 4),
        "unit": "ratio (lower is better)",
        "f32_ms": round(xla_s * 1e3, 1),
        "bf16_ms": round(bf16_s * 1e3, 1),
        "precision_bits": 16,
        "pred_parity": quant_pred_parity,
        "proba_max_abs_err": quant_proba_err,
        "shape": shape,
    })
    return [pallas_rec, quant_rec]


def bench_serve(n_rows=200_000, n_features=16, batch=4096, sweeps=3):
    """The full serve suite: the staged-vs-fused gate plus the Pallas and
    low-precision legs (all three ratios land in BASELINE.json)."""
    fused_rec = bench_serve_fused(n_rows, n_features, batch, sweeps)
    return [fused_rec] + bench_serve_pallas(n_rows, n_features, batch,
                                            sweeps)


def bench_serving(n_rows=20_000, n_features=16, n_requests=160, sweeps=3,
                  max_batch=256, max_wait_ms=2.0):
    """Dynamic micro-batching vs serial per-request dispatch (ISSUE 7).

    The workload a request-level server exists for: ``n_requests`` small
    (1-16 row, mixed-size) requests against the 3-stage serving chain
    (StandardScaler -> MinMaxScaler -> LogisticRegression score).  The
    serial baseline transforms each request on its own — one plan walk,
    one fused dispatch, one demux per REQUEST (what every caller of
    ``transform`` pays today); the server coalesces the same requests
    into full fused batches padded to the shared bucket ladder.

    The emitted ``batched_over_serial`` ratio (batched wall / serial
    wall, lower is better) is the machine-robust number BASELINE.json
    gates at <= 0.34 (>= ~3x throughput): a broken batcher serves
    request-at-a-time and drags the ratio toward 1.0 on any host.
    Asserted inside the bench, never just recorded: bit-identical
    discrete predictions per request vs solo ``transform``, genuine
    coalescing (fewer batches than requests), and ladder-flat recompiles
    across the mixed request sizes.
    """
    from flink_ml_tpu import obs
    from flink_ml_tpu.api.pipeline import Pipeline
    from flink_ml_tpu.lib import LogisticRegression
    from flink_ml_tpu.lib.feature import MinMaxScaler, StandardScaler
    from flink_ml_tpu.serving import ModelServer
    from flink_ml_tpu.table.schema import DataTypes, Schema
    from flink_ml_tpu.table.table import Table
    from flink_ml_tpu.utils import compile_cache

    rng = np.random.RandomState(23)
    X = (2.0 * rng.randn(n_rows, n_features) + 3.0).astype(np.float32)
    true_w = (rng.randn(n_features) / np.sqrt(n_features)).astype(np.float32)
    y = ((X - 3.0) @ true_w > 0).astype(np.float64)
    t = Table.from_columns(
        Schema.of(("features", DataTypes.DENSE_VECTOR), ("label", "double")),
        {"features": X, "label": y},
    )
    model = Pipeline([
        StandardScaler().set_selected_col("features"),
        MinMaxScaler().set_selected_col("features"),
        LogisticRegression().set_vector_col("features")
        .set_label_col("label").set_prediction_col("pred")
        .set_learning_rate(0.5).set_max_iter(5),
    ]).fit(t)

    sizes = rng.choice([1, 3, 8, 16], size=n_requests)
    requests, lo = [], 0
    for s in sizes:
        requests.append(t.slice_rows(lo, lo + int(s)))
        lo += int(s)
    total_rows = int(sizes.sum())

    # warm every ladder bucket the requests will hit, on BOTH paths, so
    # neither side pays a compile inside its timed window
    solo = {}
    for i, req in enumerate(requests):
        (out,) = model.transform(req)
        solo[i] = np.asarray(out.col("pred"))

    def serial_wall():
        t0 = time.perf_counter()
        for req in requests:
            model.transform(req)
        return time.perf_counter() - t0

    serial_s = float(np.median([serial_wall() for _ in range(sweeps)]))

    server = ModelServer(model, max_batch=max_batch,
                         max_wait_ms=max_wait_ms)
    for fut in [server.submit(req) for req in requests[:8]]:
        fut.result(timeout=120)  # server-side warmup (coalesced buckets)
    # timed-phase accounting: fresh shapes and dispatch batches SINCE
    # here (warmed buckets stay warm — resetting the seen-set would fake
    # coldness; the warmup submissions' batches are not the sweeps')
    fresh0 = obs.registry().counter("compile_cache.bucket_new")
    batches0 = obs.registry().counter("serving.batches")

    def batched_wall():
        t0 = time.perf_counter()
        futs = [server.submit(req) for req in requests]
        results = [f.result(timeout=120) for f in futs]
        return time.perf_counter() - t0, results

    walls = []
    for _ in range(sweeps):
        w, results = batched_wall()
        walls.append(w)
    batched_s = float(np.median(walls))
    stats = server.stats()
    server.shutdown()

    # parity: every caller's predictions bit-identical to solo transform
    for i, res in enumerate(results):
        np.testing.assert_array_equal(
            np.asarray(res.table.col("pred")), solo[i],
            err_msg=f"request {i}: batched prediction diverges from solo",
        )
    counters = obs.registry().snapshot()["counters"]
    n_batches = counters.get("serving.batches", 0) - batches0
    assert n_batches < sweeps * n_requests / 2, (
        f"no real coalescing: {n_batches} dispatch batches for "
        f"{sweeps * n_requests} timed requests"
    )
    # recompile flatness: the timed sweeps' mixed sizes may touch at most
    # the ladder's rung count in fresh padded shapes
    fresh = int(counters.get("compile_cache.bucket_new", 0) - fresh0)
    assert fresh <= len(compile_cache.BATCH_BUCKET_LADDER), (
        f"{fresh} fresh batch shapes across mixed-size requests — the "
        "bucket ladder is not bounding recompiles"
    )

    return _emit({
        "metric": "ModelServer.serve batched_over_serial",
        "value": round(batched_s / serial_s, 4),
        "unit": "ratio (lower is better)",
        "serial_ms": round(serial_s * 1e3, 1),
        "batched_ms": round(batched_s * 1e3, 1),
        "serial_rows_per_sec": round(total_rows / serial_s, 1),
        "batched_rows_per_sec": round(total_rows / batched_s, 1),
        "serial_requests_per_sec": round(n_requests / serial_s, 1),
        "batched_requests_per_sec": round(n_requests / batched_s, 1),
        "batches_per_sweep": round(n_batches / float(sweeps), 1),
        "latency_p50_ms": stats.get("latency_p50_ms"),
        "latency_p99_ms": stats.get("latency_p99_ms"),
        "fresh_batch_shapes": int(fresh),
        "pred_parity": True,  # asserted above — reaching here proves it
        "shape": f"{n_requests} mixed-size (1-16 row) requests, "
                 f"{total_rows} rows, max_batch={max_batch}, "
                 f"max_wait={max_wait_ms}ms, median of {sweeps}",
    })


def bench_trace_overhead(n_rows=16_384, n_features=256, n_requests=128,
                         sweeps=7, max_batch=512, max_wait_ms=2.0):
    """Disabled-tracing overhead on the serving path (ISSUE 8).

    The round-11 contract: every trace hook planted in the serving hot
    path (submit, dispatch, the fused plan, demux) reduces to one
    module-bool check when ``FMT_TRACE`` is off, and head sampling at 1%
    keeps the enabled path within the same envelope.  This sweep runs
    the SAME mixed-size request load through ``ModelServer`` with
    tracing disabled and enabled-at-1%-sampling, interleaved (off/on per
    sweep so drift hits both arms), and emits ``trace_on_over_off`` =
    enabled wall / disabled wall — the lower-is-better ratio
    BASELINE.json gates at <= 1.02 (the <= 2% contract; ``--check``
    fails beyond 1.122 with its +10% tolerance).

    Asserted inside the bench, never just recorded: the disabled sweeps
    record ZERO spans (the one-bool contract, structurally), and the
    1%-sampled sweeps trace well under 10% of requests (head sampling
    actually sheds the work, not just the output).
    """
    from flink_ml_tpu.api.pipeline import Pipeline
    from flink_ml_tpu.lib import LogisticRegression
    from flink_ml_tpu.lib.feature import StandardScaler
    from flink_ml_tpu.obs import trace
    from flink_ml_tpu.serving import ModelServer
    from flink_ml_tpu.table.schema import DataTypes, Schema
    from flink_ml_tpu.table.table import Table

    rng = np.random.RandomState(29)
    X = (2.0 * rng.randn(n_rows, n_features) + 1.0).astype(np.float32)
    true_w = (rng.randn(n_features) / np.sqrt(n_features)).astype(np.float32)
    y = ((X - 1.0) @ true_w > 0).astype(np.float64)
    t = Table.from_columns(
        Schema.of(("features", DataTypes.DENSE_VECTOR), ("label", "double")),
        {"features": X, "label": y},
    )
    model = Pipeline([
        StandardScaler().set_selected_col("features"),
        LogisticRegression().set_vector_col("features")
        .set_label_col("label").set_prediction_col("pred")
        .set_learning_rate(0.5).set_max_iter(3),
    ]).fit(t)

    # serving-realistic request sizes (8-64 rows): per-dispatch compute
    # must dominate, or the 1%-sampled requests' REAL span work reads as
    # hook overhead it isn't
    sizes = rng.choice([8, 16, 32, 64], size=n_requests)
    requests, lo = [], 0
    for s in sizes:
        requests.append(t.slice_rows(lo, lo + int(s)))
        lo += int(s)

    # global tracing state is mutated for the measurement: restore it on
    # EVERY exit (a failed assert mid-sweep must not leave later
    # workloads in the same bench_all invocation paying full tracing)
    prev_trace_dir = os.environ.get("FMT_TRACE_DIR")
    os.environ["FMT_TRACE_DIR"] = tempfile.mkdtemp(prefix="bench_trace_")
    server = None
    try:
        trace.enable(False)
        trace.reset()
        server = ModelServer(model, max_batch=max_batch,
                             max_wait_ms=max_wait_ms,
                             queue_cap=4 * sum(int(s) for s in sizes))
        # warm both paths (ladder buckets + the traced branch's first
        # file I/O)
        for fut in [server.submit(r) for r in requests[:8]]:
            fut.result(timeout=120)
        trace.enable(True, sample=1.0)
        for fut in [server.submit(r) for r in requests[:8]]:
            fut.result(timeout=120)
        trace.enable(False)
        trace.reset()

        def sweep():
            t0 = time.perf_counter()
            futs = [server.submit(r) for r in requests]
            for f in futs:
                f.result(timeout=120)
            return time.perf_counter() - t0

        walls_off, walls_on = [], []
        for _ in range(sweeps):
            # interleaved off/on: machine drift lands on both arms equally
            trace.enable(False)
            spans_before = len(trace.recent_spans())
            walls_off.append(sweep())
            assert len(trace.recent_spans()) == spans_before, (
                "spans recorded while tracing was DISABLED — a hook is "
                "not reducing to its one-bool check"
            )
            trace.enable(True, sample=0.01)
            walls_on.append(sweep())
            trace.enable(False)
        sampled_requests = sum(
            1 for s in trace.recent_spans()
            if s["name"] == "serving.request"
        )
        stats = server.stats()
    finally:
        if server is not None:
            server.shutdown()
        trace.enable(False, sample=1.0)
        trace.reset()
        if prev_trace_dir is None:
            os.environ.pop("FMT_TRACE_DIR", None)
        else:
            os.environ["FMT_TRACE_DIR"] = prev_trace_dir

    timed_requests = sweeps * n_requests
    assert sampled_requests < 0.1 * timed_requests, (
        f"1% head sampling traced {sampled_requests} of "
        f"{timed_requests} requests — sampling is not shedding the work"
    )
    # min-of-sweeps, not median: overhead noise (GC, a scheduler hiccup
    # landing on one arm) is strictly ADDITIVE, so each arm's best sweep
    # is its cleanest measurement of the code's own cost
    off_s = float(np.min(walls_off))
    on_s = float(np.min(walls_on))
    return _emit({
        "metric": "ModelServer.serve trace_on_over_off",
        "value": round(on_s / off_s, 4),
        "unit": "ratio (lower is better)",
        "off_ms": round(off_s * 1e3, 1),
        "on_1pct_ms": round(on_s * 1e3, 1),
        "sampled_requests": int(sampled_requests),
        "timed_requests": int(timed_requests),
        "latency_p99_ms": stats.get("latency_p99_ms"),
        "disabled_records_zero_spans": True,  # asserted above
        "shape": f"{n_requests} mixed-size (8-64 row) requests x "
                 f"{n_features} features x {sweeps} interleaved off/on "
                 f"sweeps, max_batch={max_batch}, 1% head sampling, "
                 "min-of-sweeps",
    })


def bench_telemetry(n_rows=16_384, n_features=256, n_requests=256,
                    sweeps=7, max_batch=512, max_wait_ms=2.0,
                    scrape_interval_s=0.03):
    """Exporter overhead on the serving path (ISSUE 10).

    The live-telemetry contract: an armed OpenMetrics endpoint being
    actively scraped must not slow the traffic it observes.  This sweep
    runs the SAME mixed-size request load through ``ModelServer`` with
    the exporter idle (no scrapes — the listener blocks in accept, the
    off arm) and under a ~33 Hz scrape loop (hundreds of times hotter
    than any real Prometheus interval — production scrapes every 15-60
    SECONDS), and emits ``telemetry_on_over_off`` = scraped wall /
    unscraped wall — the lower-is-better ratio BASELINE.json gates at
    <= 1.02 (the <= 2% obs-overhead contract; ``--check`` fails beyond
    1.122 with its +10% tolerance).

    The scraper runs in a SUBPROCESS, exactly like the Prometheus it
    stands in for: the ratio charges the serving process for what it
    actually pays per scrape (accept + handler thread + registry
    snapshot + rendering) and not for the client half of the HTTP
    round-trip, which never runs in a serving process.

    Asserted inside the bench, never just recorded: every scrape parses
    through the STRICT OpenMetrics parser (zero tolerated parse
    failures; parsing happens AFTER the timed sweeps — it is the
    bench's verification, not exporter cost, and must not contend with
    the dispatcher it measures), the scraped sweeps were genuinely
    scraped (>= 1 scrape per sweep), the idle sweeps genuinely were
    not, and the final scrape's counters sit within registry-snapshot
    bounds taken around it (the exporter publishes the registry, not an
    approximation).
    """
    import glob
    import subprocess
    import urllib.request

    from flink_ml_tpu import obs
    from flink_ml_tpu.api.pipeline import Pipeline
    from flink_ml_tpu.lib import LogisticRegression
    from flink_ml_tpu.lib.feature import StandardScaler
    from flink_ml_tpu.obs import telemetry
    from flink_ml_tpu.serving import ModelServer
    from flink_ml_tpu.table.schema import DataTypes, Schema
    from flink_ml_tpu.table.table import Table

    rng = np.random.RandomState(31)
    X = (2.0 * rng.randn(n_rows, n_features) + 1.0).astype(np.float32)
    true_w = (rng.randn(n_features) / np.sqrt(n_features)).astype(np.float32)
    y = ((X - 1.0) @ true_w > 0).astype(np.float64)
    t = Table.from_columns(
        Schema.of(("features", DataTypes.DENSE_VECTOR), ("label", "double")),
        {"features": X, "label": y},
    )
    model = Pipeline([
        StandardScaler().set_selected_col("features"),
        LogisticRegression().set_vector_col("features")
        .set_label_col("label").set_prediction_col("pred")
        .set_learning_rate(0.5).set_max_iter(3),
    ]).fit(t)

    sizes = rng.choice([8, 16, 32, 64], size=n_requests)
    requests, lo = [], 0
    for s in sizes:
        requests.append(t.slice_rows(lo, lo + int(s)))
        lo += int(s)

    #: the out-of-process scraper: fetch /metrics in a loop while the
    #: SCRAPE flag file exists, saving each exposition for the parent's
    #: post-hoc parse (a fetch failure saves an empty file — asserted)
    scraper_src = (
        "import os, sys, time, urllib.request\n"
        "url, outdir, interval = sys.argv[1], sys.argv[2], "
        "float(sys.argv[3])\n"
        "flag = os.path.join(outdir, 'SCRAPE')\n"
        "i = 0\n"
        "while True:\n"
        "    if os.path.exists(flag):\n"
        "        try:\n"
        "            with urllib.request.urlopen(url, timeout=10) as r:\n"
        "                text = r.read().decode()\n"
        "        except Exception:\n"
        "            text = ''\n"
        "        path = os.path.join(outdir, 'scrape-%06d.txt' % i)\n"
        "        with open(path + '.tmp', 'w') as f:\n"
        "            f.write(text)\n"
        "        os.replace(path + '.tmp', path)\n"
        "        i += 1\n"
        "    time.sleep(interval)\n"
    )
    scrape_dir = tempfile.mkdtemp(prefix="bench_telemetry_scrapes_")
    flag = os.path.join(scrape_dir, "SCRAPE")

    def scrape_files():
        return sorted(glob.glob(os.path.join(scrape_dir, "scrape-*.txt")))

    def drain_scrapes():
        """After dropping the flag, wait for QUIESCENCE — no new scrape
        for a full interval — not a fixed sleep: the scraper checks the
        flag before it fetches, so a scrape already past the check can
        land late (a stalled urlopen on a loaded machine) and poison
        the next OFF sweep's purity assert."""
        deadline = time.monotonic() + 15
        last = len(scrape_files())
        while time.monotonic() < deadline:
            time.sleep(2 * scrape_interval_s)
            n = len(scrape_files())
            if n == last:
                return
            last = n

    server = None
    endpoint = None
    scraper = None
    scrape_counts = []  # appended per timed sweep: scrapes seen during it
    try:
        server = ModelServer(model, max_batch=max_batch,
                             max_wait_ms=max_wait_ms,
                             queue_cap=4 * sum(int(s) for s in sizes))
        endpoint = telemetry.TelemetryServer(port=0).start()
        scraper = subprocess.Popen(
            [sys.executable, "-c", scraper_src, endpoint.url("/metrics"),
             scrape_dir, str(scrape_interval_s)],
        )
        # warm both paths (ladder buckets + the scrape handler's first hit)
        for fut in [server.submit(r) for r in requests[:8]]:
            fut.result(timeout=120)
        open(flag, "w").close()
        deadline = time.monotonic() + 30
        while not scrape_files() and time.monotonic() < deadline:
            time.sleep(scrape_interval_s)  # scraper subprocess is up
        assert scrape_files(), "the scraper subprocess never scraped"
        os.remove(flag)
        drain_scrapes()

        def sweep():
            t0 = time.perf_counter()
            futs = [server.submit(r) for r in requests]
            for f in futs:
                f.result(timeout=120)
            return time.perf_counter() - t0

        walls_off, walls_on = [], []
        for _ in range(sweeps):
            # interleaved idle/scraped: machine drift lands on both arms
            before = len(scrape_files())
            walls_off.append(sweep())
            assert len(scrape_files()) == before, (
                "the exporter was scraped during an OFF sweep — the off "
                "arm is not measuring an idle endpoint"
            )
            open(flag, "w").close()
            t0 = time.perf_counter()
            walls_on.append(sweep())
            # a sweep can outrun the scrape interval on a fast machine:
            # hold the arm open until at least one scrape landed in it
            while len(scrape_files()) == before and \
                    time.perf_counter() - t0 < 5.0:
                time.sleep(scrape_interval_s)
            scrape_counts.append(len(scrape_files()) - before)
            os.remove(flag)
            drain_scrapes()  # in-flight scrape lands before the next OFF arm

        # final consistency check: one scrape bounded by two snapshots
        snap_before = obs.registry().snapshot()["counters"]
        with urllib.request.urlopen(endpoint.url("/metrics"),
                                    timeout=10) as r:
            samples = telemetry.parse_openmetrics(r.read().decode())
        snap_after = obs.registry().snapshot()["counters"]
        checked = telemetry.counters_within_bounds(
            snap_before, samples, snap_after)
        stats = server.stats()
    finally:
        if scraper is not None:
            scraper.kill()
            scraper.wait()
        if endpoint is not None:
            endpoint.stop()
        if server is not None:
            server.shutdown()

    # verification AFTER the timed loop: every scrape taken during the
    # sweeps must survive the strict parser (an empty file is a failed
    # fetch — equally fatal)
    scraped_texts = [open(p).read() for p in scrape_files()]
    parse_failures = []
    for text in scraped_texts:
        try:
            telemetry.parse_openmetrics(text)
        except ValueError as exc:
            parse_failures.append(str(exc))
    assert not parse_failures, (
        f"{len(parse_failures)} of {len(scraped_texts)} scrapes failed "
        f"the strict OpenMetrics parser: {parse_failures[:3]}"
    )
    assert all(c >= 1 for c in scrape_counts), (
        f"scraped sweeps saw scrape counts {scrape_counts} — the on arm "
        "was not actually being scraped"
    )
    assert checked >= 5, f"only {checked} counters cross-checked"
    # min-of-sweeps: overhead noise is strictly additive (the
    # trace_overhead rule), so each arm's best sweep is its cleanest
    off_s = float(np.min(walls_off))
    on_s = float(np.min(walls_on))
    return _emit({
        "metric": "ModelServer.serve telemetry_on_over_off",
        "value": round(on_s / off_s, 4),
        "unit": "ratio (lower is better)",
        "off_ms": round(off_s * 1e3, 1),
        "on_scraped_ms": round(on_s * 1e3, 1),
        "scrapes_in_timed_sweeps": int(sum(scrape_counts)),
        "scrapes_parsed": len(scraped_texts),
        "scrape_interval_ms": scrape_interval_s * 1e3,
        "counters_cross_checked": int(checked),
        "latency_p99_ms": stats.get("latency_p99_ms"),
        "parse_failures": 0,  # asserted above
        "shape": f"{n_requests} mixed-size (8-64 row) requests x "
                 f"{n_features} features x {sweeps} interleaved "
                 f"idle/scraped sweeps, max_batch={max_batch}, "
                 f"~{1 / scrape_interval_s:.0f} Hz scrape loop, "
                 "min-of-sweeps",
    })


def bench_drift(n_rows=16_384, n_features=256, n_requests=256,
                sweeps=7, max_batch=512, max_wait_ms=2.0):
    """Armed drift-monitoring overhead on the serving path (ISSUE 11).

    The data-plane contract: a DriftMonitor with a frozen reference,
    sketching coalesced batches' feature and score columns on the live
    window, must cost <= 2% of serving throughput — the sketch update
    is one vectorized pass over the capped columns of rows already on
    host.  This sweep runs the SAME mixed-size request load through one
    ModelServer with its monitor detached (the off arm) and reattached
    with the reference already complete (the armed steady state — not
    reference filling) — interleaved off/on per sweep, and emits
    ``drift_on_over_off`` = armed wall / off wall, the lower-is-better
    ratio BASELINE.json gates at <= 1.02.

    Steady state includes the per-window row cap
    (``FMT_DRIFT_WINDOW_ROWS``): the monitor sketches each window's
    sample budget, then counts rows until rotation — sketching every
    row of a saturated server buys no statistical signal for real
    hot-path cost, so the armed arm measures exactly what a loaded
    production server pays.

    One server serves BOTH arms (the monitor detaches for the off
    sweeps and reattaches for the armed ones): every tap already keys
    off the server's monitor reference, so a detached monitor IS the
    drift-off configuration — and a single dispatcher thread over the
    same compiled programs removes the cross-server-instance variance
    that would otherwise dwarf a 2% contract.

    Asserted inside the bench, never just recorded: the OFF sweeps
    perform ZERO sketch updates and ZERO skip-counts (the one-bool
    disabled contract, structurally — no drift activity of any kind),
    the armed arm genuinely sketched its window sample AND genuinely
    hit the cap (both regimes exercised), every served row is accounted
    sketched-or-skipped, and the armed monitor's reference froze BEFORE
    the timed loop.
    """
    from flink_ml_tpu.api.pipeline import Pipeline
    from flink_ml_tpu.lib import LogisticRegression
    from flink_ml_tpu.lib.feature import StandardScaler
    from flink_ml_tpu.serving import ModelServer
    from flink_ml_tpu.table.schema import DataTypes, Schema
    from flink_ml_tpu.table.table import Table

    rng = np.random.RandomState(37)
    X = (2.0 * rng.randn(n_rows, n_features) + 1.0).astype(np.float32)
    true_w = (rng.randn(n_features) / np.sqrt(n_features)).astype(np.float32)
    y = ((X - 1.0) @ true_w > 0).astype(np.float64)
    t = Table.from_columns(
        Schema.of(("features", DataTypes.DENSE_VECTOR), ("label", "double")),
        {"features": X, "label": y},
    )
    model = Pipeline([
        StandardScaler().set_selected_col("features"),
        LogisticRegression().set_vector_col("features")
        .set_label_col("label").set_prediction_col("pred")
        .set_learning_rate(0.5).set_max_iter(3),
    ]).fit(t)

    sizes = rng.choice([8, 16, 32, 64], size=n_requests)
    requests, lo = [], 0
    for s in sizes:
        requests.append(t.slice_rows(lo, lo + int(s)))
        lo += int(s)

    ref_rows = 512
    prev_ref = os.environ.get("FMT_DRIFT_REF_ROWS")
    os.environ["FMT_DRIFT_REF_ROWS"] = str(ref_rows)
    server_on = None
    reg = None
    try:
        from flink_ml_tpu import obs

        reg = obs.registry()
        queue_cap = 4 * sum(int(s) for s in sizes)
        server_on = ModelServer(model, drift=True, max_batch=max_batch,
                                max_wait_ms=max_wait_ms,
                                queue_cap=queue_cap)
        monitor = server_on.drift_monitor
        # warm the serving path AND freeze the monitor's reference: the
        # timed arm must measure steady-state live sketching, not the
        # one-time reference fill
        served = 0
        i = 0
        while not monitor.reference_complete:
            r = requests[i % len(requests)]
            server_on.submit(r).result(timeout=120)
            served += r.num_rows()
            i += 1
            assert served < 64 * ref_rows, (
                "drift reference never froze during warmup"
            )

        def sweep():
            t0 = time.perf_counter()
            futs = [server_on.submit(r) for r in requests]
            for f in futs:
                f.result(timeout=120)
            return time.perf_counter() - t0

        def drift_activity():
            return (reg.counter("drift.sketch_updates"),
                    reg.counter("drift.rows"),
                    reg.counter("drift.rows_skipped"))

        arm_start = drift_activity()
        walls_off, walls_on = [], []
        for _ in range(sweeps):
            # interleaved off/on through ONE server: the monitor
            # detaches for the off sweep (every tap keys off this
            # reference — detached IS the drift-off configuration)
            server_on._drift = None
            before = drift_activity()
            walls_off.append(sweep())
            assert drift_activity() == before, (
                "drift activity recorded while the monitor was "
                "detached — a tap is not reducing to its one-bool/"
                "scope check"
            )
            server_on._drift = monitor
            walls_on.append(sweep())
        updates, rows_sketched, rows_skipped = (
            a - b for a, b in zip(drift_activity(), arm_start)
        )
        served_rows = sweeps * sum(int(s) for s in sizes)
        assert updates > 0, (
            "the armed arm performed no sketch updates — it never "
            "filled a live window sample"
        )
        assert rows_skipped > 0, (
            "the armed arm never hit the per-window row cap — the "
            "sweep is not measuring the capped steady state"
        )
        assert rows_sketched + rows_skipped >= served_rows, (
            f"row accounting leak: {rows_sketched} sketched + "
            f"{rows_skipped} skipped < {served_rows} served"
        )
        section = monitor.report_section()
        stats = server_on.stats()
    finally:
        if server_on is not None:
            server_on.shutdown()
        if prev_ref is None:
            os.environ.pop("FMT_DRIFT_REF_ROWS", None)
        else:
            os.environ["FMT_DRIFT_REF_ROWS"] = prev_ref

    # min-of-sweeps: overhead noise is strictly additive (the
    # trace_overhead rule), so each arm's best sweep is its cleanest
    off_s = float(np.min(walls_off))
    on_s = float(np.min(walls_on))
    n_cols = len(section.get("columns") or [])
    assert n_cols > 0, "armed monitor compared zero columns"
    return _emit({
        "metric": "ModelServer.serve drift_on_over_off",
        "value": round(on_s / off_s, 4),
        "unit": "ratio (lower is better)",
        "off_ms": round(off_s * 1e3, 1),
        "on_armed_ms": round(on_s * 1e3, 1),
        "columns_compared": n_cols,
        "worst_psi": (section["columns"][0]["psi"]
                      if section.get("columns") else None),
        "reference_rows": ref_rows,
        "latency_p99_ms": stats.get("latency_p99_ms"),
        "off_sweeps_zero_updates": True,  # asserted above
        "shape": f"{n_requests} mixed-size (8-64 row) requests x "
                 f"{n_features} features x {sweeps} interleaved off/on "
                 f"sweeps, max_batch={max_batch}, ref={ref_rows} rows, "
                 "16-col sketch cap, min-of-sweeps",
    })


def bench_pressure(n_rows=100_000, n_features=16, batch=4096, sweeps=5):
    """Memory-pressure resilience sweep (ISSUE 9): the 2-stage serving
    chain (StandardScaler -> LogisticRegression score) measured in three
    regimes —

    * **unpressured**: the pressure layer armed but quiet (the normal
      hot path);
    * **pressured**: a deterministic ``fault.oom>batch/4`` HBM ceiling —
      the fused plan must bisect, converge, and serve BIT-IDENTICAL
      predictions (asserted, never just recorded);
    * **recovered**: the ceiling lifts, the AIMD probe restores the full
      batch, and the steady wall is re-measured with ZERO further
      bisections (asserted).

    Emits two lower-is-better ratios BASELINE.json gates: the headline
    ``pressure_recovered_over_unpressured`` (contract <= 2.0 — recovered
    throughput must stay >= 0.5x the unpressured rate, i.e. pressure
    state must actually clear instead of pinning the plan at half
    batches forever) and ``pressure_on_over_off`` (interleaved
    ``FMT_PRESSURE`` off/on sweeps, min-of-sweeps — the <= 2%
    disabled-overhead contract every resilience layer in this repo rides).
    """
    import warnings

    from flink_ml_tpu import fault, obs
    from flink_ml_tpu.api.pipeline import Pipeline
    from flink_ml_tpu.fault import pressure
    from flink_ml_tpu.lib import LogisticRegression
    from flink_ml_tpu.lib.feature import StandardScaler
    from flink_ml_tpu.table.schema import DataTypes, Schema
    from flink_ml_tpu.table.table import Table
    from flink_ml_tpu.utils.environment import MLEnvironmentFactory

    rng = np.random.RandomState(31)
    X = (2.0 * rng.randn(n_rows, n_features) + 1.0).astype(np.float32)
    true_w = (rng.randn(n_features) / np.sqrt(n_features)).astype(np.float32)
    y = ((X - 1.0) @ true_w > 0).astype(np.float64)
    t = Table.from_columns(
        Schema.of(("features", DataTypes.DENSE_VECTOR), ("label", "double")),
        {"features": X, "label": y},
    )
    model = Pipeline([
        StandardScaler().set_selected_col("features"),
        LogisticRegression().set_vector_col("features")
        .set_label_col("label").set_prediction_col("pred")
        .set_learning_rate(0.5).set_max_iter(5),
    ]).fit(t)

    env = MLEnvironmentFactory.get_default()
    old_bs, env.default_batch_size = env.default_batch_size, batch
    old_knob = os.environ.get("FMT_PRESSURE")
    old_probe = os.environ.get("FMT_PRESSURE_PROBE_S")
    ceiling = batch // 4

    def one_wall():
        t0 = time.perf_counter()
        (out,) = model.transform(t)
        return time.perf_counter() - t0, out

    try:
        pressure.reset_states()
        (ref_out,) = model.transform(t)  # warmup: compile every bucket
        ref_pred = np.asarray(ref_out.col("pred"))

        # disabled-overhead arms, interleaved so drift lands on both
        walls_off, walls_on = [], []
        for _ in range(sweeps):
            os.environ["FMT_PRESSURE"] = "0"
            walls_off.append(one_wall()[0])
            os.environ["FMT_PRESSURE"] = "1"
            walls_on.append(one_wall()[0])
        off_s, on_s = float(np.min(walls_off)), float(np.min(walls_on))
        unpressured_s = float(np.median(walls_on))

        # the injected ceiling: bisection must converge with exact parity
        obs.reset()
        fault.configure(f"fault.oom>{ceiling}")
        try:
            with warnings.catch_warnings():
                warnings.simplefilter("ignore", RuntimeWarning)
                pressured_s, p_out = one_wall()
        finally:
            fault.configure(None)
        counters = obs.registry().snapshot()["counters"]
        n_bisections = counters.get("pressure.bisections", 0)
        assert n_bisections >= 1, counters
        assert np.array_equal(np.asarray(p_out.col("pred")), ref_pred), (
            "pressured predictions diverge from the unpressured run"
        )

        # recovery: AIMD probes back to the full batch, then re-measure
        os.environ["FMT_PRESSURE_PROBE_S"] = "0"
        deadline = time.time() + 120
        while any(
            pressure.state(name).cap is not None
            for name in list(pressure._STATES)
        ):
            assert time.time() < deadline, "AIMD never cleared the caps"
            model.transform(t)
        if old_probe is None:
            os.environ.pop("FMT_PRESSURE_PROBE_S", None)
        else:
            os.environ["FMT_PRESSURE_PROBE_S"] = old_probe
        bisections_before = obs.registry().snapshot()["counters"].get(
            "pressure.bisections", 0)
        walls_rec = []
        for _ in range(sweeps):
            w, rec_out = one_wall()
            walls_rec.append(w)
        recovered_s = float(np.median(walls_rec))
        assert obs.registry().snapshot()["counters"].get(
            "pressure.bisections", 0) == bisections_before, (
            "recovered transforms still bisecting — AIMD did not restore "
            "the full batch"
        )
        assert np.array_equal(np.asarray(rec_out.col("pred")), ref_pred)
    finally:
        fault.configure(None)
        env.default_batch_size = old_bs
        for name, old in (("FMT_PRESSURE", old_knob),
                          ("FMT_PRESSURE_PROBE_S", old_probe)):
            if old is None:
                os.environ.pop(name, None)
            else:
                os.environ[name] = old

    _emit({
        "metric": "PipelineModel.transform pressure_on_over_off",
        "value": round(on_s / off_s, 4),
        "unit": "ratio (lower is better)",
        "off_ms": round(off_s * 1e3, 1),
        "on_ms": round(on_s * 1e3, 1),
        "shape": f"{n_rows}x{n_features} f32, 2 stages, batch={batch}, "
                 f"{sweeps} interleaved off/on sweeps, min-of-sweeps",
    })
    return _emit({
        "metric": "PipelineModel.transform pressure_recovered_over_unpressured",
        "value": round(recovered_s / unpressured_s, 4),
        "unit": "ratio (lower is better)",
        "unpressured_ms": round(unpressured_s * 1e3, 1),
        "pressured_ms": round(pressured_s * 1e3, 1),
        "recovered_ms": round(recovered_s * 1e3, 1),
        "unpressured_rows_per_sec": round(n_rows / unpressured_s, 1),
        "recovered_rows_per_sec": round(n_rows / recovered_s, 1),
        "ceiling_rows": ceiling,
        "bisections_under_ceiling": int(n_bisections),
        "pred_parity": True,  # asserted above — reaching here proves it
        "shape": f"{n_rows}x{n_features} f32, 2 stages "
                 f"(scaler->LR score), batch={batch}, ceiling={ceiling} "
                 f"rows, median of {sweeps}",
    })


def bench_online_loop(n_rows=16_384, n_features=16, n_requests=192,
                      sweeps=5, max_batch=256, max_wait_ms=2.0):
    """Controller-attached serving overhead (ISSUE 14).

    The continuous-learning contract: a ``ContinuousLearningController``
    attached to a live ``ModelServer`` — window hook armed, probation
    watcher polling, stream checkpointing configured — must not slow the
    traffic it retrains behind.  This sweep serves the SAME mixed-size
    request load through one server with no controller (the off arm) and
    with the controller attached in its steady state (the on arm): the
    online fitter has proven itself live (windows trained before the
    timed phase), then sits blocked on its label stream — the shape of a
    production loop between label-arrival bursts, and the only regime a
    single-core container can measure honestly (concurrent SGD steps
    would measure CPU contention, not the controller's attachment cost).
    Emits ``online_loop_on_over_off`` = attached wall / off wall, the
    lower-is-better ratio BASELINE.json gates at <= 1.05.

    The off baseline is a SANDWICH (off sweeps before attach, off sweeps
    after the controller fully detaches), interpolated: an obs-enabled
    process slows a few percent per sweep-phase over its lifetime on
    this container (environmental, controller-independent — the
    interleaved off/on benches cancel it pairwise), and attachment being
    one-way means the attached arm always runs later; comparing it
    against the MIDPOINT of the two off phases cancels the linear drift
    the attach ordering would otherwise charge to the controller.

    Asserted inside the bench, never just recorded: per-request
    predictions bit-identical to solo transforms on the attached arm (no
    deploy lands inside the timed phase), zero failed requests, the
    trainer genuinely trained windows before the timed phase, and —
    between the attached and trailing-off phases — feeding more label
    chunks drives a VALIDATED candidate through the gate and swaps it
    under the same server (the loop the overhead is buying actually
    closes).
    """
    from flink_ml_tpu.lib import LogisticRegression
    from flink_ml_tpu.lib.online import OnlineLogisticRegression
    from flink_ml_tpu.serving import (
        ContinuousLearningController,
        ModelServer,
    )
    from flink_ml_tpu.table.schema import DataTypes, Schema
    from flink_ml_tpu.table.sources import QueueUnboundedSource
    from flink_ml_tpu.table.table import Table

    schema = Schema.of(("features", DataTypes.DENSE_VECTOR),
                       ("label", "double"))
    rng = np.random.RandomState(41)
    true_w = (rng.randn(n_features) / np.sqrt(n_features)).astype(
        np.float32)
    X = (2.0 * rng.randn(n_rows, n_features) + 1.0).astype(np.float32)
    y = ((X - 1.0) @ true_w > 0).astype(np.float64)
    t = Table.from_columns(schema, {"features": X, "label": y})
    model = (
        LogisticRegression().set_vector_col("features")
        .set_label_col("label").set_prediction_col("pred")
        .set_learning_rate(0.5).set_max_iter(3).fit(t)
    )

    sizes = rng.choice([8, 16, 32, 64], size=n_requests)
    requests, lo = [], 0
    for s in sizes:
        requests.append(t.slice_rows(lo, lo + int(s)))
        lo += int(s)
    solo = {}
    for i, req in enumerate(requests):
        (out,) = model.transform(req)
        solo[i] = np.asarray(out.col("pred"))

    def chunk(n=100, seed_off=0):
        """One label-stream chunk as the fed columns dict."""
        r = np.random.RandomState(43 + seed_off)
        Xc = (2.0 * r.randn(n, n_features) + 1.0).astype(np.float32)
        yc = ((Xc - 1.0) @ true_w > 0).astype(np.float64)
        return {"features": Xc, "label": yc}

    server = None
    controller = None
    # blocked get between feeds: the parked trainer costs zero CPU
    source = QueueUnboundedSource(schema)
    try:
        server = ModelServer(model, max_batch=max_batch,
                             max_wait_ms=max_wait_ms,
                             queue_cap=4 * int(sizes.sum()),
                             warmup=t.slice_rows(0, 8))
        for fut in [server.submit(r) for r in requests[:8]]:
            fut.result(timeout=120)  # ladder warmup

        def sweep():
            t0 = time.perf_counter()
            futs = [server.submit(r) for r in requests]
            results = [f.result(timeout=120) for f in futs]
            return time.perf_counter() - t0, results

        # each arm gets unmeasured warm-up sweeps IMMEDIATELY before its
        # timed ones: sweeps that follow idle time (the ladder warmup
        # here, the trainer feed-and-park below) run measurably slower on
        # a scheduler that just parked the process, and that cost belongs
        # to neither arm
        sweep(), sweep()
        walls_off = []
        for _ in range(sweeps):
            w, results = sweep()
            walls_off.append(w)

        # attach the controller; prove the trainer live, then let it
        # block on the drained label queue for the timed on-arm.
        # candidate_every=5 with only 4 windows fired keeps deploys out
        # of the timed phase (same compiled programs on both arms).
        est = (
            OnlineLogisticRegression().set_vector_col("features")
            .set_label_col("label").set_prediction_col("pred")
            .set_learning_rate(0.5).set_window_ms(1000)
        )
        controller = ContinuousLearningController(
            est, source, t.slice_rows(0, 512), server=server,
            candidate_every=5,
        )
        controller.start()
        source.feed(chunk())  # 100 rows x 50ms -> 4 fired windows
        deadline = time.monotonic() + 120
        while controller.windows < 4 and time.monotonic() < deadline:
            time.sleep(0.01)
        assert controller.windows >= 4, "the attached trainer never trained"
        time.sleep(0.1)  # drain: trainer parks on the empty label queue

        sweep(), sweep()  # the attached arm's own warm-up (see above)
        walls_on = []
        for _ in range(sweeps):
            w, results = sweep()
            walls_on.append(w)
        assert server.active_version == "v1", (
            "a deploy landed inside the timed phase")
        for i, res in enumerate(results):
            np.testing.assert_array_equal(
                np.asarray(res.table.col("pred")), solo[i],
                err_msg=f"request {i}: attached-arm prediction diverges",
            )

        # the loop the overhead buys must actually close: more labels ->
        # a gated candidate -> a zero-downtime swap on this same server
        for k in range(1, 4):
            source.feed(chunk(seed_off=k))
        source.close()
        controller.join(timeout=240)
        stats = controller.stats()
        assert stats.get("lifecycle.swaps", 0) >= 1, stats
        assert server.active_version.startswith("cl-"), (
            server.active_version)
        server_stats = server.stats()
        assert server_stats.get("serving.failed_requests", 0) == 0

        # the trailing off arm: the controller is fully inert (trainer
        # thread exited at stream end, probation watcher stopped) — the
        # same serving pipeline shapes on the swapped version
        controller.stop()
        sweep(), sweep()
        walls_off2 = []
        for _ in range(sweeps):
            w, _ = sweep()
            walls_off2.append(w)
    finally:
        if controller is not None:
            controller.stop()
        else:
            source.close()
        if server is not None:
            server.shutdown()

    # min-of-sweeps per phase (additive-noise convention), then the
    # sandwich midpoint as the drift-cancelled off baseline
    off1_s = float(np.min(walls_off))
    off2_s = float(np.min(walls_off2))
    on_s = float(np.min(walls_on))
    off_s = 0.5 * (off1_s + off2_s)
    return _emit({
        "metric": "ModelServer.serve online_loop_on_over_off",
        "value": round(on_s / off_s, 4),
        "unit": "ratio (lower is better)",
        "off_ms": round(off_s * 1e3, 1),
        "off_before_ms": round(off1_s * 1e3, 1),
        "off_after_ms": round(off2_s * 1e3, 1),
        "attached_ms": round(on_s * 1e3, 1),
        "windows_trained": int(stats["windows"]),
        "candidates": int(stats.get("lifecycle.candidates", 0)),
        "swaps": int(stats.get("lifecycle.swaps", 0)),
        "pred_parity": True,  # asserted above — reaching here proves it
        "shape": f"{n_requests} mixed-size (8-64 row) requests x "
                 f"{n_features} features x {sweeps} off/attached/off "
                 f"sweeps, max_batch={max_batch}, trainer parked between "
                 "label bursts, min-of-sweeps vs sandwich-midpoint "
                 "baseline",
    })


def bench_router(n_train=8192, n_features=256, n_requests=32,
                 req_rows=128, sweeps=3, k=5):
    """Replica-router overhead + scale-out sweep (ISSUE 13).

    The scale-out contract: fronting a ``ModelServer`` with the replica
    router (wire serialization, HTTP forwarding, health-aware balancing,
    one subprocess boundary) must cost <= 25% of throughput on a
    compute-bound request load — and a second replica must buy real
    parallelism on multi-core hosts.  The workload is a Knn scan
    (``n_train`` references x ``n_features`` dims, k=``k``) over
    ``req_rows``-row requests: per-request device compute in the tens of
    milliseconds against ~wire overhead in the hundreds of microseconds,
    the regime a scale-out front-end exists for (a router is not the
    tool for sub-millisecond requests — the in-process server is).

    Emits ``router_over_direct`` (1-replica router wall / in-process
    ``ModelServer`` wall, lower is better) — the BASELINE.json <= 1.25
    contract gate — and publishes ``router_scaling_2x`` (2-replica
    throughput / 1-replica; informational: this container may expose a
    single core, where two replica processes cannot beat one).  Asserted
    inside the bench, never just recorded: every routed request's
    predictions are BIT-IDENTICAL to a solo ``transform`` of its rows,
    on both router arms.
    """
    from flink_ml_tpu.lib import Knn
    from flink_ml_tpu.serving import ModelServer, ReplicaRouter
    from flink_ml_tpu.table.schema import DataTypes, Schema
    from flink_ml_tpu.table.table import Table

    rng = np.random.RandomState(37)
    Xtr = rng.randn(n_train, n_features).astype(np.float32)
    ytr = rng.randint(0, 10, size=n_train).astype(np.float64)
    train = Table.from_columns(
        Schema.of(("features", DataTypes.DENSE_VECTOR), ("label", "double")),
        {"features": Xtr, "label": ytr},
    )
    Xq = rng.randn(n_requests * req_rows, n_features).astype(np.float32)
    queries = Table.from_columns(
        Schema.of(("features", DataTypes.DENSE_VECTOR)), {"features": Xq}
    )
    model = (
        Knn().set_vector_col("features").set_label_col("label")
        .set_k(k).set_prediction_col("pred").fit(train)
    )
    model_dir = os.path.join(
        tempfile.mkdtemp(prefix="bench_router_"), "knn")
    model.save(model_dir)

    requests = [queries.slice_rows(i * req_rows, (i + 1) * req_rows)
                for i in range(n_requests)]
    solo = []
    for req in requests:
        (out,) = model.transform(req)
        solo.append(np.asarray(out.col("pred")))

    def sweep_walls(submit):
        """Median wall over ``sweeps`` rounds of the full request set
        (submitted async, gathered at the end), with per-request parity
        asserted on the last round."""
        walls = []
        for _ in range(sweeps):
            t0 = time.perf_counter()
            futures = [submit(req) for req in requests]
            results = [f.result(300) for f in futures]
            walls.append(time.perf_counter() - t0)
        for i, res in enumerate(results):
            np.testing.assert_array_equal(
                np.asarray(res.table.col("pred")), solo[i],
                err_msg=f"request {i}: routed prediction diverges from "
                        "solo transform",
            )
        return float(np.median(walls))

    total_rows = n_requests * req_rows

    # -- direct arm: the in-process ModelServer ------------------------------
    server = ModelServer(path=model_dir, version="v1", max_wait_ms=2.0)
    try:
        for fut in [server.submit(r) for r in requests[:2]]:
            fut.result(300)  # warm the serving path + ladder buckets
        direct_s = sweep_walls(server.submit)
    finally:
        server.shutdown()

    # -- router arms: 1 replica (overhead), 2 replicas (scaling) ------------
    router_s = {}
    for n_replicas in (1, 2):
        router = ReplicaRouter(model_dir, version="v1",
                               replicas=n_replicas, poll_ms=500.0,
                               dispatch_threads=8)
        try:
            assert router.ready_count() == n_replicas, router.replicas
            for fut in [router.submit(r) for r in requests[:2]]:
                fut.result(300)  # warm every replica's serving path
            if n_replicas == 2:
                for fut in [router.submit(r) for r in requests[:8]]:
                    fut.result(300)  # both replicas compile their plans
            router_s[n_replicas] = sweep_walls(router.submit)
            stats = router.stats()
            assert not stats.get("router.failed_requests"), stats
        finally:
            router.shutdown()

    over_direct = router_s[1] / direct_s
    scaling_2x = router_s[1] / router_s[2]
    return _emit({
        "metric": "ReplicaRouter.serve router_over_direct",
        "value": round(over_direct, 4),
        "unit": "ratio (lower is better)",
        "direct_ms": round(direct_s * 1e3, 1),
        "router1_ms": round(router_s[1] * 1e3, 1),
        "router2_ms": round(router_s[2] * 1e3, 1),
        "router_scaling_2x": round(scaling_2x, 4),
        "direct_rows_per_sec": round(total_rows / direct_s, 1),
        "router1_rows_per_sec": round(total_rows / router_s[1], 1),
        "router2_rows_per_sec": round(total_rows / router_s[2], 1),
        "pred_parity": True,  # asserted in every arm — reaching here proves it
        "shape": f"{n_requests} x {req_rows}-row Knn requests "
                 f"({n_train} refs x {n_features} dims, k={k}), "
                 f"median of {sweeps}",
    })


def bench_autoscale(n_train=8192, n_features=256, n_requests=32,
                    req_rows=128, sweeps=3, k=5):
    """Autoscaler idle-controller overhead (ISSUE 19).

    The elastic control loop must be FREE when the fleet is stable: a
    ``FleetAutoscaler`` pinned to ``min == max == 1`` observes every
    tick (one ``fleet_health`` sample — the liveness sweep + replica
    snapshots + door tallies) but can never act, so any throughput
    delta against the identical detached router IS the control loop's
    cost.  Same compute-bound Knn request load as ``bench_router``, on
    ONE router instance with the arms interleaved (off, on, off, on...)
    so host drift hits both equally; min-of-sweeps per arm.

    Emits ``autoscale_on_over_off`` (attached wall / detached wall,
    lower is better) — the BASELINE.json <= 1.05 contract gate.
    Asserted inside the bench: the stable fleet saw ZERO scale events
    (a controller that flaps a pinned fleet is broken regardless of
    overhead), and every routed prediction is bit-identical to a solo
    transform on both arms.
    """
    from flink_ml_tpu.lib import Knn
    from flink_ml_tpu.serving import FleetAutoscaler, ReplicaRouter
    from flink_ml_tpu.table.schema import DataTypes, Schema
    from flink_ml_tpu.table.table import Table

    rng = np.random.RandomState(41)
    Xtr = rng.randn(n_train, n_features).astype(np.float32)
    ytr = rng.randint(0, 10, size=n_train).astype(np.float64)
    train = Table.from_columns(
        Schema.of(("features", DataTypes.DENSE_VECTOR), ("label", "double")),
        {"features": Xtr, "label": ytr},
    )
    Xq = rng.randn(n_requests * req_rows, n_features).astype(np.float32)
    queries = Table.from_columns(
        Schema.of(("features", DataTypes.DENSE_VECTOR)), {"features": Xq}
    )
    model = (
        Knn().set_vector_col("features").set_label_col("label")
        .set_k(k).set_prediction_col("pred").fit(train)
    )
    model_dir = os.path.join(
        tempfile.mkdtemp(prefix="bench_autoscale_"), "knn")
    model.save(model_dir)
    requests = [queries.slice_rows(i * req_rows, (i + 1) * req_rows)
                for i in range(n_requests)]
    solo = []
    for req in requests:
        (out,) = model.transform(req)
        solo.append(np.asarray(out.col("pred")))

    def sweep_wall(router):
        t0 = time.perf_counter()
        futures = [router.submit(req) for req in requests]
        results = [f.result(300) for f in futures]
        wall = time.perf_counter() - t0
        for i, res in enumerate(results):
            np.testing.assert_array_equal(
                np.asarray(res.table.col("pred")), solo[i],
                err_msg=f"request {i}: routed prediction diverges from "
                        "solo transform",
            )
        return wall

    router = ReplicaRouter(model_dir, version="v1", replicas=1,
                           poll_ms=500.0, dispatch_threads=8)
    off_walls, on_walls = [], []
    try:
        for fut in [router.submit(r) for r in requests[:2]]:
            fut.result(300)  # warm the serving path + ladder buckets
        for _ in range(sweeps):
            off_walls.append(sweep_wall(router))
            scaler = FleetAutoscaler(
                router, min_replicas=1, max_replicas=1, window_s=1.0,
                idle_windows=3, cooldown_s=60.0, tick_s=0.05,
            ).start()
            try:
                on_walls.append(sweep_wall(router))
                sstats = scaler.stats()
                assert (sstats["scale_ups"] == 0
                        and sstats["scale_downs"] == 0), (
                    f"the pinned fleet flapped: {sstats}")
            finally:
                scaler.stop()
        assert router.fleet_size() == 1, router.replicas
        stats = router.stats()
        assert not stats.get("router.failed_requests"), stats
    finally:
        router.shutdown()

    total_rows = n_requests * req_rows
    off_s, on_s = min(off_walls), min(on_walls)
    ratio = on_s / off_s
    return _emit({
        "metric": "ReplicaRouter.serve autoscale_on_over_off",
        "value": round(ratio, 4),
        "unit": "ratio (lower is better)",
        "off_ms": round(off_s * 1e3, 1),
        "on_ms": round(on_s * 1e3, 1),
        "off_rows_per_sec": round(total_rows / off_s, 1),
        "on_rows_per_sec": round(total_rows / on_s, 1),
        "scale_events": 0,  # asserted per on-arm sweep above
        "pred_parity": True,  # asserted in every sweep on both arms
        "shape": f"{n_requests} x {req_rows}-row Knn requests "
                 f"({n_train} refs x {n_features} dims, k={k}), "
                 f"1 replica, 20 Hz control ticks, min of {sweeps}",
    })


def _multichip_tables(n_rows: int, n_features: int):
    """Deterministic serving tables shared by the parent (model fitting)
    and every serve_multichip worker (identical bytes per device count)."""
    from flink_ml_tpu.table.schema import DataTypes, Schema
    from flink_ml_tpu.table.table import Table

    rng = np.random.RandomState(23)
    X = (2.0 * rng.randn(n_rows, n_features) + 3.0).astype(np.float32)
    true_w = (rng.randn(n_features)
              / np.sqrt(n_features)).astype(np.float32)
    y = ((X - 3.0) @ true_w > 0).astype(np.float64)
    dense = Table.from_columns(
        Schema.of(("features", DataTypes.DENSE_VECTOR),
                  ("label", "double")),
        {"features": X, "label": y},
    )
    cats = [
        [f"v{rng.randint(12)}" for _ in range(n_rows)] for _c in range(3)
    ]
    y2 = (np.asarray([c == "v0" for c in cats[0]])
          | (X[:, 0] > 4.0)).astype(np.float64)
    cat = Table.from_columns(
        Schema.of(("c1", "string"), ("c2", "string"), ("c3", "string"),
                  ("label", "double")),
        {"c1": cats[0], "c2": cats[1], "c3": cats[2], "label": y2},
    )
    return dense, cat


def _serve_multichip_worker(n_dev: int, model_dir: str, out_path: str,
                            n_rows: int, n_features: int, batch: int,
                            sweeps: int) -> None:
    """One device-count arm of ``bench_serve_multichip`` — runs in a
    subprocess pinned to the CPU backend whose env already forced ``n_dev``
    virtual host devices."""
    import warnings

    import jax

    jax.config.update("jax_platforms", "cpu")
    assert jax.device_count() == n_dev, (jax.device_count(), n_dev)
    from flink_ml_tpu import obs
    from flink_ml_tpu.api.pipeline import PipelineModel
    from flink_ml_tpu.utils.environment import MLEnvironmentFactory

    dense, cat = _multichip_tables(n_rows, n_features)
    env = MLEnvironmentFactory.get_default()
    env.default_batch_size = batch
    obs.enable()
    result = {"devices": n_dev}
    with warnings.catch_warnings():
        warnings.simplefilter("ignore", UserWarning)
        for name, table, pred_col, float_col in (
            ("dense", dense, "pred", "proba"),
            ("csr", cat, "pred", None),
        ):
            model = PipelineModel.load(os.path.join(model_dir, name))
            model.transform(table)  # warmup: compile every batch bucket
            obs.reset()
            walls = []
            for _ in range(sweeps):
                t0 = time.perf_counter()
                (out,) = model.transform(table)
                walls.append(time.perf_counter() - t0)
            counters = obs.registry().snapshot()["counters"]
            n_batches = -(-n_rows // batch)
            per_transform = (
                counters.get("pipeline.fused_dispatches", 0) / sweeps
            )
            assert per_transform == n_batches, (
                f"{name}: {per_transform} fused dispatches per transform, "
                f"expected exactly {n_batches} (one per batch)")
            sharded = counters.get("fused.shard_map_dispatches", 0)
            if n_dev > 1:
                # the bypass detector: EVERY dispatch — the segment-CSR
                # plan included — must have taken the shard_map path
                assert sharded == counters.get(
                    "pipeline.fused_dispatches"), (name, counters)
            else:
                assert sharded == 0, (name, counters)
            assert not counters.get("pipeline.plan_fallback_batches"), (
                name, counters)
            rec = {
                "wall_s": float(np.median(walls)),
                "pred": np.asarray(out.col(pred_col)).tolist(),
                "shard_map_dispatches": sharded,
            }
            if float_col is not None:
                rec["proba"] = np.round(
                    np.asarray(out.col(float_col), dtype=np.float64), 7
                ).tolist()
            result[name] = rec
    with open(out_path, "w") as f:
        json.dump(result, f)


def bench_serve_multichip(n_rows=65_536, n_features=16, batch=4096,
                          sweeps=3, device_counts=(1, 2, 4, 8)):
    """SPMD serving PARITY sweep over 1-8 VIRTUAL CPU devices (ISSUE 15) —
    a check that the sharded program computes what the unsharded one does,
    not a chip measurement: every worker is pinned to ``JAX_PLATFORMS=cpu``
    and the record says ``"backend": "cpu"``.

    The parent fits two pipelines ONCE — a 3-stage dense chain
    (scaler -> scaler -> LR score) and a categorical segment-CSR chain
    (StringIndexer -> OneHotEncoder -> sparse LR) — saves them, and
    launches one subprocess per device count under
    ``XLA_FLAGS=--xla_force_host_platform_device_count=N``.  Each worker
    loads the SAME model bytes, transforms the SAME tables, and asserts
    in-process: exactly ONE fused dispatch per batch, and (on a
    multi-device mesh) EVERY dispatch through the shard_map path — the
    segment-CSR plan no longer takes the single-device bypass.

    The parent gates exact prediction parity across every device count
    (discrete bit-identical, float scores within 1e-5) and emits
    ``serve_multichip_over_single`` (8-device wall / 1-device wall,
    lower is better) as the BASELINE.json contract gate.  The gate bound
    is GENEROUS by design: forced-host "devices" are virtual slices of the
    host's cores, so the 8-way arm pays partitioning overhead with no real
    parallelism.  How serving scales over real chips is not measured here
    (ROADMAP S7); the per-device-count curve is published informationally,
    never gated.
    """
    import shutil
    import subprocess

    from flink_ml_tpu.api.pipeline import Pipeline
    from flink_ml_tpu.lib import LogisticRegression
    from flink_ml_tpu.lib.encoding import OneHotEncoder, StringIndexer
    from flink_ml_tpu.lib.feature import MinMaxScaler, StandardScaler

    dense, cat = _multichip_tables(n_rows, n_features)
    work = tempfile.mkdtemp(prefix="bench_multichip_")
    try:
        Pipeline([
            StandardScaler().set_selected_col("features"),
            MinMaxScaler().set_selected_col("features"),
            LogisticRegression().set_vector_col("features")
            .set_label_col("label").set_prediction_col("pred")
            .set_prediction_detail_col("proba")
            .set_learning_rate(0.5).set_max_iter(4),
        ]).fit(dense).save(os.path.join(work, "dense"))
        Pipeline([
            StringIndexer().set_selected_cols(["c1", "c2", "c3"])
            .set_output_cols(["i1", "i2", "i3"]),
            OneHotEncoder().set_selected_cols(["i1", "i2", "i3"])
            .set_output_col("feat"),
            LogisticRegression().set_vector_col("feat")
            .set_label_col("label").set_prediction_col("pred")
            .set_learning_rate(0.5).set_max_iter(3),
        ]).fit(cat).save(os.path.join(work, "csr"))

        results = {}
        for n_dev in device_counts:
            out_path = os.path.join(work, f"result_{n_dev}.json")
            env = dict(os.environ)
            env.pop("FMT_FAULT_INJECT", None)
            env.pop("FMT_SERVE_MESH", None)
            env["FMT_OBS"] = "1"  # in-worker counters for the asserts;
            # worker-side RunReports land in the sweep's tempdir (NOT the
            # committed reports/ default) — the parent's bench record is
            # the canonical one
            env["FMT_OBS_REPORTS"] = os.path.join(work, f"reports_{n_dev}")
            flags = [
                f for f in env.get("XLA_FLAGS", "").split()
                if "xla_force_host_platform_device_count" not in f
            ]
            flags.append(
                f"--xla_force_host_platform_device_count={n_dev}")
            env["XLA_FLAGS"] = " ".join(flags)
            env["JAX_PLATFORMS"] = "cpu"
            proc = subprocess.run(
                [sys.executable, os.path.abspath(__file__),
                 "_serve_multichip_worker", str(n_dev), work, out_path,
                 str(n_rows), str(n_features), str(batch), str(sweeps)],
                capture_output=True, text=True, timeout=1200, env=env,
                cwd=os.path.dirname(os.path.abspath(__file__)),
            )
            assert proc.returncode == 0, (
                proc.stdout[-2000:], proc.stderr[-4000:])
            with open(out_path) as f:
                results[n_dev] = json.load(f)

        base = results[device_counts[0]]
        err = 0.0
        for n_dev in device_counts[1:]:
            for name in ("dense", "csr"):
                assert (results[n_dev][name]["pred"]
                        == base[name]["pred"]), (
                    f"{name}: {n_dev}-device discrete predictions "
                    "diverge from 1-device")
            err = float(np.max(np.abs(
                np.asarray(results[n_dev]["dense"]["proba"])
                - np.asarray(base["dense"]["proba"]))))
            assert err <= 1e-5, (
                f"{n_dev}-device float scores off by {err}")
        walls = {
            n_dev: results[n_dev]["dense"]["wall_s"]
            + results[n_dev]["csr"]["wall_s"]
            for n_dev in device_counts
        }
        scaling = {
            str(n_dev): round(2 * n_rows / walls[n_dev], 1)
            for n_dev in device_counts
        }
        top = device_counts[-1]
        return _emit({
            "metric":
                "PipelineModel.transform serve_multichip_over_single",
            "value": round(walls[top] / walls[device_counts[0]], 4),
            "unit": "ratio (lower is better)",
            "single_ms": round(walls[device_counts[0]] * 1e3, 1),
            "multichip_ms": round(walls[top] * 1e3, 1),
            "rows_per_sec_by_devices": scaling,
            "csr_shard_map_dispatches":
                results[top]["csr"]["shard_map_dispatches"],
            "pred_parity": True,   # asserted above for every arm
            "proba_max_abs_err": err,
            # measured in workers pinned to virtual CPU devices
            "backend": "cpu", "platform": "cpu", "device_kind": "cpu",
            "device_count": top,
            "shape": f"{n_rows}x{n_features} dense (3-stage) + "
                     f"{n_rows}-row categorical segment-CSR (3-stage), "
                     f"batch={batch}, device_counts={list(device_counts)},"
                     f" median of {sweeps} per arm",
        })
    finally:
        shutil.rmtree(work, ignore_errors=True)


def _coldstart_worker(model_dir: str, out_path: str, n_rows: int,
                      n_features: int) -> None:
    """One arm of ``bench_coldstart`` — a FRESH process, pinned to the CPU
    backend, that deploys the saved pipeline from disk (which activates the model-adjacent
    warm-artifact store) and answers one small request.  Times
    deploy-to-first-response, then reports its own compile-ledger line
    count: the warm arm's must be ZERO — every executable replayed off
    disk, none rebuilt."""
    import warnings

    import jax

    jax.config.update("jax_platforms", "cpu")
    from flink_ml_tpu import obs
    from flink_ml_tpu.obs import trace as obs_trace
    from flink_ml_tpu.serving.versioning import VersionManager

    obs.enable()
    dense, _ = _multichip_tables(n_rows, n_features)
    warmup = dense.slice_rows(0, 8)
    request = dense.slice_rows(8, 24)
    with warnings.catch_warnings():
        warnings.simplefilter("ignore", UserWarning)
        t0 = time.perf_counter()
        vm = VersionManager()
        vm.deploy(os.path.join(model_dir, "model"), "v1", warmup=warmup)
        out = vm.active().transform(request)
        ttfr_s = time.perf_counter() - t0
    ledger_lines = 0
    try:
        with open(obs_trace.compile_ledger_path()) as f:
            ledger_lines = sum(1 for line in f if line.strip())
    except OSError:
        pass
    counters = obs.registry().snapshot()["counters"]
    with open(out_path, "w") as f:
        json.dump({
            "ttfr_s": ttfr_s,
            "ledger_lines": ledger_lines,
            "pred": np.asarray(out.col("pred")).tolist(),
            "proba": np.asarray(out.col("proba")).tolist(),
            "warm_hits": counters.get("warmstart.hits", 0),
            "warm_saves": counters.get("warmstart.saves", 0),
            "compile_skips": counters.get("warmstart.compile_skips", 0),
            "ladder_rungs": counters.get("serving.warm_ladder_rungs", 0),
            "degraded": counters.get("warmstart.degraded", 0),
        }, f)


def bench_coldstart(n_rows=2048, n_features=8):
    """Cold-start resilience gate (ISSUE 18).

    The parent fits the 3-stage dense chain ONCE (scaler -> scaler -> LR
    score, the serve_multichip shape) and saves it, then launches two
    FRESH subprocesses that each deploy it from disk and answer one small
    request.  The cold arm pays every XLA compile across the warmup
    ladder and seals the warm-artifact store beside the model; the warm
    arm — a respawned replica in miniature — must replay every executable
    off that store: its compile-ledger delta is asserted EMPTY and its
    predictions bit-identical to the cold arm's (a deserialized
    executable is the same program, not a re-derivation).

    Emits ``cold_start_over_warm`` (warm time-to-first-response / cold,
    lower is better) as the BASELINE.json contract gate.  Both arms share
    the persistent XLA compile cache directory too, so the ratio is the
    marginal win of AOT executable replay over bytecode-level caching.
    Both workers are pinned to the CPU backend (the record says
    ``"backend": "cpu"``): this is a contract check, and what a respawn
    costs on the chip is ROADMAP D4's to measure.
    """
    import shutil
    import subprocess

    from flink_ml_tpu.api.pipeline import Pipeline
    from flink_ml_tpu.lib import LogisticRegression
    from flink_ml_tpu.lib.feature import MinMaxScaler, StandardScaler

    dense, _ = _multichip_tables(n_rows, n_features)
    work = tempfile.mkdtemp(prefix="bench_coldstart_")
    try:
        Pipeline([
            StandardScaler().set_selected_col("features"),
            MinMaxScaler().set_selected_col("features"),
            LogisticRegression().set_vector_col("features")
            .set_label_col("label").set_prediction_col("pred")
            .set_prediction_detail_col("proba")
            .set_learning_rate(0.5).set_max_iter(4),
        ]).fit(dense).save(os.path.join(work, "model"))

        results = {}
        for arm in ("cold", "warm"):
            out_path = os.path.join(work, f"result_{arm}.json")
            env = dict(os.environ)
            env.pop("FMT_FAULT_INJECT", None)
            env.pop("FMT_SERVE_MESH", None)
            env.pop("FMT_WARM_DIR", None)  # store lands beside the model
            env.pop("FMT_COMPILE_CACHE", None)
            env["FMT_OBS"] = "1"
            env["FMT_OBS_REPORTS"] = os.path.join(work, f"reports_{arm}")
            env["FMT_WARMSTART"] = "1"
            # a cold arm needs a cache nobody has written to: one fresh
            # directory per bench run, shared by the two arms
            env["JAX_COMPILATION_CACHE_DIR"] = os.path.join(
                work, "xla_cache")
            env["JAX_PLATFORMS"] = "cpu"
            proc = subprocess.run(
                [sys.executable, os.path.abspath(__file__),
                 "_coldstart_worker", work, out_path, str(n_rows),
                 str(n_features)],
                capture_output=True, text=True, timeout=1200, env=env,
                cwd=os.path.dirname(os.path.abspath(__file__)),
            )
            assert proc.returncode == 0, (
                proc.stdout[-2000:], proc.stderr[-4000:])
            with open(out_path) as f:
                results[arm] = json.load(f)

        cold, warm = results["cold"], results["warm"]
        assert cold["warm_saves"] > 0, cold       # the cold arm sealed it
        assert warm["warm_hits"] > 0, warm        # ...and the warm arm hit
        assert warm["degraded"] == 0, warm
        # the contract's teeth: the warm process rebuilt NOTHING — zero
        # fresh compiles across the whole ladder — and served the same
        # bits the cold process did
        assert warm["ledger_lines"] == 0, (
            f"warm arm wrote {warm['ledger_lines']} compile-ledger lines "
            "(expected an empty delta)", warm)
        assert warm["pred"] == cold["pred"], (
            "cold/warm discrete predictions diverge")
        assert warm["proba"] == cold["proba"], (
            "cold/warm float scores are not bit-identical")
        return _emit({
            "metric": "VersionManager.deploy cold_start_over_warm",
            "value": round(warm["ttfr_s"] / cold["ttfr_s"], 4),
            "unit": "ratio (lower is better)",
            "cold_ttfr_ms": round(cold["ttfr_s"] * 1e3, 1),
            "warm_ttfr_ms": round(warm["ttfr_s"] * 1e3, 1),
            "cold_compiles": cold["ledger_lines"],
            "warm_compiles": warm["ledger_lines"],
            "warm_hits": warm["warm_hits"],
            "ladder_rungs": cold["ladder_rungs"],
            # measured in workers pinned to the CPU backend
            "backend": "cpu", "platform": "cpu", "device_kind": "cpu",
            "pred_parity": True,  # asserted bit-identical above
            "shape": f"{n_rows}x{n_features} dense 3-stage pipeline, "
                     "fresh cold/warm subprocesses sharing one "
                     "warm-artifact store + XLA disk cache",
        })
    finally:
        shutil.rmtree(work, ignore_errors=True)


def bench_multitenant(n_tenants=64, n_rows=4096, n_features=16,
                      n_requests=192, req_rows=8, sweeps=3,
                      max_batch=1024, max_wait_ms=5.0):
    """Multi-tenant model multiplexing gate (ISSUE 20).

    ``n_tenants`` same-family pipelines (identical structure, distinct
    fitted params) serve through ONE ModelServer, traffic round-robined
    across every tenant.  The solo arm serves the SAME request count
    through the same server with no tenant key — the single-model
    dispatch cost multi-tenancy is measured against.  The emitted
    ``multitenant_over_solo`` ratio (multi wall / solo wall, lower is
    better) is gated at <= 1.5 in BASELINE.json: thousand-model serving
    is only real if fanning the traffic across 64 models costs at most
    half again the one-model wall, which requires the mux to coalesce
    cross-tenant requests into ONE stacked-param fused dispatch instead
    of 64 solo dispatches.

    Asserted inside the bench, never just recorded: per-tenant discrete
    predictions bit-identical to a solo ``transform`` of that tenant's
    model, genuine cross-tenant coalescing (mux dispatches << timed
    requests), and a compile ledger FLAT over tenants (the timed phase
    may mint at most a few tenant-count rungs, nothing proportional to
    ``n_tenants``).
    """
    from flink_ml_tpu import obs
    from flink_ml_tpu.api.pipeline import Pipeline
    from flink_ml_tpu.common import fused
    from flink_ml_tpu.lib import LogisticRegression
    from flink_ml_tpu.lib.feature import MinMaxScaler, StandardScaler
    from flink_ml_tpu.serving import ModelServer
    from flink_ml_tpu.table.schema import DataTypes, Schema
    from flink_ml_tpu.table.table import Table

    rng = np.random.RandomState(29)
    X = (2.0 * rng.randn(n_rows, n_features) + 1.0).astype(np.float32)
    true_w = (rng.randn(n_features) / np.sqrt(n_features)).astype(np.float32)
    y = ((X - 1.0) @ true_w > 0).astype(np.float64)
    schema = Schema.of(("features", DataTypes.DENSE_VECTOR),
                       ("label", "double"))
    t = Table.from_columns(schema, {"features": X, "label": y})

    def fit_one(seed):
        r = np.random.RandomState(seed)
        Xs = (2.0 * r.randn(2048, n_features) + 1.0).astype(np.float32)
        ys = ((Xs - 1.0) @ true_w > 0).astype(np.float64)
        ts = Table.from_columns(schema, {"features": Xs, "label": ys})
        return Pipeline([
            StandardScaler().set_selected_col("features"),
            MinMaxScaler().set_selected_col("features"),
            LogisticRegression().set_vector_col("features")
            .set_label_col("label").set_prediction_col("pred")
            .set_learning_rate(0.5).set_max_iter(3),
        ]).fit(ts)

    model0 = fit_one(1)
    tenants = {f"t{i:03d}": fit_one(100 + i) for i in range(n_tenants)}

    # request stream: round-robin over tenants, fixed-size slices so both
    # arms ride one ladder rung and the comparison is pure dispatch cost
    names = list(tenants)
    stream = []  # (tenant, lo)
    lo = 0
    for i in range(n_requests):
        stream.append((names[i % n_tenants], lo))
        lo = (lo + req_rows) % (n_rows - req_rows)
    total_rows = n_requests * req_rows

    # per-tenant solo truth over the full table, computed ONCE
    solo_pred = {}
    for name, m in tenants.items():
        (out,) = m.transform(t)
        solo_pred[name] = np.asarray(out.col("pred"))

    # two live servers, sweeps interleaved solo/multi and min-taken, so
    # container jitter drifts BOTH arms instead of skewing the ratio
    solo_server = ModelServer(model0, max_batch=max_batch,
                              max_wait_ms=max_wait_ms)
    multi_server = ModelServer(model0, max_batch=max_batch,
                               max_wait_ms=max_wait_ms)
    for name, m in tenants.items():
        multi_server.register_tenant(name, m)
    # warm round: each tenant's FIRST serve runs solo (learning its
    # family token) and faults its model in; one full burst after that
    # warms the mux's stacked-param executables for every rung the timed
    # sweeps will hit — and the solo arm's coalesced buckets
    for name in names:
        multi_server.predict(t.slice_rows(0, req_rows), tenant=name,
                             timeout=120)
    for f in ([multi_server.submit(t.slice_rows(lo_, lo_ + req_rows),
                                   tenant=name)
               for name, lo_ in stream]
              + [solo_server.submit(t.slice_rows(lo_, lo_ + req_rows))
                 for _, lo_ in stream]):
        f.result(timeout=120)
    seen0 = len(fused._COMPILE_SEEN)
    mux0 = obs.registry().counter("serving.mux.dispatches")

    def wall(server, tenant_keyed):
        t0 = time.perf_counter()
        futs = [server.submit(t.slice_rows(lo_, lo_ + req_rows),
                              tenant=(name if tenant_keyed else None))
                for name, lo_ in stream]
        results = [f.result(timeout=120) for f in futs]
        return time.perf_counter() - t0, results

    solo_walls, multi_walls = [], []
    for _ in range(sweeps):
        w, _results = wall(solo_server, False)
        solo_walls.append(w)
        w, results = wall(multi_server, True)
        multi_walls.append(w)
    solo_s = float(np.min(solo_walls))
    multi_s = float(np.min(multi_walls))
    ledger_growth = len(fused._COMPILE_SEEN) - seen0
    counters = obs.registry().snapshot()["counters"]
    solo_server.shutdown()
    multi_server.shutdown()

    # per-tenant isolation: every response bit-identical to THAT tenant's
    # solo transform of the same rows
    for (name, lo_), res in zip(stream, results):
        np.testing.assert_array_equal(
            np.asarray(res.table.col("pred")),
            solo_pred[name][lo_:lo_ + req_rows],
            err_msg=f"tenant {name}: multiplexed prediction diverges "
                    "from solo serving",
        )
    mux_dispatches = counters.get("serving.mux.dispatches", 0) - mux0
    assert 0 < mux_dispatches < sweeps * n_requests / 4, (
        f"no real cross-tenant coalescing: {mux_dispatches} mux "
        f"dispatches for {sweeps * n_requests} timed requests"
    )
    assert ledger_growth <= 4, (
        f"{ledger_growth} fresh compile-ledger shapes during the timed "
        f"sweeps over {n_tenants} warm tenants — compiles are scaling "
        "with tenant count"
    )

    return _emit({
        "metric": "ModelServer.serve multitenant_over_solo",
        "value": round(multi_s / solo_s, 4),
        "unit": "ratio (lower is better)",
        "solo_ms": round(solo_s * 1e3, 1),
        "multitenant_ms": round(multi_s * 1e3, 1),
        "solo_requests_per_sec": round(n_requests / solo_s, 1),
        "multitenant_requests_per_sec": round(n_requests / multi_s, 1),
        "n_tenants": n_tenants,
        "mux_dispatches_per_sweep": round(mux_dispatches / float(sweeps), 1),
        "tenants_per_mux_dispatch": round(
            (counters.get("serving.mux.tenants_coalesced", 0)
             / max(1, counters.get("serving.mux.dispatches", 1))), 1),
        "mux_fallbacks": counters.get("serving.mux_fallbacks", 0),
        "timed_ledger_growth": int(ledger_growth),
        "pred_parity": True,  # asserted above — reaching here proves it
        "shape": f"{n_tenants} same-family tenants, {n_requests} "
                 f"{req_rows}-row requests round-robined, {total_rows} "
                 f"rows, max_batch={max_batch}, max_wait={max_wait_ms}ms, "
                 f"interleaved min of {sweeps} per arm",
    })


def bench_sparse_file(n_rows, dim, nnz):
    """Create (once) the synthetic Criteo-shaped LibSVM file."""
    rng = np.random.RandomState(5)
    path = os.path.join(tempfile.gettempdir(), f"criteo_shaped_{n_rows}.svm")
    if not os.path.exists(path):
        hot = rng.randint(0, 50_000, size=(n_rows, nnz - 10))
        cold = rng.randint(50_000, dim, size=(n_rows, 10))
        idx = np.concatenate([hot, cold], axis=1)
        idx.sort(axis=1)
        true_w = rng.randn(dim).astype(np.float32) * 0.3
        with open(path, "w") as f:
            for i in range(n_rows):
                ii = np.unique(idx[i])
                label = 1 if true_w[ii].sum() > 0 else 0
                f.write(str(label) + " " +
                        " ".join(f"{j}:1" for j in ii) + "\n")
    return path


WORKLOADS = {
    "logreg": bench_logreg,
    "logreg_wide": bench_logreg_wide,
    "kmeans": bench_kmeans,
    "linreg": bench_linreg,
    "knn": bench_knn,
    "online": bench_online,
    "sparse": bench_sparse,
    "sparse_scale": bench_sparse_scale,
    "sparse_ooc": bench_sparse_ooc,
    "pipeline": bench_pipeline,
    "warmfit": bench_warm_fit,
    "serve": bench_serve,
    "serving": bench_serving,
    "trace_overhead": bench_trace_overhead,
    "pressure": bench_pressure,
    "telemetry": bench_telemetry,
    "drift": bench_drift,
    "online_loop": bench_online_loop,
    "router": bench_router,
    "autoscale": bench_autoscale,
    "serve_multichip": bench_serve_multichip,
    "coldstart": bench_coldstart,
    "multitenant": bench_multitenant,
}


def main(argv):
    from flink_ml_tpu import obs

    obs.enable()
    names = argv or list(WORKLOADS)
    results = {}
    for name in names:
        # fresh registry per workload: each bench RunReport's metrics
        # snapshot describes that workload's fits alone
        obs.reset()
        results[name] = WORKLOADS[name]()
    return results


if __name__ == "__main__":
    if sys.argv[1:2] == ["_serve_multichip_worker"]:
        # one device-count arm of bench_serve_multichip, re-exec'd with
        # XLA_FLAGS already forcing its mesh width (never a workload name)
        _a = sys.argv[2:]
        _serve_multichip_worker(
            int(_a[0]), _a[1], _a[2], int(_a[3]), int(_a[4]), int(_a[5]),
            int(_a[6]),
        )
    elif sys.argv[1:2] == ["_coldstart_worker"]:
        # one cold/warm arm of bench_coldstart, re-exec'd in a fresh
        # process so deploy-to-first-response includes real compile (or
        # warm-replay) cost — never a workload name
        _a = sys.argv[2:]
        _coldstart_worker(_a[0], _a[1], int(_a[2]), int(_a[3]))
    else:
        main(sys.argv[1:])
