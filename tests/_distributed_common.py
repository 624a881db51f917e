"""Shared epoch definition for the cross-process equivalence test.

test_distributed.py (single-process 8-device reference) and
distributed_worker.py (2-process x 4-device run) must execute the IDENTICAL
training epoch; importing the definition from one place makes that
invariant structural rather than copy-synced.
"""

import numpy as np

N_DEV = 8
GLOBAL_BATCH = 16
LEARNING_RATE = 0.5


def make_epoch_inputs():
    """(combined minibatch stack view, zero params) for the shared epoch."""
    from flink_ml_tpu.lib.common import _combined_view, pack_minibatches

    rng = np.random.RandomState(0)
    Xg = rng.randn(64, 3)
    yg = (Xg @ np.array([1.0, -1.0, 0.5]) > 0).astype(np.float64)
    stack = pack_minibatches(
        Xg, yg, n_dev=N_DEV, global_batch_size=GLOBAL_BATCH
    )
    params0 = (np.zeros((3,), np.float32), np.zeros((), np.float32))
    return _combined_view(stack), params0


def make_epoch_step(mesh):
    from flink_ml_tpu.lib.classification import _log_loss_grads
    from flink_ml_tpu.lib.common import make_glm_epoch_step

    return make_glm_epoch_step(
        _log_loss_grads(True), mesh, learning_rate=LEARNING_RATE, reg=0.0
    )


# -- per-process file-shard fit (VERDICT r3 item 2) ---------------------------

SHARD_ROWS = 128     # rows per process shard (equal shards by contract)
SHARD_DIM = 6
SHARD_G = 32         # GLOBAL batch size
SHARD_EPOCHS = 5
SHARD_FEATURES = [f"f{i}" for i in range(SHARD_DIM)]


def shard_schema():
    from flink_ml_tpu.table.schema import Schema

    return Schema(SHARD_FEATURES + ["label"],
                  ["double"] * (SHARD_DIM + 1))


def make_shard_rows(num_processes):
    """The full deterministic dataset, one (X, y) block per process shard."""
    rng = np.random.RandomState(7)
    n = SHARD_ROWS * num_processes
    X = rng.randn(n, SHARD_DIM)
    y = (X @ rng.randn(SHARD_DIM) > 0).astype(np.float64)
    return [
        (X[p * SHARD_ROWS:(p + 1) * SHARD_ROWS],
         y[p * SHARD_ROWS:(p + 1) * SHARD_ROWS])
        for p in range(num_processes)
    ]


def write_shard_csv(path, X, y):
    with open(path, "w") as f:
        for row, lab in zip(X, y):
            f.write(",".join(f"{v:.17g}" for v in row) + f",{lab:.1f}\n")


def interleaved_rows(shards, num_processes):
    """The single-process row order equivalent to the multi-process schedule:
    global SGD step s consumes each process's s-th (G/P)-row window, so the
    canonical order interleaves per-shard windows round-robin."""
    g_local = SHARD_G // num_processes
    Xs = [s[0] for s in shards]
    ys = [s[1] for s in shards]
    xw, yw = [], []
    for start in range(0, SHARD_ROWS, g_local):
        for p in range(num_processes):
            xw.append(Xs[p][start:start + g_local])
            yw.append(ys[p][start:start + g_local])
    return np.concatenate(xw), np.concatenate(yw)


def fit_shard_table(table):
    """The estimator-level fit both sides run (identical hyperparameters);
    ``table`` may be a materialized Table or a ChunkedTable (out-of-core)."""
    from flink_ml_tpu.lib import LogisticRegression

    est = (
        LogisticRegression().set_feature_cols(SHARD_FEATURES)
        .set_label_col("label").set_prediction_col("pred")
        .set_learning_rate(LEARNING_RATE).set_max_iter(SHARD_EPOCHS)
        .set_global_batch_size(SHARD_G)
    )
    model = est.fit(table)
    (mt,) = model.get_model_data()
    w = np.asarray(mt.col("coefficients")[0].to_dense().values)
    b = float(mt.col("intercept")[0])
    return w, b


# -- per-process SPARSE shard fit (cross-process nnz_pad agreement) -----------

SPARSE_DIM = 2048
#: per-process nnz density — deliberately UNEQUAL so the local packs land on
#: different padded nnz widths (512 vs 1024 at pad_multiple=512) and the
#: cross-process agree_max repack is genuinely exercised, not a no-op
SPARSE_NNZ_BASE = 5
SPARSE_NNZ_STEP = 145


def make_sparse_shard_rows(num_processes):
    """One (vectors, y) block per process shard; process p's rows carry
    ``SPARSE_NNZ_BASE + p * SPARSE_NNZ_STEP`` stored entries each."""
    from flink_ml_tpu.ops.vector import SparseVector

    rng = np.random.RandomState(13)
    true_w = rng.randn(SPARSE_DIM)
    shards = []
    for p in range(num_processes):
        nnz = SPARSE_NNZ_BASE + p * SPARSE_NNZ_STEP
        vecs, ys = [], []
        for _ in range(SHARD_ROWS):
            idx = np.sort(rng.choice(SPARSE_DIM, nnz, replace=False))
            vals = rng.randn(nnz)
            vecs.append(SparseVector(SPARSE_DIM, idx.astype(np.int64), vals))
            ys.append(float((vals @ true_w[idx]) > 0))
        shards.append((vecs, np.asarray(ys)))
    return shards


def make_unequal_sparse_shard_rows(num_processes):
    """Shards with UNEQUAL row counts (process p holds SHARD_ROWS + 32*p
    rows): the shorter shard must pad its out-of-core epochs with gated
    no-op blocks up to the agreed per-epoch block count, or the collective
    chunk calls deadlock."""
    from flink_ml_tpu.ops.vector import SparseVector

    rng = np.random.RandomState(29)
    true_w = rng.randn(SPARSE_DIM)
    shards = []
    for p in range(num_processes):
        vecs, ys = [], []
        for _ in range(SHARD_ROWS + 32 * p):
            idx = np.sort(rng.choice(SPARSE_DIM, 5, replace=False))
            vals = rng.randn(5)
            vecs.append(SparseVector(SPARSE_DIM, idx.astype(np.int64), vals))
            ys.append(float((vals @ true_w[idx]) > 0))
        shards.append((vecs, np.asarray(ys)))
    return shards


def sparse_shard_schema():
    from flink_ml_tpu.table.schema import DataTypes, Schema

    return Schema.of(
        ("features", DataTypes.SPARSE_VECTOR), ("label", "double")
    )


def interleaved_sparse_rows(shards, num_processes):
    """Single-process row order equivalent to the multi-process sparse
    schedule (same windowing rule as :func:`interleaved_rows`)."""
    g_local = SHARD_G // num_processes
    vecs, ys = [], []
    for start in range(0, SHARD_ROWS, g_local):
        for p in range(num_processes):
            vecs.extend(shards[p][0][start:start + g_local])
            ys.extend(shards[p][1][start:start + g_local])
    return vecs, np.asarray(ys)


KM_K = 5
KM_EPOCHS = 5
KM_SEED = 3


def fit_kmeans_shard_table(table):
    """KMeans fit both sides run.  NOTE the single-process reference table
    must hold the shards CONCATENATED in process order (not interleaved):
    KMeans shards rows as contiguous device blocks, so process p's rows map
    to devices [p*4, (p+1)*4) — the same partition the concatenated order
    produces on the 8-device mesh."""
    from flink_ml_tpu.lib import KMeans

    est = (
        KMeans().set_feature_cols(SHARD_FEATURES)
        .set_prediction_col("cluster").set_k(KM_K)
        .set_max_iter(KM_EPOCHS).set_seed(KM_SEED)
    )
    model = est.fit(table)
    (mt,) = model.get_model_data()
    cents = np.asarray(
        [v.to_dense().values for v in mt.col("centroid")], dtype=np.float64
    )
    return cents, float(model.train_cost_)


def fit_sparse_shard_table(table, checkpoint_dir=None, max_iter=None):
    from flink_ml_tpu.lib import LogisticRegression

    est = (
        LogisticRegression().set_vector_col("features")
        .set_label_col("label").set_prediction_col("pred")
        .set_num_features(SPARSE_DIM)
        .set_learning_rate(LEARNING_RATE)
        .set_max_iter(SHARD_EPOCHS if max_iter is None else max_iter)
        .set_global_batch_size(SHARD_G)
    )
    if checkpoint_dir is not None:
        est.set_checkpoint_dir(str(checkpoint_dir)).set_checkpoint_interval(1)
    model = est.fit(table)
    (mt,) = model.get_model_data()
    w = np.asarray(mt.col("coefficients")[0].to_dense().values)
    b = float(mt.col("intercept")[0])
    return w, b
