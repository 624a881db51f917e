"""KMeans — Lloyd iterations on the device mesh (ROADMAP.md, Reach: k=100).

The reference has no KMeans; this is the workload the roadmap names, built
on the same bounded-iteration + in-step-psum pattern as the GLMs: centroids
replicated, rows sharded over the ``data`` axis, one epoch = one device call
computing assignments (argmin over an MXU-friendly x·cᵀ distance matrix) and
the psum'd per-cluster sums/counts that yield the next centroids.

Init is k-means++ on a host sample (seeded, reproducible); empty clusters
keep their previous centroid.
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from flink_ml_tpu import fault, obs
from flink_ml_tpu.api.core import Estimator
from flink_ml_tpu.common.mapper import ModelMapper
from flink_ml_tpu.lib.common import apply_sharded, resolve_features
from flink_ml_tpu.lib.model_base import TableModelBase
from flink_ml_tpu.lib.params import (
    HasCheckpoint,
    HasFeatureColsDefaultAsNull,
    HasK,
    HasMaxIter,
    HasSeed,
    HasTol,
    HasVectorColDefaultAsNull,
)
from flink_ml_tpu.ops.vector import DenseVector
from flink_ml_tpu.parallel.collectives import psum
from flink_ml_tpu.params.shared import (
    HasPredictionCol,
    HasPredictionDetailCol,
    HasReservedCols,
)
from flink_ml_tpu.table.schema import DataTypes, Schema
from flink_ml_tpu.table.table import Table
from flink_ml_tpu.utils.environment import MLEnvironmentFactory

CENTROID_SCHEMA = Schema.of(
    ("clusterId", DataTypes.LONG), ("centroid", DataTypes.DENSE_VECTOR)
)


class KMeansParams(
    HasVectorColDefaultAsNull,
    HasFeatureColsDefaultAsNull,
    HasK,
    HasReservedCols,
    HasPredictionCol,
    HasPredictionDetailCol,
):
    """Shared column/k vocabulary for estimator and model."""


def _pairwise_sq_dists(x, c):
    """(n, k) squared distances; the x·cᵀ term is the MXU matmul."""
    x2 = jnp.sum(x * x, axis=1, keepdims=True)
    c2 = jnp.sum(c * c, axis=1)
    return jnp.maximum(x2 - 2.0 * (x @ c.T) + c2, 0.0)


# module-level + memoized so the jit cache survives across mapper instances
def _assign_fn(x, c):
    d = _pairwise_sq_dists(x, c)
    return jnp.stack(
        [jnp.argmin(d, axis=1).astype(jnp.float64),
         jnp.min(d, axis=1).astype(jnp.float64)],
        axis=1,
    )


from functools import lru_cache


@lru_cache(maxsize=32)
def _assign_apply(mesh):
    """Mesh-sharded assignment: rows over 'data', centroids replicated
    (plain jit on a single chip)."""
    from flink_ml_tpu.parallel.collectives import make_data_parallel_apply

    return make_data_parallel_apply(_assign_fn, mesh, n_args=2)


def make_kmeans_train_fn(mesh, k: int, max_iter: int, tol: float):
    """The WHOLE Lloyd run as one compiled device program.

    Reuses the GLM fused-loop scaffolding (lib/common.py
    ``_build_fused_train_fn``) with a Lloyd ``epoch_fn``: epochs are a
    ``lax.while_loop`` with the convergence test (centroid-shift norm vs
    tol) evaluated on device, so training runs start-to-finish with zero
    host round-trips — one transfer in (rows + weights), one out (centroids
    + cost history + epochs).  Rows shard over ``data``; the per-cluster
    sums/counts/cost ``psum`` over it (the reference's reduce-average round,
    SURVEY.md §3.3, fused on-chip); empty clusters keep their previous
    centroid.
    """
    from flink_ml_tpu.lib.common import _build_fused_train_fn

    key = ("kmeans", mesh, int(k), int(max_iter), float(tol))

    def lloyd_epoch(c, batch):
        x, w = batch  # local shards: (rows, d), (rows,)
        d = _pairwise_sq_dists(x, c)
        assign = jnp.argmin(d, axis=1)
        cost = psum(jnp.sum(jnp.min(d, axis=1) * w), "data")
        sums = psum(
            jax.ops.segment_sum(x * w[:, None], assign, num_segments=k),
            "data",
        )
        counts = psum(jax.ops.segment_sum(w, assign, num_segments=k), "data")
        new_c = jnp.where(
            counts[:, None] > 0,
            sums / jnp.maximum(counts[:, None], 1.0),
            c,
        )
        delta = jnp.sqrt(jnp.sum((new_c - c) ** 2))
        return new_c, cost, delta

    return _build_fused_train_fn(
        key, None, mesh, 0.0, 0.0, max_iter, tol, epoch_fn=lloyd_epoch
    )


def train_kmeans(
    init_centroids,
    k: int,
    Xp: np.ndarray,
    wp: np.ndarray,
    mesh,
    max_iter: int,
    tol: float,
    n_rows: int,
    checkpoint=None,
    device_batch=None,
):
    """Drive fused Lloyd iterations to termination (TrainResult contract).

    ``init_centroids`` may be a thunk (the k-means++ host pass): it is only
    resolved on a fresh start — a checkpoint resume (or a finished-run no-op
    re-fit) never pays for it.  With a CheckpointConfig the run executes as
    fused chunks with centroid snapshots between them, through the same
    chunked-checkpoint driver as the sparse GLM path (lib/common.py
    ``run_chunked_checkpoint``)."""
    from flink_ml_tpu.lib.common import (
        _resolve_thunk,
        _run_fused_train,
        run_chunked_checkpoint,
    )

    batch = (Xp, wp)

    def run(n_epochs, cents, dev_batch=None):
        return _run_fused_train(
            make_kmeans_train_fn(mesh, k, n_epochs, tol),
            jnp.asarray(cents, dtype=jnp.float32),
            batch if dev_batch is None else dev_batch, mesh,
            batch_preplaced=dev_batch is not None, n_rows=n_rows,
        )

    if checkpoint is None:
        cents0 = np.asarray(_resolve_thunk(init_centroids), dtype=np.float32)
        return run(max_iter, cents0, _resolve_thunk(device_batch))
    dim = Xp.shape[1]
    return run_chunked_checkpoint(
        run, init_centroids, max_iter, tol, checkpoint, mesh, batch,
        device_batch=device_batch,
        like=np.zeros((k, dim), dtype=np.float32),  # structure template only
    )


def _allgather_sample_pool(local_sample: np.ndarray, per: int, dim: int,
                           k: int) -> np.ndarray:
    """Build the cross-process k-means++ init pool: every process ships a
    mask-padded ``per``-row block of its local sample (gathers need equal
    shapes, but shards may be skewed — a small shard contributes all its
    rows instead of capping everyone else), and the concatenated masked
    rows are identical on every process.  Shared by the in-memory and
    out-of-core multi-process fits."""
    from jax.experimental import multihost_utils

    s_p = int(local_sample.shape[0])
    local = np.zeros((per, dim), dtype=np.float64)
    mask = np.zeros((per,), dtype=bool)
    if s_p:
        local[:s_p] = np.asarray(local_sample, dtype=np.float64)
        mask[:s_p] = True
    pool_rows = multihost_utils.process_allgather(
        np.ascontiguousarray(local)
    ).reshape(-1, dim)
    pool_mask = multihost_utils.process_allgather(mask).ravel()
    pool = pool_rows[pool_mask]
    if pool.shape[0] < k:
        raise ValueError(
            f"k={k} exceeds the {pool.shape[0]}-row init pool "
            f"(raise INIT_SAMPLE_CAP or lower k)"
        )
    return pool


def kmeans_plus_plus(X: np.ndarray, k: int, rng: np.random.RandomState) -> np.ndarray:
    """Standard k-means++ seeding on the host (runs on a bounded sample)."""
    n = X.shape[0]
    first = rng.randint(n)
    centers = [X[first]]
    d2 = np.sum((X - X[first]) ** 2, axis=1)
    for _ in range(1, k):
        total = d2.sum()
        if total <= 0:
            centers.append(X[rng.randint(n)])
            continue
        probs = d2 / total
        idx = rng.choice(n, p=probs)
        centers.append(X[idx])
        d2 = np.minimum(d2, np.sum((X - X[idx]) ** 2, axis=1))
    return np.stack(centers)


class KMeansModelMapper(ModelMapper):
    """Batched nearest-centroid assignment."""

    def __init__(self, model: "KMeansModel", data_schema: Schema):
        self._model_stage = model
        super().__init__([CENTROID_SCHEMA], data_schema, model.get_params())

    def reserved_cols(self) -> Optional[list]:
        return self._model_stage.get_reserved_cols()

    def output_cols(self):
        model = self._model_stage
        names = [model.get_prediction_col()]
        types = [DataTypes.LONG]
        if model.get_prediction_detail_col() is not None:
            names.append(model.get_prediction_detail_col())
            types.append(DataTypes.DOUBLE)
        return names, types

    def load_model(self, *model_tables: Table) -> None:
        (t,) = model_tables
        order = np.argsort(np.asarray(t.col("clusterId"), dtype=np.int64))
        cents = np.stack(
            [t.col("centroid")[i].to_dense().values for i in order]
        )
        self._centroids = jnp.asarray(cents, dtype=jnp.float32)
        # host copy for the circuit-breaker CPU fallback
        self._centroids_np = np.asarray(cents, dtype=np.float32)

    def serve_validation_spec(self):
        model = self._model_stage
        return {
            "dim": int(self._centroids.shape[1]),
            "vector_col": model.get_vector_col(),
            "feature_cols": model.get_feature_cols(),
        }

    def map_batch(self, batch: Table):
        from flink_ml_tpu import serve

        model = self._model_stage
        X, _ = resolve_features(batch, model, dim=int(self._centroids.shape[1]))
        X = X.astype(np.float32)
        n = X.shape[0]
        both = serve.dispatch(
            self.serve_name(),
            device=lambda: apply_sharded(_assign_apply, X, self._centroids),
            fallback=lambda: self._assign_cpu(X),
        )
        return self._assign_cols(both[:n])

    def _assign_cols(self, both):
        model = self._model_stage
        out = {model.get_prediction_col(): both[:, 0].astype(np.int64)}
        detail = model.get_prediction_detail_col()
        if detail is not None:
            out[detail] = np.sqrt(both[:, 1])
        return out

    def fused_kernel(self):
        from flink_ml_tpu.common.fused import FusedInput, FusedKernel

        model = self._model_stage
        feature_cols = model.get_feature_cols()

        def fn(x, cents):
            return {"assign": _assign_fn(x, cents)}

        return FusedKernel(
            inputs=[FusedInput(
                dim=int(self._centroids.shape[1]),
                vector_col=model.get_vector_col(),
                feature_cols=tuple(feature_cols) if feature_cols else None,
            )],
            fn=fn,
            out_keys=("assign",),
            model_args=(self._centroids,),
            finalize=lambda fetched, n: self._assign_cols(
                fetched["assign"]
            ),
        )

    def _assign_cpu(self, X: np.ndarray) -> np.ndarray:
        """NumPy nearest-centroid fallback (same distance formula and
        lowest-id tie-break as the device argmin)."""
        c = self._centroids_np
        d = np.maximum(
            np.sum(X * X, axis=1, keepdims=True)
            - 2.0 * (X @ c.T)
            + np.sum(c * c, axis=1),
            0.0,
        )
        return np.stack(
            [np.argmin(d, axis=1).astype(np.float64), np.min(d, axis=1)],
            axis=1,
        )


class KMeansModel(TableModelBase, KMeansParams):
    """Nearest-centroid assignment model; model data = the centroid table."""

    REQUIRED_MODEL_COL = "centroid"

    def centroids(self) -> np.ndarray:
        (t,) = self.get_model_data()
        order = np.argsort(np.asarray(t.col("clusterId"), dtype=np.int64))
        return np.stack([t.col("centroid")[i].to_dense().values for i in order])

    def _make_mapper(self, data_schema: Schema) -> KMeansModelMapper:
        return KMeansModelMapper(self, data_schema)


class KMeans(Estimator, KMeansParams, HasMaxIter, HasTol, HasSeed, HasCheckpoint):
    """Estimator: k-means++ init + FUSED data-parallel Lloyd iterations.

    The whole run is one device program (:func:`make_kmeans_train_fn`) — no
    per-epoch host sync; with a checkpoint dir configured, fused chunks with
    centroid snapshots between them (resume restores the latest snapshot and
    skips re-init)."""

    INIT_SAMPLE_CAP = 100_000  # k-means++ host sample bound

    def _checkpoint_config(self):
        directory = self.get_checkpoint_dir()
        if directory is None:
            return None
        from flink_ml_tpu.iteration.checkpoint import CheckpointConfig

        return CheckpointConfig(
            directory=directory, every_n_epochs=self.get_checkpoint_interval()
        )

    def fit(self, *inputs) -> KMeansModel:
        import time as _time

        from flink_ml_tpu.table import slab_pool

        self._fit_pool_stats0 = (
            *slab_pool.pool().counters(), _time.perf_counter()
        )
        (table,) = inputs
        if getattr(table, "is_chunked", False):
            return self._fit_out_of_core(table)
        X, dim = resolve_features(table, self)
        k = self.get_k()
        n = X.shape[0]
        n_proc = jax.process_count()

        checkpoint = self._checkpoint_config()

        env = MLEnvironmentFactory.get_default()
        mesh = env.get_mesh()
        from flink_ml_tpu.parallel.mesh import (
            agree_max,
            agree_sum,
            local_data_parallel_size,
        )

        n_global = int(agree_sum(np.asarray([n]))[0]) if n_proc > 1 else n
        if n_global < k:
            raise ValueError(f"k={k} exceeds number of rows {n_global}")
        n_dev = local_data_parallel_size(mesh)

        if n_proc > 1:
            # cross-process consistent seeding: each process contributes an
            # equal-size deterministic sample of ITS shard; the allgathered
            # pool is identical on every process, so the same-seeded
            # k-means++ pass picks the same replicated centroids everywhere.
            # Eager (not inside the init thunk): the gather is a collective
            # every process must reach, never skipped by a lazy resolve.
            rng = np.random.RandomState(self.get_seed())
            per = -(-self.INIT_SAMPLE_CAP // n_proc)
            s_p = min(n, per)
            local_sample = (
                X if n == s_p else X[rng.choice(n, s_p, replace=False)]
            )
            pool = _allgather_sample_pool(local_sample, per, dim, k)

            def init():
                return kmeans_plus_plus(
                    pool, k, np.random.RandomState(self.get_seed())
                )
        else:
            def init():
                # the k-means++ host pass, as a thunk: resolved by
                # train_kmeans only on a fresh start — a snapshot resume
                # skips it entirely
                rng = np.random.RandomState(self.get_seed())
                sample = X if n <= self.INIT_SAMPLE_CAP else X[
                    rng.choice(n, self.INIT_SAMPLE_CAP, replace=False)
                ]
                return kmeans_plus_plus(sample.astype(np.float64), k, rng)

        # local rows pad to a per-shard row count agreed across processes
        # (shard_batch needs identically-shaped local blocks; pad rows
        # carry zero weight)
        rows_per_shard = -(-n // n_dev)
        if n_proc > 1:
            (rows_per_shard,) = agree_max(rows_per_shard)

        def build():
            n_pad = rows_per_shard * n_dev
            Xp = np.zeros((n_pad, dim), dtype=np.float32)
            Xp[:n] = X
            wp = np.zeros((n_pad,), dtype=np.float32)
            wp[:n] = 1.0
            return Xp, wp

        layout_key = ("kmeans", self.get_vector_col(),
                      tuple(self.get_feature_cols() or ()), n_dev,
                      rows_per_shard)
        Xp, wp = table.cached_pack(layout_key, build)
        # a thunk: a no-op resume (finished snapshot) must not pay the
        # host->device transfer, so placement resolves lazily downstream;
        # the placement itself rides the cross-fit slab pool (re-fitting
        # the same table content skips the transfer) and double-buffers
        # the H2D hop
        from flink_ml_tpu.parallel.mesh import shard_batch_prefetched
        from flink_ml_tpu.table import slab_pool

        kmeans_cols = (
            [self.get_vector_col()] if self.get_vector_col() is not None
            else list(self.get_feature_cols() or ())
        )
        device_batch = lambda: slab_pool.get_or_place(  # noqa: E731
            table, layout_key + ("dev",), mesh,
            lambda: shard_batch_prefetched(mesh, (Xp, wp)),
            cols=kmeans_cols or None,
        )

        # guarded for the health sentinel's diagnostics, but with NO retry
        # budget: KMeans has no learning rate to back off, so a replay
        # would re-diverge bit-identically — fail fast with the guard's
        # framing instead of multiplying time-to-error
        result = fault.run_guarded(
            lambda _lr_scale: train_kmeans(
                init, k, Xp, wp, mesh,
                max_iter=self.get_max_iter(), tol=self.get_tol(),
                n_rows=n_global,
                checkpoint=checkpoint, device_batch=device_batch,
            ),
            what=type(self).__name__, max_retries=0,
        )
        return self._finish(result, k)

    def _finish(self, result, k: int) -> KMeansModel:
        from flink_ml_tpu.lib.common import fit_pool_extra

        centroids = np.asarray(result.params, dtype=np.float64)
        model_table = Table.from_rows(
            [(int(i), DenseVector(centroids[i])) for i in range(k)],
            CENTROID_SCHEMA,
        )
        model = KMeansModel()
        model.get_params().merge(self.get_params())
        model.set_model_data(model_table)
        model.train_epochs_ = result.epochs
        model.train_cost_ = float(result.losses[-1]) if result.losses else 0.0
        model.train_metrics_ = result.metrics
        obs.fit_report(
            type(self).__name__,
            step_metrics=result.metrics,
            extra={"epochs": result.epochs, "cost": model.train_cost_,
                   "k": int(k), **fit_pool_extra(self, result)},
        )
        return model

    def _fit_out_of_core(self, table) -> KMeansModel:
        """Streaming Lloyd over a ChunkedTable: per-epoch passes accumulate
        cluster sums/counts chunk by chunk on device (lib/out_of_core.py),
        so the dataset never materializes on the host.

        Matches the in-memory fit to float accumulation order: chunked
        partial segment-sums add in a different order than one whole-shard
        segment_sum, so centroids agree to ~1e-5 relative, not bit-for-bit
        (unlike the GLM paths, whose minibatch structure chunking preserves
        exactly).  The k-means++ init draws a UNIFORM reservoir sample of
        up to INIT_SAMPLE_CAP rows over one full stream pass (sorted or
        grouped files must not bias the seeding); under the cap the sample
        is the whole dataset, matching the in-memory path.
        """
        from flink_ml_tpu.table.sources import chunk_cache

        # the reservoir init is a full stream pass: record binary chunks
        # there so the first training epoch replays pages instead of
        # re-parsing text — one text read total (VERDICT r4 #3)
        with chunk_cache(table) as table:
            return self._fit_out_of_core_impl(table)

    def _fit_out_of_core_impl(self, table) -> KMeansModel:
        from flink_ml_tpu.lib import out_of_core as oc
        from flink_ml_tpu.parallel.mesh import (
            agree_max,
            agree_sum,
            local_data_parallel_size,
        )

        env = MLEnvironmentFactory.get_default()
        mesh = env.get_mesh()
        n_proc = jax.process_count()
        n_dev = local_data_parallel_size(mesh)
        # on a 2-D mesh the centroids replicate over 'model' (like the
        # in-memory Lloyd path); rows shard over 'data' only
        k = self.get_k()
        checkpoint = self._checkpoint_config()

        def extract(t):
            X, _ = resolve_features(t, self)
            return (np.asarray(X),)

        # init from a uniform reservoir sample; skipped entirely on resume
        # single-process.  Multi-process always runs the sampling pass:
        # the per-epoch block count derives from the row count it returns
        # (every process must dispatch the same number of collective chunk
        # calls — short shards pad with zero-weight blocks), and the
        # allgather is a collective every process must reach.
        resuming = False
        if checkpoint is not None:
            from flink_ml_tpu.iteration.checkpoint import (
                agreed_latest_checkpoint,
            )

            resuming = agreed_latest_checkpoint(checkpoint.directory) is not None
        rng = np.random.RandomState(self.get_seed())
        rows_per_block = max(n_dev, (table.chunk_rows // n_dev) * n_dev)
        pad_to_blocks = None
        if n_proc > 1:
            per = -(-self.INIT_SAMPLE_CAP // n_proc)
            sample, n_seen = oc.reservoir_sample_rows(
                table.chunks(), extract, per, rng, allow_empty=True
            )
            # an empty local shard cannot know the feature width, but it
            # still owes every collective: agree the width first, then
            # contribute an empty masked block to the pool
            (dim,) = agree_max(sample.shape[1] if n_seen else 0)
            if dim == 0:
                raise ValueError("empty source")
            # the row-count check precedes the pool build so an under-k
            # dataset reports 'k exceeds number of rows', not the pool's
            # 'raise INIT_SAMPLE_CAP' (which could not help) — matching
            # the in-memory path's diagnostic order
            n_global = int(agree_sum(np.asarray([n_seen]))[0])
            if n_global < k:
                raise ValueError(f"k={k} exceeds number of rows {n_global}")
            pool = _allgather_sample_pool(
                sample.reshape(-1, dim) if n_seen else
                np.zeros((0, dim), dtype=np.float64),
                per, dim, k,
            )
            (pad_to_blocks,) = agree_max(-(-n_seen // rows_per_block))
            cents0 = kmeans_plus_plus(
                pool, k, np.random.RandomState(self.get_seed())
            )
        elif resuming:
            first = next(iter(table.chunks()), None)
            if first is None:
                raise ValueError("empty source")
            dim = extract(first)[0].shape[1]
            cents0 = np.zeros((k, dim), dtype=np.float32)  # template only
        else:
            sample, n_seen = oc.reservoir_sample_rows(
                table.chunks(), extract, self.INIT_SAMPLE_CAP, rng
            )
            dim = sample.shape[1]
            if n_seen < k:
                raise ValueError(f"k={k} exceeds number of rows {n_seen}")
            cents0 = kmeans_plus_plus(sample.astype(np.float64), k, rng)

        blocks = oc.rows_blocks_factory(table, extract, n_dev, rows_per_block,
                                        pad_to_blocks=pad_to_blocks,
                                        pad_dim=dim)
        key = ("chunk-kmeans", mesh, int(k), rows_per_block, dim)
        use_spill = getattr(table, "spill", False) and self.get_max_iter() > 1
        with oc.maybe_spill(blocks, use_spill) as blocks:
            result = fault.run_guarded(
                lambda _lr_scale: oc.train_out_of_core(
                    jnp.asarray(cents0, dtype=jnp.float32),
                    blocks,
                    lambda: oc.make_kmeans_chunk_fn(key, k, mesh),
                    mesh,
                    max_iter=self.get_max_iter(),
                    tol=self.get_tol(),
                    checkpoint=checkpoint,
                    make_carry=oc.kmeans_make_carry,
                    finalize=oc.kmeans_finalize,
                ),
                what=type(self).__name__, max_retries=0,  # no lr to back off
            )
        return self._finish(result, k)
