"""Generalized-linear-model Estimator/Model base.

The reference ships the *infrastructure* for such estimators but no concrete
implementation (SURVEY.md §0.3); its only trainer is the hand-rolled BGD
LinearRegression example (examples-batch/.../LinearRegression.java:108-121).
This module is that training topology productized: Estimator.fit packs rows
once, runs the data-parallel SGD epochs (in-step psum allreduce — the
UpdateAccumulator/Update reduce-average pair fused on device), and returns a
Model whose transform is a batched mapper apply.

Model data follows the reference convention — rows of a table
(Model.getModelData, Model.java:48): one row holding the coefficient vector
and the intercept, persisted via the columnar table codec.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from flink_ml_tpu import fault, obs
from flink_ml_tpu.api.core import Estimator
from flink_ml_tpu.common.mapper import ModelMapper
from flink_ml_tpu.lib.common import (
    apply_sharded,
    fit_pool_extra,
    pack_minibatches,
    pack_sparse_minibatches,
    resolve_features,
    train_glm,
    train_glm_sparse,
)
from flink_ml_tpu.lib.model_base import TableModelBase
from flink_ml_tpu.lib.params import (
    HasCheckpoint,
    HasFeatureColsDefaultAsNull,
    HasNumFeatures,
    HasGlobalBatchSize,
    HasLabelCol,
    HasLearningRate,
    HasMaxIter,
    HasReg,
    HasSeed,
    HasTol,
    HasVectorColDefaultAsNull,
    HasWithIntercept,
)
from flink_ml_tpu.ops.vector import DenseVector, SparseVector
from flink_ml_tpu.params.shared import (
    HasPredictionCol,
    HasPredictionDetailCol,
    HasReservedCols,
)
from flink_ml_tpu.table.schema import DataTypes, Schema
from flink_ml_tpu.table.table import Table
from flink_ml_tpu.utils.environment import MLEnvironmentFactory

MODEL_SCHEMA = Schema.of(
    ("coefficients", DataTypes.DENSE_VECTOR), ("intercept", DataTypes.DOUBLE)
)


class GlmFeatureParams(
    HasVectorColDefaultAsNull,
    HasFeatureColsDefaultAsNull,
    HasReservedCols,
    HasPredictionCol,
    HasPredictionDetailCol,
):
    """Input/output column vocabulary shared by GLM estimators and models."""


class GlmTrainParams(
    GlmFeatureParams,
    HasLabelCol,
    HasLearningRate,
    HasMaxIter,
    HasGlobalBatchSize,
    HasTol,
    HasReg,
    HasWithIntercept,
    HasNumFeatures,
    HasCheckpoint,
    HasSeed,
):
    """Training vocabulary for GLM estimators."""


class GlmModelBase(TableModelBase, GlmFeatureParams):
    """Model over (coefficients, intercept) model-data tables
    (model-as-table contract implemented by TableModelBase)."""

    REQUIRED_MODEL_COL = "coefficients"

    # convenience for algorithm code
    def coefficients(self) -> np.ndarray:
        (t,) = self.get_model_data()
        return np.asarray(t.col("coefficients")[0].to_dense().values)

    def intercept(self) -> float:
        (t,) = self.get_model_data()
        return float(t.col("intercept")[0])


def make_model_table(weights: np.ndarray, intercept: float) -> Table:
    return Table.from_rows(
        [(DenseVector(np.asarray(weights, dtype=np.float64)), float(intercept))],
        MODEL_SCHEMA,
    )


def _zero_start(dim: int):
    """Every GLM fit's start: zero weights and intercept, made on the host,
    so that making them runs no device program.  The fused driver places a
    replicated zero start once and hands the same device arrays to every
    later fit of a program that frees none of its params
    (``lib/common.py:_place_start``); a route's own placer places them as
    any start."""
    return np.zeros((dim,), np.float32), np.float32(0)


# module-level + memoized so the jit cache is shared across mapper instances —
# a fresh jit() per load_model would recompile on every transform call
def _score_fn(x, w, b):
    return x @ w + b


from functools import lru_cache


@lru_cache(maxsize=32)
def _score_apply(mesh):
    """Mesh-sharded scorer: query rows over the 'data' axis, model replicated
    (the ModelMapperAdapter.java:53-61 parallel-apply analog; plain jit on a
    single chip)."""
    from flink_ml_tpu.parallel.collectives import make_data_parallel_apply

    return make_data_parallel_apply(_score_fn, mesh, n_args=3)


@jax.jit
def _sparse_score_fn(csr, w, b):
    return csr.matvec(w.astype(jnp.float32)) + b


def _col_is_sparse(table: Table, col: str) -> bool:
    values = table.col(col)
    return len(values) > 0 and isinstance(values[0], SparseVector)


class LinearScoreMapper(ModelMapper):
    """Batched x·w + b scorer; subclasses shape the output columns.

    The replacement for the reference's per-record ModelMapper hot loop
    (ModelMapperAdapter.java:58-61): one jitted matvec per row bucket.
    """

    def __init__(self, model: GlmModelBase, data_schema: Schema):
        self._model_stage = model
        super().__init__([MODEL_SCHEMA], data_schema, model.get_params())

    def reserved_cols(self) -> Optional[list]:
        return self._model_stage.get_reserved_cols()

    def load_model(self, *model_tables: Table) -> None:
        (t,) = model_tables
        w = np.asarray(t.col("coefficients")[0].to_dense().values)
        self._w = jnp.asarray(w, dtype=jnp.float32)
        self._b = jnp.asarray(float(t.col("intercept")[0]), dtype=jnp.float32)
        # host copies for the circuit-breaker CPU fallback: when the device
        # path is open-circuited, scoring must not touch device memory at all
        self._w_np = np.asarray(w, dtype=np.float32)
        self._b_np = np.float32(t.col("intercept")[0])

    def serve_validation_spec(self):
        model = self._model_stage
        return {
            "dim": int(self._w.shape[0]),
            "vector_col": model.get_vector_col(),
            "feature_cols": model.get_feature_cols(),
        }

    #: subclasses turn fetched fused scores into their output columns
    #: (mirroring their map_batch tail); None keeps the mapper out of
    #: fused plans — a custom LinearScoreMapper subclass with its own
    #: map_batch but no finalize must split the plan, never be mis-served
    _fused_finalize = None

    def fused_kernel(self):
        if type(self)._fused_finalize is None:
            return None
        from flink_ml_tpu.common.fused import FusedInput, FusedKernel

        model = self._model_stage
        feature_cols = model.get_feature_cols()

        def dense_fn(x, w, b):
            return {"scores": _score_fn(x, w, b)}

        def csr_fn(csr, w, b):
            return {"scores": csr.matvec(w.astype(jnp.float32)) + b}

        return FusedKernel(
            inputs=[FusedInput(
                dim=int(self._w.shape[0]),
                vector_col=model.get_vector_col(),
                feature_cols=tuple(feature_cols) if feature_cols else None,
            )],
            fn=dense_fn,
            csr_fn=csr_fn,
            out_keys=("scores",),
            model_args=(self._w, self._b),
            finalize=self._fused_finalize,
            pallas_op="glm_score",  # x @ w + b
        )

    def _scores(self, batch: Table) -> np.ndarray:
        model = self._model_stage
        vector_col = model.get_vector_col()
        if vector_col is not None and _col_is_sparse(batch, vector_col):
            # wide models never densify: segment-CSR matvec on device.  Row
            # count is bucketed (power of two) so varying batch sizes reuse
            # one compiled program; pad rows receive only zero contributions
            # and are sliced away.
            from flink_ml_tpu import serve
            from flink_ml_tpu.lib.common import bucket_rows
            from flink_ml_tpu.ops.batch import CsrBatch

            csr = batch.features_csr(vector_col, n_cols=int(self._w.shape[0]))
            n = csr.n_rows
            padded = CsrBatch(
                csr.indices, csr.values, csr.row_ids,
                n_rows=bucket_rows(max(n, 1)), n_cols=csr.n_cols,
            )
            return serve.dispatch(
                self.serve_name(),
                device=lambda: np.asarray(
                    _sparse_score_fn(padded, self._w, self._b)
                )[:n],
                fallback=lambda: self._scores_cpu_sparse(csr, n),
            )
        X, _ = resolve_features(batch, model, dim=int(self._w.shape[0]))
        # asarray, not astype: a matrix-backed f32 column passes through
        # zero-copy, so the slab pool sees a STABLE buffer and re-scoring
        # the same table reuses the placed padded batch.  Pool ONLY that
        # case — a freshly materialized buffer (f64 column, object rows,
        # featureCols matrix) gets a new identity every batch, so pooling
        # it would be pure tokenize+insert overhead with zero possible hits
        X = np.asarray(X, dtype=np.float32)
        col = (
            batch.col(vector_col) if vector_col is not None
            and batch.schema.contains(vector_col) else None
        )
        pool_key = (
            ("linear_scores", vector_col, int(self._w.shape[0]))
            if X is col else None
        )
        from flink_ml_tpu import serve

        return serve.dispatch(
            self.serve_name(),
            device=lambda: apply_sharded(
                _score_apply, X, self._w, self._b, pool_key=pool_key
            ),
            fallback=lambda: X @ self._w_np + self._b_np,
        )

    def _scores_cpu_sparse(self, csr, n: int) -> np.ndarray:
        """NumPy segment-matvec fallback (same math as _sparse_score_fn;
        f32 accumulation order may differ by summation grouping)."""
        out = np.zeros(n + 1, dtype=np.float32)  # slot n absorbs pad entries
        np.add.at(
            out,
            np.minimum(np.asarray(csr.row_ids), n),
            np.asarray(csr.values, dtype=np.float32)
            * self._w_np[np.asarray(csr.indices)],
        )
        return out[:n] + self._b_np


class GlmEstimatorBase(Estimator, GlmTrainParams):
    """Shared fit: rows -> minibatch stack -> data-parallel SGD epochs."""

    def _grad_fn(self):
        """(params, x, y, w) -> (grads, weighted loss sum, weight sum)."""
        raise NotImplementedError

    def _make_model(self) -> GlmModelBase:
        raise NotImplementedError

    def _labels(self, table: Table) -> np.ndarray:
        return np.asarray(table.col(self.get_label_col()), dtype=np.float64)

    def _checkpoint_config(self):
        directory = self.get_checkpoint_dir()
        if directory is None:
            return None
        from flink_ml_tpu.iteration.checkpoint import CheckpointConfig

        return CheckpointConfig(
            directory=directory, every_n_epochs=self.get_checkpoint_interval()
        )

    #: loss kind for the sparse fused path ('logistic' | 'squared')
    LOSS_KIND: str = ""

    def fit(self, *inputs) -> GlmModelBase:
        import time as _time

        from flink_ml_tpu.table import slab_pool

        # fit.wall's direct children (fit.prepare, slab_pool.lookup,
        # train.*, fit.finish, fit.report) account for a warm fit; what
        # they leave is its self time
        with obs.span("fit.wall"):
            # scope the slab-pool stats + wall clock to THIS fit: _finish
            # stamps the delta (hits/misses/hit rate/fit_wall_ms) into the
            # RunReport so warm fits are self-identifying (the CI warm-path
            # gate reads exactly this)
            self._fit_pool_stats0 = (
                *slab_pool.pool().counters(), _time.perf_counter()
            )
            (table,) = inputs
            if getattr(table, "is_chunked", False):
                return self._fit_out_of_core(table)
            with obs.span("fit.prepare"):
                train = self._prepare(table)
            return train()

    def _prepare(self, table):
        """Everything before the train call: labels, the layout's route,
        and for the dense layout the features, the (cached) pack and the
        zero start.  Returns the layout's fit, bound to its arguments."""
        y = self._labels(table)
        env = MLEnvironmentFactory.get_default()
        mesh = env.get_mesh()
        # rows shard over the data axis only; other mesh axes replicate.
        # Multi-process, `table` is this process's file shard: packing
        # targets the LOCAL share of the data axis and batch size, and
        # shard_batch assembles the global batch from per-process slices.
        from flink_ml_tpu.parallel.mesh import (
            local_batch_share,
            local_data_parallel_size,
        )

        n_dev = local_data_parallel_size(mesh)
        batch_share = local_batch_share(self.get_global_batch_size())

        vector_col = self.get_vector_col()
        if (vector_col is None) == (self.get_feature_cols() is None):
            raise ValueError("set exactly one of vectorCol / featureCols")
        if vector_col is not None and _col_is_sparse(table, vector_col):
            return self._prepare_sparse(table, y, mesh, n_dev, batch_share)

        model_sharded = dict(mesh.shape).get("model", 1) > 1
        X, dim = resolve_features(table, self)
        layout_key = ("dense", vector_col, tuple(self.get_feature_cols() or ()),
                      self.get_label_col(), n_dev, batch_share)
        # the columns this layout READS — pool tokens scope to them, so a
        # select()/with_column() re-wrap sharing these buffers still hits
        layout_cols = (
            [vector_col] if vector_col is not None
            else list(self.get_feature_cols() or ())
        ) + [self.get_label_col()]
        self._layout_cols = layout_cols
        stack = table.cached_pack(
            layout_key,
            lambda: pack_minibatches(X, y, n_dev, batch_share),
        )
        if model_sharded:
            # wide-dense story: weight vector + feature columns shard over
            # the 'model' axis (train_glm_dense_2d) instead of replicating
            return functools.partial(self._fit_dense_2d, stack, mesh,
                                     layout_key, dim, table)
        return functools.partial(self._fit_dense, table, stack, mesh,
                                 layout_key, layout_cols, _zero_start(dim))

    def _fit_dense(self, table, stack, mesh, layout_key, layout_cols,
                   init_params) -> GlmModelBase:
        """Dense data-parallel fit: the pooled slab, one fused program."""
        # device residency: re-fits of the same table CONTENT (sweeps,
        # benches, a re-wrapped Table over the same buffers) skip the
        # host->device hop via the process-wide slab pool — the analog of
        # the CPU path's data already sitting in RAM.  Keyed by mesh: a
        # different mesh is a different placement.  Only the fused path
        # consumes this layout; the checkpointed path shards (x, y, w)
        # itself, so placing the combined view there would transfer the
        # dataset twice.
        checkpoint = self._checkpoint_config()
        device_batch = None
        if checkpoint is None:
            from flink_ml_tpu.lib.common import _combined_view
            from flink_ml_tpu.parallel.mesh import shard_batch_prefetched
            from flink_ml_tpu.table import slab_pool

            # a THUNK, resolved inside train_glm's memory-pressure scope:
            # this closure must hold no reference to the placed whole-
            # batch slab — an OOM fallback that streams windows has to be
            # able to actually FREE that allocation first — and under an
            # already-known pressure cap train_glm skips the placement
            # entirely
            device_batch = lambda: slab_pool.get_or_place(  # noqa: E731
                table, layout_key + ("dev",), mesh,
                lambda: shard_batch_prefetched(mesh, _combined_view(stack)),
                cols=layout_cols,
            )

        # guarded: a NaN/Inf fit rolls back to the last good checkpoint
        # (or the zero init) and retries at a backed-off learning rate
        lr = self.get_learning_rate()
        result = fault.run_guarded(
            lambda lr_scale: train_glm(
                init_params,
                stack,
                self._grad_fn(),
                mesh,
                learning_rate=lr * lr_scale,
                max_iter=self.get_max_iter(),
                reg=self.get_reg(),
                tol=self.get_tol(),
                checkpoint=checkpoint,
                device_batch=device_batch,
            ),
            what=type(self).__name__,
        )
        return self._finish(result)

    def _fit_dense_2d(self, stack, mesh, layout_key, dim, table) -> GlmModelBase:
        """Dense feature-sharded (data x model) fit — VERDICT r3 item 5."""
        if not self.LOSS_KIND:
            raise NotImplementedError(
                f"{type(self).__name__} has no fused loss kind for the "
                "feature-sharded dense path"
            )
        from flink_ml_tpu.lib.common import (
            make_feature_shard_placer,
            place_dense_2d_batch,
            train_glm_dense_2d,
        )
        from flink_ml_tpu.table import slab_pool

        model_size = dict(mesh.shape)["model"]
        _, _, dim_pad = make_feature_shard_placer(mesh, dim, model_size)
        # thunk: resolved lazily so a no-op checkpoint resume skips the hop
        device_batch = lambda: slab_pool.get_or_place(  # noqa: E731
            table, layout_key + ("dev2d",), mesh,
            lambda: place_dense_2d_batch(mesh, stack, dim_pad),
            cols=getattr(self, "_layout_cols", None),
        )
        w0, b0 = _zero_start(dim)
        lr = self.get_learning_rate()
        result = fault.run_guarded(
            lambda lr_scale: train_glm_dense_2d(
                (w0, b0),
                stack,
                self.LOSS_KIND,
                mesh,
                learning_rate=lr * lr_scale,
                max_iter=self.get_max_iter(),
                reg=self.get_reg(),
                tol=self.get_tol(),
                with_intercept=self.get_with_intercept(),
                checkpoint=self._checkpoint_config(),
                device_batch=device_batch,
            ),
            what=type(self).__name__,
        )
        return self._finish(result)

    def _prepare_sparse(self, table: Table, y, mesh, n_dev: int,
                        batch_share: int):
        """The sparse layout's part of :meth:`_prepare` (inside
        ``fit.prepare``): the layout's agreement, the (cached) pack
        (segment-CSR, or row-regular where the plain route's row widths
        allow it) and the zero start.  Returns the layout's fit, bound to
        its arguments."""
        if not self.LOSS_KIND:
            raise NotImplementedError(
                f"{type(self).__name__} has no sparse loss kind"
            )
        from flink_ml_tpu.parallel.mesh import agree_max

        num_features = self.get_num_features()
        if jax.process_count() > 1:
            if num_features is None:
                raise ValueError(
                    "multi-process sparse training requires numFeatures "
                    "(each process would otherwise infer a different "
                    "dimension from its own file shard)"
                )
            if not batch_share or batch_share <= 0:
                raise ValueError(
                    "multi-process sparse training requires an explicit "
                    "globalBatchSize: the full-batch default would derive "
                    "the per-device minibatch from each process's LOCAL "
                    "row count, compiling mismatched block shapes when "
                    "shards are unequal"
                )
        # multi-process: the packed nnz width and step count derive from
        # LOCAL rows, but every process must compile the same block shapes.
        # A cheap pre-scan (row counts only, no stack materialized) computes
        # the local layout scalars, agree_max reconciles them, and the ONE
        # pack runs with the agreed floors.  The nnz floor is schedule-
        # neutral (pad entries carry zero weight); the steps floor only
        # differs when shards are unequal-sized, where the shorter shard's
        # trailing all-pad steps contribute zero gradient (with reg > 0
        # those steps still apply weight decay, like any zero-gradient step)
        if jax.process_count() > 1:
            from flink_ml_tpu.lib.common import (
                sparse_layout_floors,
                sparse_row_counts,
            )

            counts = sparse_row_counts(table.col(self.get_vector_col()))
            nnz_pad, steps = agree_max(
                *sparse_layout_floors(counts, n_dev, batch_share)
            )
        else:
            nnz_pad, steps = 0, 0  # pack's own natural layout
        # the plain step takes either layout, and the pack picks by the row
        # widths it observes; the 2-D step reads segment-CSR, and processes
        # agree on nnz_pad and steps only
        row_regular = (jax.process_count() == 1
                       and dict(mesh.shape).get("model", 1) == 1)
        layout_key = ("sparse", self.get_vector_col(), self.get_label_col(),
                      n_dev, batch_share, num_features, nnz_pad, steps,
                      row_regular)
        sstack = table.cached_pack(
            layout_key,
            lambda: pack_sparse_minibatches(
                table.col(self.get_vector_col()), y, n_dev,
                batch_share, dim=num_features,
                min_nnz_pad=nnz_pad, min_steps=steps,
                row_regular=row_regular,
            ),
        )
        if nnz_pad and (sstack.nnz_pad, sstack.steps) != (nnz_pad, steps):
            # the pre-scan must predict the pack's layout exactly, or the
            # processes compile mismatched shapes and the collective hangs
            raise AssertionError(
                f"sparse layout pre-scan predicted (nnz_pad={nnz_pad}, "
                f"steps={steps}) but the pack chose "
                f"({sstack.nnz_pad}, {sstack.steps})"
            )
        return functools.partial(self._fit_sparse, table, sstack, mesh,
                                 layout_key, _zero_start(sstack.dim))

    def _fit_sparse(self, table: Table, sstack, mesh, layout_key,
                    init_params) -> GlmModelBase:
        """Sparse-feature training: segment-CSR or row-regular (ELL)
        minibatches, as ``sstack`` is laid; fused device loop."""
        from flink_ml_tpu.parallel.mesh import shard_batch_prefetched
        from flink_ml_tpu.table import slab_pool

        # thunk: resolved lazily so a no-op checkpoint resume skips the hop
        sparse_cols = [self.get_vector_col(), self.get_label_col()]
        device_batch = lambda: slab_pool.get_or_place(  # noqa: E731
            table, layout_key + ("dev",), mesh,
            lambda: shard_batch_prefetched(mesh, sstack.batch),
            cols=sparse_cols,
        )
        lr = self.get_learning_rate()
        result = fault.run_guarded(
            lambda lr_scale: train_glm_sparse(
                init_params,
                sstack,
                self.LOSS_KIND,
                mesh,
                learning_rate=lr * lr_scale,
                max_iter=self.get_max_iter(),
                reg=self.get_reg(),
                tol=self.get_tol(),
                with_intercept=self.get_with_intercept(),
                checkpoint=self._checkpoint_config(),
                device_batch=device_batch,
            ),
            what=type(self).__name__,
        )
        return self._finish(result)

    def _fit_out_of_core(self, table) -> GlmModelBase:
        """Streaming fit over a :class:`~flink_ml_tpu.table.sources.ChunkedTable`.

        The dataset is never materialized: chunks stream through the fused
        per-chunk program (lib/out_of_core.py) with host->device prefetch.
        Step-major packing makes the result bit-identical to the in-memory
        fit of the same rows on the same step layout; the chunk program's
        is segment-CSR, so an in-memory fit that took the row-regular step
        (:meth:`_prepare_sparse`) agrees to float32 rounding (a row's
        products summed in another order).  Requires an explicit
        ``globalBatchSize`` (full-batch SGD needs the entire dataset
        resident by definition).

        Configurations with a full layout pre-pass (the multi-process
        shape/count scans) run under a
        :func:`~flink_ml_tpu.table.sources.chunk_cache`: the scan's text
        parse records binary chunks, the pack pass replays them — ONE text
        read of the source total (VERDICT r4 #3).
        """
        from flink_ml_tpu.table.sources import chunk_cache

        with chunk_cache(table, enabled=jax.process_count() > 1) as table:
            return self._fit_out_of_core_impl(table)

    def _fit_out_of_core_impl(self, table) -> GlmModelBase:
        from flink_ml_tpu.lib import out_of_core as oc
        from flink_ml_tpu.parallel.mesh import (
            data_parallel_size,
            local_data_parallel_size,
        )
        from flink_ml_tpu.table.schema import DataTypes

        env = MLEnvironmentFactory.get_default()
        mesh = env.get_mesh()
        # mb (per-device rows) comes from the GLOBAL axis; block packing
        # targets this process's LOCAL share (multi-process, each process
        # streams its own file shard into the global block queue)
        n_dev = data_parallel_size(mesh)
        n_dev_pack = local_data_parallel_size(mesh)
        model_size = data_parallel_size(mesh, "model")
        gbs = self.get_global_batch_size()
        if gbs is None or gbs <= 0:
            raise ValueError(
                "out-of-core training requires an explicit globalBatchSize "
                "(full batch would need the whole dataset resident)"
            )
        mb = max(1, -(-gbs // n_dev))
        G_local = mb * n_dev_pack
        steps_per_chunk = max(1, table.chunk_rows // G_local)
        label = self.get_label_col()
        vector_col = self.get_vector_col()
        if (vector_col is None) == (self.get_feature_cols() is None):
            raise ValueError("set exactly one of vectorCol / featureCols")
        lr, reg = self.get_learning_rate(), self.get_reg()
        checkpoint = self._checkpoint_config()
        schema = table.schema
        is_sparse = (
            vector_col is not None
            and schema.type_of(vector_col) == DataTypes.SPARSE_VECTOR
        )

        if is_sparse:
            if not self.LOSS_KIND:
                raise NotImplementedError(
                    f"{type(self).__name__} has no sparse loss kind"
                )
            dim = self.get_num_features()
            if dim is None:
                # a CSR-backed column carries the global width (the
                # categorical pipeline's encoder stamps it per chunk) —
                # peek one chunk before demanding the param
                from flink_ml_tpu.ops.batch import CsrRows

                chunks = table.chunks()
                try:
                    first = next(chunks, None)
                finally:
                    close = getattr(chunks, "close", None)
                    if close is not None:
                        close()
                if first is not None:
                    col = first.col(vector_col)
                    if isinstance(col, CsrRows):
                        dim = int(col.dim)
            if dim is None:
                raise ValueError(
                    "out-of-core sparse training requires numFeatures (the "
                    "global dimension cannot be inferred from a stream of "
                    "per-row sparse vectors)"
                )
            pad_to_blocks = None
            if jax.process_count() > 1:
                from flink_ml_tpu.parallel.mesh import agree_max

                # every process must compile the same block shapes AND
                # dispatch the same number of collective chunk calls per
                # epoch: ONE exact scan of the local shard (the sampled
                # estimate would disagree across processes), then agree on
                # the pad and the per-epoch block count — short shards pad
                # their epochs with gated no-op blocks
                nnz_local, rows_local = oc.scan_sparse_stream(
                    table, vector_col, mb
                )
                rows_per_block = steps_per_chunk * mb * n_dev_pack
                nnz_pad, pad_to_blocks = agree_max(
                    nnz_local, -(-rows_local // rows_per_block)
                )
            else:
                nnz_pad = oc.estimate_nnz_pad(table, vector_col, mb, n_dev)

            def extract(t):
                # the column passes through as-is: CsrRows (native stream)
                # stays vectorized end-to-end, object columns stay lists
                return (
                    t.col(vector_col),
                    np.asarray(t.col(label), dtype=np.float64),
                )

            blocks = oc.sparse_blocks_factory(
                table, extract, n_dev_pack, mb, steps_per_chunk, dim,
                nnz_pad, pad_to_blocks=pad_to_blocks,
            )
            if model_size > 1:
                # the north-star 2-D configuration: rows stream over 'data'
                # while the weight vector shards over 'model' — Criteo-scale
                # data AND a wider-than-one-chip model at once
                from jax.sharding import PartitionSpec as P

                from flink_ml_tpu.lib.common import (
                    make_feature_shard_placer,
                    make_sparse_mb_grad_step_2d,
                )

                place_params, trim, dim_pad = make_feature_shard_placer(
                    mesh, dim, model_size
                )
                mb_grad = make_sparse_mb_grad_step_2d(
                    self.LOSS_KIND, mb, nnz_pad, dim_pad // model_size,
                    self.get_with_intercept(),
                )
                param_spec = (P("model"), P())
                key = ("chunk-sparse2d", self.LOSS_KIND, mesh, mb, nnz_pad,
                       dim_pad, float(lr), float(reg),
                       self.get_with_intercept())
            else:
                from flink_ml_tpu.lib.common import make_sparse_mb_grad_step

                mb_grad = make_sparse_mb_grad_step(
                    self.LOSS_KIND, mb, nnz_pad, dim, self.get_with_intercept()
                )
                param_spec = None
                place_params = None
                trim = None
                key = ("chunk-sparse", self.LOSS_KIND, mesh, mb, nnz_pad, dim,
                       float(lr), float(reg), self.get_with_intercept())
        else:
            dim = self.get_num_features()
            if dim is None and self.get_feature_cols() is not None:
                dim = len(self.get_feature_cols())
            if dim is None:
                # vectorCol with unknown width: peek one chunk to pin it
                chunks = table.chunks()
                try:
                    first = next(chunks, None)
                finally:
                    close = getattr(chunks, "close", None)
                    if close is not None:
                        close()
                if first is None:
                    raise ValueError("empty source")
                _, dim = resolve_features(first, self)

            def extract(t):
                X, _ = resolve_features(t, self, dim=dim)
                return np.asarray(X), np.asarray(
                    t.col(label), dtype=np.float64
                )

            pad_to_blocks = None
            if jax.process_count() > 1:
                from flink_ml_tpu.parallel.mesh import agree_max

                # every process must dispatch the same number of collective
                # chunk calls per epoch: one row-count pass, then agree —
                # short shards pad with gated no-op blocks
                rows_per_block = steps_per_chunk * mb * n_dev_pack
                (pad_to_blocks,) = agree_max(
                    -(-oc.count_stream_rows(table) // rows_per_block)
                )
            blocks = oc.dense_blocks_factory(
                table, extract, n_dev_pack, mb, steps_per_chunk,
                pad_to_blocks=pad_to_blocks, pad_dim=dim,
            )
            grad_fn = self._grad_fn()

            def mb_grad(p, mbs):
                return grad_fn(p, mbs[..., :-2], mbs[..., -2], mbs[..., -1])

            param_spec = None
            place_params = None
            trim = None
            key = ("chunk-dense", grad_fn, mesh, float(lr), float(reg))

        w0, b0 = _zero_start(dim)
        use_spill = getattr(table, "spill", False) and self.get_max_iter() > 1
        with oc.maybe_spill(blocks, use_spill) as blocks:
            # guarded: a rollback retries at a backed-off learning rate —
            # the scale joins the program key so the colder-step chunk
            # program compiles fresh instead of hitting the hot one
            result = fault.run_guarded(
                lambda lr_scale: oc.train_out_of_core(
                    (w0, b0),
                    blocks,
                    lambda: oc.make_chunk_step_fn(
                        key + ("lrs", lr_scale), mb_grad, mesh,
                        lr * lr_scale, reg, param_spec=param_spec,
                    ),
                    mesh,
                    max_iter=self.get_max_iter(),
                    tol=self.get_tol(),
                    checkpoint=checkpoint,
                    place_params=place_params,
                ),
                what=type(self).__name__,
            )
        if trim is not None:  # the placer's own inverse: trim 2-D padding
            w_t, b_t = trim(result.params)
            result.params = (np.asarray(w_t), b_t)
        return self._finish(result)

    def _finish(self, result) -> GlmModelBase:
        with obs.span("fit.finish"):
            w, b = result.params
            if not self.get_with_intercept():
                b = 0.0
            model = self._make_model()
            model.get_params().merge(self.get_params())
            model.set_model_data(make_model_table(w, float(b)))
            model.train_epochs_ = result.epochs
            model.train_losses_ = result.losses
            model.train_metrics_ = result.metrics
        # the program's own exporter, inside the fit: a registry snapshot,
        # a JSON line and a file append whenever obs is on
        with obs.span("fit.report"):
            obs.fit_report(
                type(self).__name__,
                step_metrics=result.metrics,
                extra={
                    "epochs": result.epochs,
                    "loss": result.losses[-1] if result.losses else None,
                    **fit_pool_extra(self, result),
                },
            )
        return model
