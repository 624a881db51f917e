"""Knn — brute-force k-nearest-neighbors classification (ROADMAP.md, Reach: MNIST Knn).

Model data is the training set itself (vectors + labels), following the
model-as-table convention.  ``transform`` is the benchmark workload: each
query batch computes one (batch, train) distance matrix — the x·cᵀ term is a
single MXU matmul — then ``lax.top_k`` + a one-hot vote picks the label.
Per-record distance loops (the reference's Mapper shape) never appear.

Large training sets are chunked on device to bound the distance-matrix
footprint; the running top-k is merged across chunks, so memory is
O(batch × chunk) instead of O(batch × train).
"""

from __future__ import annotations

from functools import lru_cache, partial
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from flink_ml_tpu import obs
from flink_ml_tpu.api.core import Estimator
from flink_ml_tpu.common.mapper import ModelMapper
from flink_ml_tpu.lib.common import apply_sharded, resolve_features
from flink_ml_tpu.parallel.collectives import pvary, shard_map
from flink_ml_tpu.lib.model_base import TableModelBase
from flink_ml_tpu.lib.params import (
    HasBf16Distances,
    HasFeatureColsDefaultAsNull,
    HasK,
    HasLabelCol,
    HasShardModelData,
    HasVectorColDefaultAsNull,
)
from flink_ml_tpu.params.shared import (
    HasPredictionCol,
    HasPredictionDetailCol,
    HasReservedCols,
)
from flink_ml_tpu.table.schema import DataTypes, Schema
from flink_ml_tpu.table.table import Table

KNN_MODEL_SCHEMA = Schema.of(
    ("features", DataTypes.DENSE_VECTOR), ("label", DataTypes.DOUBLE)
)


class KnnParams(
    HasVectorColDefaultAsNull,
    HasFeatureColsDefaultAsNull,
    HasK,
    HasBf16Distances,
    HasShardModelData,
    HasReservedCols,
    HasPredictionCol,
    HasPredictionDetailCol,
):
    """Shared vocabulary for the Knn estimator and model."""


@partial(jax.jit, static_argnums=(3, 4, 5))
def _knn_chunked(xq, xt, yt, k, chunk, bf16=False):
    """Top-k labels for query batch xq against chunked training data.

    Returns (labels (n, k), dists (n, k)).  xt/yt are padded to a multiple of
    ``chunk``; padded rows carry +inf distance so they never enter the top-k.

    Tie-breaking is canonical by (distance, global row index), including for
    exact distance ties, by induction over the scan: ``lax.top_k`` keeps the
    lower-*position* element on ties, and every merge's concatenation is in
    global-row-index order within tied groups — the carry holds the running
    best lex-sorted by (d, idx) (top_k returns sorted output), and each new
    chunk's rows appear in index order with indices larger than everything
    already carried.  The sharded path's cross-shard merge preserves the same
    invariant (shard order = row-block order), so replicated and sharded
    selections match bit-for-bit even on tied data — asserted by the
    duplicate-row tie test in tests/test_parallel_inference.py
    (test_exact_distance_ties_match_across_paths).
    """
    n = xq.shape[0]
    n_chunks = xt.shape[0] // chunk
    xq2 = jnp.sum(xq * xq, axis=1, keepdims=True)
    is_real = jnp.isfinite(yt)

    xq_mm = xq.astype(jnp.bfloat16) if bf16 else xq

    def scan_chunk(carry, idx):
        best_d, best_y = carry
        xc = jax.lax.dynamic_slice_in_dim(xt, idx * chunk, chunk)
        yc = jax.lax.dynamic_slice_in_dim(yt, idx * chunk, chunk)
        valid = jax.lax.dynamic_slice_in_dim(is_real, idx * chunk, chunk)
        if bf16:
            # bf16Distances: the cross term on the MXU in bf16 with f32
            # accumulation; norms stay f32 (HasBf16Distances contract)
            cross = jax.lax.dot_general(
                xq_mm, xc.astype(jnp.bfloat16),
                (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32,
            )
        else:
            cross = xq @ xc.T
        d = xq2 - 2.0 * cross + jnp.sum(xc * xc, axis=1)
        d = jnp.where(valid, d, jnp.inf)
        # merge running best with this chunk, re-select top-k
        cat_d = jnp.concatenate([best_d, d], axis=1)
        cat_y = jnp.concatenate([best_y, jnp.broadcast_to(yc, (n, chunk))], axis=1)
        neg_top, pos = jax.lax.top_k(-cat_d, k)
        return (-neg_top, jnp.take_along_axis(cat_y, pos, axis=1)), None

    # the +0 broadcasts inherit the inputs' varying-manual-axes (vma) status,
    # so the scan carry type-checks both under plain jit and inside a
    # shard_map where xt/yt vary over the mesh
    init = (
        jnp.full((n, k), jnp.inf, dtype=xq.dtype) + 0.0 * xq[:, :1],
        jnp.zeros((n, k), dtype=yt.dtype) + 0.0 * yt[:1],
    )
    (best_d, best_y), _ = jax.lax.scan(scan_chunk, init, jnp.arange(n_chunks))
    return best_y, best_d


@lru_cache(maxsize=32)
def _knn_apply_model_sharded(mesh, k, chunk, n_classes, bf16=False):
    """Reference-set-sharded kNN: the model (xt/yt) shards over 'data' so it
    need not fit one chip's HBM; queries replicate.

    Each device computes the full query batch's top-k against its local
    reference shard (the per-shard candidates), then one ``all_gather`` of
    the (n, k) candidate sets over ICI merges them into the global top-k —
    broadcast-variable semantics (ModelMapperAdapter.java:53-61) scaled past
    one device's memory.  Work parallelizes over the reference dimension
    instead of the query dimension; total FLOPs are identical to the
    replicated path and the candidate exchange is k/|shard| of the distance
    traffic a naive gather of distances would move.
    """
    from jax.sharding import PartitionSpec as P

    def local_candidates(xq, xt_local, yt_local):
        # queries are replicated (unvarying) but meet the varying reference
        # shard inside the top-k scan carry: mark them varying up front
        xq = pvary(xq, ("data",))
        labels, dists = _knn_chunked(xq, xt_local, yt_local, k, chunk, bf16)
        # leading size-1 axis: the shard_map output gather stacks shards
        # there, giving (n_dev, n, k, 2) without any in-program collective
        return jnp.stack([labels, dists], axis=2)[None]

    sharded = shard_map(
        local_candidates,
        mesh=mesh,
        in_specs=(P(), P("data"), P("data")),
        out_specs=P("data"),
        check_vma=True,
    )

    def apply(xq, xt, yt):
        cand = sharded(xq, xt, yt)  # (n_dev, n, k, 2) per-shard candidates
        n = xq.shape[0]
        # concat in mesh-device order = global row-block order, each shard's
        # candidates lex-sorted by (d, idx): positional top_k tie-break
        # therefore equals the canonical (d, global idx) selection the
        # replicated scan makes (see _knn_chunked docstring)
        cat_y = jnp.transpose(cand[..., 0], (1, 0, 2)).reshape(n, -1)
        cat_d = jnp.transpose(cand[..., 1], (1, 0, 2)).reshape(n, -1)
        neg_top, pos = jax.lax.top_k(-cat_d, k)
        best_d = -neg_top
        best_y = jnp.take_along_axis(cat_y, pos, axis=1)
        pred = _majority_vote(best_y.astype(jnp.int32), best_d, n_classes)
        return jnp.concatenate(
            [pred[:, None].astype(xq.dtype), best_d.astype(xq.dtype)], axis=1
        )

    return jax.jit(apply)


@lru_cache(maxsize=32)
def _knn_apply(mesh, k, chunk, n_classes, bf16=False):
    """Mesh-sharded kNN transform: query rows shard over 'data', the training
    set (the model) replicates to every device — the broadcast-variable
    analog (ModelMapperAdapter.java:53-61) for the benchmark transform
    workload.  Plain jit on a single chip."""
    from flink_ml_tpu.parallel.collectives import make_data_parallel_apply

    def forward(xq, xt, yt):
        labels, dists = _knn_chunked(xq, xt, yt, k, chunk, bf16)
        pred = _majority_vote(labels.astype(jnp.int32), dists, n_classes)
        # class ids and distances are exact in f32 (ids are small ints);
        # staying f32 avoids per-call x64 truncation on TPU
        return jnp.concatenate(
            [pred[:, None].astype(xq.dtype), dists.astype(xq.dtype)], axis=1
        )

    return make_data_parallel_apply(forward, mesh, n_args=3)


@partial(jax.jit, static_argnums=(2,))
def _majority_vote(labels, dists, n_classes):
    """Mode of each row of integer class ids via one-hot sum (ties -> lowest id).

    Slots that never matched a real training row (distance inf — possible when
    k exceeds the training-set size) carry no vote: one_hot of an out-of-range
    id contributes all-zeros.
    """
    labels = jnp.where(jnp.isfinite(dists), labels, n_classes)
    one_hot = jax.nn.one_hot(labels, n_classes, dtype=jnp.float32)
    votes = jnp.sum(one_hot, axis=1)
    return jnp.argmax(votes, axis=1)


class KnnModelMapper(ModelMapper):
    def __init__(self, model: "KnnModel", data_schema: Schema):
        self._model_stage = model
        super().__init__([KNN_MODEL_SCHEMA], data_schema, model.get_params())

    def reserved_cols(self) -> Optional[list]:
        return self._model_stage.get_reserved_cols()

    def output_cols(self):
        model = self._model_stage
        names = [model.get_prediction_col()]
        types = [DataTypes.DOUBLE]
        if model.get_prediction_detail_col() is not None:
            names.append(model.get_prediction_detail_col())
            types.append(DataTypes.DOUBLE)
        return names, types

    def load_model(self, *model_tables: Table) -> None:
        (t,) = model_tables
        X = t.features_dense("features")  # matrix-backed or object column
        y = np.asarray(t.col("label"), dtype=np.float64)
        k = self._model_stage.get_k()
        if k > len(y):
            raise ValueError(f"k={k} exceeds training-set size {len(y)}")
        # class-id encoding for the vote
        self._classes = np.unique(y)
        y_ids = np.searchsorted(self._classes, y)
        # host references for the circuit-breaker CPU fallback (the
        # reference set IS the model; a dead device path must still answer
        # queries).  References, not f32 copies: the fallback converts one
        # reference chunk at a time, so the healthy path pays no extra
        # host residency beyond the model table it already holds
        self._xt_host = X
        self._yt_ids = np.asarray(y_ids, dtype=np.int64)

        from flink_ml_tpu.parallel.mesh import (
            data_parallel_size,
            inference_mesh,
        )
        from flink_ml_tpu.utils.environment import MLEnvironmentFactory

        # multi-process, the model places on the process-LOCAL mesh: each
        # process holds its own full model copy and scores its own rows
        # (subtask-local ModelMapperAdapter semantics); shardModelData then
        # spreads the reference set over this process's chips only
        mesh = inference_mesh(MLEnvironmentFactory.get_default().get_mesh())
        n_dev = data_parallel_size(mesh)
        self._sharded = (
            bool(self._model_stage.get_shard_model_data()) and n_dev > 1
        )
        shards = n_dev if self._sharded else 1
        # chunk bounds the per-device distance-matrix slice; under model
        # sharding it is sized on the LOCAL shard, so per-device HBM holds
        # 1/n_dev of the reference set
        local = -(-max(X.shape[0], 1) // shards)
        chunk = min(8192, max(256, 1 << int(np.ceil(np.log2(local)))))
        n_pad = shards * (-(-local // chunk) * chunk)

        def place_model():
            Xp = np.zeros((n_pad, X.shape[1]), dtype=np.float32)
            Xp[: X.shape[0]] = X
            # inf marks padding (never wins top-k); f32 holds class ids
            # exactly
            yp = np.full((n_pad,), np.inf, dtype=np.float32)
            yp[: y.shape[0]] = y_ids
            if self._sharded:
                # direct local placement (not shard_batch, whose
                # multi-process branch assembles GLOBAL batches): the
                # inference mesh is fully addressable by this process in
                # every configuration
                from jax.sharding import NamedSharding, PartitionSpec as P

                return (
                    jax.device_put(Xp, NamedSharding(mesh, P("data"))),
                    jax.device_put(yp, NamedSharding(mesh, P("data"))),
                )
            return jnp.asarray(Xp), jnp.asarray(yp)

        # the placed reference set IS the model; for Knn that is the whole
        # training table, so re-loading the same model content (a fresh
        # mapper over the same model table) must hit the slab pool instead
        # of re-transferring the training set
        from flink_ml_tpu.table import slab_pool

        if slab_pool.enabled():
            refs: list = []
            token = (slab_pool.array_token(X, refs),
                     slab_pool.array_token(y, refs))
            # agreed=False: model load happens on the process-LOCAL
            # inference mesh with no cross-process collectives — the pool
            # must not add one
            self._xt, self._yt = slab_pool.pool().get_or_build(
                ("knn-model", mesh, self._sharded, chunk, n_pad, token),
                place_model, refs=refs, agreed=False,
            )
        else:
            self._xt, self._yt = place_model()
        self._chunk = chunk

    def serve_validation_spec(self):
        model = self._model_stage
        return {
            "dim": int(self._xt.shape[1]),
            "vector_col": model.get_vector_col(),
            "feature_cols": model.get_feature_cols(),
        }

    def map_batch(self, batch: Table):
        from flink_ml_tpu import serve

        model = self._model_stage
        k = model.get_k()
        X, _ = resolve_features(batch, model, dim=int(self._xt.shape[1]))
        X = X.astype(np.float32)
        n = X.shape[0]
        apply_factory = (
            _knn_apply_model_sharded if self._sharded else _knn_apply
        )
        out = serve.dispatch(
            self.serve_name(),
            device=lambda: apply_sharded(
                lambda mesh: apply_factory(
                    mesh, k, self._chunk, len(self._classes),
                    bool(model.get_bf16_distances()),
                ),
                X, self._xt, self._yt,
            ),
            fallback=lambda: self._map_cpu(X, k),
        )
        return self._vote_cols(out[:n])

    def _vote_cols(self, out):
        model = self._model_stage
        pred_ids = out[:, 0].astype(np.int64)
        result = {model.get_prediction_col(): self._classes[pred_ids]}
        detail = model.get_prediction_detail_col()
        if detail is not None:
            result[detail] = np.sqrt(np.maximum(out[:, 1], 0.0))  # nearest distance
        return result

    def fused_kernel(self):
        if self._sharded:
            # a data-axis-sharded reference set computes under its own
            # collective-bearing apply; it cannot ride a replicated-args
            # fused program — the plan splits and serves as today
            return None
        from flink_ml_tpu.common.fused import FusedInput, FusedKernel

        model = self._model_stage
        k = model.get_k()
        chunk = self._chunk
        n_classes = len(self._classes)
        bf16 = bool(model.get_bf16_distances())
        feature_cols = model.get_feature_cols()

        def fn(xq, xt, yt):
            labels, dists = _knn_chunked(xq, xt, yt, k, chunk, bf16)
            pred = _majority_vote(labels.astype(jnp.int32), dists, n_classes)
            return {"knn": jnp.concatenate(
                [pred[:, None].astype(xq.dtype), dists.astype(xq.dtype)],
                axis=1,
            )}

        return FusedKernel(
            inputs=[FusedInput(
                dim=int(self._xt.shape[1]),
                vector_col=model.get_vector_col(),
                feature_cols=tuple(feature_cols) if feature_cols else None,
            )],
            fn=fn,
            out_keys=("knn",),
            # fn closes over program-shaping constants invisible in the
            # arg shapes — they must key the warm-artifact entry
            cache_token=(k, chunk, n_classes, bf16),
            model_args=(self._xt, self._yt),
            finalize=lambda fetched, n: self._vote_cols(fetched["knn"]),
        )

    #: reference rows per CPU-fallback chunk — bounds the fallback's
    #: distance-matrix slice to O(batch x chunk), mirroring the device scan
    CPU_FALLBACK_CHUNK = 8192

    def _map_cpu(self, X: np.ndarray, k: int) -> np.ndarray:
        """NumPy top-k + vote fallback with the device scan's memory bound:
        the reference set streams through in chunks, a running best-k
        carries across them, and memory stays O(batch x chunk) — never the
        full (batch, train) matrix (a million-row model's fallback must
        not OOM the serving host during the exact outage it exists for).
        Tie-break parity with the device scan: the carry is sorted by
        (distance, global row index) and each chunk appends rows in index
        order, so a stable selection keeps the lower global index on exact
        ties; votes break ties toward the lowest class id."""
        xt, yt = self._xt_host, self._yt_ids
        n = X.shape[0]
        chunk = self.CPU_FALLBACK_CHUNK
        x2 = np.sum(X * X, axis=1, keepdims=True, dtype=np.float32)
        best_d = np.full((n, k), np.inf, dtype=np.float32)
        best_y = np.zeros((n, k), dtype=np.int64)
        for a in range(0, xt.shape[0], chunk):
            xc = np.asarray(xt[a : a + chunk], dtype=np.float32)
            yc = yt[a : a + chunk]
            d = x2 - 2.0 * (X @ xc.T) + np.sum(xc * xc, axis=1)
            cat_d = np.concatenate([best_d, d.astype(np.float32)], axis=1)
            cat_y = np.concatenate(
                [best_y, np.broadcast_to(yc, (n, yc.shape[0]))], axis=1
            )
            order = np.argsort(cat_d, axis=1, kind="stable")[:, :k]
            best_d = np.take_along_axis(cat_d, order, axis=1)
            best_y = np.take_along_axis(cat_y, order, axis=1)
        n_classes = len(self._classes)
        votes = np.zeros((n, n_classes), dtype=np.int64)
        for c in range(n_classes):
            votes[:, c] = np.sum(
                np.logical_and(best_y == c, np.isfinite(best_d)), axis=1
            )
        pred = np.argmax(votes, axis=1)  # argmax keeps the lowest id on ties
        return np.concatenate(
            [pred[:, None].astype(np.float32), best_d], axis=1
        )


class KnnModel(TableModelBase, KnnParams):
    """Brute-force kNN classifier; model data = the training table."""

    REQUIRED_MODEL_COL = "features"

    def _make_mapper(self, data_schema: Schema) -> KnnModelMapper:
        return KnnModelMapper(self, data_schema)


class Knn(Estimator, KnnParams, HasLabelCol):
    """Estimator: fit = pack the training table into the model-data layout."""

    def fit(self, *inputs: Table) -> KnnModel:
        (table,) = inputs
        X, dim = resolve_features(table, self)
        y = np.asarray(table.col(self.get_label_col()), dtype=np.float64)
        model = KnnModel()
        model.get_params().merge(self.get_params())
        # matrix-backed model column: the training set stays one contiguous
        # array end-to-end (fit -> model table -> device placement)
        model.set_model_data(Table.from_columns(
            KNN_MODEL_SCHEMA, {"features": np.asarray(X), "label": y}
        ))
        obs.fit_report(
            type(self).__name__,
            extra={"n_train": int(len(y)), "dim": int(dim)},
        )
        return model
