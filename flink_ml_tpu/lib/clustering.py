"""KMeans — Lloyd iterations on the device mesh (ROADMAP.md, Reach: k=100).

The reference has no KMeans; this is the workload the roadmap names, built
on the same bounded-iteration + in-step-psum pattern as the GLMs: centroids
replicated, rows sharded over the ``data`` axis, one epoch = one device call
computing assignments (argmin over an MXU-friendly x·cᵀ distance matrix) and
the psum'd per-cluster sums/counts that yield the next centroids.

Init is k-means++ over a seeded sample of at most ``INIT_SAMPLE_CAP`` rows,
run on the device (:func:`kmeans_plus_plus`); empty clusters keep their
previous centroid.  Every product is computed in float32: the distance
product in six bfloat16 passes (``Precision.HIGHEST``), the per-cluster sums
as a one-hot product (:func:`_onehot_sums`).  An iteration reads its rows a
tile at a time (:func:`_lloyd_pass`), by one of two lowerings of the one
algorithm, picked from what the fit can observe, no knob
(:func:`_lloyd_kernel_rows`): on a TPU, float32 rows a multiple of 128 wide
(every table 512 columns wide or wider, as :func:`packed_width` lays it) go
through ONE Pallas call a pass (``ops/pallas_kernels.py:lloyd_sums``: a tile
read once, the sums from its three bfloat16 pieces, nine MXU passes a row);
any other table, mesh or platform through two XLA operations a tile (two
reads, twelve passes).  ``train.kmeans_onepass_fits`` /
``train.kmeans_onepass_declined`` say which a fit took.
"""

from __future__ import annotations

from functools import lru_cache
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
from flink_ml_tpu.parallel.collectives import psum, pvary
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


def _pairwise_sq_dists(x, c, x2=None):
    """(n, k) squared distances; the x·cᵀ term is the MXU matmul, at a
    STATED float32 precision: left to JAX's default a float32 product on a
    TPU is one bfloat16 pass, and ``x2 - 2 x·c + c2`` cancels, so that the
    nearest centroid of a row would be decided by the rounding.  HIGHEST is
    six bfloat16 passes (both operands in three pieces).  ``x2`` (n,) is the
    rows' squared norms where the caller holds them."""
    if x2 is None:
        x2 = jnp.sum(x * x, axis=1)
    c2 = jnp.sum(c * c, axis=1)
    xc = jnp.dot(x, c.T, precision=jax.lax.Precision.HIGHEST)
    return jnp.maximum(x2[:, None] - 2.0 * xc + c2, 0.0)


# module-level + memoized so the jit cache survives across mapper instances
def _assign_fn(x, c):
    d = _pairwise_sq_dists(x, c)
    return jnp.stack(
        [jnp.argmin(d, axis=1).astype(jnp.float64),
         jnp.min(d, axis=1).astype(jnp.float64)],
        axis=1,
    )


@lru_cache(maxsize=32)
def _assign_apply(mesh):
    """Mesh-sharded assignment: rows over 'data', centroids replicated
    (plain jit on a single chip)."""
    from flink_ml_tpu.parallel.collectives import make_data_parallel_apply

    return make_data_parallel_apply(_assign_fn, mesh, n_args=2)


def _onehot_sums(member, x):
    """``memberᵀ @ x``: (k, d) per-cluster sums of the rows of ``x`` (rows, d)
    by the membership ``member`` (rows, k) of zeros and ones.

    A row scatter (``segment_sum``) is serial per row on a TPU (66 ms a pass
    over 2,025,000 x 784 rows); the same sums as a product run on the MXU
    (13.7 ms).  0 and 1 are exact in bfloat16
    and ``HIGHEST`` multiplies a float32 value as its three bfloat16 pieces,
    so the product gives the scatter's sums to the order of a sum."""
    return jnp.dot(member.T, x, precision=jax.lax.Precision.HIGHEST)


#: rows a Lloyd iteration takes at a time.  The iteration is written over
#: row tiles so that no temporary is the table's size: the (tile, k) distances
#: and the one-hot membership are what it holds beside the resident rows
#: (48 MB).  On the chip 16384 / 32768 / 65536 rows read 29.14 / 28.43 /
#: 28.32 ms an iteration at 2,025,000 x 896 (PERF.md §6, PR 31).
_LLOYD_TILE_ROWS = 32768
#: a TPU's lanes: rows at least ``_LANE_PAD_FROM`` wide are packed to a
#: multiple of it (zero columns, at most a quarter more bytes), see
#: :func:`packed_width`
_LANES, _LANE_PAD_FROM = 128, 512


def packed_width(dim: int) -> int:
    """The width the fit's pack gives rows ``dim`` wide.  The distance
    product streams rows through the MXU features-minor, and the chip lays a
    float32 table whose width is no multiple of its 128 lanes rows-minor
    instead (784 wide: no padding that way), so that the compiler opens the
    program with a copy of the WHOLE table into the padded features-minor
    layout: a temporary larger than the table (7.26 GB beside 6.35 at
    2,025,000 x 784) and 20 ms a fit.  Packed lane-aligned the table lies as
    the program reads it.  Zero columns change no distance and no sum; a
    narrow table is left as it is (its padding would be most of it)."""
    if dim < _LANE_PAD_FROM:
        return dim
    return -(-dim // _LANES) * _LANES


def _lloyd_kernel_platform(mesh) -> bool:
    """Are the mesh's devices what the one-read kernel is built for: TPUs.
    (The tier-1 parity harness says yes for its CPU devices, and the kernel
    then runs on the interpreter: ``pallas_kernels.launch_interpreted``.)"""
    return mesh.devices.flat[0].platform == "tpu"


def _lloyd_kernel_rows(mesh, Xp, k: int) -> int:
    """The one-read kernel's row tile for a fit of the pack ``Xp`` (this
    process's rows, padded), or 0 for the XLA tiles: what the code can
    observe, no knob.  The kernel (``ops/pallas_kernels.py:lloyd_sums``)
    takes float32 rows a multiple of 128 wide (what :func:`packed_width`
    gives every table 512 columns wide or wider), at most 256 centroids, at
    least a lane chunk of rows a device, on a 1-D mesh of TPUs.  A fit on
    such a mesh that keeps the XLA tiles for its table is counted
    (``train.kmeans_onepass_declined``)."""
    if not _lloyd_kernel_platform(mesh):
        return 0
    rows = 0
    if len(mesh.axis_names) == 1 and Xp.ndim == 2 \
            and Xp.dtype == np.float32:
        from flink_ml_tpu.ops import pallas_kernels
        from flink_ml_tpu.parallel.mesh import local_data_parallel_size

        rows = pallas_kernels.lloyd_sums_tile(
            Xp.shape[0] // local_data_parallel_size(mesh), Xp.shape[1], k)
    if not rows:
        obs.counter_add("train.kmeans_onepass_declined")
    return rows


def _lloyd_pass(x, w, x2, c, k: int, tile: int, kernel_rows: int = 0,
                interpret: bool = False):
    """One pass over the local rows, a tile at a time: (cost, per-cluster
    sums (k, d), counts (k,)) of the rows under their nearest centroid of
    ``c``.  ``w`` is the pack's mask (1 a row of the table, 0 a pad row),
    ``x2`` the rows' squared norms.  With ``kernel_rows`` (what
    :func:`_lloyd_kernel_rows` found) the whole tiles of that many rows go
    through ONE Pallas call that reads each once; else two XLA operations
    read every tile of ``tile`` rows, one after the other.  The rows a whole
    number of tiles leaves are the XLA operations' either way."""
    n = x.shape[0]
    clusters = jnp.arange(k, dtype=jnp.int32)

    def part(xt, wt, x2t):
        with jax.named_scope("fmt.train.kmeans.assign"):
            d = _pairwise_sq_dists(xt, c, x2t)
            assign = jnp.argmin(d, axis=1).astype(jnp.int32)
            cost = jnp.sum(jnp.min(d, axis=1) * wt)
        with jax.named_scope("fmt.train.kmeans.update"):
            member = jnp.where(
                (assign[:, None] == clusters[None, :]) & (wt[:, None] > 0),
                1.0, 0.0).astype(jnp.float32)
            sums = _onehot_sums(member, xt)
            counts = jnp.sum(member, axis=0)
        return cost, sums, counts

    if kernel_rows:
        from flink_ml_tpu.ops import pallas_kernels

        with jax.named_scope("fmt.train.kmeans.onepass"):
            acc = pallas_kernels.lloyd_sums(
                x, w, x2, c, tile_rows=kernel_rows, interpret=interpret)
        done = n // kernel_rows * kernel_rows
    else:
        tile = max(1, min(int(tile), n))

        def body(i, acc):
            at = [jax.lax.dynamic_slice_in_dim(a, i * tile, tile, axis=0)
                  for a in (x, w, x2)]
            return jax.tree_util.tree_map(jnp.add, acc, part(*at))

        # the sums of a shard vary over the data axis from the first tile on
        acc = tuple(pvary(jnp.zeros(shape, jnp.float32)) for shape in
                    ((), (k, x.shape[1]), (k,)))
        acc = jax.lax.fori_loop(0, n // tile, body, acc)
        done = n // tile * tile
    if done < n:  # the rows a whole number of tiles leaves
        acc = jax.tree_util.tree_map(
            jnp.add, acc, part(*(a[done:] for a in (x, w, x2))))
    return acc


def make_kmeans_train_fn(mesh, k: int, max_iter: int, tol: float,
                         bundle: bool = True, kernel_rows: int = 0):
    """The WHOLE Lloyd run as one compiled device program.

    Reuses the GLM fused-loop scaffolding (lib/common.py
    ``_build_fused_train_fn``) with a Lloyd ``epoch_fn``: epochs are a
    ``lax.while_loop`` with the convergence test (centroid-shift norm vs
    tol) evaluated on device, so the iterations run start-to-finish with no
    host round-trip.  A fit makes two device calls: the k-means++ init over
    the resident rows (:func:`kmeans_plus_plus_rows`, whose centroids come
    back to the host once), then this program, bundled as the GLM fits are
    (``jit_bundled``: centroids + cost history + epochs + shift in ONE
    buffer, one fetch).  The rows go up once a table, through the slab pool.
    Rows shard over ``data``; the per-cluster sums/counts/cost ``psum`` over
    it (the reference's reduce-average round, SURVEY.md §3.3, fused
    on-chip); empty clusters keep their previous centroid.  An iteration
    reads its rows tile by tile (:func:`_lloyd_pass`).  With ``kernel_rows``
    > 0 (what :func:`_lloyd_kernel_rows` found for the fit's table) a tile
    is read ONCE, by one Pallas call under ``fmt.train.kmeans.onepass``
    (``ops/pallas_kernels.py:lloyd_sums``: the distance product's six
    bfloat16 passes, argmin, cost, and the sums from the tile's three
    bfloat16 pieces, nine MXU passes a row); otherwise twice, by two XLA
    operations of six passes each: distance product, argmin and cost under
    ``fmt.train.kmeans.assign``, the one-hot sums and counts under
    ``fmt.train.kmeans.update``.  The program's state is
    ``(centroids (k, d), trail (max_iter, k, d))``: the trail keeps the
    centroids every iteration started from, which come back with the result
    (``KMeansModel.train_centroids_``).
    """
    from flink_ml_tpu.lib.common import _build_fused_train_fn

    tile = int(_LLOYD_TILE_ROWS)
    key = ("kmeans", mesh, int(k), int(max_iter), float(tol), tile,
           int(kernel_rows))
    interpret = False
    if kernel_rows:
        from flink_ml_tpu.ops import pallas_kernels

        interpret = pallas_kernels.launch_interpreted()

    def lloyd_epoch(params, batch):
        # the trail holds the centroids each of the last ``max_iter``
        # iterations started from, oldest first: every iteration drops the
        # oldest and appends its own (7 MB moved at k 100 x 896, 20 deep)
        c, trail = params
        x, w = batch  # local shards: (rows, d), (rows,)
        with jax.named_scope("fmt.train.kmeans.assign"):
            # the rows' squared norms do not change with the centroids: the
            # compiler moves this pass out of the loop over iterations, one
            # a fit (8 ms) where a tile's own would be one an iteration
            x2 = jnp.sum(x * x, axis=1)
        cost, sums, counts = (
            psum(a, "data") for a in _lloyd_pass(
                x, w, x2, c, k, tile, int(kernel_rows), interpret))
        with jax.named_scope("fmt.train.kmeans.update"):
            new_c = jnp.where(
                counts[:, None] > 0,
                sums / jnp.maximum(counts[:, None], 1.0),
                c,
            )
            delta = jnp.sqrt(jnp.sum((new_c - c) ** 2))
            trail = jnp.concatenate([trail[1:], c[None]])
        return (new_c, trail), cost, delta

    train_fn = _build_fused_train_fn(
        key, None, mesh, 0.0, 0.0, max_iter, tol, epoch_fn=lloyd_epoch,
        # the interpreter's pallas_call fails strict vma (see
        # lib/common.py:make_glm_train_fn); Mosaic's passes it
        check_vma=not interpret, bundle=bundle,
    )
    if bundle and kernel_rows:
        #: read by _run_fused_train, which counts the interpreted fits
        train_fn.pallas_interpret = interpret
    return train_fn


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

    ``init_centroids`` may be a thunk (the k-means++ pass): it is only
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

    trails = []  # of the fused runs this call makes, in order

    def run(n_epochs, cents, dev_batch=None):
        cents = jnp.asarray(cents, dtype=jnp.float32)
        kernel_rows = _lloyd_kernel_rows(mesh, Xp, k)
        result = _run_fused_train(
            make_kmeans_train_fn(mesh, k, n_epochs, tol,
                                 kernel_rows=kernel_rows),
            # host zeros: placed once, as the program frees none of them
            (cents, np.zeros((n_epochs,) + cents.shape, np.float32)),
            batch if dev_batch is None else dev_batch, mesh,
            batch_preplaced=dev_batch is not None, n_rows=n_rows,
        )
        # the state's trail goes its own way: the centroids are the params
        # (what a snapshot holds, what the next chunk starts from)
        result.params, trail = result.params
        trails.append(trail[n_epochs - result.epochs:])
        # beside train.fused_runs: the Lloyd runs, and the rows they
        # assigned (rows x iterations run)
        obs.counter_add("train.kmeans_fits")
        obs.counter_add("train.kmeans_row_iters", n_rows * result.epochs)
        # of those, the runs whose program holds the one-read kernel (0
        # keeps the counter there from the first fit)
        obs.counter_add("train.kmeans_onepass_fits", int(kernel_rows > 0))
        return result

    def with_trail(result):
        # the centroids every iteration THIS call ran started from
        result.centroid_trail = (
            trails[0] if len(trails) == 1 else  # no copy of the usual one
            np.concatenate(trails) if trails else
            np.zeros((0,) + np.shape(result.params), np.float32))
        return result

    if checkpoint is None:
        cents0 = np.asarray(_resolve_thunk(init_centroids), dtype=np.float32)
        return with_trail(run(max_iter, cents0, _resolve_thunk(device_batch)))
    dim = Xp.shape[1]
    return with_trail(run_chunked_checkpoint(
        run, init_centroids, max_iter, tol, checkpoint, mesh, batch,
        device_batch=device_batch,
        like=np.zeros((k, dim), dtype=np.float32),  # structure template only
    ))


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


def _d2_race(sample, k: int, seed):
    """k-means++ over the rows of ``sample`` (S, d) float32: the first
    centre uniform, each further one by D² sampling.

    The draw is an exponential race: row i's clock rings at ``e_i / d2_i``
    with ``e_i`` standard exponential, and the first to ring is chosen, which
    picks row i with probability ``d2_i / sum(d2)`` as the inverted
    cumulative sum does.  The race is an argmax, so that rounding decides a
    draw only where two clocks ring within a rounding of each other; through
    100,000 partial sums rounding would pick a neighbouring row every few
    draws, and no second implementation could be held to the same rows.
    Returns the centres (k, d)."""
    size = sample.shape[0]
    key = jax.random.PRNGKey(seed)

    def dist_to(row):
        return jnp.sum((sample - sample[row]) ** 2, axis=1)

    first = jax.random.randint(
        jax.random.fold_in(key, 0), (), 0, size, jnp.int32)
    chosen0 = jnp.zeros((k,), jnp.int32).at[0].set(first)

    def draw(j, carry):
        d2, chosen = carry
        clock = jax.random.exponential(
            jax.random.fold_in(key, j), (size,), jnp.float32)
        # every row already a centre: any row, uniformly
        rate = jnp.where(jnp.sum(d2) > 0, d2, 1.0)
        row = jnp.argmax(rate / jnp.maximum(clock, 1e-30)).astype(jnp.int32)
        return jnp.minimum(d2, dist_to(row)), chosen.at[j].set(row)

    _d2, chosen = jax.lax.fori_loop(
        1, k, draw, (dist_to(first), chosen0))
    return sample[chosen]


@lru_cache(maxsize=32)
def _kmeans_pp_fn(k: int):
    return jax.jit(lambda sample, seed: _d2_race(
        sample.astype(jnp.float32), k, seed))


def kmeans_plus_plus(X, k: int, seed: int) -> np.ndarray:
    """Standard k-means++ seeding of a bounded sample ``X`` (rows, d), from
    ``seed``, in float32 on the device (:func:`_d2_race`): k − 1 passes over
    the sample.  Returns the centres (k, d) as a host float32 array."""
    sample = jnp.asarray(np.asarray(X, dtype=np.float32))
    centres = _kmeans_pp_fn(int(k))(sample, np.uint32(int(seed) % 2**32))
    return np.asarray(centres, dtype=np.float32)


@lru_cache(maxsize=32)
def _kmeans_pp_rows_fn(mesh, k: int):
    """k-means++ over rows ``take`` of the RESIDENT table (the fit's placed
    batch, rows sharded over ``data``): every device gathers the sample rows
    it holds, the ``psum`` makes the sample whole on each, and each runs the
    same race.  No row goes through the host."""
    from jax.sharding import PartitionSpec as P

    from flink_ml_tpu.parallel.collectives import shard_map

    def local(x, take, seed):
        rows = x.shape[0]
        at = take - jax.lax.axis_index("data") * rows
        mine = (at >= 0) & (at < rows)
        sample = jnp.where(
            mine[:, None], x[jnp.clip(at, 0, rows - 1)], 0.0)
        sample = psum(sample, "data")
        return _d2_race(sample, k, seed)

    return jax.jit(shard_map(
        local, mesh=mesh, in_specs=(P("data"), P(), P()), out_specs=P(),
        check_vma=False,
    ))


def kmeans_plus_plus_rows(device_batch, take: np.ndarray, k: int, seed: int,
                          mesh) -> np.ndarray:
    """:func:`kmeans_plus_plus` over the rows ``take`` (row numbers in the
    table's order) of the placed batch: the same centres as
    ``kmeans_plus_plus(X[take], k, seed)``, computed where the rows are."""
    x, _w = device_batch
    centres = _kmeans_pp_rows_fn(mesh, int(k))(
        x, jnp.asarray(take, dtype=jnp.int32),
        np.uint32(int(seed) % 2**32))
    return np.asarray(centres, dtype=np.float32)


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
    """Nearest-centroid assignment model; model data = the centroid table.

    A model that ``KMeans.fit`` returned also says how the fit went:
    ``train_epochs_`` (iterations run), ``train_costs_`` (the cost of every
    one: the sum of squared distances under the centroids it started from),
    ``train_cost_`` (the last of them) and ``train_centroids_`` (those
    centroids, ``(iterations, k, dim)`` float32, the init first)."""

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

    INIT_SAMPLE_CAP = 100_000  # k-means++ sample bound

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

        # fit.wall's direct children (fit.prepare, slab_pool.lookup,
        # kmeans.init, train.*, fit.finish, fit.report) account for a warm
        # fit, as GlmEstimatorBase.fit's do
        with obs.span("fit.wall"):
            self._fit_pool_stats0 = (
                *slab_pool.pool().counters(), _time.perf_counter()
            )
            (table,) = inputs
            if getattr(table, "is_chunked", False):
                return self._fit_out_of_core(table)
            with obs.span("fit.prepare"):
                train = self._prepare(table)
            return self._finish(train(), self.get_k())

    #: seeded samples kept a table (400 KB each): a grid of seeds re-fitted
    #: in turn finds every one of its samples drawn
    _INIT_ROWS_KEPT = 16

    def _init_rows(self, table, n: int) -> np.ndarray:
        """The seeded sample k-means++ runs over, as row numbers: all rows
        of a table within ``INIT_SAMPLE_CAP``, else that many drawn without
        replacement from the seed.  Kept with the table's packs, one entry
        for all seeds: the draw shuffles every row number, tens of
        milliseconds at two million rows."""
        cap, seed = self.INIT_SAMPLE_CAP, self.get_seed()
        if n <= cap:
            return np.arange(n, dtype=np.int32)
        drawn = table.cached_pack(("kmeans-init-rows", n, cap), dict)
        if seed not in drawn:
            while len(drawn) >= self._INIT_ROWS_KEPT:
                drawn.pop(next(iter(drawn)))
            drawn[seed] = np.random.RandomState(seed).choice(
                n, cap, replace=False).astype(np.int32)
        return drawn[seed]

    def _prepare(self, table):
        """Everything before the device calls: the features, the (cached)
        pack, the sample's rows.  Returns the fit, bound to its arguments."""
        from flink_ml_tpu.table import slab_pool

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
            shard_batch_prefetched,
        )

        n_global = int(agree_sum(np.asarray([n]))[0]) if n_proc > 1 else n
        if n_global < k:
            raise ValueError(f"k={k} exceeds number of rows {n_global}")
        n_dev = local_data_parallel_size(mesh)

        # local rows pad to a per-shard row count agreed across processes
        # (shard_batch needs identically-shaped local blocks; pad rows
        # carry zero weight)
        rows_per_shard = -(-n // n_dev)
        if n_proc > 1:
            (rows_per_shard,) = agree_max(rows_per_shard)

        width = packed_width(dim)
        if _lloyd_kernel_platform(mesh) and width % _LANES == 0:
            # Pallas and Mosaic take a second of host to import: on a
            # thread, ahead of the placement that hides it
            from flink_ml_tpu.lib.common import _start_kernels_import

            _start_kernels_import()

        def build():
            n_pad = rows_per_shard * n_dev
            Xp = np.zeros((n_pad, width), dtype=np.float32)
            Xp[:n, :dim] = X
            wp = np.zeros((n_pad,), dtype=np.float32)
            wp[:n] = 1.0
            return Xp, wp

        layout_key = ("kmeans", self.get_vector_col(),
                      tuple(self.get_feature_cols() or ()), n_dev,
                      rows_per_shard, width)
        Xp, wp = table.cached_pack(layout_key, build)
        kmeans_cols = (
            [self.get_vector_col()] if self.get_vector_col() is not None
            else list(self.get_feature_cols() or ())
        )
        placed = []

        def device_batch():
            # a thunk: a no-op resume (finished snapshot) must not pay the
            # host->device transfer, so placement resolves lazily, once, for
            # the init and the train call alike; the placement itself rides
            # the cross-fit slab pool (re-fitting the same table content
            # skips the transfer) and double-buffers the H2D hop
            if not placed:
                placed.append(slab_pool.get_or_place(
                    table, layout_key + ("dev",), mesh,
                    lambda: shard_batch_prefetched(mesh, (Xp, wp)),
                    cols=kmeans_cols or None,
                ))
            return placed[0]

        seed = self.get_seed()
        if n_proc > 1:
            # cross-process consistent seeding: each process contributes an
            # equal-size deterministic sample of ITS shard; the allgathered
            # pool is identical on every process, so the same-seeded
            # k-means++ pass picks the same replicated centroids everywhere.
            # Eager (not inside the init thunk): the gather is a collective
            # every process must reach, never skipped by a lazy resolve.
            rng = np.random.RandomState(seed)
            per = -(-self.INIT_SAMPLE_CAP // n_proc)
            s_p = min(n, per)
            local_sample = (
                X if n == s_p else X[rng.choice(n, s_p, replace=False)]
            )
            pool = _allgather_sample_pool(local_sample, per, dim, k)

            def init():
                with obs.span("kmeans.init"):
                    return np.pad(kmeans_plus_plus(pool, k, seed),
                                  ((0, 0), (0, width - dim)))
        else:
            take = self._init_rows(table, n)

            def init():
                # the k-means++ pass over the resident rows, as a thunk:
                # resolved by train_kmeans only on a fresh start — a
                # snapshot resume skips it entirely.  The pool lookup is
                # its own span, ahead of this one
                batch = device_batch()
                with obs.span("kmeans.init"):
                    return kmeans_plus_plus_rows(batch, take, k, seed, mesh)

        # guarded for the health sentinel's diagnostics, but with NO retry
        # budget: KMeans has no learning rate to back off, so a replay
        # would re-diverge bit-identically — fail fast with the guard's
        # framing instead of multiplying time-to-error
        def train():
            result = fault.run_guarded(
                lambda _lr_scale: train_kmeans(
                    init, k, Xp, wp, mesh,
                    max_iter=self.get_max_iter(), tol=self.get_tol(),
                    n_rows=n_global,
                    checkpoint=checkpoint, device_batch=device_batch,
                ),
                what=type(self).__name__, max_retries=0,
            )
            # the pack's zero columns go (see packed_width)
            result.params = np.asarray(result.params)[:, :dim]
            result.centroid_trail = result.centroid_trail[:, :, :dim]
            return result

        return train

    def _finish(self, result, k: int) -> KMeansModel:
        from flink_ml_tpu.lib.common import fit_pool_extra

        with obs.span("fit.finish"):
            centroids = np.asarray(result.params, dtype=np.float64)
            model_table = Table.from_rows(
                [(int(i), DenseVector(centroids[i])) for i in range(k)],
                CENTROID_SCHEMA,
            )
            model = KMeansModel()
            model.get_params().merge(self.get_params())
            model.set_model_data(model_table)
            model.train_epochs_ = result.epochs
            # the cost of every iteration run (the sum of squared distances
            # under the centroids the iteration STARTED from), and the last
            model.train_costs_ = [float(c) for c in result.losses]
            model.train_cost_ = (
                model.train_costs_[-1] if model.train_costs_ else 0.0)
            # the centroids every iteration this fit ran started from
            # (iterations, k, dim); the out-of-core fit keeps none
            model.train_centroids_ = np.asarray(
                getattr(result, "centroid_trail",
                        np.zeros((0,) + centroids.shape)), np.float32)
            model.train_metrics_ = result.metrics
        with obs.span("fit.report"):
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
            cents0 = kmeans_plus_plus(pool, k, self.get_seed())
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
            cents0 = kmeans_plus_plus(sample, k, self.get_seed())

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
