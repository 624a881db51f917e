"""Out-of-core training: epochs stream source chunks through one compiled
per-chunk device program, with host parse/pack/transfer prefetched one chunk
ahead of device compute.

The reference trains on datasets no node holds by streaming partitions
through Flink's network stack (the partitioned CSV read in
examples-batch/.../LinearRegression.java:91-102); every prior path here
materialized the whole dataset on the host (VERDICT r02 gap #1).  The
TPU-first replacement:

  * a :class:`~flink_ml_tpu.table.sources.ChunkedTable` yields bounded
    chunk Tables from a (possibly sharded) file source — host residency is
    ~two chunks, never the dataset;
  * chunks are re-buffered into fixed blocks of ``steps_per_chunk`` global
    SGD steps and packed step-major (``pack_minibatches``), so the
    row->update-step mapping is *identical* to the in-memory fused run —
    out-of-core results bit-match in-memory results by construction, for
    any chunk size;
  * one ``jit(shard_map(lax.scan(...)))`` program advances
    ``(params, loss_sum, weight_sum)`` through a block; whole-pad steps
    (the tail of the final block) are gated no-ops;
  * a background thread parses/packs/places block N+1 while the device runs
    block N (JAX dispatch is async, so device compute, host parse, and
    host->device DMA overlap);
  * per-epoch loss/delta stay on device; with ``tol == 0`` the entire
    multi-epoch run syncs exactly once, at the final fetch.

Works on 1-D (data) and 2-D (data x model) meshes: by default the weight
pytree replicates; the feature-sharded 2-D configuration passes a
``param_spec``/``place_params`` pair so rows stream over ``data`` while the
weight vector stays sharded over ``model`` — Criteo-scale data and a
wider-than-one-chip model at once.
"""

from __future__ import annotations

import contextlib
import os
import warnings
from collections import deque
from typing import Callable, Iterator, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from flink_ml_tpu import fault, obs
from flink_ml_tpu.lib.common import (
    TrainResult,
    _cache_get,
    _cache_put,
    _combined_view,
    _meta_converged,
    _psum_step,
    fetch_flat,
    make_sgd_update,
    pack_minibatches,
    pack_sparse_minibatches,
)
from flink_ml_tpu.ops.batch import CsrRows
from flink_ml_tpu.parallel.collectives import psum, shard_map
from flink_ml_tpu.table.table import Table
from flink_ml_tpu.utils.metrics import StepMetrics


def make_chunk_step_fn(key, mb_grad_step, mesh, learning_rate: float, reg: float,
                       param_spec=None):
    """One chunk — a ``lax.scan`` over its minibatch groups — as a single
    compiled device call: ``chunk_fn(carry, batch) -> (carry, tick)`` with
    ``carry = (params, loss_sum, weight_sum)`` and ``tick`` a scalar the
    engine blocks on to bound the async pipeline.

    The minibatch math and SGD update are the exact objects the in-memory
    fused loop uses (``mb_grad_step``, :func:`make_sgd_update`), so a live
    step's update is bit-identical; a whole-pad step (``weight sum == 0``,
    only possible in the final block's tail) is gated to a no-op so padding
    can never apply an extra decay step.  ``param_spec`` overrides the
    replicated param placement (feature-sharded weights on the ``model``
    axis — the 2-D Criteo configuration).
    """
    cached = _cache_get(key)
    if cached is not None:
        return cached
    sgd_update = make_sgd_update(learning_rate, reg)

    def local_chunk(carry, batch):
        def mb_step(c, xs):
            p, loss_acc, w_acc = c
            grads, loss_sum, w_sum = mb_grad_step(p, xs)
            with jax.named_scope("fmt.train.grad"):
                grads, loss_sum, w_sum = _psum_step(grads, loss_sum, w_sum)
            count = jnp.maximum(w_sum, 1.0)
            new_p = sgd_update(p, grads, count)
            live = w_sum > 0.0
            new_p = jax.tree_util.tree_map(
                lambda a, b: jnp.where(live, a, b), new_p, p
            )
            # accumulators stay f32 regardless of param dtype (x64 resume)
            return (
                new_p,
                loss_acc + loss_sum.astype(loss_acc.dtype),
                w_acc + w_sum.astype(w_acc.dtype),
            ), None

        carry, _ = jax.lax.scan(mb_step, carry, batch)
        # the tick: a scalar the engine can block on to bound the async
        # pipeline.  optimization_barrier guarantees a distinct buffer —
        # a folded alias of carry[2] would be deleted by the next call's
        # donation, breaking the block_until_ready contract
        return carry, jax.lax.optimization_barrier(carry[2])

    from jax.sharding import PartitionSpec as P

    carry_spec = (param_spec if param_spec is not None else P(), P(), P())
    sharded = shard_map(
        local_chunk,
        mesh=mesh,
        in_specs=(carry_spec, P("data")),
        out_specs=(carry_spec, P()),
        check_vma=True,
    )
    return _cache_put(key, jax.jit(sharded, donate_argnums=(0,)))


@jax.jit
def _l2_delta(params, start):
    return jnp.sqrt(
        sum(
            jnp.sum((a - b) ** 2)
            for a, b in zip(
                jax.tree_util.tree_leaves(params),
                jax.tree_util.tree_leaves(start),
            )
        )
    )


def _block_rows(chunks: Iterator[Table], extract, rows_per_block: int):
    """Re-buffer arbitrary-size source chunks into exact ``rows_per_block``
    row blocks (the final block may be short).  ``extract(table) ->
    per-column host arrays/lists``; yields tuples of re-sliced columns.

    Source chunk boundaries need not align with block boundaries — the
    carry-over buffer here is what makes the update schedule independent of
    how the files happen to be cut.
    """
    buffers: Optional[list] = None
    have = 0
    for t in chunks:
        cols = extract(t)
        if buffers is None:
            buffers = [[] for _ in cols]
        for buf, col in zip(buffers, cols):
            buf.append(col)
        have += len(cols[-1])
        while have >= rows_per_block:
            joined = [_join(parts) for parts in buffers]
            head = [j[:rows_per_block] for j in joined]
            rest = [j[rows_per_block:] for j in joined]
            buffers = [[r] for r in rest]
            have -= rows_per_block
            yield tuple(head)
    if have:
        yield tuple(_join(parts) for parts in buffers)


def _join(parts: list):
    if len(parts) == 1:
        return parts[0]
    if all(isinstance(p, CsrRows) for p in parts):
        return CsrRows.concat(parts)
    if isinstance(parts[0], np.ndarray) and parts[0].dtype != object:
        return np.concatenate(parts)
    out = []
    for p in parts:
        out.extend(p)
    return out


def _prefetch(items: Iterator, depth: int = 2) -> Iterator:
    """Run an iterator on a background thread, ``depth`` items ahead.

    The producer packs a block and places it on the mesh (an async DMA), so
    host parse + pack + transfer of block N+1 overlap device compute of
    block N.  Exceptions re-raise at the consumer; when the consumer
    abandons the stream early, the producer thread is joined and a recorded
    producer exception surfaces as a RuntimeWarning instead of being
    silently dropped with the queue (the ONE shared implementation lives in
    :func:`flink_ml_tpu.utils.prefetch.prefetch_iter` — the slab pool's
    double-buffered placement uses the same idiom)."""
    from flink_ml_tpu.utils.prefetch import prefetch_iter

    return prefetch_iter(items, depth=depth, name="oo-prefetch")


_serialized_chunks_warned = False


def _warn_serialized_chunks_once():
    """One-time notice that the async chunk pipeline is serialized (gloo
    rendezvous livelock workaround on multi-process CPU); set
    FLINK_ML_TPU_ASYNC_CPU_CHUNKS=1 to keep the pipeline async."""
    global _serialized_chunks_warned
    if not _serialized_chunks_warned:
        _serialized_chunks_warned = True
        warnings.warn(
            "multi-process CPU backend: serializing out-of-core chunk "
            "programs to avoid a gloo in-process rendezvous livelock; "
            "set FLINK_ML_TPU_ASYNC_CPU_CHUNKS=1 to keep the async "
            "pipeline on",
            stacklevel=3,
        )


def train_out_of_core(
    init_params,
    blocks_factory: Callable[[], Iterator[Tuple]],
    chunk_fn_factory: Callable[[], Callable],
    mesh,
    max_iter: int,
    tol: float,
    checkpoint=None,
    make_carry: Optional[Callable] = None,
    finalize: Optional[Callable] = None,
    place_params: Optional[Callable] = None,
    max_inflight_chunks: int = 4,
) -> TrainResult:
    """The streaming epoch engine.

    ``blocks_factory()`` restarts the chunk stream for an epoch, yielding
    host ``(batch, n_real_rows)``; the prefetch thread places each block on
    the mesh (async DMA) while the device runs the previous one.
    ``chunk_fn_factory()`` returns the compiled chunk program
    (``chunk_fn(carry, batch) -> (carry, tick)``).  Convergence
    (update-norm vs ``tol``) and checkpoint/resume semantics mirror the
    fused in-memory loop; with ``tol == 0`` and no checkpoint, the whole
    run syncs once at the end.

    SGD-shaped algorithms use the default carry ``(params, loss_sum,
    weight_sum)`` updated per minibatch.  Accumulate-then-finalize
    algorithms (KMeans' Lloyd step) pass ``make_carry(params) -> carry``
    (fresh per-epoch accumulators) and ``finalize(carry, epoch_start) ->
    (params, loss_sum, weight_sum, delta)`` (the per-epoch reduction, e.g.
    centroid division), both running on device.  ``place_params`` overrides
    the default replicated placement (feature-sharded weights live on the
    ``model`` axis); the default delta/loss math operates on global arrays,
    so it is sharding-agnostic.

    ``max_inflight_chunks`` bounds the async pipeline depth: JAX dispatch
    returns before transfer or compute finishes, so without a bound every
    block of an epoch can pile up in flight (host staging + HBM for each).
    The consumer blocks on a chunk-completion tick N chunks back before
    dispatching chunk N, capping live-block residency at ~(prefetch depth +
    max_inflight) while keeping the device busy.
    """
    from flink_ml_tpu.parallel.mesh import replicate, shard_batch

    # cross-process chunk programs carry collectives; letting several run
    # concurrently on the CPU gloo backend intermittently livelocks its
    # in-process rendezvous (observed: both workers wedge mid-epoch with
    # all programs dispatched).  Serialize there: each chunk completes —
    # collectives included — before the next dispatches (prefetch still
    # overlaps host parse/pack with device compute).  Scoped to the CPU
    # backend: multihost TPU collectives run on per-core hardware queues
    # where concurrent in-flight programs are the designed norm, so the
    # async pipeline stays on for the production platform.
    # Escape hatch for intentional multi-process CPU deployments that do
    # not hit the gloo livelock: FLINK_ML_TPU_ASYNC_CPU_CHUNKS=1 keeps the
    # async pipeline on.
    serialize_chunks = (
        jax.process_count() > 1
        and jax.default_backend() == "cpu"
        and os.environ.get("FLINK_ML_TPU_ASYNC_CPU_CHUNKS", "0") != "1"
    )
    if serialize_chunks:
        _warn_serialized_chunks_once()

    start_epoch = 0
    losses: list = []
    if checkpoint is not None:
        from flink_ml_tpu.iteration.checkpoint import (
            agreed_latest_checkpoint,
            load_checkpoint,
        )

        latest = agreed_latest_checkpoint(checkpoint.directory)
        if latest is not None:
            init_params, meta = load_checkpoint(latest, like=init_params)
            if "hotcold_layout" in meta:
                # written by the retired numHotFeatures route, whose
                # streamed checkpoints hold the weights in a permuted
                # feature order that can have the table's own shape
                raise ValueError(
                    f"checkpoint {latest} was written by the retired "
                    "numHotFeatures (hot/cold) route and holds its weights "
                    "in a permuted feature order; start the fit afresh "
                    "in an empty checkpointDir"
                )
            start_epoch = int(meta["epoch"]) + 1
            losses = list(meta.get("losses", []))
            if _meta_converged(meta, tol) or start_epoch >= max_iter:
                delta = meta.get("final_delta")
                return TrainResult(
                    params=init_params, epochs=start_epoch, losses=losses,
                    final_delta=None if delta is None else float(delta),
                )

    metrics = StepMetrics("stream_train")
    metrics.start_step()
    params = (
        place_params(init_params) if place_params is not None
        else replicate(mesh, init_params)
    )
    params = jax.tree_util.tree_map(
        lambda p, o: jnp.copy(p) if isinstance(o, jax.Array) else p,
        params, init_params,
    )
    chunk_fn = chunk_fn_factory()
    pending: list = []  # (loss_sum, weight_sum) device scalars per epoch
    last_delta_dev = None
    total_rows = 0
    final_delta: Optional[float] = None
    epoch = start_epoch
    converged = False
    # checkpointed runs catch SIGTERM for the duration of the loop: the
    # flag is polled at epoch boundaries (the only points bit-identical to
    # an uninterrupted run), an emergency snapshot commits, and the process
    # exits cleanly for the existing resume path to continue
    scope = (
        fault.preemption_scope() if checkpoint is not None
        else contextlib.nullcontext()
    )
    with scope:
        while epoch < max_iter and not converged:
            epoch_start = jax.tree_util.tree_map(jnp.copy, params)
            # fresh accumulators every epoch: the chunk program donates its
            # carry, so a reused zero scalar would be a deleted buffer
            if make_carry is not None:
                carry = make_carry(params)
            else:
                carry = (params, jnp.zeros((), dtype=jnp.float32),
                         jnp.zeros((), dtype=jnp.float32))
            n_rows = 0

            def placed_blocks():
                from flink_ml_tpu.fault.retry import with_retry

                for batch, real in blocks_factory():
                    # per-block H2D placement is a transient-failure
                    # surface (device blips, injected chaos): retried with
                    # backoff so one hiccup doesn't abort the epoch
                    placed = with_retry(
                        lambda b=batch: shard_batch(mesh, b), "ooc.place"
                    )
                    yield placed, real

            inflight: deque = deque()
            for placed, real_rows in _prefetch(placed_blocks()):
                carry, tick = chunk_fn(carry, placed)
                n_rows += real_rows
                if serialize_chunks:
                    jax.block_until_ready(tick)
                    continue
                inflight.append(tick)
                if len(inflight) > max_inflight_chunks:
                    jax.block_until_ready(inflight.popleft())
            inflight.clear()
            if finalize is not None:
                params, loss_sum, w_sum, last_delta_dev = finalize(
                    carry, epoch_start
                )
            else:
                params, loss_sum, w_sum = carry
                last_delta_dev = _l2_delta(params, epoch_start)
            pending.append((loss_sum, w_sum))
            total_rows += n_rows
            epoch += 1
            obs.counter_add("train.ooc_epochs")
            obs.counter_add("train.ooc_rows", n_rows)
            if tol > 0.0:
                final_delta = float(last_delta_dev)  # per-epoch sync tol demands
                converged = final_delta <= tol
            # a run that just FINISHED (converged or out of epochs) at this
            # boundary returns its result instead of exiting for resume —
            # same rule as run_chunked_checkpoint's epilogue
            preempt_now = (
                checkpoint is not None and fault.preempted()
                and not converged and epoch < max_iter
            )
            at_boundary = checkpoint is not None and (
                (epoch - start_epoch) % checkpoint.every_n_epochs == 0
                or epoch == max_iter or converged
            )
            if at_boundary or preempt_now:
                from flink_ml_tpu.iteration.checkpoint import (
                    prune_checkpoints,
                    save_checkpoint,
                )

                losses.extend(_drain_pending(pending))
                leaves, treedef = jax.tree_util.tree_flatten(params)
                host_leaves = fetch_flat(*leaves)
                host_params = jax.tree_util.tree_unflatten(
                    treedef, host_leaves
                )
                # health BEFORE the snapshot: the latest checkpoint must
                # always be the last GOOD state, or the guard's rollback
                # would resume straight back into the divergence
                fault.check_health(
                    losses, host_leaves, where="stream_train"
                )

                def _snapshot():
                    save_checkpoint(
                        checkpoint.directory, epoch - 1, host_params,
                        meta={"losses": losses, "converged": converged,
                              "tol": tol, "final_delta": final_delta},
                    )
                    prune_checkpoints(checkpoint.directory, checkpoint.keep)

                if preempt_now:
                    fault.emergency_save(_snapshot)  # raises Preempted
                _snapshot()

    losses.extend(_drain_pending(pending))
    leaves, treedef = jax.tree_util.tree_flatten(params)
    if final_delta is None and last_delta_dev is not None:
        fetched = fetch_flat(*leaves, last_delta_dev)
        final_delta = float(fetched[-1])
        host_leaves = fetched[: len(leaves)]
    else:
        host_leaves = fetch_flat(*leaves)
    host_params = jax.tree_util.tree_unflatten(treedef, host_leaves)
    fault.check_health(losses, host_leaves, final_delta, where="stream_train")
    metrics.end_step(
        samples=total_rows, epochs=epoch - start_epoch,
        loss=losses[-1] if losses else 0.0,
    )
    return TrainResult(
        params=host_params, epochs=epoch, losses=losses,
        final_delta=final_delta, metrics=metrics,
    )


def _drain_pending(pending: list):
    """Fetch the per-epoch (loss, weight) device scalars accumulated so far
    and clear the list; returns the epoch mean losses."""
    if not pending:
        return []
    flat = []
    for loss_sum, w_sum in pending:
        flat.extend((loss_sum, w_sum))
    fetched = fetch_flat(*flat)
    out = []
    for i in range(0, len(fetched), 2):
        loss_sum, w_sum = float(fetched[i]), float(fetched[i + 1])
        out.append(loss_sum / max(w_sum, 1.0))
    pending.clear()
    return out


# -- block builders -----------------------------------------------------------


def _pad_stream_to(blocks: Iterator[Tuple], pad_to_blocks: Optional[int],
                   make_empty: Callable[[], Tuple]):
    """Append empty no-op blocks to a block stream up to the agreed
    per-epoch count — the ONE copy of the multi-process padding tail every
    block factory wraps its generator with.  ``make_empty()`` builds the
    (reusable) all-pad block lazily, after the stream pinned any
    data-derived shape it needs."""
    emitted = 0
    for item in blocks:
        yield item
        emitted += 1
    if pad_to_blocks is not None and emitted < pad_to_blocks:
        empty = make_empty()
        for _ in range(pad_to_blocks - emitted):
            yield empty, 0


def count_stream_rows(chunked_table) -> int:
    """Row count of a chunk stream — the dense multi-process pre-pass
    (the per-epoch block count must agree across processes; sparse fits
    get the count from their layout scan, dense fits only need this)."""
    n = 0
    chunks = chunked_table.chunks()
    try:
        for t in chunks:
            n += t.num_rows()
    finally:
        close = getattr(chunks, "close", None)
        if close is not None:
            close()
    return n


def dense_blocks_factory(
    chunked_table,
    extract: Callable[[Table], Tuple[np.ndarray, np.ndarray]],
    n_dev: int,
    mb: int,
    steps_per_chunk: int,
    pad_to_blocks: Optional[int] = None,
    pad_dim: Optional[int] = None,
):
    """Blocks of ``steps_per_chunk`` global steps in the combined dense
    layout, packed step-major; yields host ``(batch, n_rows)`` (the engine's
    prefetch thread does the mesh placement).  ``pad_to_blocks`` appends
    all-pad blocks (zero weight — the chunk program's live gate makes
    their steps exact no-ops) up to the agreed multi-process per-epoch
    count; ``pad_dim`` is the feature width for those pads."""
    rows_per_block = steps_per_chunk * mb * n_dev

    def factory():
        seen_dim = [pad_dim]

        def gen():
            for X, y in _block_rows(
                chunked_table.chunks(), extract, rows_per_block
            ):
                X = np.asarray(X)
                y = np.asarray(y)
                seen_dim[0] = X.shape[1]
                stack = pack_minibatches(
                    X, y, n_dev, global_batch_size=mb * n_dev,
                    min_steps=steps_per_chunk,
                )
                yield _combined_view(stack), stack.n_rows

        def make_empty():
            if seen_dim[0] is None:
                raise ValueError(
                    "cannot pad an empty stream to the agreed block "
                    "count without a known feature width"
                )
            return np.zeros(
                (n_dev * steps_per_chunk, mb, seen_dim[0] + 2),
                dtype=np.float32,
            )

        return _pad_stream_to(gen(), pad_to_blocks, make_empty)

    return factory


def _pack_sparse_block(vectors, y, n_dev: int, mb: int,
                       steps_per_chunk: int, dim: int, nnz_pad: int):
    """Pack one streamed block into the segment-CSR layout with the
    stream-wide fixed ``nnz_pad``.  A block denser than ``nnz_pad`` fails
    loudly rather than silently recompiling per block."""
    if not isinstance(vectors, CsrRows):
        vectors = list(vectors)
    stack = pack_sparse_minibatches(
        vectors, np.asarray(y), n_dev,
        global_batch_size=mb * n_dev, dim=dim,
        min_nnz_pad=nnz_pad, min_steps=steps_per_chunk,
    )
    if stack.nnz_pad != nnz_pad:
        raise ValueError(
            f"a minibatch holds {stack.nnz_pad} nnz > the configured "
            f"nnz_pad={nnz_pad}; raise nnz_pad (or lower the batch size) "
            f"so one compiled program covers the stream"
        )
    return stack


def _empty_sparse_block(n_groups: int, mb: int, nnz_pad: int):
    """An all-pad segment-CSR block (zero live rows): every entry carries
    the pad row id ``mb``, every weight is zero.  The chunk program's
    ``live = w_sum > 0`` gate makes its steps exact no-ops (no update, no
    decay) — the multi-process filler for shards with fewer blocks than
    the agreed per-epoch count (every process must dispatch the same
    number of collective chunk calls or the mesh hangs)."""
    ints = np.zeros((n_groups, 2, nnz_pad), dtype=np.int32)
    ints[:, 1, :] = mb
    floats = np.zeros((n_groups, nnz_pad + 2 * mb), dtype=np.float32)
    return ints, floats


def sparse_blocks_factory(
    chunked_table,
    extract: Callable[[Table], Tuple[list, np.ndarray]],
    n_dev: int,
    mb: int,
    steps_per_chunk: int,
    dim: int,
    nnz_pad: int,
    pad_to_blocks: Optional[int] = None,
):
    """Sparse counterpart: blocks in the segment-CSR layout with a fixed
    ``nnz_pad`` so every block reuses one compiled program (sizing via
    ``estimate_nnz_pad``, or :func:`scan_sparse_stream` + ``agree_max``
    multi-process; see :func:`_pack_sparse_block`).  ``pad_to_blocks``
    appends empty no-op blocks up to the agreed per-epoch count."""
    rows_per_block = steps_per_chunk * mb * n_dev

    def factory():
        def gen():
            for vectors, y in _block_rows(
                chunked_table.chunks(), extract, rows_per_block
            ):
                stack = _pack_sparse_block(
                    vectors, y, n_dev, mb, steps_per_chunk, dim, nnz_pad
                )
                yield (stack.ints, stack.floats), stack.n_rows

        return _pad_stream_to(
            gen(), pad_to_blocks,
            lambda: _empty_sparse_block(n_dev * steps_per_chunk, mb, nnz_pad),
        )

    return factory


def rows_blocks_factory(
    chunked_table,
    extract: Callable[[Table], Tuple[np.ndarray]],
    n_dev: int,
    rows_per_block: int,
    pad_to_blocks: Optional[int] = None,
    pad_dim: Optional[int] = None,
):
    """Plain padded row blocks ``(X, w)`` for whole-batch epoch algorithms
    (KMeans' Lloyd step): every block has exactly ``rows_per_block`` rows
    (multiple of ``n_dev``; the final block zero-weight-pads), so one
    compiled program covers the stream.  ``pad_to_blocks`` appends
    all-zero-weight blocks up to the agreed per-epoch count (multi-process
    short shards; zero-weight rows contribute nothing to the Lloyd
    accumulators exactly); ``pad_dim`` supplies the feature width when the
    local stream could be empty."""
    if rows_per_block % n_dev:
        raise ValueError("rows_per_block must be a multiple of n_dev")

    def factory():
        seen_dim = [pad_dim]

        def gen():
            for (X,) in _block_rows(
                chunked_table.chunks(), extract, rows_per_block
            ):
                X = np.asarray(X, dtype=np.float32)
                seen_dim[0] = X.shape[1]
                n = X.shape[0]
                Xp = np.zeros((rows_per_block, X.shape[1]), dtype=np.float32)
                wp = np.zeros((rows_per_block,), dtype=np.float32)
                Xp[:n] = X
                wp[:n] = 1.0
                yield (Xp, wp), n

        def make_empty():
            if seen_dim[0] is None:
                raise ValueError(
                    "cannot pad an empty stream to the agreed block "
                    "count without a known feature width"
                )
            return (
                np.zeros((rows_per_block, seen_dim[0]), dtype=np.float32),
                np.zeros((rows_per_block,), dtype=np.float32),
            )

        return _pad_stream_to(gen(), pad_to_blocks, make_empty)

    return factory


def make_kmeans_chunk_fn(key, k: int, mesh):
    """Lloyd accumulation over one row block as a compiled device call:
    ``chunk_fn(carry, (x, w)) -> (carry, tick)`` with ``carry = (centroids,
    sums, counts, cost)``.  Assignments are against the epoch's centroids
    (held fixed in the carry); per-cluster sums/counts/cost ``psum`` over
    the data axis and accumulate across blocks; the per-epoch centroid
    division happens in :func:`kmeans_finalize`.  Zero-weight padding rows
    contribute nothing exactly."""
    cached = _cache_get(key)
    if cached is not None:
        return cached

    def local_chunk(carry, batch):
        from flink_ml_tpu.lib.clustering import _pairwise_sq_dists

        c, sums, counts, cost = carry
        x, w = batch  # local shard: (rows_local, d), (rows_local,)
        d = _pairwise_sq_dists(x, c)
        assign = jnp.argmin(d, axis=1)
        cost = cost + psum(jnp.sum(jnp.min(d, axis=1) * w), "data")
        sums = sums + psum(
            jax.ops.segment_sum(x * w[:, None], assign, num_segments=k), "data"
        )
        counts = counts + psum(
            jax.ops.segment_sum(w, assign, num_segments=k), "data"
        )
        # tick: distinct buffer by construction (see make_chunk_step_fn)
        return (c, sums, counts, cost), jax.lax.optimization_barrier(cost)

    from jax.sharding import PartitionSpec as P

    sharded = shard_map(
        local_chunk,
        mesh=mesh,
        in_specs=(P(), P("data")),
        out_specs=(P(), P()),
        check_vma=True,
    )
    return _cache_put(key, jax.jit(sharded, donate_argnums=(0,)))


def kmeans_make_carry(centroids):
    """Fresh per-epoch Lloyd accumulators (sums, counts, cost)."""
    k, d = centroids.shape
    return (
        centroids,
        jnp.zeros((k, d), dtype=jnp.float32),
        jnp.zeros((k,), dtype=jnp.float32),
        jnp.zeros((), dtype=jnp.float32),
    )


@jax.jit
def kmeans_finalize(carry, epoch_start):
    """Per-epoch Lloyd reduction: divide sums by counts (empty clusters
    keep their previous centroid), centroid-shift norm for convergence.
    Returns the engine's ``(params, loss_sum, weight_sum, delta)``; the
    weight of 1 makes the drained epoch loss the total cost, matching the
    in-memory fused path."""
    c, sums, counts, cost = carry
    new_c = jnp.where(
        counts[:, None] > 0, sums / jnp.maximum(counts[:, None], 1.0), c
    )
    delta = jnp.sqrt(jnp.sum((new_c - epoch_start) ** 2))
    return new_c, cost, jnp.ones((), dtype=jnp.float32), delta


@contextlib.contextmanager
def maybe_spill(blocks_factory, enabled: bool):
    """Wrap a block factory in a :class:`BlockSpill` with a per-fit
    temporary directory, cleaned up on exit.  The single spill lifecycle
    shared by every out-of-core estimator; a no-op when ``enabled`` is
    false (single-epoch fits have no later epoch to amortize the disk
    copy)."""
    if not enabled:
        yield blocks_factory
        return
    import tempfile

    spill = BlockSpill(tempfile.mkdtemp(prefix="fmt_spill_"))
    try:
        yield spill.wrap(blocks_factory)
    finally:
        spill.close()


def reservoir_sample_rows(chunks: Iterator[Table], extract, cap: int, rng,
                          allow_empty: bool = False):
    """Uniform sample of ``cap`` rows over a chunk stream (vectorized
    Algorithm R), plus the true row count.

    The out-of-core replacement for ``rng.choice`` over a materialized
    array: one pass, O(cap) memory.  When the stream holds <= cap rows the
    sample IS the dataset (in order).  Used for k-means++ seeding, where
    the in-memory path draws a uniform subsample — a stream-head sample
    would bias the init toward the file's leading rows whenever the data
    is sorted or grouped.
    """
    sample: Optional[np.ndarray] = None
    filled = 0
    seen = 0
    for t in chunks:
        (X,) = extract(t)
        X = np.asarray(X)
        m = X.shape[0]
        if sample is None:
            sample = np.empty((cap, X.shape[1]), dtype=X.dtype)
        take = min(m, cap - filled)
        if take > 0:
            sample[filled : filled + take] = X[:take]
            filled += take
        if take < m:
            rest = X[take:]
            # row with global index i replaces a slot with prob cap/(i+1)
            idx = np.arange(seen + take, seen + m)
            j = (rng.random_sample(rest.shape[0]) * (idx + 1)).astype(np.int64)
            hit = j < cap
            sample[j[hit]] = rest[hit]
        seen += m
    if sample is None:
        if allow_empty:
            # multi-process: an empty local shard is legal — the caller
            # still owes its collectives, so it must not raise unilaterally
            return np.zeros((0, 0), dtype=np.float64), 0
        raise ValueError("empty source")
    return sample[:filled] if filled < cap else sample, seen


class _Crc32Writer:
    """File wrapper that CRCs and counts every byte as ``np.save`` streams
    it — the sidecar commit record in the SAME pass as the write.  Reading
    the file back to checksum it would double the save epoch's I/O, and
    spill-scale data is by definition too large for the page cache to
    absorb the second pass."""

    def __init__(self, f):
        self._f = f
        self.crc = 0
        self.size = 0

    def write(self, b):
        import zlib

        self.crc = zlib.crc32(b, self.crc)
        self.size += len(b)
        return self._f.write(b)

    def __getattr__(self, name):  # tell/seek/flush pass through
        return getattr(self._f, name)


class BlockSpill:
    """Parse once, stream binary thereafter — in final packed layout.

    Text parsing (CSV/LibSVM) is orders of magnitude slower than the device
    program, so re-parsing the source every epoch leaves the chip idle.
    Wrapping a host-block factory in a BlockSpill writes each packed
    block's leaves as raw ``.npy`` files during the first epoch; later
    epochs hand the device memory-MAPPED views of those files — the blocks
    are spilled in the exact layout the chunk program consumes, so a
    steady epoch does no repacking and no zip-layer copy (``np.load`` of
    an ``.npz`` streams every byte through the zip reader — measured ~1
    GB/s, slower than the chunk compute itself; a page-cache-warm mmap is
    a no-op until ``device_put`` pulls the pages, one copy total).  Host
    memory stays bounded at one block of pages; disk pays one packed copy
    of the dataset (the same trade Flink's runtime makes when it spills
    partitions to local disk between supersteps).

    The spill directory is owned by the caller and deleted via ``close()``
    (the estimator uses a per-fit temporary directory).

    **Fault tolerance** (PR 3): every block carries a sidecar
    ``block-NNNNNN.meta.json`` recording each leaf file's on-disk length
    and CRC32, written AFTER the leaf files as the block's commit record.
    Replay epochs validate the sidecars first — lengths every epoch (a
    handful of stats), checksums once per file (the first replay pays one
    extra read of pages ``device_put`` was about to pull anyway) — and a
    corrupted or truncated block downgrades the epoch to a transparent
    rebuild from the source factory instead of feeding the device garbage
    or crashing.  An INTERRUPTED first epoch (exception mid-save, a
    preemption) leaves ``complete=False`` with orphan block files on
    disk; the next wrap restarts the save cleanly — stale blocks from the
    dead attempt are truncated first, so a shorter re-run can never
    replay a longer dead run's tail.  Block writes and replay opens ride
    the transient-I/O retry policy (``fault.retry``).
    """

    def __init__(self, directory: str):
        import os

        self.directory = directory
        os.makedirs(directory, exist_ok=True)
        self.complete = False
        self._meta: list = []  # (n_rows, n_leaves) per block
        self._treedef = None
        self._crc_checked = False  # first replay verifies checksums once

    def wrap(self, factory: Callable[[], Iterator]) -> Callable[[], Iterator]:
        def wrapped():
            if self.complete:
                if self._validate():
                    return self._load_iter()
                # corrupted/truncated spill: degrade to a rebuild from
                # the source, never crash the epoch (the factory is the
                # durable truth; the spill is just its binary cache)
                obs.counter_add("fault.spill_rebuilds")
                warnings.warn(
                    "spill block validation failed (corrupted or "
                    "truncated block files); rebuilding the spill from "
                    "the source factory for this epoch",
                    RuntimeWarning,
                    stacklevel=2,
                )
            return self._save_iter(factory())

        return wrapped

    def _path(self, i: int, j: int) -> str:
        import os

        return os.path.join(self.directory, f"block-{i:06d}-{j:03d}.npy")

    def _block_meta_path(self, i: int) -> str:
        import os

        return os.path.join(self.directory, f"block-{i:06d}.meta.json")

    def _reset_partial(self):
        """Truncate every artifact of a dead or invalid save attempt so
        the restarted save starts from a clean directory — re-wrapping
        after a mid-iteration failure must never interleave two attempts'
        blocks (the old attempt may have written MORE blocks than the new
        one will)."""
        import os

        self.complete = False
        self._meta.clear()
        self._crc_checked = False
        for name in os.listdir(self.directory):
            if name.startswith("block-"):
                try:
                    os.remove(os.path.join(self.directory, name))
                except OSError:
                    pass  # best effort; a leftover .tmp never replays

    def _save_iter(self, items: Iterator):
        import json
        import os

        from flink_ml_tpu.fault.injection import maybe_fail
        from flink_ml_tpu.fault.retry import with_retry

        self._reset_partial()
        i = 0
        for batch, n_rows in items:
            with obs.phase("spill.write_block"):
                leaves, treedef = jax.tree_util.tree_flatten(batch)
                self._treedef = treedef
                nbytes = 0
                leaf_meta = []
                for j, x in enumerate(leaves):
                    arr = np.asarray(x)
                    p = self._path(i, j)

                    def write(p=p, arr=arr):
                        # tmp + rename atomicity with the CRC computed in
                        # the same pass the bytes are written
                        maybe_fail("spill.write")
                        tmp = p + ".tmp"
                        with open(tmp, "wb") as f:
                            w = _Crc32Writer(f)
                            np.save(w, arr)
                            stats = {"size": w.size, "crc32": w.crc}
                        os.replace(tmp, p)
                        return stats

                    leaf_meta.append(with_retry(write, "spill.write"))
                    nbytes += arr.nbytes
                # sidecar last: the block's commit record (a crash between
                # leaf writes leaves no sidecar -> validation fails -> the
                # next wrap rebuilds); rides the same transient-I/O retry
                # as the leaf writes it commits
                def write_sidecar(i=i, n_rows=n_rows, leaf_meta=leaf_meta):
                    mp = self._block_meta_path(i)
                    with open(mp + ".tmp", "w") as f:
                        json.dump(
                            {"n_rows": int(n_rows), "leaves": leaf_meta}, f
                        )
                    os.replace(mp + ".tmp", mp)

                with_retry(write_sidecar, "spill.write")
            obs.counter_add("spill.blocks_written")
            obs.counter_add("spill.bytes_written", nbytes)
            self._meta.append((int(n_rows), len(leaves)))
            i += 1
            yield batch, n_rows
        self.complete = True

    def _validate(self) -> bool:
        """Do the on-disk blocks still match their commit records?

        Lengths are checked every replay (cheap stats); CRCs once, on the
        first replay (one extra read of pages the same epoch was about to
        pull through ``device_put`` anyway).  Any mismatch — or an
        injected ``spill.read`` fault — reports the spill as corrupt."""
        import json
        import os
        import zlib

        from flink_ml_tpu.fault.injection import InjectedFault, maybe_fail

        try:
            for i, (n_rows, n_leaves) in enumerate(self._meta):
                maybe_fail("spill.read")
                with open(self._block_meta_path(i)) as f:
                    side = json.load(f)
                if side["n_rows"] != n_rows or len(side["leaves"]) != n_leaves:
                    return False
                for j, leaf in enumerate(side["leaves"]):
                    p = self._path(i, j)
                    if os.path.getsize(p) != leaf["size"]:
                        return False
                    if not self._crc_checked:
                        # streamed CRC: one whole-file read() would spike
                        # host RSS by the largest leaf — spill-scale data
                        # is exactly what must not be materialized at once
                        crc = 0
                        with open(p, "rb") as f:
                            for chunk in iter(lambda: f.read(1 << 20), b""):
                                crc = zlib.crc32(chunk, crc)
                        if crc != leaf["crc32"]:
                            return False
        except (OSError, ValueError, KeyError, InjectedFault):
            return False
        self._crc_checked = True
        return True

    def _load_iter(self):
        from flink_ml_tpu.fault.retry import with_retry

        for i, (n_rows, n_leaves) in enumerate(self._meta):
            leaves = [
                with_retry(
                    lambda p=self._path(i, j): np.load(p, mmap_mode="r"),
                    "spill.read",
                )
                for j in range(n_leaves)
            ]
            obs.counter_add("spill.blocks_replayed")
            yield jax.tree_util.tree_unflatten(self._treedef, leaves), n_rows

    def close(self):
        import shutil

        # removes committed blocks AND any partial-save leftovers (.tmp
        # staging files, orphan leaves of an interrupted attempt)
        shutil.rmtree(self.directory, ignore_errors=True)
        self.complete = False
        self._meta.clear()


def scan_sparse_stream(chunked_table, vector_col: str, mb: int,
                       pad_multiple: int = 512):
    """One full pass over the stream: (exact nnz_pad, total rows).

    The multi-process replacement for :func:`estimate_nnz_pad`'s
    sampled+safety heuristic — processes must agree on EXACT block shapes,
    so each scans its whole shard (window max over the mb-aligned row
    windows the packer budgets; block boundaries are mb-aligned, so the
    window set equals the packer's group set) and ``agree_max`` reconciles
    the results.  Also the row count, from which the per-epoch block count
    derives (short shards pad their epochs with empty no-op blocks)."""
    worst = 1
    n_rows = 0
    carry = np.zeros((0,), dtype=np.int64)  # partial trailing mb-window
    from flink_ml_tpu.lib.common import sparse_row_counts

    chunks = chunked_table.chunks()
    try:
        for t in chunks:
            counts = sparse_row_counts(t.col(vector_col))
            n_rows += len(counts)
            arr = np.concatenate([carry, np.asarray(counts, np.int64)])
            n_full = len(arr) // mb
            if n_full:
                sums = arr[: n_full * mb].reshape(n_full, mb).sum(axis=1)
                worst = max(worst, int(sums.max()))
            carry = arr[n_full * mb:]
    finally:
        close = getattr(chunks, "close", None)
        if close is not None:
            close()
    if carry.size:
        worst = max(worst, int(carry.sum()))
    nnz_pad = -(-worst // pad_multiple) * pad_multiple
    return nnz_pad, n_rows


def estimate_nnz_pad(
    chunked_table, vector_col: str, mb: int, n_dev: int,
    pad_multiple: int = 512, sample_chunks: int = 2, safety: float = 1.5,
) -> int:
    """Size the per-minibatch nnz budget from the stream's head.

    Reads ``sample_chunks`` chunks, takes the max nnz over the mb-row
    per-device minibatch windows (the unit ``pack_sparse_minibatches``
    budgets — step-major groups start at mb-row boundaries), and pads by
    ``safety`` then up to ``pad_multiple``.  For Criteo-style fixed-slots
    data (constant nnz per row) the estimate is exact; for skewed data a
    denser later block fails loudly in :func:`sparse_blocks_factory` and
    the caller re-fits with a bigger pad.
    """
    del n_dev  # the window is per-device (mb rows), not per-step (mb*n_dev)
    worst = 1
    chunks = chunked_table.chunks()
    counts: list = []
    try:
        for _ in range(sample_chunks):
            t = next(chunks, None)
            if t is None:
                break
            col = t.col(vector_col)
            if isinstance(col, CsrRows):
                counts.extend(col.nnz_per_row().tolist())
            else:
                for v in col:
                    counts.append(len(v.indices))
    finally:
        close = getattr(chunks, "close", None)
        if close is not None:
            close()
    if not counts:
        raise ValueError("empty source: cannot size the sparse layout")
    counts_arr = np.asarray(counts, dtype=np.int64)
    for lo in range(0, len(counts_arr), mb):
        worst = max(worst, int(counts_arr[lo : lo + mb].sum()))
    padded = int(np.ceil(worst * safety))
    return -(-padded // pad_multiple) * pad_multiple
