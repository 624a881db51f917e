"""Device mesh construction and data placement.

The mesh is N-dimensional from the start (SURVEY.md §2.6: keep
``('data', 'model')`` possible even though the reference only has data
parallelism) so feature-dimension sharding (TP) can be enabled per-algorithm
without redesign.  Intra-slice traffic rides ICI; multi-host initialization
goes through ``jax.distributed`` (DCN for cross-slice).
"""

from __future__ import annotations

import functools
import math
from typing import Dict, Optional, Sequence

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from flink_ml_tpu import obs
from flink_ml_tpu.fault.injection import maybe_fail
from flink_ml_tpu.fault.watchdog import with_timeout
from flink_ml_tpu.utils import knobs


def backend_initialized() -> bool:
    """Has this process created a JAX backend yet — and so, on a TPU host,
    taken the chip?  Asking for devices would create one; this only looks.
    (The ONE use of jax's private probe: launchers that must not take the
    chip from their children read it here.)"""
    from jax._src import xla_bridge

    return xla_bridge.backends_are_initialized()


def default_mesh(axis_names: Sequence[str] = ("data",), devices=None) -> Mesh:
    """All available devices laid out on the first axis (pure data parallel)."""
    devices = list(jax.devices()) if devices is None else list(devices)
    shape = [len(devices)] + [1] * (len(axis_names) - 1)
    arr = np.array(devices).reshape(shape)
    return Mesh(arr, tuple(axis_names))


def create_mesh(axes: Dict[str, int], devices=None) -> Mesh:
    """Mesh from an ordered ``{axis_name: size}`` spec, e.g. {'data': 4, 'model': 2}."""
    devices = list(jax.devices()) if devices is None else list(devices)
    total = math.prod(axes.values())
    if total != len(devices):
        raise ValueError(
            f"mesh axes {axes} require {total} devices, have {len(devices)}"
        )
    arr = np.array(devices).reshape(list(axes.values()))
    return Mesh(arr, tuple(axes.keys()))


def data_parallel_size(mesh: Mesh, axis: str = "data") -> int:
    """Size of the data-parallel axis — the number of row shards.

    On a multi-axis mesh (e.g. ``('data','model')``) batches shard over the
    ``data`` axis only (other axes replicate), so packing/layout must use
    this, not the total device count.
    """
    return dict(mesh.shape).get(axis, 1)


def local_data_parallel_size(mesh: Mesh, axis: str = "data") -> int:
    """This PROCESS's share of the data axis — the row-shard count a local
    packing must target.

    Single-process this equals :func:`data_parallel_size`.  Multi-process
    (``jax.distributed``), each process packs only its own rows for its own
    devices (the per-process file-shard contract, see :func:`shard_batch`),
    so layout functions must divide the axis across processes.  The data
    axis must be process-aligned (every process contributes whole data-axis
    positions — the default mesh over ``jax.devices()`` is).
    """
    n = data_parallel_size(mesh, axis)
    p = jax.process_count()
    if p == 1:
        return n
    if n % p != 0:
        raise ValueError(
            f"data axis size {n} not divisible by process count {p}"
        )
    return n // p


def local_batch_share(global_batch_size):
    """This process's slice of a global SGD batch size.

    Packing is per-process multi-host (each process packs its own rows for
    its own devices), so layout code pairs this with
    :func:`local_data_parallel_size` — the per-device minibatch
    ``ceil(share / local_shards)`` then equals the single-process
    ``ceil(global / global_shards)``.  Passes 0/None (full batch) through.
    """
    if not global_batch_size or global_batch_size <= 0:
        return global_batch_size
    p = jax.process_count()
    if p == 1:
        return global_batch_size
    if global_batch_size % p != 0:
        raise ValueError(
            f"globalBatchSize {global_batch_size} not divisible by "
            f"process count {p}"
        )
    return global_batch_size // p


def agree_max(*values: int):
    """Cross-process agreement on data-dependent layout scalars: the
    element-wise MAX over all processes (identity single-process).

    Multi-process compiled programs need identical static shapes on every
    process, but layout scalars like the sparse stack's padded nnz width
    derive from each process's local rows.  Each process computes its local
    value, all processes agree on the max, and packers accept the agreed
    value as a floor (``min_nnz_pad`` / ``min_steps``) — padding is free
    (pad entries carry zero weight), divergence is a hang or a silent
    wrong answer.

    Guarded by the ``FMT_AGREE_TIMEOUT_S`` watchdog: a dead peer turns the
    allgather into an infinite hang, which the watchdog converts into a
    :class:`~flink_ml_tpu.fault.watchdog.CollectiveTimeoutError` naming
    this collective."""
    maybe_fail("agree")
    if jax.process_count() == 1:
        return values
    from jax.experimental import multihost_utils

    gathered = with_timeout(
        lambda: multihost_utils.process_allgather(
            np.asarray(values, np.int64)
        ),
        name="agree_max",
    )
    return tuple(int(v) for v in np.max(gathered, axis=0))


def agree_sum(array: np.ndarray) -> np.ndarray:
    """Cross-process element-wise SUM (identity single-process) — e.g. the
    global row count every process must derive identically before the
    centroid fit's streamed init (each process only sees its own shard's
    rows).  Same ``FMT_AGREE_TIMEOUT_S`` watchdog as :func:`agree_max`."""
    maybe_fail("agree")
    if jax.process_count() == 1:
        return np.asarray(array)
    from jax.experimental import multihost_utils

    gathered = with_timeout(
        lambda: multihost_utils.process_allgather(np.asarray(array)),
        name="agree_sum",
    )
    return np.sum(gathered, axis=0)


def shard_batch(mesh: Mesh, batch, axis: str = "data"):
    """Place a host batch pytree on the mesh, sharded along ``axis`` on dim 0.

    The device-side analog of Flink distributing row partitions to subtasks
    (``env.readCsvFile`` producing a partitioned DataSet,
    LinearRegression.java:91-102).  Leading dimensions must divide the axis
    size (pad at the data-plane level).

    **Multi-process contract** (``jax.process_count() > 1``): ``batch`` is
    this process's LOCAL rows — each process reads its own file shards and
    contributes its slice of the global batch
    (``jax.make_array_from_process_local_data``); the global leading dim is
    ``local_rows * process_count`` in process order.  Every process must
    contribute identically-shaped local blocks (equal row shards; pack with
    :func:`local_data_parallel_size` shards and the per-process slice of the
    global batch size).  Single-process behavior is unchanged.
    """
    maybe_fail("place.h2d")

    def _put(x):
        ndim = getattr(x, "ndim", 0)
        return _place_local_block(
            mesh, x, P(axis) if ndim >= 1 else P()
        )

    return jax.tree_util.tree_map(_put, batch)


def _place_local_block(mesh: Mesh, x, spec: P):
    """The ONE copy of the per-process batch-assembly contract: a host
    array holding this process's LOCAL rows becomes its slice of the
    global batch (``jax.make_array_from_process_local_data``; global
    leading dim = local * process_count in process order), or a plain
    sharded device_put single-process.  ``spec``'s leading entry is the
    row axis; other entries may shard trailing dims the process spans in
    full (e.g. the dense 2-D ('data', None, 'model') layout)."""
    n_proc = jax.process_count()
    ndim = getattr(x, "ndim", 0)
    if n_proc > 1:
        x = np.asarray(x)
        global_shape = (
            (x.shape[0] * n_proc,) + x.shape[1:] if ndim >= 1 else x.shape
        )
        return jax.make_array_from_process_local_data(
            NamedSharding(mesh, spec), x, global_shape=global_shape
        )
    return jax.device_put(x, NamedSharding(mesh, spec))


#: slice size (bytes) of the double-buffered H2D pipeline; one slice is in
#: DMA flight while the next is being cut/staged on the host
_CHUNK_BYTES_DEFAULT = 32 << 20
#: leaves below this stay on the one-shot device_put path — slicing +
#: re-concatenation only pays off when the transfer itself is long
_CHUNKED_MIN_BYTES_DEFAULT = 64 << 20


def _placement_chunk_bytes() -> int:
    return knobs.knob_int("FMT_SLAB_CHUNK_MB") * (1 << 20) \
        or _CHUNK_BYTES_DEFAULT


@functools.lru_cache(maxsize=64)
def _concat_placed_fn(mesh: Mesh, spec: P, n_parts: int):
    """Jitted concat-along-dim-0 pinned to an output sharding — reassembles
    the double-buffered slices into the ONE array the train program
    consumes.  lru_cached so repeated placements reuse the compiled
    executable (jit's own cache then covers varying shapes per arity).

    Over more than one shard of ``spec``'s row axis every slice holds a
    piece of EACH shard's block (:func:`_put_slices`), and the concat runs
    under ``shard_map``: every device joins the pieces it was sent, nothing
    crosses between devices and none waits for another's.

    The assembly transiently holds the slices alongside the full output (a
    ~2x device-memory spike at exactly the sizes this path targets).  The
    slices are NOT donated: XLA can only alias a donated input to an output
    of its own shape, and no slice has the output's — on the v5e every
    slice came back "Some donated buffers were not usable" (PR 21), so the
    donation this function used to request never freed anything."""

    def concat(*parts):
        import jax.numpy as jnp

        return jnp.concatenate(parts, axis=0)

    if _row_shards(mesh, spec) == 1:
        return jax.jit(concat, out_shardings=NamedSharding(mesh, spec))
    from flink_ml_tpu.parallel.collectives import shard_map

    return jax.jit(shard_map(concat, mesh=mesh, in_specs=(spec,) * n_parts,
                             out_specs=spec))


def _row_shards(mesh: Mesh, spec: P) -> int:
    """Over how many shards ``spec`` divides dim 0."""
    return dict(mesh.shape).get(spec[0], 1) if len(spec) else 1


def _put_slices(mesh: Mesh, x: np.ndarray, spec: P, chunk_bytes: int):
    """Double-buffered H2D placement of one host array: every shard's block
    of dim 0 (one block on one device; the k-th ``1/n`` of dim 0 on the
    k-th of ``n`` devices) is cut into slices, and a background thread
    enqueues each slice's async device_put straight to the device that
    will hold it (the ``_prefetch`` idiom from lib/out_of_core.py — host
    staging of slice N+1 overlaps the DMA of slice N), the devices taking
    turns slice by slice so that their copies run side by side.  Returns
    the placed slices — the j-th an array whose shard on a device is the
    j-th slice of that device's block — for :func:`_concat_placed_fn` to
    reassemble under the final sharding.  ``place.h2d`` times the enqueue
    of one device's slice."""
    from flink_ml_tpu.utils.prefetch import prefetch_iter

    sharding = NamedSharding(mesh, spec)
    shards = _row_shards(mesh, spec)
    block = x.shape[0] // shards  # dim-0 rows a shard holds
    row_bytes = max(x.nbytes // max(x.shape[0], 1), 1)
    rows_per_chunk = max(1, chunk_bytes // (row_bytes * shards))
    bounds = list(range(0, block, rows_per_chunk))
    if len(bounds) < 2:
        with obs.span("place.h2d"):
            return [jax.device_put(x, sharding)]

    # (device, where its block starts in dim 0), replicas included
    owners = [(device, index[0].start or 0) for device, index in
              sharding.addressable_devices_indices_map(x.shape).items()]

    def pieces():
        for lo in bounds:
            hi = min(lo + rows_per_chunk, block)
            placed = []
            for device, start in owners:
                # device_put returns immediately (async DMA); issuing it
                # from the producer thread pipelines staging against the
                # transfer
                with obs.span("place.h2d"):
                    placed.append(
                        jax.device_put(x[start + lo:start + hi], device))
            yield jax.make_array_from_single_device_arrays(
                (shards * (hi - lo),) + x.shape[1:], sharding, placed)

    return list(prefetch_iter(pieces(), depth=2, name="h2d-prefetch"))


def shard_batch_prefetched(mesh: Mesh, batch, axis: str = "data",
                           chunk_bytes: Optional[int] = None,
                           min_bytes: Optional[int] = None):
    """:func:`shard_batch` with double-buffered, chunked H2D placement.

    Large leaves are cut into shard-aligned dim-0 slices and transferred
    through a 2-deep prefetch pipeline (host staging of slice N+1 overlaps
    the async DMA of slice N — the same overlap the out-of-core engine gets
    from its block prefetch), then reassembled on device under the final
    ``P(axis)`` sharding.  Small leaves and scalars take the plain path;
    multi-process placement always falls back to :func:`shard_batch`
    (chunking would change the local-block assembly contract).  Tune with
    ``FMT_SLAB_CHUNK_MB``; results are identical to :func:`shard_batch` —
    only the transfer schedule differs."""
    if jax.process_count() > 1:
        return shard_batch(mesh, batch, axis=axis)
    maybe_fail("place.h2d")
    if chunk_bytes is None:
        chunk_bytes = _placement_chunk_bytes()
    if min_bytes is None:
        min_bytes = _CHUNKED_MIN_BYTES_DEFAULT

    def _put(x):
        # ``place.h2d`` is the host's side of a copy, once a leaf or, of a
        # leaf cut into slices, once a device's slice: device_put is
        # asynchronous and nothing waits here, so it ends when the copy is
        # ENQUEUED, not when it has arrived
        if getattr(x, "ndim", 0) < 1:
            with obs.span("place.h2d"):
                return jax.device_put(x, NamedSharding(mesh, P()))
        x = np.asarray(x)
        if x.nbytes < max(min_bytes, 2 * chunk_bytes):
            with obs.span("place.h2d"):
                return jax.device_put(x, NamedSharding(mesh, P(axis)))
        parts = _put_slices(mesh, x, P(axis), chunk_bytes)
        # outside the spans: the reassembling program, which a cold compile
        # cache compiles here
        if len(parts) == 1:
            return parts[0]
        return _concat_placed_fn(mesh, P(axis), len(parts))(*parts)

    return jax.tree_util.tree_map(_put, batch)


def shard_batch_specs(mesh: Mesh, arrays: Sequence, specs: Sequence[P]):
    """Per-leaf-spec variant of :func:`shard_batch` for layouts beyond
    row-axis-only sharding; same multi-process local-block contract
    (:func:`_place_local_block`)."""
    return tuple(
        _place_local_block(mesh, a, s) for a, s in zip(arrays, specs)
    )


def mesh_spans_processes(mesh: Mesh) -> bool:
    """Does this mesh hold devices owned by more than one process?

    The serving stack's breaker/pressure agreement trigger: a dispatch
    surface whose mesh crosses processes must agree degradation decisions
    (open-wins ``agree_max``) or a collective-bearing program would split
    between a device path and a fallback path.  Single-process — and the
    process-local :func:`inference_mesh` — always answer False, keeping
    the default serving contract collective-free."""
    if jax.process_count() == 1:
        return False
    pi = jax.process_index()
    return any(d.process_index != pi for d in mesh.devices.flat)


def inference_mesh(mesh: Mesh) -> Mesh:
    """The mesh model-apply paths run on: the session mesh single-process;
    multi-process, a LOCAL data-parallel mesh over this process's devices.

    Inference is row-parallel with a broadcast model — the reference's
    ModelMapperAdapter semantic (ModelMapperAdapter.java:53-61: every
    subtask materializes the model and maps its own partition
    independently) — so transform time never needs a cross-process
    collective; each process scores its own rows on its own chips."""
    if jax.process_count() == 1:
        return mesh
    return Mesh(np.array(jax.local_devices()), ("data",))


def global_put(mesh: Mesh, host_array, spec: P):
    """Place a host array every process holds IN FULL (identical values —
    the broadcast-variable contract) onto an arbitrary mesh sharding.

    ``jax.device_put`` cannot target shardings spanning other processes'
    devices; ``make_array_from_callback`` can — each process serves only
    its addressable shards by slicing its full host copy.  This is what
    unlocks model-axis (feature-sharded) parameters in multi-process runs:
    the weight pytree is deterministically derived on every process, and
    each process materializes just its slice.  Single-process it is
    equivalent to a plain sharded device_put."""
    arr = np.asarray(host_array)
    sharding = NamedSharding(mesh, spec)
    return jax.make_array_from_callback(
        arr.shape, sharding, lambda idx: arr[idx]
    )


def replicate(mesh: Mesh, pytree):
    """Replicate a pytree to every device — the broadcast-variable analog
    (BroadcastVariableModelSource.java:44-46 -> one all-devices placement).
    Multi-process, every process must pass the same values (the model is
    deterministically derived or broadcast out-of-band, exactly the
    broadcast-variable contract)."""
    n_proc = jax.process_count()

    def _put(x):
        if n_proc > 1:
            x = np.asarray(x)
            return jax.make_array_from_process_local_data(
                NamedSharding(mesh, P()), x, global_shape=x.shape
            )
        return jax.device_put(x, NamedSharding(mesh, P()))

    return jax.tree_util.tree_map(_put, pytree)


def initialize_distributed(
    coordinator_address: Optional[str] = None,
    num_processes: Optional[int] = None,
    process_id: Optional[int] = None,
) -> None:
    """Multi-host bring-up via jax.distributed (DCN control plane).

    No-op when single-process args are absent — single-host meshes need no
    initialization.  Call once per host before building a multi-host mesh.
    """
    if coordinator_address is None:
        return
    jax.distributed.initialize(
        coordinator_address=coordinator_address,
        num_processes=num_processes,
        process_id=process_id,
    )


def shutdown_distributed() -> None:
    """Tear down the jax.distributed control plane (idempotent)."""
    try:
        jax.distributed.shutdown()
    except RuntimeError:
        # Not initialized — single-host runs never bring the service up.
        pass
