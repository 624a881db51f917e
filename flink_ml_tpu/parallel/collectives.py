"""Collective wrappers + the data-parallel step combinator.

The reference's training round is: per-subtask gradient map, network-shuffle
``reduce`` to one node, divide by count, re-broadcast
(LinearRegression.java:113-121, UpdateAccumulator:235-246).  The TPU-native
replacement (ROADMAP.md's north star) keeps everything inside one jitted
step: local grads on each mesh slice, ``pmean`` over the ``data`` axis riding
ICI, parameters updated replicated — no host round-trip, no reduce node.
"""

from __future__ import annotations

from typing import Callable

import jax
from jax.sharding import Mesh, PartitionSpec as P


def shard_map(f, mesh, in_specs, out_specs, check_vma: bool = True):
    """``jax.shard_map`` with the repo's default (strict ``check_vma``) —
    the ONE shard_map entry point, so a program that relaxes the
    varying-axes check is a visible ``check_vma=False`` at its call."""
    return jax.shard_map(
        f, mesh=mesh, in_specs=in_specs, out_specs=out_specs,
        check_vma=check_vma,
    )


def pvary(x, axes=("data",)):
    """Mark a replicated value as varying over mesh axes (vma) inside a
    shard_map."""
    return jax.lax.pcast(x, tuple(axes), to="varying")


def psum(x, axis_name: str = "data"):
    """Allreduce-sum over a mesh axis (usable inside shard_map/pmapped fns)."""
    return jax.lax.psum(x, axis_name)


def pmean(x, axis_name: str = "data"):
    """Allreduce-mean — the model-averaging collective (Update.java:249-256 analog)."""
    return jax.lax.pmean(x, axis_name)


def all_gather(x, axis_name: str = "data", axis: int = 0, tiled: bool = True):
    """Gather shards along an axis — the broadcast-variable analog in-step."""
    return jax.lax.all_gather(x, axis_name, axis=axis, tiled=tiled)


def make_data_parallel_step(
    local_step: Callable,
    mesh: Mesh,
    axis: str = "data",
    donate_state: bool = True,
    max_inflight: int = None,
) -> Callable:
    """Lift ``local_step(state, batch) -> (state, aux)`` to the mesh.

    ``local_step`` computes on its local batch shard and may call
    ``psum``/``pmean`` with ``axis`` for cross-shard reductions (gradient
    averaging).  State is replicated; the batch is sharded along ``axis`` on
    dim 0.  The result is jitted once and reusable every epoch — the whole
    reference round (map + reduce + update + rebroadcast) in one XLA program.

    ``max_inflight`` bounds the number of un-synced async dispatches: the
    returned callable blocks on results every that-many calls.  On the CPU
    backend (virtual multi-device test meshes) it defaults to 1 — XLA's
    in-process collective rendezvous deadlocks when many cross-device
    executions queue up on few host cores.  On TPU it defaults to 64, which
    keeps the dispatch pipeline full without unbounded queuing.
    """
    # strict check_vma makes shard_map verify that outputs declared replicated
    # really are (i.e. the user ran the collective); a local_step that forgets
    # its pmean fails loudly instead of silently returning one shard's value.
    sharded = shard_map(
        local_step,
        mesh=mesh,
        # pytree-prefix specs: state replicated, batch sharded on dim 0
        in_specs=(P(), P(axis)),
        out_specs=(P(), P()),
    )
    donate = (0,) if donate_state else ()
    fn = jax.jit(sharded, donate_argnums=donate)
    if max_inflight is None:
        max_inflight = 1 if jax.default_backend() == "cpu" else 64
    return _BoundedDispatch(fn, max_inflight)


def make_data_parallel_apply(
    fn: Callable,
    mesh: Mesh,
    axis: str = "data",
    n_args: int = 1,
) -> Callable:
    """Lift a row-aligned inference fn onto the mesh for model *apply*.

    Arg 0's rows shard over ``axis``; the remaining ``n_args - 1`` args (the
    model) replicate — the TPU analog of the reference running its
    ModelMapperAdapter at operator parallelism (ModelMapperAdapter.java:53-61:
    model rows broadcast to every subtask at open, input rows partitioned).
    ``fn`` must be row-aligned (row i of the output depends only on row i of
    arg 0), and the row count must be a multiple of the axis size — pad via
    ``apply_batched(..., row_multiple=...)``.

    Degenerates to a plain jit when the axis has size 1 (single chip), so one
    call path serves both.  No collectives are involved, hence no vma check.
    """
    if dict(mesh.shape).get(axis, 1) == 1:
        return jax.jit(fn)
    in_specs = (P(axis),) + (P(),) * (n_args - 1)
    sharded = shard_map(
        fn, mesh=mesh, in_specs=in_specs, out_specs=P(axis), check_vma=False
    )
    return jax.jit(sharded)


class _BoundedDispatch:
    """Wraps an async-dispatching jitted fn, keeping at most ``max_inflight``
    results outstanding (blocks on the oldest live output, not the whole
    pipeline).  Caveat: when the step's aux output holds no arrays and state
    is donated, every older entry's buffers are gone, so the sync falls back
    to the newest output and drains the pipeline once per ``max_inflight``
    calls — return a small aux array (e.g. the loss) to keep full overlap."""

    def __init__(self, fn: Callable, max_inflight: int):
        from collections import deque

        self._fn = fn
        self._max_inflight = max(1, int(max_inflight))
        self._pending = deque()

    def __call__(self, *args, **kwargs):
        out = self._fn(*args, **kwargs)
        self._pending.append(out)
        if len(self._pending) >= self._max_inflight:
            # With donate_state=True the state leaves of a pending output are
            # deleted the moment the *next* call donates them, so they cannot
            # be waited on.  Walk from the oldest entry to the first one with
            # a live (non-donated) leaf — typically the aux part — and block
            # on that; entries whose every buffer was donated are already
            # consumed by a later dispatched computation and need no wait.
            # The newest entry always has live leaves (nothing has donated
            # them yet), so this terminates having synced the pipeline.
            while self._pending:
                oldest = self._pending.popleft()
                live = [
                    x
                    for x in jax.tree_util.tree_leaves(oldest)
                    if not (hasattr(x, "is_deleted") and x.is_deleted())
                ]
                if live:
                    jax.block_until_ready(live)
                    break
        return out

    @property
    def jitted(self) -> Callable:
        """The underlying jitted function (for AOT lowering/compile checks)."""
        return self._fn
