"""Shared training/inference harness for the algorithm library.

This is where the reference's training topology (SURVEY.md §3.3: per-record
gradient map -> network-shuffle reduce -> average -> rebroadcast, repeated
per round) becomes one compiled TPU program per epoch:

  * rows are packed ONCE into device-major minibatch stacks (static shapes,
    padded with zero-weight rows so padding never biases gradients);
  * one epoch = one ``make_data_parallel_step`` call: each mesh slice scans
    its local minibatches with ``lax.scan``, gradients are ``psum``'d over
    the ``data`` axis inside the step (the allreduce rides ICI), parameters
    stay replicated — the whole round trip that Flink does through its
    network stack never leaves the chip;
  * epochs surface through the bounded iteration runtime, so listeners and
    termination (max epochs / tol on update norm — the device-friendly analog
    of the empty-termination-criteria-stream rule) keep reference semantics.

Inference: model packed to device arrays once (the broadcast-variable analog),
rows applied in padded power-of-two buckets to bound jit recompiles.
"""

from __future__ import annotations

import os
import threading
from concurrent.futures import ThreadPoolExecutor
from dataclasses import dataclass, field
from typing import Callable, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from flink_ml_tpu import fault, obs
from flink_ml_tpu.iteration.bounded import (
    IterationBodyResult,
    ReplayableInputs,
    iterate_bounded,
)
from flink_ml_tpu.iteration.config import IterationConfig
from flink_ml_tpu.parallel.collectives import (
    make_data_parallel_step,
    psum,
    shard_map,
)
from flink_ml_tpu.table.table import Table
from flink_ml_tpu.utils.metrics import StepMetrics


def resolve_features(
    table: Table, stage, dim: Optional[int] = None
) -> Tuple[np.ndarray, int]:
    """Feature matrix from either ``vectorCol`` or ``featureCols`` params.

    The column-selection convention of the shared param vocabulary
    (SURVEY.md §2.3.5): an algorithm reads its features from one vector
    column or a list of numeric columns.  ``dim`` pins the vector width at
    inference time (the trained model's dimension).
    """
    vector_col = stage.get_vector_col()
    feature_cols = stage.get_feature_cols()
    if (vector_col is None) == (feature_cols is None):
        raise ValueError("set exactly one of vectorCol / featureCols")
    if vector_col is not None:
        X = table.features_dense(vector_col, dim=dim)
    else:
        X = table.numeric_matrix(feature_cols)
    return X, X.shape[1]


@dataclass
class MinibatchStack:
    """Device-major stacked minibatches with a padding mask, in ONE host
    array: ``combined`` ``(n_dev*steps, mb, d+2)`` — features, then the
    label, then the weight: the slab the fused program scans.

    ``x``/``y``/``w`` are views of it with leading dims ``(n_dev * steps,
    mb)`` — dim 0 is sharded over the ``data`` mesh axis, so each device
    scans ``steps`` local minibatches of ``mb`` rows.  ``w`` is 1.0 for real
    rows, 0.0 for padding.
    """

    combined: np.ndarray  # (n_dev*steps, mb, d+2)
    steps: int
    mb: int
    n_rows: int = 0  # true (un-padded) row count, for throughput metrics

    @property
    def x(self) -> np.ndarray:  # (n_dev*steps, mb, d)
        return self.combined[..., :-2]

    @property
    def y(self) -> np.ndarray:  # (n_dev*steps, mb)
        return self.combined[..., -2]

    @property
    def w(self) -> np.ndarray:  # (n_dev*steps, mb)
        return self.combined[..., -1]


#: the dense pack takes one more thread for every 16 MB it lays: a small
#: table (the tests', a serving batch) is laid on the caller's thread
_PACK_BYTES_A_THREAD_SHIFT = 24


@obs.phased("pack_dense")
def pack_minibatches(
    X: np.ndarray,
    y: np.ndarray,
    n_dev: int,
    global_batch_size: int = 0,
    dtype=np.float32,
    min_steps: int = 0,
) -> MinibatchStack:
    """Pack rows into the device-major minibatch layout.

    ``global_batch_size`` rows are consumed per SGD step across the whole
    mesh (0 = full batch).  Rows are padded to fill the last minibatch; pad
    rows carry weight 0 so sums/counts are exact.  ``min_steps`` floors the
    step count (whole-pad steps are all-zero-weight) — the out-of-core feed
    uses it so every chunk shares one compiled program shape.  The padding
    is laid whole (the slab's shape is the program's), and the one-pass
    kernel reads none of a minibatch's row tiles after its last weighted
    row (``ops/pallas_kernels.py:glm_grad_schedule``); the XLA step still
    reads all of them.

    The table is copied ONCE, straight into the slab the device will hold
    (features, label and weight side by side, device-major), a device's
    minibatch at a time over the machine's cores: a table that needs a
    host's four chips is 25 GB, and a padded copy, a transposed copy and a
    concatenated copy of it beside it were three times that.
    """
    n, d = X.shape
    if global_batch_size <= 0:
        global_batch_size = max(n, n_dev)
    mb = max(1, -(-global_batch_size // n_dev))  # per-device minibatch rows
    steps = max(max(1, -(-n // (mb * n_dev))), int(min_steps))

    # step-major rows in a device-contiguous layout: global SGD step s
    # consumes rows [s*G, (s+1)*G) where G = n_dev*mb — the reference's
    # record order — and device k takes the k-th mb-slice of each step
    # window.  Dim 0 stays device-contiguous so it shards over the 'data'
    # axis; crucially the step->rows mapping does not depend on the total
    # row count, so a chunked (out-of-core) feed cut at G-row boundaries
    # replays the identical update schedule (lib/out_of_core.py).
    comb = np.empty((n_dev * steps, mb, d + 2), dtype=dtype)

    def lay(block):  # the block-th mb rows of the table: step s, device k
        s, k = divmod(block, n_dev)
        lo = min(block * mb, n)
        m = min(lo + mb, n) - lo
        out = comb[k * steps + s]
        out[:m, :d] = X[lo:lo + m]
        out[:m, d] = y[lo:lo + m]
        out[:m, d + 1] = 1.0
        out[m:] = 0.0

    blocks = n_dev * steps
    threads = min(blocks, os.cpu_count() or 1,
                  max(1, comb.nbytes >> _PACK_BYTES_A_THREAD_SHIFT))
    if threads > 1:
        with ThreadPoolExecutor(threads) as pool:
            list(pool.map(lay, range(blocks)))
    else:
        for block in range(blocks):
            lay(block)
    return MinibatchStack(combined=comb, steps=steps, mb=mb, n_rows=n)


# A gradient function: (params, x_mb, y_mb, w_mb) ->
#   (grads pytree matching params, weighted loss sum, weight sum)
GradFn = Callable


def make_sgd_update(learning_rate: float, l2: float):
    """``update(params, grads, count)``: one SGD step with L2 weight decay.

    Weight decay skips scalar leaves (the intercept) — the sklearn/Spark
    convention of not regularizing the bias term.  Shared by every training
    path (dense/sparse fused loops, epoch step, streaming SGD) so the update
    rule cannot drift between them.
    """
    lr = float(learning_rate)
    l2 = float(l2)

    def update(params, grads, count):
        return jax.tree_util.tree_map(
            lambda pi, gi: pi - lr * (gi / count + (l2 if pi.ndim else 0.0) * pi),
            params, grads,
        )

    return update


def _psum_step(grads, loss_sum, w_sum):
    """An SGD step's collectives: the sums of every gradient leaf, of the
    loss and of the row weights over the ``data`` axis, under a scope of
    their own (``fmt.train.psum``, inside the caller's ``fmt.train.grad``)
    so that a trace names the all-reduces of every family's fit.  One
    ``psum`` a leaf and two more: what ``train.psum_calls`` counts."""
    with jax.named_scope("fmt.train.psum"):
        grads = jax.tree_util.tree_map(lambda g: psum(g, "data"), grads)
        return grads, psum(loss_sum, "data"), psum(w_sum, "data")


@dataclass
class SparseMinibatchStack:
    """Device-major sparse minibatches in padded segment-CSR layout: one of
    the sparse route's step layouts, the others being the row-regular
    :class:`EllMinibatchStack` and :class:`ClassedEllMinibatchStack` that
    the pack lays where the rows' widths allow it
    (:data:`_ELL_MAX_SLOT_RATIO`).

    The Criteo-scale replacement for per-record SparseVector math
    (BLAS.java:205-233, SURVEY.md §7.3 'sparse features at Criteo scale'):
    every minibatch is a fixed-size segment-COO block, so the whole training
    set is two dense arrays XLA can shard and scan — no ragged shapes.

      ints   (n_dev*steps, 2, nnz_pad) int32 — [col index, local row id] per
             stored value; pad entries carry row id ``mb`` (dropped by
             segment_sum) and col index 0 with value 0.
      floats (n_dev*steps, nnz_pad + 2*mb) — [values | y | w] concatenated so
             the host->device hop is one float and one int transfer.
    """

    ints: np.ndarray
    floats: np.ndarray
    steps: int
    mb: int
    nnz_pad: int
    dim: int
    n_rows: int = 0  # true (un-padded) row count, for throughput metrics
    n_entries: int = 0  # stored entries (pads not counted), likewise
    #: the pack was asked for the row-regular layout and the rows' widths
    #: failed its rule (``train.sparse_ell_declined`` counts such fits)
    ell_declined: bool = False
    #: the widest row the pack observed when it was asked for either layout
    #: (0 where it was not): with ``mb`` and ``nnz_pad`` the rule's inputs
    widest_row: int = 0
    #: slots a device's step WOULD walk laid row-regular, as the pack
    #: reckoned them for its rule (in the width classes it would cut; 0
    #: where the pack was not asked): ``train.sparse_ell_slots_reckoned``
    #: counts them a fit, beside ``train.sparse_slots``
    ell_step_slots: int = 0

    row_regular = False  # the layout, for ``train.sparse_ell_fits``
    ell_classes = 0  # and its width classes: ``train.sparse_ell_classes``
    hot_ids = None  # no frequency split: ``train.sparse_hot_fits`` reads it
    hot_declined = False

    @property
    def batch(self):
        """The leaves the train program reads, each sharded on its first
        axis."""
        return self.ints, self.floats

    @property
    def step_slots(self) -> int:
        """Slots a device's step walks, pads included."""
        return self.nnz_pad

    def grad_step(self, kind: str, with_intercept: bool = True):
        """This layout's minibatch gradient step, behind the part of a
        program's cache key that names it: what
        :func:`make_sparse_glm_train_fn` builds on."""
        return (("sparse", self.mb, self.nnz_pad, self.dim),
                make_sparse_mb_grad_step(kind, self.mb, self.nnz_pad,
                                         self.dim, with_intercept))


@dataclass
class EllMinibatchStack:
    """Device-major sparse minibatches in the row-regular (ELL) layout: the
    rows of a step side by side at ONE width (the table's largest stored
    entry count a row), entries-major, so that a row's score is a sum over
    an axis and its error reaches its entries by a broadcast — no row ids,
    one gather and one scatter a step where segment-CSR makes two of each.

      ints   (n_dev*steps, width, mb) int32 — feature ids; a shorter row
             pads with id 0 at value 0.0 (adds 0 to its score and 0 to
             slot 0's gradient).  Entries-major: a minor axis of ``width``
             would be padded to the chip's 128 lanes, the sublane axis pads
             to a multiple of 8.
      floats (n_dev*steps, width + 2, mb) — values at ``ints``' shape, then
             one row of labels and one of row weights (0 past the table's
             end): two leaves, as segment-CSR has.

    **The frequency split** (``hot_ids`` set; the pack lays it where the
    feature counts it observes pass :func:`_hot_split_wins`).  The
    ``len(hot_ids)`` most frequent features are looked up by comparison,
    not by address: in ``ints`` a hot entry holds its CODE, its feature's
    place in ``hot_ids``, and the step finds the weight of a code by a
    one-hot product with the hot weights laid ``(codes / 128, 128)``
    (``ops/pallas_kernels.py:hot_scores``), which costs nothing a slot that
    depends on ``dim``.  Every other entry leaves the two leaves (code 0 at
    value 0.0 in its place, as a pad) for the step's COLD LIST, row-regular
    too, in the table's own feature ids.  A split step's rows stand in
    descending order of their COLD width (the count of a row's entries
    that hold no code; stable, the pad rows of a short step last), in both
    leaves, labels and row weights with them (``order``: the row at each
    place), and the cold entries lie plane by plane: plane ``j`` holds the
    ``j``-th cold entry, in stored order, of every row that has one, which
    are the first ``n_j`` places, and the planes follow one another with no
    pad between them:

      cold_idx  (n_dev*steps, cold_slots) int32 -- feature ids; past the
                step's cold entries id 0 at value 0.0, up to ``cold_slots``,
                the fullest step's count rounded by :func:`padded_nnz`.
      cold_vals (n_dev*steps, cold_slots) float32.
      cold_cuts (n_dev*steps, 2, width) int32 -- [``off_j``, ``n_j``]: where
                plane ``j`` starts in the list and how many places it holds
                (an empty plane starts where the entries end).  DATA, not
                shapes: the program's constants are ``mb``, ``width``,
                ``dim``, ``cold_slots`` and K, so one program serves every
                table of the shape whatever cold widths its rows draw.
      hot_ids   (n_dev, K) int32 -- the same ids for every device (a leaf is
                sharded on its first axis); past ``dim`` features, id 0,
                whose codes no entry holds.

    With the rows in that order a row's cold score is a sum over planes of
    contiguous slices and its error reaches its cold entries by contiguous
    writes: ONE take and ONE scatter a cold slot where a segment-COO list
    made two of each (3.46 -> 1.62 ms a step on the Criteo-shaped table: my
    chip runs, PR 36).  The order of rows inside a step changes no sum's
    members; the loss is summed in the step's order.  Every stored entry is
    in exactly one of the two parts, and the weights stay in the table's
    own id space: no permutation of features, float32 throughout.
    """

    ints: np.ndarray
    floats: np.ndarray
    steps: int
    mb: int
    width: int
    dim: int
    n_rows: int = 0  # true (un-padded) row count, for throughput metrics
    n_entries: int = 0  # stored entries (pads not counted), likewise
    cold_idx: Optional[np.ndarray] = None
    cold_vals: Optional[np.ndarray] = None
    cold_cuts: Optional[np.ndarray] = None
    hot_ids: Optional[np.ndarray] = None
    #: a split step's order of rows, the row at each place ``(n_dev*steps,
    #: mb)``; host only (no program reads it: the loss is summed in it)
    order: Optional[np.ndarray] = None
    n_hot_entries: int = 0  # stored entries that hold a code
    #: the pack counted the features' entries and kept the unsplit step
    #: (``train.sparse_hot_declined`` counts such fits)
    hot_declined: bool = False

    row_regular = True
    ell_declined = False
    ell_classes = 1

    @property
    def batch(self):
        """As :attr:`SparseMinibatchStack.batch`."""
        if self.hot_ids is None:
            return self.ints, self.floats
        return (self.ints, self.floats, self.cold_idx, self.cold_vals,
                self.cold_cuts, self.hot_ids)

    @property
    def cold_slots(self) -> int:
        """Slots of a device's step's cold list (0 unsplit)."""
        return 0 if self.hot_ids is None else self.cold_idx.shape[-1]

    @property
    def hot_slots(self) -> int:
        """The slots the hot kernels walk over the steps, an epoch, pads
        included: every plane of every step (0 unsplit)."""
        if self.hot_ids is None:
            return 0
        return self.width * self.mb * len(self.ints)

    @property
    def step_slots(self) -> int:
        """Slots a device's step walks, pads included."""
        return self.width * self.mb + self.cold_slots

    @property
    def ell_step_slots(self) -> int:
        """As :attr:`SparseMinibatchStack.ell_step_slots`: what the rule
        reckoned, the cold list's slots not among them."""
        return self.width * self.mb

    def grad_step(self, kind: str, with_intercept: bool = True):
        """As :meth:`SparseMinibatchStack.grad_step`, for this layout."""
        if self.hot_ids is None:
            return (("sparse-ell", self.mb, self.width, self.dim),
                    make_ell_mb_grad_step(kind, self.mb, self.width,
                                          self.dim, with_intercept))
        return (("sparse-ell-hot", self.mb, self.width, self.dim,
                 self.cold_slots, self.hot_ids.shape[-1]),
                make_hot_ell_grad_step(kind, self.mb, self.width, self.dim,
                                       with_intercept))


@dataclass
class ClassedEllMinibatchStack:
    """Device-major sparse minibatches in the row-regular layout IN WIDTH
    CLASSES: what the pack lays where a table's widest row fails
    :data:`_ELL_MAX_SLOT_RATIO` at one width and its rows, ordered by width
    inside each step, pass it at a few (:func:`_width_classes`).  A step's
    rows stand in descending order of stored width (stable; the pad rows of
    a short step have width 0 and fall last); ``classes`` cuts the ``mb``
    places of that order into runs of whole lane blocks, ``(rows, width)``
    each, the same for every step and device, a class as wide as the widest
    row any step holds in it.  One gather and one scatter a slot, as
    :class:`EllMinibatchStack`, and no row ids.

      ints   (n_dev*steps, slots + 2*mb) int32 -- feature ids, class after
             class, each class entries-major ``(width, rows)``; a shorter
             row pads with id 0 at value 0.0, and a tail of such pads rounds
             the classes' slots up to ``slots`` (:func:`padded_nnz`'s
             length).  Then the step's order both ways: the row at each
             place, and the place of each row.
      floats (n_dev*steps, slots + 2*mb) -- values at the ids' places, then
             labels and row weights in the TABLE's row order, as
             segment-CSR holds them (weight 0 past the table's end).

    The order of rows inside a step changes no sum's members: a step's
    gradient is the mean over the same rows.  The step puts the scores back
    into the table's order before the loss, so that the loss and the
    intercept's gradient are summed over the rows in the order every other
    layout (and a plain reference) sums them: summed in the step's order
    the loss read up to two float32 places off the reference's on the chip,
    of the three its limit allows (my chip runs, PR 34).

    **The frequency split** (``hot_ids`` set; the pack lays it where the
    feature counts pass :func:`_hot_split_wins` for the classes' slots, as
    :class:`EllMinibatchStack` lays its own).  A hot entry is looked up by
    comparison, every other one goes to the step's cold list, and each part
    has its own order of the step's rows; the classes cut neither (they
    stay the rule's reckoning).  ``ints`` and ``floats`` then hold a step's
    rows alone:

      ints      (n_dev*steps, 4, mb) int32 -- the HOT order (the row at
                each place), the place of each row in it, then the same two
                for the COLD order.
      floats    (n_dev*steps, 2, mb) -- labels and row weights in the
                table's order.
      hot_codes (n_dev*steps, nb, P, T) int32 -- codes, in BLOCKS of ``P``
                (:data:`_HOT_BLOCK_PLANES`) planes of one row tile of ``T``
                (:func:`_hot_block_tile`) places of the hot order
                (rows in descending order of their hot width, stable; the
                pad rows of a short step last): plane ``j`` of a tile holds
                the ``j``-th hot entry, in stored order, of each of its
                rows, and a tile has as many blocks as its first (widest)
                row fills, at least one; the tiles' blocks follow one
                another, then pads, up to ``nb`` (the fullest step's count
                rounded up to a multiple of 8).  A shorter row pads with
                code 0 at value 0.0.
      hot_vals  (n_dev*steps, nb, P, T) float32, at the codes' places.
      hot_sched (n_dev*steps, 2, nb) int32 -- DATA: the block each of the
                kernels' grid steps reads and its row tile; past the
                step's blocks the last of both again
                (``pallas_kernels.hot_scores_blocks``).
      cold_idx / cold_vals / cold_cuts -- the cold list as
                :class:`EllMinibatchStack` lays it, plane by plane over the
                COLD order, ``cold_cuts`` ``(n_dev*steps, 2, planes)`` with
                the planes rounded up to a multiple of 128.
      hot_ids   (n_dev, K) int32, as :class:`EllMinibatchStack`'s.

    The kernels walk the blocks a step's rows fill, not a rectangle of its
    widest row, and no constant of the program comes from the widths a
    table drew: its shapes are ``mb``, ``nb``, ``T``, the cold list's
    slots and planes, ``dim`` and K.  The scores of both parts go into the
    table's order for the loss and the error back into each part's (four
    takes of ``mb``); float32 throughout, every stored entry in exactly one
    part.
    """

    ints: np.ndarray
    floats: np.ndarray
    steps: int
    mb: int
    classes: tuple  # ((rows, width), ...): rows sum to mb
    slots: int
    dim: int
    n_rows: int = 0  # true (un-padded) row count, for throughput metrics
    n_entries: int = 0  # stored entries (pads not counted), likewise
    hot_codes: Optional[np.ndarray] = None
    hot_vals: Optional[np.ndarray] = None
    hot_sched: Optional[np.ndarray] = None
    cold_idx: Optional[np.ndarray] = None
    cold_vals: Optional[np.ndarray] = None
    cold_cuts: Optional[np.ndarray] = None
    hot_ids: Optional[np.ndarray] = None
    n_hot_entries: int = 0  # stored entries that hold a code
    #: the slots the hot kernels walk over the steps, an epoch: the live
    #: blocks', pads of a block included (``train.sparse_hot_slots``)
    hot_slots: int = 0
    #: as :attr:`EllMinibatchStack.hot_declined`
    hot_declined: bool = False

    row_regular = True
    ell_declined = False

    @property
    def batch(self):
        """As :attr:`SparseMinibatchStack.batch`."""
        if self.hot_ids is None:
            return self.ints, self.floats
        return (self.hot_codes, self.hot_vals, self.hot_sched, self.cold_idx,
                self.cold_vals, self.cold_cuts, self.ints, self.floats,
                self.hot_ids)

    @property
    def ell_classes(self) -> int:
        return len(self.classes)

    @property
    def cold_slots(self) -> int:
        """As :attr:`EllMinibatchStack.cold_slots`."""
        return 0 if self.hot_ids is None else self.cold_idx.shape[-1]

    @property
    def step_slots(self) -> int:
        """Slots a device's step walks, the pads of the tail included (the
        split: its blocks' and its cold list's, pads of both included)."""
        if self.hot_ids is None:
            return self.slots
        return int(np.prod(self.hot_codes.shape[1:])) + self.cold_slots

    @property
    def ell_step_slots(self) -> int:
        """As :attr:`SparseMinibatchStack.ell_step_slots`: the classes'
        slots, which the rule reckoned."""
        return sum(rows * width for rows, width in self.classes)

    def grad_step(self, kind: str, with_intercept: bool = True):
        """As :meth:`SparseMinibatchStack.grad_step`, for this layout."""
        if self.hot_ids is None:
            return (("sparse-ell-classed", self.mb, self.classes, self.slots,
                     self.dim),
                    make_classed_ell_grad_step(kind, self.mb, self.classes,
                                               self.slots, self.dim,
                                               with_intercept))
        return (("sparse-ell-classed-hot", self.mb, self.hot_codes.shape[1:],
                 self.cold_idx.shape[-1], self.cold_cuts.shape[-1],
                 self.hot_ids.shape[-1], self.dim),
                make_classed_hot_grad_step(kind, self.mb, self.dim,
                                           with_intercept))


#: the most slots a row-regular step may walk for ONE slot of the
#: segment-CSR step it replaces (``mb * width <= 1.75 * nnz_pad``).  By the
#: chip's per-slot costs (PERF.md §5; my chip runs, PR 28) a segment-CSR
#: slot costs 7.1 (take of the weights) + 8.7 (sorted scatter-add into the
#: rows) + 7.1 (take of the error by row id) + 6.6 (scatter into ``dim``) =
#: 29.5 ns and a row-regular slot 7.1 + 6.6 = 13.7 ns: the layouts break
#: even at 29.5 / 13.7 = 2.15 slots for one (ragged tables read 2.0-2.05:
#: row-regular x1.40 faster at 1.45 slots for one, 12% slower at 2.30).
#: 1.75 leaves room on both measured sides: the row-regular step is then
#: at least 2.0 / 1.75 = 1.14 times as fast, for leaves at most 1.17 times
#: segment-CSR's bytes (the int leaf, no row-id plane, 0.875 of it; the
#: float leaf 1.75).  (ISSUE 28 reckoned 26.2 / 10.4 = 2.5, and so asked
#: for 2, from a scatter at 3.3 ns: half its cost, lost by the benchmark's
#: breakdown where two programs name an operation alike).  Since PR 34 the
#: pack holds a table that fails the rule at ONE width to the same rule in
#: width classes (:func:`_width_classes`): on LIBSVM url_combined's shape
#: one width would walk 3.9-4.2 slots for one, the classes walk 1.026, and
#: the step runs x1.96 faster than segment-CSR, 61.2 against 119.8 ms (a
#: take 6.63 + a scatter 7.99 ns a slot against 6.63 + 8.61 + 6.63 + 7.98;
#: my chip runs, PR 34)
_ELL_MAX_SLOT_RATIO = 1.75

#: the classed row-regular layout (:class:`ClassedEllMinibatchStack`) cuts a
#: step's rows into classes of whole lane blocks of this many rows (the
#: step's rows lie on the chip's 128 lanes), at most this many classes: a
#: class is a reshape, a sum over an axis and a broadcast of its own in the
#: step's program, about 5 microseconds of a 60 ms step.  On LIBSVM
#: url_combined's shape (2.4 M rows of 24-512 entries, log-normal about
#: 115.6; my chip runs, PR 34) the best cut into 8 / 16 / 32 classes walks
#: 4.38 / 4.16 / 4.07 M slots a step for segment-CSR's 3.97 M and a fit
#: lasted 4.838 / 4.593 / 4.477 s against 8.868 (before the scores went
#: back into the table's order for the loss: 0.05 s a fit more): the slots
#: decide (a take 6.63 ns, a scatter 8.00 ns a slot at every cut), so the
#: cap is the largest read; past it a cut gains under 1%
_LANE_BLOCK = 128
_ELL_MAX_CLASSES = 32

#: features the frequency split looks up by comparison: 128 x 128, one MXU
#: tile squared (a code is a row of the hot weights' table and a lane).  On
#: the cell's table 16384 features hold 90.9% of the stored entries, 4096
#: hold 84.8%, and a fit lasts a third longer at 4096 (my chip run, PR 30)
_HOT_K = 16384
#: what a slot costs on a TPU v5e, in ns, on the cell's table (PERF.md §5):
#: a row-regular slot 3.108 s / (175 x 1,277,952) = 13.9 (take 7.1 +
#: scatter 6.6 + the rest; my chip runs, PR 30, and 13.90 again in PR 36);
#: the hot lookup, both kernels and the hot weights' take and scatter, 1.87
#: ms a step of 1,277,952 slots = 1.5, whatever the slot holds (PR 30; 1.45
#: in PR 36).  A cold slot, since PR 36 ONE take and ONE scatter over a list
#: laid plane by plane: 1.619 ms a step of 117,248 slots = 13.8 (take 6.63
#: + scatter 6.66 + the planes' slices, writes and gaps 0.5; one traced fit,
#: my chip runs, PR 36; whole fits of three thinner skews less the hot
#: lookup read 13.4-13.5).  As a segment-COO list with row ids it paid four
#: random accesses, 30.3 (PR 30).  The three costs predicted the split fits
#: of those skews (hot shares 0.82, 0.55, 0.28) to 1-2.5%
_ELL_SLOT_NS = 13.9
_COLD_SLOT_NS = 13.8
_HOT_SLOT_NS = 1.5
#: the split engages where those costs say it takes at most this share of
#: the unsplit step's time: break-even is a hot share of 0.10, the rule
#: asks for 0.30 on a table of one width (0.59 and 0.68 while a cold slot
#: cost 30.3).  The chip read the split x3.51 faster at a hot share of
#: 0.82, x1.84 at 0.55 and x1.25 at 0.28, where the costs say 0.82 of the
#: unsplit step's time and the rule declines (my chip runs, PR 36)
_HOT_SPLIT_ROOM = 0.8


def _hot_split_wins(hot_share: float, slots: int, entries: int) -> bool:
    """Does a row-regular step of ``slots`` slots, ``hot_share`` of whose
    ``entries`` stored entries fall on the :data:`_HOT_K` most frequent
    features, run faster split?  By the per-slot costs above: every slot
    pays the hot lookup, a cold entry one take and one scatter in the cold
    list, and the unsplit step pays the two on every slot."""
    split = slots * _HOT_SLOT_NS + (1.0 - hot_share) * entries * _COLD_SLOT_NS
    return split <= _HOT_SPLIT_ROOM * slots * _ELL_SLOT_NS


def _hot_split_measured() -> bool:
    """Were those costs measured on what this process computes on?  They
    are a TPU's: a CPU gathers cheaply and multiplies one-hots dearly, and
    its pack neither counts nor splits."""
    return jax.devices()[0].platform == "tpu"


def _hot_features(indices, dim: int):
    """``(hot_ids (_HOT_K,) int32, their share of the stored entries)``:
    the most frequent features of a validated CSR column's ``indices``,
    ties to the lower id; past ``dim`` features, id 0.  Counted on the
    machine's cores where the native library counts int32 ids
    (``native.count_ids``: 277 M ids took one core 2.5-3 s), else block by
    block, in the indices' own dtype (``np.bincount`` makes an int64 copy
    of what it is given)."""
    from flink_ml_tpu import native

    counts = native.count_ids(indices, dim)
    if counts is None:
        counts = np.zeros(dim, np.int64)
        for lo in range(0, len(indices), _ORDER_CHECK_BLOCK):
            counts += np.bincount(indices[lo : lo + _ORDER_CHECK_BLOCK],
                                  minlength=dim)
    top = np.argsort(-counts, kind="stable")[:_HOT_K]
    hot_ids = np.zeros(_HOT_K, np.int32)
    hot_ids[: len(top)] = top
    return hot_ids, float(counts[top].sum()) / max(1, len(indices))


def padded_nnz(nnz_max: int, pad_multiple: int, min_nnz_pad: int = 0) -> int:
    """The padded entry count of a segment-CSR step whose fullest minibatch
    stores ``nnz_max`` entries.  Where a caller brings a floor (the width
    that processes or out-of-core chunks agreed on) it is the larger of the
    floor and ``nnz_max`` rounded up to ``pad_multiple``, as ever.  Where
    nothing else fixes the width the count is rounded up to an ODD multiple
    of ``pad_multiple``: the chip's gather of N addresses runs 7% faster
    at an odd multiple of 512 than at a multiple of 1024 (a TPU v5e, N =
    3.97 M, my chip runs, PR 33: 6.63 against 7.13 ns an address on five
    widths of one table and six tables, whatever is done to the slices
    that feed it), so a table's fit was 2.7% faster or slower by the
    residue its widths happened to leave; at most ``pad_multiple`` slots
    more buy the faster one."""
    blocks = -(-nnz_max // pad_multiple)
    if min_nnz_pad:
        return max(blocks * pad_multiple, int(min_nnz_pad))
    return (blocks | 1) * pad_multiple


@obs.phased("pack_sparse")
def pack_sparse_minibatches(
    vectors: Sequence,
    y: np.ndarray,
    n_dev: int,
    global_batch_size: int = 0,
    dim: Optional[int] = None,
    pad_multiple: int = 512,
    min_nnz_pad: int = 0,
    min_steps: int = 0,
    row_regular: bool = False,
):
    """Pack sparse rows into the device-major sparse layout.

    ``vectors`` is a sequence of SparseVector (per-row Python loop) or a
    :class:`~flink_ml_tpu.ops.batch.CsrRows` column (fully vectorized — the
    fast path the native streaming loader feeds).  Out-of-range feature
    indices fail loudly here: XLA's gather clamps and segment_sum drops
    them, which would silently train a corrupted model.  ``min_nnz_pad``
    floors the padded nnz width — the out-of-core feed uses it to keep one
    static shape (one compiled program) across chunks.

    ``row_regular`` is the caller saying that its step may be either
    layout (the plain route on one process and a 1-D mesh; the 2-D mesh,
    multi-process and out-of-core fits read segment-CSR and leave it
    off).  A CSR column then packs as an :class:`EllMinibatchStack`
    where ``mb * width <= _ELL_MAX_SLOT_RATIO * nnz_pad`` — a choice made
    from the row widths the pack observes — else as a
    :class:`ClassedEllMinibatchStack` where the slots of a step whose rows
    are ordered by width and laid in a few width classes
    (:func:`_width_classes`) pass the same rule, and as segment-CSR, byte
    for byte what it is without the flag and marked ``ell_declined``, where
    they fail it too.  A per-object column keeps segment-CSR.  Where either
    row-regular layout is taken, on a TPU, the pack also counts the stored
    entries a feature and lays the frequency split
    (:class:`EllMinibatchStack`, :class:`ClassedEllMinibatchStack`) where
    :func:`_hot_split_wins` says it pays for the slots the layout walks:
    the hot features' entries as codes, each step's rows ordered by their
    cold width and its cold entries plane by plane, the planes' cuts as
    data (:func:`_pack_ell_split`, :func:`_pack_ell_classed_split`: the
    classed table's codes in blocks of a row tile over its rows ordered by
    their hot width; gauges ``pack_sparse.cold_step_slots`` and
    ``pack_sparse.cold_planes`` beside ``.ell_step_slots``).  A table that
    fails the split's rule keeps the unsplit leaves byte for byte and is
    marked ``hot_declined``.
    """
    from flink_ml_tpu.ops.batch import CsrRows

    if isinstance(vectors, CsrRows):
        return _pack_sparse_minibatches_csr(
            vectors, y, n_dev, global_batch_size, dim, pad_multiple,
            min_nnz_pad, min_steps, row_regular,
        )
    n = len(vectors)
    max_idx = -1
    for r, v in enumerate(vectors):
        if len(v.indices):
            if int(v.indices.min()) < 0:
                raise ValueError(f"row {r}: negative feature index")
            max_idx = max(max_idx, int(v.indices.max()))
    if dim is None:
        dim = max_idx + 1
        for v in vectors:
            size = v.size()
            if size >= 0:
                dim = max(dim, size)
    elif max_idx >= dim:
        raise ValueError(
            f"feature index {max_idx} out of range for numFeatures={dim}"
        )
    dim = max(dim, 1)
    mb, steps, n_groups, _group_lo = _sparse_layout(
        n, n_dev, global_batch_size, min_steps
    )

    # max nnz over minibatches, padded to a bucket multiple (shared static shape)
    nnz_max = 1
    for g in range(n_groups):
        lo = _group_lo(g)
        nnz_max = max(
            nnz_max,
            sum(len(vectors[i].indices) for i in range(lo, min(lo + mb, n))),
        )
    nnz_pad = padded_nnz(nnz_max, pad_multiple, min_nnz_pad)

    ints = np.zeros((n_groups, 2, nnz_pad), dtype=np.int32)
    ints[:, 1, :] = mb  # pad row id -> dropped segment
    floats = np.zeros((n_groups, nnz_pad + 2 * mb), dtype=np.float32)
    for g in range(n_groups):
        lo = _group_lo(g)
        pos = 0
        for j in range(mb):
            i = lo + j
            if i >= n:
                break
            v = vectors[i]
            cnt = len(v.indices)
            ints[g, 0, pos : pos + cnt] = v.indices
            ints[g, 1, pos : pos + cnt] = j
            floats[g, pos : pos + cnt] = v.vals
            pos += cnt
            floats[g, nnz_pad + j] = y[i]
            floats[g, nnz_pad + mb + j] = 1.0
    return SparseMinibatchStack(
        ints=ints, floats=floats, steps=steps, mb=mb, nnz_pad=nnz_pad, dim=dim,
        n_rows=n, n_entries=sum(len(v.indices) for v in vectors),
    )


def _sparse_layout(n: int, n_dev: int, global_batch_size: int, min_steps: int):
    """The ONE copy of the sparse stack's scalar layout math: per-device
    minibatch rows, step count, group count, and the step-major group->row
    mapping (group g = device k, local step s covers rows
    [s*G + k*mb, s*G + (k+1)*mb) with G = n_dev*mb).  Shared by the per-row
    and vectorized CSR packers so their layouts cannot drift (their outputs
    are asserted byte-identical in tests)."""
    if global_batch_size <= 0:
        global_batch_size = max(n, n_dev)
    mb = max(1, -(-global_batch_size // n_dev))
    steps = max(max(1, -(-n // (mb * n_dev))), int(min_steps))

    def group_lo(g: int) -> int:
        k, s = divmod(g, steps)
        return s * (n_dev * mb) + k * mb

    return mb, steps, n_dev * steps, group_lo


def sparse_row_counts(vectors) -> np.ndarray:
    """Stored-entry count per row (CsrRows: vectorized; else per object)."""
    from flink_ml_tpu.ops.batch import CsrRows

    if isinstance(vectors, CsrRows):
        return vectors.nnz_per_row()
    return np.fromiter(
        (len(v.indices) for v in vectors), np.int64, len(vectors)
    )


def sparse_layout_floors(counts: np.ndarray, n_dev: int,
                         global_batch_size: int,
                         pad_multiple: int = 512):
    """(nnz_pad, steps) the pack WOULD choose for these row counts — without
    materializing the stack.  The multi-process agreement pre-scan: each
    process computes its local value here, ``agree_max`` reconciles them,
    and the single pack runs with the agreed floors (no throwaway pack)."""
    n = int(len(counts))
    mb, steps, n_groups, group_lo = _sparse_layout(
        n, n_dev, global_batch_size, 0
    )
    csum = np.concatenate([[0], np.cumsum(np.asarray(counts, np.int64))])
    los = np.minimum(
        np.asarray([group_lo(g) for g in range(n_groups)], np.int64), n
    )
    his = np.minimum(los + mb, n)
    nnz_max = max(1, int((csum[his] - csum[los]).max(initial=0)))
    return -(-nnz_max // pad_multiple) * pad_multiple, steps


#: entries a block of the CSR order check looks at (its temporaries are a
#: few bytes an entry: a whole-column check of a 447 M-entry click log took
#: an int64 copy and its differences, 7 GB, for one boolean)
_ORDER_CHECK_BLOCK = 1 << 24


def _csr_rows_out_of_order(indptr, indices, nnz_total: int) -> bool:
    """Does any row hold an index BELOW the one stored before it?  Equal
    neighbours (a hashed column's collisions inside a row) are in order:
    downstream needs non-decreasing ids, and a stable sort would leave them
    where they are.  Checked block by block, in the indices' own dtype."""
    boundary = indptr[1:-1]  # entry b starts a row: the pair (b-1, b) crosses
    boundary = boundary[(boundary > 0) & (boundary < nnz_total)]
    for lo in range(0, nnz_total - 1, _ORDER_CHECK_BLOCK):
        hi = min(lo + _ORDER_CHECK_BLOCK, nnz_total - 1)
        falls = indices[lo + 1 : hi + 1] < indices[lo:hi]  # pair (i, i+1)
        if not falls.any():
            continue
        crossing = boundary[np.searchsorted(boundary, lo + 1):
                            np.searchsorted(boundary, hi + 1)]
        falls[crossing - 1 - lo] = False
        if falls.any():
            return True
    return False


@obs.phased("pack_csr")
def _pack_sparse_minibatches_csr(
    rows, y, n_dev: int, global_batch_size: int, dim, pad_multiple: int,
    min_nnz_pad: int, min_steps: int, row_regular: bool = False,
):
    """Vectorized packing from a CSR column: identical layout and validation
    to the per-row path (shared tests assert bit-equality), but the inner
    work is numpy slice copies — O(groups) Python instead of O(rows).  With
    ``row_regular`` the row widths decide between this layout,
    :func:`_pack_ell` and :func:`_pack_ell_classed` (see
    :func:`pack_sparse_minibatches`)."""
    n = len(rows)
    indptr, indices, values = rows.indptr, rows.indices, rows.values
    nnz_total = int(indptr[-1]) if n else 0
    max_idx = int(indices.max()) if nnz_total else -1
    if nnz_total and int(indices.min()) < 0:
        first_bad = int(np.argmax(indices < 0))
        row = int(np.searchsorted(indptr, first_bad, side="right")) - 1
        raise ValueError(f"row {row}: negative feature index")
    if dim is None:
        dim = max(max_idx + 1, rows.dim)
    elif max_idx >= dim:
        raise ValueError(
            f"feature index {max_idx} out of range for numFeatures={dim}"
        )
    dim = max(dim, 1)
    mb, steps, n_groups, _group_lo = _sparse_layout(
        n, n_dev, global_batch_size, min_steps
    )

    counts = rows.nnz_per_row()
    nnz_max = 1
    bounds = []
    for g in range(n_groups):
        lo = _group_lo(g)
        hi = min(lo + mb, n)
        if lo >= n:
            bounds.append((lo, lo, 0, 0))
            continue
        e0, e1 = int(indptr[lo]), int(indptr[hi])
        bounds.append((lo, hi, e0, e1))
        nnz_max = max(nnz_max, e1 - e0)
    nnz_pad = padded_nnz(nnz_max, pad_multiple, min_nnz_pad)

    width = slots = 0
    if row_regular:
        # the slots either layout would walk a step, from the widths alone:
        # the rule's inputs, said in the pack's own phase.  Row-regular at
        # ONE width first; where that fails the rule, with a step's rows
        # ordered by width and laid in a few width classes
        width = max(1, int(counts.max(initial=0)))
        slots, classes = mb * width, ((mb, width),)
        if slots > _ELL_MAX_SLOT_RATIO * nnz_pad:
            orders, classes = _width_classes(bounds, counts, mb)
            slots = sum(r * w for r, w in classes)
        obs.gauge_set("pack_sparse.widest_row", width)
        obs.gauge_set("pack_sparse.mean_row", nnz_total / max(1, n))
        obs.gauge_set("pack_sparse.ell_classes", len(classes))
        obs.gauge_set("pack_sparse.ell_step_slots", slots)
        obs.gauge_set("pack_sparse.csr_step_slots", nnz_pad)
        if slots <= _ELL_MAX_SLOT_RATIO * nnz_pad:
            # and the feature counts decide whether the hot features leave
            # the gather and the scatter (either layout's split)
            hot_ids = None
            counted = _hot_split_measured()
            if counted:
                # a split needs the kernels' module: its import on a thread
                # beside the count, which holds no GIL
                _start_kernels_import()
                hot_ids, hot_share = _hot_features(indices[:nnz_total], dim)
                if not _hot_split_wins(hot_share, slots, nnz_max):
                    hot_ids = None
            if len(classes) > 1 and hot_ids is None:
                stack = _pack_ell_classed(rows, y, bounds, counts, orders,
                                          classes, mb, steps, dim,
                                          pad_multiple)
            elif len(classes) > 1:
                stack = _pack_ell_classed_split(
                    rows, y, bounds, counts, classes, mb, steps, dim, n_dev,
                    pad_multiple, hot_ids)
            elif hot_ids is None:
                stack = _pack_ell(rows, y, bounds, counts, width, mb, steps,
                                  dim)
            else:
                stack = _pack_ell_split(rows, y, bounds, counts, width, mb,
                                        steps, dim, n_dev, pad_multiple,
                                        hot_ids)
            stack.hot_declined = counted and hot_ids is None
            return stack

    if nnz_total:
        # per-row ascending ids are a layout invariant (the per-object
        # pack's rows ascend, and this pack lays the same bytes); the
        # SparseVector path sorts at construction, but CSR columns from
        # the native loader carry file order verbatim — sort here when a
        # file violates it (one vectorized pass detects; per-row argsort
        # only runs on violation).  The row-regular layout above sums a
        # row's entries whatever their order, and skips this
        if _csr_rows_out_of_order(indptr, indices, nnz_total):
            order = np.argsort(
                indices + (np.repeat(
                    np.arange(n, dtype=np.int64), np.diff(indptr)
                ) << 32),
                kind="stable",
            )
            indices = indices[order]
            values = values[order]

    ints = np.zeros((n_groups, 2, nnz_pad), dtype=np.int32)
    ints[:, 1, :] = mb  # pad row id -> dropped segment
    floats = np.zeros((n_groups, nnz_pad + 2 * mb), dtype=np.float32)
    for g, (lo, hi, e0, e1) in enumerate(bounds):
        if lo >= n:
            continue
        cnt = e1 - e0
        ints[g, 0, :cnt] = indices[e0:e1]
        ints[g, 1, :cnt] = np.repeat(
            np.arange(hi - lo, dtype=np.int32), counts[lo:hi]
        )
        floats[g, :cnt] = values[e0:e1]
        floats[g, nnz_pad : nnz_pad + (hi - lo)] = y[lo:hi]
        floats[g, nnz_pad + mb : nnz_pad + mb + (hi - lo)] = 1.0
    return SparseMinibatchStack(
        ints=ints, floats=floats, steps=steps, mb=mb, nnz_pad=nnz_pad, dim=dim,
        n_rows=n, n_entries=nnz_total, ell_declined=row_regular,
        widest_row=width, ell_step_slots=slots,
    )


def _width_classes(bounds, counts, mb: int):
    """``(orders, classes)`` of the classed row-regular layout, from the
    widths alone.  ``orders[g]`` puts a device-step's rows (``bounds[g]``)
    in descending order of stored width, stable.  The ENVELOPE of those
    orders, the widest row any step holds at each of the ``mb`` places, is
    what one program for every step has to hold; ``classes`` cuts the places
    into at most :data:`_ELL_MAX_CLASSES` runs of whole lane blocks,
    ``((rows, width), ...)``, each as wide as its first place's envelope (at
    least 1), so that the slots a step walks, ``sum(rows * width)``, are the
    fewest such a cut allows (a dynamic programme over the blocks; of two
    cuts with the same slots, the one with fewer classes)."""
    envelope = np.zeros(mb, np.int64)
    orders = []
    for lo, hi, _e0, _e1 in bounds:
        widths = counts[lo:hi]
        order = np.argsort(-widths, kind="stable")
        orders.append(order)
        np.maximum(envelope[: hi - lo], widths[order],
                   out=envelope[: hi - lo])
    starts = np.arange(0, mb, _LANE_BLOCK)
    ends = np.minimum(starts + _LANE_BLOCK, mb)
    n_blocks = len(starts)
    need = np.maximum(envelope[starts], 1)
    # cost[a, b - 1]: the slots of ONE class over blocks a..b-1
    # (float64 holds every count exactly, and inf marks b <= a)
    cost = np.where(ends[None, :] > starts[:, None],
                    (ends[None, :] - starts[:, None]) * need[:, None],
                    np.inf)
    # best[k][b]: the fewest slots of blocks 0..b-1 cut into k + 1 classes
    best, cut = [cost[0]], []
    for _k in range(1, min(_ELL_MAX_CLASSES, n_blocks)):
        # the last class starts at block a >= 1, after best[-1][a - 1]
        total = best[-1][:-1, None] + cost[1:, :]
        cut.append(np.argmin(total, axis=0) + 1)
        best.append(np.min(total, axis=0))
    totals = [b[-1] for b in best]
    k = totals.index(min(totals))
    edges, b = [n_blocks], n_blocks
    for a_of in reversed(cut[:k]):
        b = int(a_of[b - 1])
        edges.append(b)
    edges.append(0)
    edges.reverse()
    classes = tuple(
        (int(ends[b - 1] - starts[a]), int(need[a]))
        for a, b in zip(edges[:-1], edges[1:]))
    return orders, classes


def _pack_ell_classed(rows, y, bounds, counts, orders, classes, mb: int,
                      steps: int, dim: int,
                      pad_multiple: int) -> ClassedEllMinibatchStack:
    """Lay a validated CSR column out row-regular in the width classes
    :func:`_width_classes` chose, a device's step at a time.  A step's
    destination order is built ONCE for both leaves, as the stored entry each
    slot holds (a pad holds the step's appended zero entry), and the leaves
    are gathered through it, not scattered; the steps are spread over
    threads (numpy's ``take`` releases the lock)."""
    indptr, indices, values = rows.indptr, rows.indices, rows.values
    slots = padded_nnz(sum(r * w for r, w in classes), pad_multiple)
    ints = np.zeros((len(bounds), slots + 2 * mb), dtype=np.int32)
    floats = np.zeros((len(bounds), slots + 2 * mb), dtype=np.float32)

    def lay(g):
        lo, hi, e0, e1 = bounds[g]
        m, n = hi - lo, e1 - e0
        # the pad rows of a short step keep their places, after every row
        order = np.arange(mb, dtype=np.int32)
        order[:m] = orders[g]
        ints[g, slots : slots + mb] = order
        ints[g, slots + mb :][order] = np.arange(mb, dtype=np.int32)
        if not m:
            return
        order = order[:m]
        # a place's row: its first entry in the step, and its width (a pad
        # row: width 0)
        first = np.zeros(mb, np.int32)
        width = np.zeros(mb, np.int32)
        first[:m] = indptr[lo:hi][order] - e0
        width[:m] = counts[lo:hi][order]
        ids = np.zeros(n + 1, np.int32)  # the step's entries, then the pad's
        vals = np.zeros(n + 1, np.float32)
        ids[:n], vals[:n] = indices[e0:e1], values[e0:e1]
        at = place = 0
        for rows_c, width_c in classes:
            here = slice(place, place + rows_c)
            nth = np.arange(width_c, dtype=np.int32)[:, None]
            src = first[None, here] + nth  # (width_c, rows_c)
            np.copyto(src, n, where=nth >= width[None, here])
            src = src.ravel()
            np.take(ids, src, out=ints[g, at : at + len(src)], mode="clip")
            np.take(vals, src, out=floats[g, at : at + len(src)],
                    mode="clip")
            at += len(src)
            place += rows_c
        floats[g, slots : slots + m] = y[lo:hi]
        floats[g, slots + mb : slots + mb + m] = 1.0

    with ThreadPoolExecutor(min(len(bounds), os.cpu_count() or 1)) as pool:
        list(pool.map(lay, range(len(bounds))))
    return ClassedEllMinibatchStack(
        ints=ints, floats=floats, steps=steps, mb=mb, classes=classes,
        slots=slots, dim=dim, n_rows=len(rows),
        n_entries=int(indptr[-1]) if len(rows) else 0,
    )


def _pack_ell(rows, y, bounds, counts, width: int, mb: int, steps: int,
              dim: int) -> EllMinibatchStack:
    """Lay a validated CSR column out row-regular, a device's step (one
    entry of ``bounds``: rows ``[lo, hi)``, entries ``[e0, e1)``) at a
    time, so that the temporaries are a step's: a step whose rows are all
    ``width`` long is one transposing copy, a ragged one scatters its
    entries by (position in the row, row).  Rows keep their stored order of
    entries: the step sums a row whatever its order.  (Split by frequency:
    :func:`_pack_ell_split`.)"""
    indptr, indices, values = rows.indptr, rows.indices, rows.values
    ints = np.zeros((len(bounds), width, mb), dtype=np.int32)
    floats = np.zeros((len(bounds), width + 2, mb), dtype=np.float32)
    for g, (lo, hi, e0, e1) in enumerate(bounds):
        m = hi - lo
        if not m:
            continue
        ids, vals = indices[e0:e1], values[e0:e1]
        if e1 - e0 == m * width:
            ints[g, :, :m] = ids.reshape(m, width).T
            floats[g, :width, :m] = vals.reshape(m, width).T
        elif e1 > e0:
            rid = np.repeat(np.arange(m, dtype=np.int32), counts[lo:hi])
            pos = np.arange(e1 - e0, dtype=np.int32) - np.repeat(
                (indptr[lo:hi] - e0).astype(np.int32), counts[lo:hi])
            ints[g, pos, rid] = ids
            floats[g, pos, rid] = vals
        floats[g, width, :m] = y[lo:hi]
        floats[g, width + 1, :m] = 1.0
    return EllMinibatchStack(
        ints=ints, floats=floats, steps=steps, mb=mb, width=width, dim=dim,
        n_rows=len(rows), n_entries=int(indptr[-1]) if len(rows) else 0,
    )


def _pack_ell_split(rows, y, bounds, counts, width: int, mb: int, steps: int,
                    dim: int, n_dev: int, pad_multiple: int,
                    hot_ids) -> EllMinibatchStack:
    """:func:`_pack_ell` with the frequency split (see
    :class:`EllMinibatchStack`): an entry of one of ``hot_ids``' features is
    laid as its code, every other entry leaves code 0 at value 0.0 behind
    and goes to its step's cold list.  A step's rows are ordered by their
    cold width (descending, stable) and EVERYTHING of the step is laid in
    that order: both planes' columns, labels, row weights, and the cold
    entries plane by plane (a row's ``j``-th cold entry, in stored order, at
    its place in plane ``j``), the planes' starts and lengths beside them
    as data.  The steps are spread over threads (numpy's copies and takes
    release the lock), as :func:`_pack_ell_classed`'s."""
    indptr, indices, values = rows.indptr, rows.indices, rows.values
    blocks = len(bounds)
    ints = np.zeros((blocks, width, mb), dtype=np.int32)
    floats = np.zeros((blocks, width + 2, mb), dtype=np.float32)
    cuts = np.zeros((blocks, 2, width), dtype=np.int32)
    order = np.tile(np.arange(mb, dtype=np.int32), (blocks, 1))
    code_of = np.full(dim, -1, np.int32)
    n_hot = min(len(hot_ids), dim)
    code_of[hot_ids[:n_hot]] = np.arange(n_hot, dtype=np.int32)
    cold = [None] * blocks  # a step's (feature ids, values) in plane order

    def lay(g):
        lo, hi, e0, e1 = bounds[g]
        m, n = hi - lo, e1 - e0
        if not m:
            return
        codes = code_of[indices[e0:e1]]
        is_cold = codes < 0
        # cold entries stored before each entry, and before each row's first
        before = np.zeros(n + 1, np.int32)
        np.cumsum(is_cold, out=before[1:])
        first = (indptr[lo : hi + 1] - e0).astype(np.int32)
        cold_width = np.diff(before[first])
        by_width = np.argsort(-cold_width, kind="stable")
        order[g, :m] = by_width
        place_of = np.empty(m, np.int32)
        place_of[by_width] = np.arange(m, dtype=np.int32)
        # plane j holds the rows with more than j cold entries: the first
        # n_j places; it starts where the planes before it end
        wider = m - np.cumsum(np.bincount(cold_width, minlength=width + 1))
        cuts[g, 1] = wider[:width]
        cuts[g, 0, 1:] = np.cumsum(wider[: width - 1])
        np.maximum(codes, 0, out=codes)
        vals = np.where(is_cold, np.float32(0.0), values[e0:e1])
        rid = None  # a step whose rows are all ``width`` long needs none
        if n == m * width:
            ints[g, :, :m] = codes.reshape(m, width)[by_width].T
            floats[g, :width, :m] = vals.reshape(m, width)[by_width].T
        elif n:
            rid = np.repeat(np.arange(m, dtype=np.int32), counts[lo:hi])
            pos = np.arange(n, dtype=np.int32) - first[rid]
            ints[g, pos, place_of[rid]] = codes
            floats[g, pos, place_of[rid]] = vals
        floats[g, width, :m] = y[lo:hi][by_width]
        floats[g, width + 1, :m] = 1.0
        at = np.flatnonzero(is_cold)
        if not len(at):
            return
        row = at // width if rid is None else rid[at]
        dest = cuts[g, 0][before[at] - before[first[row]]] + place_of[row]
        ids_c = np.empty(len(at), np.int32)
        vals_c = np.empty(len(at), np.float32)
        ids_c[dest] = indices[e0:e1][at]
        vals_c[dest] = values[e0:e1][at]
        cold[g] = (ids_c, vals_c)

    with ThreadPoolExecutor(min(blocks, os.cpu_count() or 1)) as pool:
        list(pool.map(lay, range(blocks)))
    n_cold = [0 if c is None else len(c[0]) for c in cold]
    cold_slots = padded_nnz(max(n_cold), pad_multiple)
    cold_idx = np.zeros((blocks, cold_slots), dtype=np.int32)
    cold_vals = np.zeros((blocks, cold_slots), dtype=np.float32)
    for g, c in enumerate(cold):
        if c is not None:
            cold_idx[g, : n_cold[g]], cold_vals[g, : n_cold[g]] = c
    n_entries = int(indptr[-1]) if len(rows) else 0
    obs.gauge_set("pack_sparse.cold_step_slots", cold_slots)
    obs.gauge_set("pack_sparse.cold_planes",
                  int(np.count_nonzero(cuts[:, 1].max(axis=0))))
    return EllMinibatchStack(
        ints=ints, floats=floats, steps=steps, mb=mb, width=width, dim=dim,
        n_rows=len(rows), n_entries=n_entries, cold_idx=cold_idx,
        cold_vals=cold_vals, cold_cuts=cuts, order=order,
        hot_ids=np.tile(hot_ids, (n_dev, 1)),
        n_hot_entries=n_entries - sum(n_cold),
    )


#: the cold planes of a classed split step, rounded up to this many: the
#: cuts' shape, so that tables of one shape share a program
_COLD_PLANES_MULTIPLE = _LANE_BLOCK
#: a classed split step's hot blocks, rounded up to this many, likewise
_HOT_BLOCKS_MULTIPLE = 8
#: planes a block of the classed split's codes holds: one sublane tile
_HOT_BLOCK_PLANES = 8


def _hot_block_tile(mb: int) -> int:
    """The row tile of the classed split's codes: 1024 places, as the hot
    kernels cut a plane (``pallas_kernels._HOT_TILE``), or a step's rows
    padded to whole lane blocks where fewer."""
    return min(1024, -(-mb // _LANE_BLOCK) * _LANE_BLOCK)


def _pack_ell_classed_split(rows, y, bounds, counts, classes, mb: int,
                            steps: int, dim: int, n_dev: int,
                            pad_multiple: int,
                            hot_ids) -> ClassedEllMinibatchStack:
    """Lay a validated CSR column out as the classed layout's frequency
    split (see :class:`ClassedEllMinibatchStack`).  In two passes over a
    device's steps, spread over threads as :func:`_pack_ell_split`'s: the
    first finds each row's hot and cold widths, the two orders of rows and
    the blocks each row tile fills, from which the leaves' shapes follow;
    the second lays every entry where its part and its rank in its row put
    it, both parts by a scatter of the step's entries."""
    indptr, indices, values = rows.indptr, rows.indices, rows.values
    n_blocks = len(bounds)
    planes, tile = _HOT_BLOCK_PLANES, _hot_block_tile(mb)
    n_tiles = -(-mb // tile)
    code_of = np.full(dim, -1, np.int32)
    n_hot = min(len(hot_ids), dim)
    code_of[hot_ids[:n_hot]] = np.arange(n_hot, dtype=np.int32)

    def split(g):
        """A step's codes (cold: -1) and which entries are cold."""
        _lo, _hi, e0, e1 = bounds[g]
        codes = code_of[indices[e0:e1]]
        return codes, codes < 0

    def widths(g):
        """A step's two orders of rows both ways, its rows' cold widths,
        and the hot width of each row tile's first (widest) row."""
        lo, hi, e0, _e1 = bounds[g]
        m = hi - lo
        ints = np.tile(np.arange(mb, dtype=np.int32), (4, 1))
        tile_w = np.zeros(n_tiles, np.int32)
        if not m:
            return ints, np.zeros(0, np.int32), tile_w
        _codes, is_cold = split(g)
        # a row's cold entries: a sum over its run (an empty row's run is
        # the next row's first entry, or the zero appended)
        cold_w = np.add.reduceat(
            np.append(is_cold, False).view(np.uint8),
            (indptr[lo:hi] - e0).astype(np.int64), dtype=np.int32)
        cold_w[counts[lo:hi] == 0] = 0
        hot_w = counts[lo:hi] - cold_w
        for part, w in enumerate((hot_w, cold_w)):
            order = np.argsort(-w, kind="stable").astype(np.int32)
            ints[2 * part, :m] = order
            ints[2 * part + 1, order] = np.arange(m, dtype=np.int32)
        widest = hot_w[ints[0, np.arange(0, m, tile)]]
        tile_w[: len(widest)] = widest
        return ints, cold_w, tile_w

    with ThreadPoolExecutor(min(n_blocks, os.cpu_count() or 1)) as pool:
        firsts = list(pool.map(widths, range(n_blocks)))
        ints = np.stack([f[0] for f in firsts])
        cold_max = max([int(f[1].max(initial=0)) for f in firsts] + [1])
        n_cold = [int(f[1].sum()) for f in firsts]
        # a row tile's blocks: as many as its widest row fills, at least
        # one (a tile with no hot entry zeroes its scores and walks none)
        blocks = [np.maximum(1, -(-f[2] // planes)) for f in firsts]
        nb = max(int(b.sum()) for b in blocks)
        nb = -(-nb // _HOT_BLOCKS_MULTIPLE) * _HOT_BLOCKS_MULTIPLE
        n_planes = -(-cold_max // _COLD_PLANES_MULTIPLE) \
            * _COLD_PLANES_MULTIPLE
        cold_slots = padded_nnz(max(n_cold), pad_multiple)
        hot_codes = np.zeros((n_blocks, nb, planes, tile), np.int32)
        hot_vals = np.zeros((n_blocks, nb, planes, tile), np.float32)
        sched = np.zeros((n_blocks, 2, nb), np.int32)
        cold_idx = np.zeros((n_blocks, cold_slots), np.int32)
        cold_vals = np.zeros((n_blocks, cold_slots), np.float32)
        cuts = np.zeros((n_blocks, 2, n_planes), np.int32)
        floats = np.zeros((n_blocks, 2, mb), np.float32)
        walked = np.zeros(n_blocks, np.int64)

        def lay(g):
            lo, hi, e0, e1 = bounds[g]
            m = hi - lo
            _ints, cold_w, tile_w = firsts[g]
            hot_place, cold_place = ints[g, 1], ints[g, 3]
            # the schedule: a tile's blocks in a run, then pads that name
            # the last block and tile again
            at = np.zeros(n_tiles + 1, np.int32)
            np.cumsum(blocks[g], out=at[1:])
            used = int(at[-1])
            sched[g, 0, :used] = np.arange(used, dtype=np.int32)
            sched[g, 0, used:] = used - 1
            sched[g, 1, :used] = np.repeat(np.arange(n_tiles, dtype=np.int32),
                                           blocks[g])
            sched[g, 1, used:] = n_tiles - 1
            # (a tile with no hot entry is walked, all pads: it holds none
            # of the slots counted)
            walked[g] = int(blocks[g][tile_w > 0].sum()) * planes * tile
            if not m:
                return
            floats[g, 0, :m] = y[lo:hi]
            floats[g, 1, :m] = 1.0
            # plane j of the cold list holds the rows with more than j
            # cold entries: the first n_j places of the cold order
            wider = m - np.cumsum(np.bincount(cold_w, minlength=n_planes + 1))
            cuts[g, 1] = wider[:n_planes]
            cuts[g, 0, 1:] = np.cumsum(wider[: n_planes - 1])
            codes, is_cold = split(g)
            is_hot = ~is_cold
            # the cold and the hot entries stored before a row's first
            cold_start = np.zeros(m, np.int64)
            np.cumsum(cold_w[:-1], out=cold_start[1:])
            hot_start = indptr[lo:hi] - e0 - cold_start
            # a row's k-th hot entry lies k planes past its place in its
            # tile's first block: (block, plane, lane) flat, row by row
            hot_place = hot_place[:m]
            base = (at[hot_place // tile].astype(np.int64) * planes * tile
                    + hot_place % tile - hot_start * tile)
            flat = np.repeat(base, counts[lo:hi] - cold_w) \
                + np.arange(len(codes) - cold_start[-1] - cold_w[-1],
                            dtype=np.int64) * tile
            hot_codes[g].reshape(-1)[flat] = codes[is_hot]
            hot_vals[g].reshape(-1)[flat] = values[e0:e1][is_hot]
            # and its k-th cold entry at its place in plane k
            rank = np.arange(cold_start[-1] + cold_w[-1]) - np.repeat(
                cold_start, cold_w)
            dest = cuts[g, 0][rank] + np.repeat(cold_place[:m], cold_w)
            cold_idx[g, dest] = indices[e0:e1][is_cold]
            cold_vals[g, dest] = values[e0:e1][is_cold]

        list(pool.map(lay, range(n_blocks)))
    n_entries = int(indptr[-1]) if len(rows) else 0
    obs.gauge_set("pack_sparse.cold_step_slots", cold_slots)
    obs.gauge_set("pack_sparse.cold_planes",
                  int(np.count_nonzero(cuts[:, 1].max(axis=0))))
    return ClassedEllMinibatchStack(
        ints=ints, floats=floats, steps=steps, mb=mb, classes=classes,
        slots=0, dim=dim, n_rows=len(rows), n_entries=n_entries,
        hot_codes=hot_codes, hot_vals=hot_vals, hot_sched=sched,
        cold_idx=cold_idx, cold_vals=cold_vals, cold_cuts=cuts,
        hot_ids=np.tile(hot_ids, (n_dev, 1)),
        n_hot_entries=n_entries - sum(n_cold),
        hot_slots=int(walked.sum()),
    )


# Compiled epoch steps are reused across fit() calls: rebuilding the jitted
# shard_map per fit would force a fresh XLA compile every time (~1s), which
# dominates short training runs.  Keyed on (grad_fn, mesh, lr, reg) — grad-fn
# factories are memoized by their hyper-flags so equal configs hit the cache.
# LRU-bounded: long-lived processes sweeping hyperparameters (or chunked
# checkpoint runs with varying chunk sizes) would otherwise retain every
# compiled executable forever.
from collections import OrderedDict

_EPOCH_STEP_CACHE: OrderedDict = OrderedDict()
_EPOCH_STEP_CACHE_CAPACITY = 32

#: builds consumed by the most recent fused run (compile-run attribution)
_RUN_BUILDS_SEEN = 0


def _cache_get(key):
    fn = _EPOCH_STEP_CACHE.get(key)
    if fn is not None:
        _EPOCH_STEP_CACHE.move_to_end(key)
    return fn


#: monotonic count of FUSED-train program builds this process (independent
#: of the obs registry so it survives ``obs.reset()`` and runs with obs
#: off).  Only programs consumed by :func:`_run_fused_train` count — chunk
#: programs (out_of_core) share the cache but have their own driver, and
#: attributing their builds here would mark a cache-warm fused fit as
#: compile-bearing whenever the paths interleave.
_FUSED_PROGRAM_BUILDS = 0


def _cache_put(key, fn, fused: bool = False):
    global _FUSED_PROGRAM_BUILDS
    _EPOCH_STEP_CACHE[key] = fn
    while len(_EPOCH_STEP_CACHE) > _EPOCH_STEP_CACHE_CAPACITY:
        _EPOCH_STEP_CACHE.popitem(last=False)
    # a build here means the next dispatch pays an XLA compile — the
    # counter lets a RunReport distinguish compile-bearing fits from
    # cache-warm ones
    if fused:
        _FUSED_PROGRAM_BUILDS += 1
    obs.counter_add("train.program_builds")
    return fn


def make_glm_epoch_step(
    grad_fn: GradFn,
    mesh,
    learning_rate: float,
    reg: float = 0.0,
):
    """One epoch (all local minibatches, SGD updates with in-step psum) as a
    single data-parallel device call.

    Returns a callable ``epoch_step(params, batch) -> (params, (loss, delta))``
    where ``batch`` is the sharded MinibatchStack pytree ``(x, y, w)``,
    ``loss`` is the epoch's mean training loss and ``delta`` the L2 norm of
    the epoch's total parameter update (the convergence criterion).
    """
    key = (grad_fn, mesh, float(learning_rate), float(reg))
    cached = _cache_get(key)
    if cached is not None:
        return cached
    sgd_update = make_sgd_update(learning_rate, reg)

    def local_epoch(params, batch):
        x, y, w = batch  # local: (steps, mb, d), (steps, mb), (steps, mb)

        def mb_step(p, xs):
            xb, yb, wb = xs
            grads, loss_sum, w_sum = grad_fn(p, xb, yb, wb)
            with jax.named_scope("fmt.train.grad"):
                grads, loss_sum, w_sum = _psum_step(grads, loss_sum, w_sum)
            count = jnp.maximum(w_sum, 1.0)
            new_p = sgd_update(p, grads, count)
            return new_p, (loss_sum / count, w_sum)

        start = params
        params, (losses, counts) = jax.lax.scan(mb_step, params, (x, y, w))
        # weighted mean loss over the epoch; update norm for convergence
        total = jnp.maximum(jnp.sum(counts), 1.0)
        loss = jnp.sum(losses * counts) / total
        delta = jnp.sqrt(
            sum(
                jnp.sum((a - b) ** 2)
                for a, b in zip(
                    jax.tree_util.tree_leaves(params),
                    jax.tree_util.tree_leaves(start),
                )
            )
        )
        return params, (loss, delta)

    return _cache_put(key, make_data_parallel_step(local_epoch, mesh))


@dataclass
class TrainResult:
    params: tuple
    epochs: int
    losses: list
    final_delta: Optional[float] = None
    #: StepMetrics recorded by the driver (SURVEY §5.5: samples/sec/chip is
    #: first-class) — fused runs record one step per device program, host-loop
    #: runs one step per epoch.  Zero-work resumes carry an empty recorder so
    #: ``metrics.summary()`` is always safe to call.
    metrics: StepMetrics = field(default_factory=lambda: StepMetrics("fused_train"))


def _combined_view(stack: MinibatchStack) -> np.ndarray:
    """x, y, w in one (n_dev*steps, mb, d+2) array — a single host->device
    transfer instead of three (one placement, one pooled slab, one scanned
    operand in the fused program).  The pack lays it (no copy here: the span
    stays for the readers of a placement's parts), and every call presents
    the SAME host array, so the slab pool's identity keying hits on a
    repeated fit from a retained stack."""
    with obs.span("place.host_view"):
        return stack.combined


def _build_fused_train_fn(key, mb_grad_step, mesh, learning_rate, reg,
                          max_iter, tol, in_specs=None, out_specs=None,
                          delta_fn=None, epoch_fn=None, check_vma=True,
                          bundle=False, whole_batch_step=None,
                          per_fit=None):
    """The WHOLE training run as one compiled device program.

    Epochs are a ``lax.while_loop`` around the minibatch ``lax.scan``; the
    convergence test (update norm vs tol — the criteria-stream-empty analog)
    evaluates on device, so training runs start-to-finish with zero host
    round-trips: one transfer in (the packed batch), one out (params +
    per-epoch losses + epochs-run).  This is the fast path ``train_glm``
    takes when no per-epoch listeners are registered; the epoch watermark
    degenerates to the loop-carried epoch counter.

    ``mb_grad_step(params, mb_slice) -> (grads, loss_sum, w_sum)`` consumes
    one scanned minibatch slice of the batch pytree — the dense, sparse, and
    feature-sharded layouts differ only there.  ``in_specs``/``out_specs``
    override the default replicated-params/data-sharded-batch placement
    (feature sharding puts the weight leaf on the ``model`` axis) and
    ``delta_fn(params, start)`` overrides the convergence norm when params
    are sharded.  Non-SGD algorithms (KMeans' Lloyd step) pass ``epoch_fn
    (params, batch) -> (params, loss, delta)`` instead of ``mb_grad_step`` to
    reuse the identical while_loop/termination/history scaffolding.
    ``whole_batch_step(params, batch, step) -> (grads, loss_sum, w_sum)``
    takes ``mb_grad_step``'s place for a step that reads its minibatch out of
    the whole batch itself (the dense one-pass kernel): the scan then runs
    over step numbers and slices nothing, since a slice the scan makes is a
    copy of the minibatch before its first use.  ``per_fit(batch)``, where
    given, is computed once a fit, before the epoch loop, and the scan runs
    over its rows in place of the step numbers (the one-pass kernel's
    schedule: a step's index and the row tiles it reads).

    ``bundle`` folds the result packing INTO the training program: the four
    outputs (params pytree, loss history, epochs, delta) ravel and
    concatenate in-program into ONE flat device buffer, so the driver's
    readback is a single ``np.asarray`` — :func:`fetch_flat`'s separate
    concat program (an extra dispatch on the per-fit critical path)
    disappears.  Bundled fns return that flat buffer instead of the 4-tuple
    and carry ``bundle_fetch=True`` / ``loss_hist_len`` attrs for
    :func:`_run_fused_train`; direct callers (diagnose_perf, the graft
    entry) keep the default unbundled 4-tuple contract.  Bundling requires
    the default replicated out_specs — custom placements (feature
    sharding) would concatenate MIXED shardings — so custom ``out_specs``
    forces it off.  The bundled program donates NOTHING: its one output is
    the flat result buffer, and XLA can only alias a donated input to an
    output of its own shape — on the v5e both the params and a donated
    batch came back "Some donated buffers were not usable" (PR 21).  Every
    fn built here says so in ``donates_params`` (``True`` unbundled,
    ``False`` bundled): :func:`_run_fused_train` copies a caller's device
    arrays only for a fn that donates them.
    """
    bundle = bundle and out_specs is None
    key = key + (bool(bundle),)
    cached = _cache_get(key)
    if cached is not None:
        return cached
    sgd_update = make_sgd_update(learning_rate, reg)
    tol_ = float(tol)

    # named scopes are metadata on the operations (the compiled program
    # and its cache key do not change): a profile finds the program's
    # parts under names the program owns.  The dense grad fns scope their
    # own scores/loss and gradient (fmt.train.scores, fmt.train.grad)
    @jax.named_scope("fmt.train")
    def local_train(params, batch):
        if whole_batch_step is None:
            xs, grad_step = batch, mb_grad_step
        else:
            if per_fit is None:
                xs = jnp.arange(jax.tree_util.tree_leaves(batch)[0].shape[0],
                                dtype=jnp.int32)
            else:
                xs = per_fit(batch)

            def grad_step(p, step):
                return whole_batch_step(p, batch, step)

        def mb_step(p, xs):
            grads, loss_sum, w_sum = grad_step(p, xs)
            with jax.named_scope("fmt.train.grad"):
                grads, loss_sum, w_sum = _psum_step(grads, loss_sum, w_sum)
            with jax.named_scope("fmt.train.update"):
                count = jnp.maximum(w_sum, 1.0)
                new_p = sgd_update(p, grads, count)
            return new_p, (loss_sum / count, w_sum)

        def sgd_epoch(params):
            start = params
            params, (losses, counts) = jax.lax.scan(mb_step, params, xs)
            total = jnp.maximum(jnp.sum(counts), 1.0)
            loss = jnp.sum(losses * counts) / total
            if delta_fn is not None:
                delta = delta_fn(params, start)
            else:
                delta = jnp.sqrt(
                    sum(
                        jnp.sum((a - b) ** 2)
                        for a, b in zip(
                            jax.tree_util.tree_leaves(params),
                            jax.tree_util.tree_leaves(start),
                        )
                    )
                )
            return params, loss, delta

        if epoch_fn is not None:
            def run_epoch(params):
                return epoch_fn(params, batch)
        else:
            run_epoch = sgd_epoch

        def cond(carry):
            _, epoch, delta, _ = carry
            not_done = epoch < max_iter
            if tol_ > 0.0:
                not_done = jnp.logical_and(
                    not_done, jnp.logical_or(epoch == 0, delta > tol_)
                )
            return not_done

        def body(carry):
            params, epoch, _, loss_hist = carry
            params, loss, delta = run_epoch(params)
            loss_hist = loss_hist.at[epoch].set(loss.astype(loss_hist.dtype))
            return params, epoch + 1, delta, loss_hist

        loss_hist0 = jnp.zeros((max_iter,), dtype=jnp.float32)
        params, epochs, delta, loss_hist = jax.lax.while_loop(
            cond, body, (params, jnp.asarray(0), jnp.asarray(jnp.inf), loss_hist0)
        )
        return params, loss_hist, epochs, delta

    from jax.sharding import PartitionSpec as P

    sharded = shard_map(
        local_train,
        mesh=mesh,
        in_specs=in_specs if in_specs is not None else (P(), P("data")),
        out_specs=(
            out_specs if out_specs is not None else (P(), P(), P(), P())
        ),
        # relaxed only for the one-pass kernel on the interpreter (see
        # make_glm_train_fn) — every other path stays strict
        check_vma=check_vma,
    )
    if not bundle:
        jitted = jax.jit(sharded, donate_argnums=(0,))
        jitted.donates_params = True
        return _cache_put(key, jitted, fused=True)

    # the dispatch-diet program (ISSUE 17): all four outputs are replicated
    # under the default out_specs, so raveling them into one buffer is
    # sharding-safe.  The fetch dtype mirrors fetch_flat (f64 only on the
    # x64 CPU test mesh) so bundled and unbundled fits return bit-identical
    # host values.
    fetch_dtype = jnp.float64 if jax.config.jax_enable_x64 else jnp.float32

    # the function's name is the program's on the device (``jit_bundled``:
    # what a trace's module events are called); tests pin it
    def bundled(params, batch):
        params, loss_hist, epochs, delta = sharded(params, batch)
        with jax.named_scope("fmt.train.bundle"):
            pieces = [
                jnp.ravel(a).astype(fetch_dtype)
                for a in jax.tree_util.tree_leaves(params)
            ]
            pieces.append(loss_hist.astype(fetch_dtype))
            pieces.append(jnp.reshape(epochs, (1,)).astype(fetch_dtype))
            pieces.append(jnp.reshape(delta, (1,)).astype(fetch_dtype))
            return jnp.concatenate(pieces)

    jitted = jax.jit(bundled)

    def train_fn(placed, device_batch):
        return jitted(placed, device_batch)

    # the driver's attrs ride the closure the program is called through
    train_fn.bundle_fetch = True
    train_fn.loss_hist_len = int(max_iter)
    train_fn.donates_params = False
    #: the minibatch steps' collectives are in the program (not an
    #: ``epoch_fn``'s own): _run_fused_train counts them from shapes
    train_fn.step_psums = epoch_fn is None
    return _cache_put(key, train_fn, fused=True)


#: replicated zero starts, placed once a mesh, shape and dtype: read, never
#: freed, by the programs that do not donate their params
_PLACED_ZEROS: OrderedDict = OrderedDict()
_PLACED_ZEROS_CAPACITY = 8
_PLACED_ZEROS_LOCK = threading.Lock()


def _place_start(mesh, init_params):
    """``replicate(mesh, init_params)`` for a program that frees none of its
    params, where a host leaf of zero bytes is placed once and the same
    device array is handed to every later fit: a transfer a fit is a wait
    of its own between the dispatch and the program's start (1.8 ms a fit
    on epsilon's chip, PR 38), where a read-only start needs none."""
    from flink_ml_tpu.parallel.mesh import replicate

    def place(x):
        if isinstance(x, jax.Array):
            return replicate(mesh, x)
        x = np.asarray(x)
        if np.ascontiguousarray(x).reshape(-1).view(np.uint8).any():
            return replicate(mesh, x)
        key = (mesh, x.shape, x.dtype)
        with _PLACED_ZEROS_LOCK:
            placed = _PLACED_ZEROS.get(key)
            if placed is None:
                placed = _PLACED_ZEROS[key] = replicate(mesh, x)
                if len(_PLACED_ZEROS) > _PLACED_ZEROS_CAPACITY:
                    _PLACED_ZEROS.popitem(last=False)
        return placed

    return jax.tree_util.tree_map(place, init_params)


def _run_fused_train(train_fn, init_params, batch, mesh,
                     place_params=None, batch_preplaced=False,
                     n_rows: int = 0) -> TrainResult:
    """Shared epilogue: run the fused program and fetch params + loss
    history + epoch count + final update norm back in ONE transfer.
    ``place_params`` overrides the default replicated placement (feature
    sharding); ``batch_preplaced`` skips the device transfer when the caller
    already sharded the batch (chunked checkpoint loops place it once).
    ``n_rows`` (true rows per epoch) feeds the recorded throughput metrics —
    a fused run is ONE device program, so it records one StepMetrics step
    covering all epochs (the fetch is the sync point).

    A ``train_fn`` built with ``bundle=True`` returns one flat device
    buffer instead of the 4-tuple; the driver reads its ``bundle_fetch`` /
    ``loss_hist_len`` attrs and splits the single ``np.asarray`` readback
    by the placed leaves' shapes.

    A ``train_fn`` whose ``donates_params`` is ``False`` (the bundled
    program) reads its params and frees none of them, so the caller's
    device arrays go in as they are and a replicated zero start is placed
    once (:func:`_place_start`); one that donates (or says nothing) trains
    on copies of them, counted in ``train.param_copies``."""
    from flink_ml_tpu.parallel.mesh import replicate
    from flink_ml_tpu.table import slab_pool

    global _RUN_BUILDS_SEEN

    metrics = StepMetrics("fused_train")
    metrics.start_step()
    donates = getattr(train_fn, "donates_params", True)
    with obs.span("train.place_params"):
        if place_params is not None:
            placed = place_params(init_params)
        elif donates:
            placed = replicate(mesh, init_params)
        else:
            placed = _place_start(mesh, init_params)
        # the unbundled fns donate their params (jit donate_argnums): when
        # the caller passes already-placed device arrays, placement may
        # alias their buffers (device_put returns the same buffer for a
        # no-op placement) and donation would delete the CALLER's data — a
        # second fit from the same initial params would crash.  Those fns
        # train on a copy of any leaf whose origin is a device array
        # (host-sourced leaves were freshly placed already).  The bundled
        # program donates nothing, so there a copy protects nothing and is
        # one more device program a fit
        copies = 0
        if donates:
            origins = jax.tree_util.tree_leaves(init_params)
            copies = sum(isinstance(o, jax.Array) for o in origins)
            placed = jax.tree_util.tree_map(
                lambda p, o: jnp.copy(p) if isinstance(o, jax.Array) else p,
                placed, init_params,
            )
    if batch_preplaced:
        device_batch = batch
    else:
        # pooled + double-buffered: a warm re-fit of the same host arrays
        # skips the transfer entirely (slab_pool hit); a cold placement
        # overlaps host staging with the async H2D DMA
        with obs.span("train.place"):
            device_batch = slab_pool.place_batch(mesh, batch)
    # pin the (possibly pooled) batch for the whole dispatch+fetch window:
    # budget eviction must never drop the pool's reference while a program
    # is in flight over these buffers.  ``train.dispatch`` is the enqueue,
    # and on a cold program the trace, lowering and compile (or cache read)
    # too: those are timed by name under ``compile.under/train.dispatch``
    # (obs/registry.py), so dispatch less that is the enqueue on a cold call
    # as well; ``train.sync`` is device execution + readback
    bundled = getattr(train_fn, "bundle_fetch", False)
    with slab_pool.pool().pinned(device_batch):
        if bundled:
            with obs.span("train.dispatch"):
                flat = train_fn(placed, device_batch)
            with obs.span("train.sync"):
                # ONE readback for the whole result: param leaves + loss
                # history + epochs + delta ride a single flat buffer packed
                # in-program
                buf = np.asarray(flat)
        else:
            with obs.span("train.dispatch"):
                params, loss_hist, epochs, delta = train_fn(
                    placed, device_batch)
            leaves, treedef = jax.tree_util.tree_flatten(params)
            with obs.span("train.sync"):
                # fetch_flat is the single sync point: it absorbs transfer
                # + program + readback (no extra block_until_ready
                # round-trips)
                fetched = fetch_flat(
                    *leaves, loss_hist, jnp.asarray(epochs),
                    jnp.asarray(delta)
                )
    with obs.span("train.demux"):
        if bundled:
            # split the flat buffer by the placed leaves' shapes
            leaves, treedef = jax.tree_util.tree_flatten(placed)
            hist_len = int(train_fn.loss_hist_len)
            fetched = []
            off = 0
            for a in leaves:
                size = int(np.prod(a.shape))
                fetched.append(buf[off : off + size].reshape(a.shape))
                off += size
            fetched.append(buf[off : off + hist_len])
            fetched.append(buf[off + hist_len])
            fetched.append(buf[off + hist_len + 1])
        n_epochs = int(fetched[-2])
        losses = [float(x) for x in fetched[-3][:n_epochs]]
        # call_latency_ms: the DRIVER's device-call window — param
        # placement, any driver-internal batch placement, dispatch, sync.
        # Estimator paths place their batch via the slab pool BEFORE this
        # driver runs; that cost lands in the slab_pool.build timing and
        # in the fit-level fit_wall_ms (fit_pool_extra), which is what the
        # warm-fit telemetry reads end-to-end.  The dispatch/sync/place
        # split is the spans' own (registry timings, there when obs is on).
        step = metrics.end_step(
            samples=n_rows * n_epochs, epochs=n_epochs,
            loss=losses[-1] if losses else 0.0,
        )
        step["call_latency_ms"] = step["seconds"] * 1e3
        obs.counter_add("train.fused_runs")
        # the leaves copied because the program donates them (0 keeps the
        # counter there: every bundled fit reads 0)
        obs.counter_add("train.param_copies", copies)
        _count_collectives(train_fn, mesh, placed, device_batch, n_epochs)
        # of those, the fits whose program holds the one-pass kernel (0
        # keeps the counter there for a reader to find)
        obs.counter_add("train.onepass_fits",
                        int(getattr(train_fn, "onepass", False)))
        _count_onepass_tiles(train_fn, mesh, device_batch, n_rows, n_epochs)
        if getattr(train_fn, "pallas_interpret", False):
            # the kernel on the interpreter (the CPU parity harness); a
            # chip run asserts this is zero
            obs.counter_add("train.pallas_interpreted")
        obs.counter_add("train.epochs", n_epochs)
        obs.counter_add("train.rows", n_rows * n_epochs)
        # a run whose program was built since the previous fused run (the
        # factory runs strictly before this driver) pays the XLA compile —
        # count it so reports separate compile-bearing fits from
        # cache-warm ones
        if _FUSED_PROGRAM_BUILDS > _RUN_BUILDS_SEEN:
            obs.counter_add("train.compile_runs")
        _RUN_BUILDS_SEEN = _FUSED_PROGRAM_BUILDS
        host_params = jax.tree_util.tree_unflatten(
            treedef, fetched[: len(leaves)])
    with obs.span("train.health"):
        obs.record_hbm_gauges()
        # numeric-health sentinel on the values just fetched (free: no
        # extra sync): a diverged fit raises here and the estimator-level
        # guard rolls back / retries with a backed-off learning rate
        fault.check_health(
            losses, fetched[: len(leaves)],
            float(fetched[-1]) if n_epochs else None,  # 0-epoch delta is inf
            where="fused_train",
        )
    return TrainResult(
        params=host_params,
        epochs=n_epochs,
        losses=losses,
        final_delta=float(fetched[-1]),
        metrics=metrics,
    )


def _count_collectives(train_fn, mesh, placed, device_batch,
                       n_epochs: int) -> None:
    """Beside ``train.fused_runs``: over how many shards of the ``data`` axis
    the fit ran (``train.data_shards``) and, for a program whose minibatch
    steps sum over it (:func:`_psum_step`), what the program ASKED of the
    collective, from shapes alone: ``train.psum_calls`` (one a parameter
    leaf and two more, a step, an epoch) and ``train.psum_bytes`` (what
    those carry: a device's shard of every leaf, and two scalars of the
    first leaf's type).  A trace's all-reduce time is set against them."""
    from flink_ml_tpu.parallel.mesh import data_parallel_size

    shards = data_parallel_size(mesh)
    obs.counter_add("train.data_shards", shards)
    if not getattr(train_fn, "step_psums", False):
        return
    leaves = jax.tree_util.tree_leaves(placed)
    steps = jax.tree_util.tree_leaves(device_batch)[0].shape[0] // shards
    sizes = [int(np.prod(a.sharding.shard_shape(a.shape))) * a.dtype.itemsize
             for a in leaves]
    a_step = sum(sizes) + 2 * leaves[0].dtype.itemsize
    obs.counter_add("train.psum_calls", (len(leaves) + 2) * steps * n_epochs)
    obs.counter_add("train.psum_bytes", a_step * steps * n_epochs)


def onepass_tile_counts(n_rows: int, shards: int, steps: int, mb: int,
                        tile: int) -> tuple:
    """``(read, skipped)``: the one-pass kernel's row tiles of ``tile`` rows
    in an epoch over a dense pack of ``n_rows`` rows, ``steps`` minibatches
    of ``mb`` rows a shard over ``shards`` (as :func:`pack_minibatches`
    lays them: step s, shard k holds the table's rows from ``(s * shards +
    k) * mb``, then padding).  A minibatch reads its tiles up to its last
    real row, and one at least (``pallas_kernels.glm_grad_schedule`` on the
    device, here from the geometry alone)."""
    per_mb = mb // tile
    blocks = np.arange(shards * steps, dtype=np.int64)
    real = np.clip(int(n_rows) - blocks * mb, 0, mb)
    read = int(np.maximum(-(-real // tile), 1).sum())
    return read, shards * steps * per_mb - read


def _count_onepass_tiles(train_fn, mesh, device_batch, n_rows: int,
                         n_epochs: int) -> None:
    """Beside ``train.onepass_fits``: the row tiles the kernel read in the
    fit (``train.onepass_tiles``) and those it passed over, weight-0
    padding alone (``train.onepass_tiles_skipped``), reckoned from the
    pack's geometry; 0 and 0 where the fit keeps the XLA step."""
    from flink_ml_tpu.parallel.mesh import data_parallel_size

    read = skipped = 0
    tile = getattr(train_fn, "onepass_rows", 0)
    if tile:
        shards = data_parallel_size(mesh)
        blocks, mb = device_batch.shape[:2]
        read, skipped = onepass_tile_counts(n_rows, shards, blocks // shards,
                                            mb, tile)
    obs.counter_add("train.onepass_tiles", read * n_epochs)
    obs.counter_add("train.onepass_tiles_skipped", skipped * n_epochs)


def make_glm_train_fn(
    grad_fn: GradFn,
    mesh,
    learning_rate: float,
    reg: float,
    max_iter: int,
    tol: float,
    bundle: bool = False,
    onepass_rows: int = 0,
):
    """Fused training over the dense combined layout
    (see :func:`_build_fused_train_fn` for the program structure;
    ``bundle`` selects the single-buffer-fetch program variant driven by
    :func:`_run_fused_train` — direct callers that unpack the 4-tuple keep
    the default).

    ``onepass_rows`` > 0 (what :func:`_onepass_rows` found for the fit's
    slab) makes the minibatch step ONE Pallas call that reads its slice of
    the resident slab in place, once (``ops/pallas_kernels.py:glm_grad``,
    row tile ``onepass_rows``), where the XLA step copies the slice out of
    the slab and then reads it twice.  The psums, the update and the bundle
    stay where they are; only the gradient's sums come from the kernel.
    Once a fit, before the epoch loop, the program counts from the slab's
    weight row the row tiles each step holds weighted rows in
    (``pallas_kernels.glm_grad_schedule``); the kernel reads no tile after
    those.  The count is data: the program, and its cache key, are one for
    every table of a slab shape."""
    key = ("train", grad_fn, mesh, float(learning_rate), float(reg),
           int(max_iter), float(tol), int(onepass_rows))
    if not onepass_rows:
        def mb_grad_step(p, mb):
            return grad_fn(p, mb[..., :-2], mb[..., -2], mb[..., -1])

        return _build_fused_train_fn(
            key, mb_grad_step, mesh, learning_rate, reg, max_iter, tol,
            bundle=bundle,
        )

    from flink_ml_tpu.ops import pallas_kernels

    interpret = pallas_kernels.launch_interpreted()
    kind = grad_fn.glm_kind
    keep_b = 1.0 if grad_fn.with_intercept else 0.0

    def schedule(slab):
        with jax.named_scope("fmt.train.onepass_schedule"):
            return pallas_kernels.glm_grad_schedule(slab, int(onepass_rows))

    def onepass_step(p, slab, step):
        wts, b = p
        with jax.named_scope("fmt.train.onepass"):
            g_w, g_b, loss_sum, w_sum = pallas_kernels.glm_grad(
                slab, step, wts, b, kind=kind, tile_rows=int(onepass_rows),
                interpret=interpret,
            )
        return (g_w.astype(wts.dtype), g_b * keep_b), loss_sum, w_sum

    train_fn = _build_fused_train_fn(
        key, None, mesh, learning_rate, reg, max_iter, tol,
        # interpret-mode pallas_call mixes data-varying and unvarying
        # operands in a dynamic_slice, which strict-vma shard_map rejects
        # (a JAX-internal limit; the Mosaic lowering passes strict — seen on
        # 1- and 4-chip v5e meshes), so only the CPU parity harness relaxes
        check_vma=not interpret, bundle=bundle,
        whole_batch_step=onepass_step, per_fit=schedule,
    )
    if bundle:
        #: read by _run_fused_train, which counts the fits that hold the
        #: kernel and the row tiles it read and passed over, and, apart,
        #: those that ran it on the interpreter
        train_fn.onepass = True
        train_fn.onepass_rows = int(onepass_rows)
        train_fn.pallas_interpret = interpret
    return train_fn


_KERNELS_MODULE = "flink_ml_tpu.ops.pallas_kernels"


def _onepass_eligible(grad_fn: GradFn, mesh) -> bool:
    """Could this fit take the one-pass kernel, whatever its slab: a dense
    GLM (a grad fn that names its ``glm_kind``) on a 1-D mesh of TPUs."""
    return getattr(grad_fn, "glm_kind", None) is not None \
        and len(mesh.axis_names) == 1 \
        and mesh.devices.flat[0].platform == "tpu"


def _import_kernels_early(grad_fn: GradFn, mesh) -> None:
    """Pallas and Mosaic take about a second of host to import, in every
    process.  A fit that may take the kernel starts that import on a thread
    BEFORE it places its slab (seconds of host copies that hold no GIL), so
    that the first fit of a process does not pay it after the placement."""
    if _onepass_eligible(grad_fn, mesh):
        _start_kernels_import()


def _start_kernels_import() -> None:
    """The import of :data:`_KERNELS_MODULE` on a thread, once a process."""
    import sys

    if _KERNELS_MODULE in sys.modules:
        return
    import importlib
    import threading

    threading.Thread(
        target=importlib.import_module, args=(_KERNELS_MODULE,),
        name="fmt-kernels-import", daemon=True,
    ).start()


def _onepass_rows(grad_fn: GradFn, mesh, slab) -> int:
    """The one-pass kernel's row tile for this fit, or 0 for the XLA step:
    what the code can observe, no knob.  The kernel takes an eligible fit
    (:func:`_onepass_eligible`) whose float32 slab lies rows-minor on the
    device, features next, steps major (``{1,2,0}``; the chip picks a
    slab's layout from its shape alone, and on any other the kernel's view
    of it would be a transposing copy of the whole slab), at a width and
    minibatch for which a row tile fits VMEM
    (``pallas_kernels.glm_grad_tile``: arithmetic on the shape).  An
    eligible fit that keeps the XLA step for its slab is counted
    (``train.onepass_declined``)."""
    if not _onepass_eligible(grad_fn, mesh):
        return 0
    rows = 0
    layout = getattr(getattr(slab, "format", None), "layout", None)
    if getattr(slab, "ndim", 0) == 3 and slab.dtype == jnp.float32 \
            and layout is not None \
            and tuple(layout.major_to_minor) == (0, 2, 1) \
            and tuple(layout.tiling or ())[:1] == ((8, 128),):
        from flink_ml_tpu.ops import pallas_kernels

        rows = pallas_kernels.glm_grad_tile(
            int(slab.shape[1]), int(slab.shape[2]) - 2)
    if not rows:
        obs.counter_add("train.onepass_declined")
    return rows


def _sparse_loss(kind: str, logits, y, w):
    """Shared loss/error math for the sparse paths."""
    if kind == "logistic":
        prob = jax.nn.sigmoid(logits)
        err = (prob - y) * w
        loss_sum = jnp.sum(w * (jnp.logaddexp(0.0, logits) - y * logits))
    else:
        err = (logits - y) * w
        loss_sum = 0.5 * jnp.sum(err * (logits - y))
    return err, loss_sum


def make_sparse_mb_grad_step(kind: str, mb: int, nnz_pad: int, dim: int,
                             with_intercept: bool = True):
    """The sparse minibatch gradient: ``(params, (ints, floats) slice) ->
    (grads, weighted loss sum, weight sum)``.

    The forward is ``segment_sum(values * gather(w))`` — the batched
    static-shape replacement for the reference's hand-rolled sparse gemv
    (BLAS.java:205-233); the gradient scatters back through the same
    segments.  Shared by the fused in-memory loop and the out-of-core chunk
    program so the two paths cannot drift.
    """
    keep_b = 1.0 if with_intercept else 0.0

    def mb_grad_step(params, xs):
        ints, floats = xs  # (2, nnz_pad), (nnz_pad + 2*mb,)
        idx, rid, vals, y, w = _segment_csr_unpack(ints, floats, nnz_pad, mb)
        wts, b = params
        # the step's two random-access halves, named for a profile: take,
        # multiply and the sorted segment sum; the scatter into ``dim``
        with jax.named_scope("fmt.train.sparse.forward"):
            logits = _segment_csr_forward(wts, idx, rid, vals, mb) + b
        err, loss_sum = _sparse_loss(kind, logits, y, w)
        with jax.named_scope("fmt.train.sparse.backward"):
            g_w = _segment_csr_backward(err, idx, rid, vals, dim)
        g_b = jnp.sum(err) * keep_b
        return (g_w, g_b), loss_sum, jnp.sum(w)

    return mb_grad_step


def make_ell_mb_grad_step(kind: str, mb: int, width: int, dim: int,
                          with_intercept: bool = True):
    """:func:`make_sparse_mb_grad_step`'s gradient over one step of an
    :class:`EllMinibatchStack`: the same loss, the same scopes' names, the
    same update downstream.  With the step's rows side by side the score is
    a sum over the entries' axis and the error reaches a row's entries by a
    broadcast: ONE gather (the weights) and ONE scatter (the gradient),
    float32 throughout, a row's products summed in its stored order."""
    keep_b = 1.0 if with_intercept else 0.0

    def mb_grad_step(params, xs):
        idx, floats = xs  # (width, mb), (width + 2, mb)
        vals, y, w = floats[:width], floats[width], floats[width + 1]
        wts, b = params
        with jax.named_scope("fmt.train.sparse.forward"):
            logits = jnp.sum(vals * jnp.take(wts, idx, axis=0), axis=0) + b
        err, loss_sum = _sparse_loss(kind, logits, y, w)
        with jax.named_scope("fmt.train.sparse.backward"):
            g_w = jax.ops.segment_sum(
                (err[None, :] * vals).reshape(width * mb),
                idx.reshape(width * mb), num_segments=dim,
            )
        g_b = jnp.sum(err) * keep_b
        return (g_w, g_b), loss_sum, jnp.sum(w)

    return mb_grad_step


def make_classed_ell_grad_step(kind: str, mb: int, classes, slots: int,
                               dim: int, with_intercept: bool = True):
    """:func:`make_ell_mb_grad_step`'s gradient over one step of a
    :class:`ClassedEllMinibatchStack`: ONE take of the weights over all
    ``slots``, a class's scores the sum of its ``(width, rows)`` products
    over the entries' axis, the error broadcast back class by class and ONE
    scatter into ``dim`` (``fmt.train.sparse.take_weights`` and ``.scatter``
    inside the step's two halves, as segment-CSR names its own).  The
    scores go back into the table's row order for the loss and the error
    back into the step's (two takes of ``mb`` addresses); float32
    throughout, a row's products summed along its own axis."""
    keep_b = 1.0 if with_intercept else 0.0
    spans, at, place = [], 0, 0  # a class: its slots and its places
    for rows, width in classes:
        spans.append((at, at + rows * width, place, place + rows, width))
        at, place = at + rows * width, place + rows

    def mb_grad_step(params, xs):
        ints, floats = xs  # (slots + 2*mb,) each
        idx, order, place_of = (ints[:slots], ints[slots : slots + mb],
                                ints[slots + mb :])
        vals, y, w = (floats[:slots], floats[slots : slots + mb],
                      floats[slots + mb :])
        wts, b = params
        with jax.named_scope("fmt.train.sparse.forward"):
            with jax.named_scope("fmt.train.sparse.take_weights"):
                prods = vals * jnp.take(wts, idx, axis=0)
            logits = jnp.take(jnp.concatenate([
                prods[lo:hi].reshape(width, -1).sum(axis=0)
                for lo, hi, _p, _q, width in spans]), place_of, axis=0) + b
        err_rows, loss_sum = _sparse_loss(kind, logits, y, w)
        with jax.named_scope("fmt.train.sparse.backward"):
            err = jnp.take(err_rows, order, axis=0)
            spread = [
                (err[p:q] * vals[lo:hi].reshape(width, -1)).reshape(-1)
                for lo, hi, p, q, width in spans]
            spread.append(jnp.zeros((slots - at,), vals.dtype))  # the tail
            with jax.named_scope("fmt.train.sparse.scatter"):
                g_w = jax.ops.segment_sum(
                    jnp.concatenate(spread), idx, num_segments=dim)
        g_b = jnp.sum(err_rows) * keep_b
        return (g_w, g_b), loss_sum, jnp.sum(w)

    return mb_grad_step


def make_hot_ell_grad_step(kind: str, mb: int, width: int, dim: int,
                           with_intercept: bool = True,
                           interpret: Optional[bool] = None):
    """:func:`make_ell_mb_grad_step`'s gradient over one step of a SPLIT
    :class:`EllMinibatchStack`: ``(params, the device's whole batch, step
    number) -> (grads, weighted loss sum, weight sum)`` (the step slices
    its own leaves: ``hot_ids`` is not a step's).  The coded slots are
    looked up by comparison, one Pallas call a direction
    (``ops/pallas_kernels.py:hot_scores`` / ``hot_grad``, under
    ``fmt.train.sparse.hot``; ``interpret`` as
    ``pallas_kernels.launch_interpreted`` says unless given, and the step
    says which as ``pallas_interpret``).  The cold list, laid plane by
    plane over rows that stand in the order of their cold width, pays ONE
    take and ONE scatter a slot (:func:`_cold_planes_forward` /
    :func:`_cold_planes_backward`: ``fmt.train.sparse.take_weights`` and
    ``.scatter`` inside the step's two halves); the planes' starts and
    lengths are DATA (``cold_cuts``), so no constant of the program comes
    from the widths a table happened to draw.  The same loss, summed in the
    step's order of rows; float32 throughout, a row's products summed in
    its stored order."""
    from flink_ml_tpu.ops import pallas_kernels

    if interpret is None:
        interpret = pallas_kernels.launch_interpreted()
    keep_b = 1.0 if with_intercept else 0.0

    def grad_step(params, batch, step):
        codes, floats, cold_idx, cold_vals, cuts = (
            jax.lax.dynamic_index_in_dim(leaf, step, keepdims=False)
            for leaf in batch[:5])
        hot_ids = batch[5][0]
        vals, y, w = floats[:width], floats[width], floats[width + 1]
        wts, b = params
        with jax.named_scope("fmt.train.sparse.forward"):
            with jax.named_scope("fmt.train.sparse.hot"):
                logits = pallas_kernels.hot_scores(
                    jnp.take(wts, hot_ids, axis=0), codes, vals,
                    interpret=interpret)
            logits = logits + _cold_planes_forward(
                wts, cold_idx, cold_vals, cuts, mb) + b
        err, loss_sum = _sparse_loss(kind, logits, y, w)
        with jax.named_scope("fmt.train.sparse.backward"):
            g_w = _cold_planes_backward(err, cold_idx, cold_vals, cuts, dim)
            with jax.named_scope("fmt.train.sparse.hot"):
                g_w = g_w.at[hot_ids].add(pallas_kernels.hot_grad(
                    err, codes, vals, k=hot_ids.shape[0],
                    interpret=interpret))
        g_b = jnp.sum(err) * keep_b
        return (g_w, g_b), loss_sum, jnp.sum(w)

    grad_step.pallas_interpret = interpret
    return grad_step


def _cold_planes_forward(wts, idx, vals, cuts, mb: int):
    """A split step's cold scores, ``(mb,)`` in the step's order of rows,
    from its cold list laid plane by plane (:class:`EllMinibatchStack`):
    ONE take of the weights over the list, then a row's score is the sum
    over planes, in stored order, of the ``mb`` products from the plane's
    start on, masked to the plane's length (``cuts``: [starts, lengths],
    data): contiguous reads, no addresses.  ``mb`` zeros behind the list
    keep every plane's slice inside it."""
    starts, lengths = cuts[0], cuts[1]
    with jax.named_scope("fmt.train.sparse.take_weights"):
        prods = vals * jnp.take(wts, idx, axis=0)
    prods = jnp.concatenate([prods, jnp.zeros((mb,), prods.dtype)])
    place = jnp.arange(mb, dtype=jnp.int32)
    scores = jnp.zeros((mb,), prods.dtype)
    for j in range(cuts.shape[1]):
        plane = jax.lax.dynamic_slice(prods, (starts[j],), (mb,))
        scores = scores + jnp.where(place < lengths[j], plane, 0.0)
    return scores


def _cold_planes_backward(err, idx, vals, cuts, dim: int):
    """The cold list's gradient: the error (``(mb,)``, in the step's order)
    reaches the slots by writing it whole at each plane's start, plane
    after plane in ascending order, into a buffer ``mb`` longer than the
    list (a later plane overwrites what the one before wrote past its own
    length; what the last leaves past the entries meets value 0.0 at id
    0); times the values, then ONE scatter-add into ``dim``."""
    starts, slots = cuts[0], vals.shape[0]
    spread = jnp.zeros((slots + err.shape[0],), err.dtype)
    for j in range(cuts.shape[1]):
        spread = jax.lax.dynamic_update_slice(spread, err, (starts[j],))
    with jax.named_scope("fmt.train.sparse.scatter"):
        return jax.ops.segment_sum(spread[:slots] * vals, idx,
                                   num_segments=dim)


def make_classed_hot_grad_step(kind: str, mb: int, dim: int,
                               with_intercept: bool = True,
                               interpret: Optional[bool] = None):
    """:func:`make_classed_ell_grad_step`'s gradient over one step of a
    SPLIT :class:`ClassedEllMinibatchStack`, with
    :func:`make_hot_ell_grad_step`'s signature (the step slices its own
    leaves, and reads the hot blocks where they lie).  The hot part is ONE
    Pallas call a direction whatever the widths
    (``ops/pallas_kernels.py:hot_scores_blocks`` / ``hot_grad_blocks``,
    under ``fmt.train.sparse.hot``), walking the blocks the step's
    schedule lists; the cold list pays ONE take and ONE scatter a slot, its
    planes summed and written by loops whose trip count is the step's own
    planes (:func:`_cold_loop_forward` / :func:`_cold_loop_backward`).
    The scores of both parts go into the table's order of rows for the
    loss, and the error back into each part's, as the unsplit classed step
    does its one order (four takes of ``mb``, ``fmt.train.sparse.orders``);
    float32 throughout."""
    from flink_ml_tpu.ops import pallas_kernels

    if interpret is None:
        interpret = pallas_kernels.launch_interpreted()
    keep_b = 1.0 if with_intercept else 0.0

    def grad_step(params, batch, step):
        codes, vals = batch[0], batch[1]
        sched, cold_idx, cold_vals, cuts, ints, floats = (
            jax.lax.dynamic_index_in_dim(leaf, step, keepdims=False)
            for leaf in batch[2:8])
        hot_ids = batch[8][0]
        hot_order, hot_place, cold_order, cold_place = (
            ints[0], ints[1], ints[2], ints[3])
        y, w = floats[0], floats[1]
        wts, b = params
        with jax.named_scope("fmt.train.sparse.forward"):
            with jax.named_scope("fmt.train.sparse.hot"):
                hot = pallas_kernels.hot_scores_blocks(
                    jnp.take(wts, hot_ids, axis=0), codes, vals, step,
                    sched, mb=mb, interpret=interpret)
            cold = _cold_loop_forward(wts, cold_idx, cold_vals, cuts, mb)
            with jax.named_scope("fmt.train.sparse.orders"):
                logits = (jnp.take(hot, hot_place, axis=0)
                          + jnp.take(cold, cold_place, axis=0) + b)
        err, loss_sum = _sparse_loss(kind, logits, y, w)
        with jax.named_scope("fmt.train.sparse.backward"):
            with jax.named_scope("fmt.train.sparse.orders"):
                err_hot = jnp.take(err, hot_order, axis=0)
                err_cold = jnp.take(err, cold_order, axis=0)
            g_w = _cold_loop_backward(err_cold, cold_idx, cold_vals, cuts,
                                      dim)
            with jax.named_scope("fmt.train.sparse.hot"):
                g_w = g_w.at[hot_ids].add(pallas_kernels.hot_grad_blocks(
                    err_hot, codes, vals, step, sched, k=hot_ids.shape[0],
                    interpret=interpret))
        g_b = jnp.sum(err) * keep_b
        return (g_w, g_b), loss_sum, jnp.sum(w)

    grad_step.pallas_interpret = interpret
    return grad_step


def _cold_loop_forward(wts, idx, vals, cuts, mb: int):
    """:func:`_cold_planes_forward` with the planes summed by a loop whose
    trip count is DATA, the step's planes (a plane holds at least one
    place), not one slice a plane the program holds."""
    starts, lengths = cuts[0], cuts[1]
    with jax.named_scope("fmt.train.sparse.take_weights"):
        prods = vals * jnp.take(wts, idx, axis=0)
    prods = jnp.concatenate([prods, jnp.zeros((mb,), prods.dtype)])
    place = jnp.arange(mb, dtype=jnp.int32)

    def plane(j, scores):
        here = jax.lax.dynamic_slice(prods, (starts[j],), (mb,))
        return scores + jnp.where(place < lengths[j], here, 0.0)

    return jax.lax.fori_loop(0, jnp.sum(lengths > 0, dtype=jnp.int32), plane,
                             jnp.zeros_like(prods[:mb]))


def _cold_loop_backward(err, idx, vals, cuts, dim: int):
    """:func:`_cold_planes_backward` with the error's writes made by a loop
    whose trip count is the step's planes, as :func:`_cold_loop_forward`'s
    sums."""
    starts, slots = cuts[0], vals.shape[0]

    def plane(j, spread):
        return jax.lax.dynamic_update_slice(spread, err, (starts[j],))

    spread = jax.lax.fori_loop(
        0, jnp.sum(cuts[1] > 0, dtype=jnp.int32), plane,
        jnp.zeros_like(jnp.concatenate([vals, err])))
    with jax.named_scope("fmt.train.sparse.scatter"):
        return jax.ops.segment_sum(spread[:slots] * vals, idx,
                                   num_segments=dim)


def _segment_csr_unpack(ints, floats, nnz_pad: int, mb: int):
    """Unpack one packed sparse minibatch slice into (idx, rid, vals, y, w)
    — the ONE copy of the [values | y | w] layout decode (the sparse and
    2-D builders both read it, so the layouts cannot drift)."""
    idx = ints[0]
    rid = ints[1]
    vals = floats[:nnz_pad]
    y = floats[nnz_pad : nnz_pad + mb]
    w = floats[nnz_pad + mb :]
    return idx, rid, vals, y, w


def _segment_csr_forward(wts, idx, rid, vals, mb: int):
    """Partial logits from stored entries: segment_sum(values * gather(w))
    — pad entries carry rid == mb and drop out of the segment range.
    Entries are packed row-major (rid non-decreasing, pads at the tail —
    asserted by the pack tests), so the segment reduction takes the
    sorted-indices lowering."""
    # the step's four random-access operations, each named for a profile
    with jax.named_scope("fmt.train.sparse.take_weights"):
        prods = vals * jnp.take(wts, idx, axis=0)
    with jax.named_scope("fmt.train.sparse.row_sum"):
        return jax.ops.segment_sum(
            prods, rid, num_segments=mb, indices_are_sorted=True,
        )


def _segment_csr_backward(err, idx, rid, vals, dim: int):
    """Feature-gradient scatter through the same segments; the appended
    zero row is the pad sink (rid == mb gathers it, contributing nothing)."""
    with jax.named_scope("fmt.train.sparse.take_error"):
        err_ext = jnp.concatenate([err, jnp.zeros((1,), err.dtype)])
        prods = vals * jnp.take(err_ext, rid, axis=0)
    with jax.named_scope("fmt.train.sparse.scatter"):
        return jax.ops.segment_sum(prods, idx, num_segments=dim)


def make_sparse_glm_train_fn(
    kind: str,
    mesh,
    sstack,
    learning_rate: float,
    reg: float,
    max_iter: int,
    tol: float,
    with_intercept: bool = True,
):
    """Fused training over the batches of ``sstack``, a
    :class:`SparseMinibatchStack`, an :class:`EllMinibatchStack` or a
    :class:`ClassedEllMinibatchStack` (read for its shapes only): the stack
    names its step (``grad_step``).

    ``kind`` picks the loss ('logistic' | 'squared'); the minibatch math is
    :func:`make_sparse_mb_grad_step`, :func:`make_ell_mb_grad_step`,
    :func:`make_classed_ell_grad_step` or, on a stack split by frequency,
    :func:`make_hot_ell_grad_step` / :func:`make_classed_hot_grad_step`.
    Program structure is shared with the dense path via
    :func:`_build_fused_train_fn`, bundled as the dense estimator fit is
    (one program named ``jit_bundled``, one buffer to fetch):
    :func:`_run_fused_train` is its one caller's driver.
    """
    if kind not in ("logistic", "squared"):
        raise ValueError(f"unknown loss kind {kind!r}")
    step_key, grad_step = sstack.grad_step(kind, with_intercept)
    key = (*step_key, kind, mesh,
           float(learning_rate), float(reg), int(max_iter), float(tol),
           bool(with_intercept))
    if sstack.hot_ids is None:
        return _build_fused_train_fn(
            key, grad_step, mesh, learning_rate, reg, max_iter, tol,
            bundle=True,
        )
    # the split step reads its slices out of the whole batch itself, and
    # holds two Pallas calls: as make_glm_train_fn's one-pass step
    interpret = grad_step.pallas_interpret
    train_fn = _build_fused_train_fn(
        key, None, mesh, learning_rate, reg, max_iter, tol,
        check_vma=not interpret, bundle=True, whole_batch_step=grad_step,
    )
    train_fn.pallas_interpret = interpret
    return train_fn


def make_sparse_mb_grad_step_2d(kind: str, mb: int, nnz_pad: int,
                                dim_local: int, with_intercept: bool = True):
    """Feature-sharded counterpart of :func:`make_sparse_mb_grad_step`:
    shard i of the ``model`` axis owns features [i*dim_local, (i+1)*dim_local);
    partial logits complete with one ``psum`` over ``model`` (the TP
    allreduce riding ICI) and gradients scatter only into the local shard.
    Shared by the fused in-memory 2-D loop and the out-of-core chunk
    program."""
    keep_b = 1.0 if with_intercept else 0.0

    def mb_grad_step(params, xs):
        ints, floats = xs
        idx = ints[0]
        rid = ints[1]
        vals = floats[:nnz_pad]
        y = floats[nnz_pad : nnz_pad + mb]
        w = floats[nnz_pad + mb :]
        wts_local, b = params
        lo = jax.lax.axis_index("model") * dim_local
        local_idx = idx - lo
        mine = jnp.logical_and(local_idx >= 0, local_idx < dim_local)
        safe_idx = jnp.clip(local_idx, 0, dim_local - 1)
        contrib = jnp.where(
            mine, vals * jnp.take(wts_local, safe_idx, axis=0), 0.0
        )
        partial = jax.ops.segment_sum(contrib, rid, num_segments=mb)
        # the TP allreduce: complete logits across feature shards
        logits = jax.lax.psum(partial, "model") + b
        err, loss_sum = _sparse_loss(kind, logits, y, w)
        err_ext = jnp.concatenate([err, jnp.zeros((1,), err.dtype)])
        scatter = jnp.where(mine, vals * jnp.take(err_ext, rid, axis=0), 0.0)
        g_w = jax.ops.segment_sum(scatter, safe_idx, num_segments=dim_local)
        g_b = jnp.sum(err) * keep_b
        return (g_w, g_b), loss_sum, jnp.sum(w)

    return mb_grad_step


def make_sparse_glm_train_fn_2d(
    kind: str,
    mesh,
    mb: int,
    nnz_pad: int,
    dim: int,
    learning_rate: float,
    reg: float,
    max_iter: int,
    tol: float,
    with_intercept: bool = True,
):
    """Feature-dimension-sharded sparse training over a ('data','model') mesh.

    For models too wide for one chip's HBM (Criteo-scale hashed features,
    SURVEY.md §5.7): the weight vector is sharded over the ``model`` axis —
    shard i owns the contiguous feature range [i*dim_local, (i+1)*dim_local).
    Each minibatch forward computes partial logits from locally-owned
    features and one ``psum`` over ``model`` (the tensor-parallel allreduce,
    riding ICI) completes them; gradients scatter back only into the local
    shard, so weight traffic never crosses chips.  ``dim`` must be divisible
    by the model-axis size (pad the feature space up).  Loop scaffolding is
    shared with every other path via :func:`_build_fused_train_fn`.
    """
    if kind not in ("logistic", "squared"):
        raise ValueError(f"unknown loss kind {kind!r}")
    model_size = dict(mesh.shape)["model"]
    if dim % model_size != 0:
        raise ValueError(
            f"dim={dim} not divisible by model axis size {model_size}"
        )
    dim_local = dim // model_size
    key = ("sparse2d", kind, mesh, mb, nnz_pad, dim,
           float(learning_rate), float(reg), int(max_iter), float(tol),
           bool(with_intercept))
    mb_grad_step = make_sparse_mb_grad_step_2d(
        kind, mb, nnz_pad, dim_local, with_intercept
    )

    from jax.sharding import PartitionSpec as P

    return _build_fused_train_fn(
        key, mb_grad_step, mesh, learning_rate, reg, max_iter, tol,
        in_specs=((P("model"), P()), P("data")),
        out_specs=((P("model"), P()), P(), P(), P()),
        delta_fn=_feature_sharded_delta,
    )


def _feature_sharded_delta(params, start):
    """Convergence norm for a ``model``-axis-sharded (w, b) pytree:
    shard-local weight squares summed across 'model'; the replicated
    intercept counts once.  Shared by the sparse and dense 2-D builders."""
    return jnp.sqrt(
        jax.lax.psum(jnp.sum((params[0] - start[0]) ** 2), "model")
        + (params[1] - start[1]) ** 2
    )


def make_dense_mb_grad_step_2d(kind: str, with_intercept: bool = True):
    """Feature-sharded DENSE minibatch gradient (VERDICT r3 item 5).

    Shard i of the ``model`` axis owns columns [i*d_local, (i+1)*d_local) of
    both the minibatch and the weight vector; each step is a local
    ``(mb, d_local) @ (d_local,)`` matvec producing partial logits, one
    ``psum`` over ``model`` (the TP allreduce riding ICI) completes them,
    and the backward ``x.T @ err`` lands only in the local column range —
    weight traffic never crosses chips.  The wide-dense analog of
    :func:`make_sparse_mb_grad_step_2d`, sharing its loss math.
    """
    keep_b = 1.0 if with_intercept else 0.0

    def mb_grad_step(params, xs):
        xb, yb, wb = xs  # (mb, d_local), (mb,), (mb,)
        wts_local, b = params
        partial = xb @ wts_local
        logits = jax.lax.psum(partial, "model") + b
        err, loss_sum = _sparse_loss(kind, logits, yb, wb)
        g_w = xb.T @ err
        g_b = jnp.sum(err) * keep_b
        return (g_w, g_b), loss_sum, jnp.sum(wb)

    return mb_grad_step


def make_dense_glm_train_fn_2d(
    kind: str,
    mesh,
    learning_rate: float,
    reg: float,
    max_iter: int,
    tol: float,
    with_intercept: bool = True,
):
    """Fused dense training over a ('data','model') mesh: rows shard over
    ``data``, feature columns (and the weight vector) over ``model``.  The
    loop scaffolding (while_loop epochs, tol, loss history) is shared with
    every other path via :func:`_build_fused_train_fn`."""
    if kind not in ("logistic", "squared"):
        raise ValueError(f"unknown loss kind {kind!r}")
    key = ("dense2d", kind, mesh, float(learning_rate), float(reg),
           int(max_iter), float(tol), bool(with_intercept))
    mb_grad_step = make_dense_mb_grad_step_2d(kind, with_intercept)

    from jax.sharding import PartitionSpec as P

    return _build_fused_train_fn(
        key, mb_grad_step, mesh, learning_rate, reg, max_iter, tol,
        in_specs=((P("model"), P()), (P("data", None, "model"), P("data"), P("data"))),
        out_specs=((P("model"), P()), P(), P(), P()),
        delta_fn=_feature_sharded_delta,
    )


def place_dense_2d_batch(mesh, stack: MinibatchStack, dim_pad: int):
    """Device placement for the feature-sharded dense layout: x's feature
    dim pads to the model-axis multiple and shards over ('data', -, 'model');
    y/w shard over 'data' only (replicated across feature shards).

    Multi-process, ``stack`` holds this process's LOCAL rows (the
    per-process file-shard contract): each process owns whole data-axis
    positions spanning ALL model columns, so its local block is its full
    addressable portion and rides the same local-block assembly as every
    other batch (:func:`~flink_ml_tpu.parallel.mesh.shard_batch_specs`)."""
    from jax.sharding import PartitionSpec as P

    from flink_ml_tpu.parallel.mesh import shard_batch_specs

    x = stack.x
    if dim_pad != x.shape[2]:
        xp = np.zeros((x.shape[0], x.shape[1], dim_pad), dtype=x.dtype)
        xp[..., : x.shape[2]] = x
        x = xp
    return shard_batch_specs(
        mesh, (x, stack.y, stack.w),
        (P("data", None, "model"), P("data"), P("data")),
    )


def train_glm_dense_2d(
    init_params,
    stack: MinibatchStack,
    kind: str,
    mesh,
    learning_rate: float,
    max_iter: int,
    reg: float = 0.0,
    tol: float = 0.0,
    with_intercept: bool = True,
    checkpoint=None,
    device_batch=None,
) -> TrainResult:
    """Dense counterpart of the 2-D branch of :func:`train_glm_sparse`: a
    wide dense GLM whose weight vector (and activations) shard over the
    ``model`` axis — the wider-than-one-chip story for dense features
    (SURVEY §5.7).  Numerics match the replicated path to ulp-level f32
    rounding: splitting the d-dim contraction into per-shard partials
    changes only the summation grouping, not the update schedule."""
    model_size = dict(mesh.shape).get("model", 1)
    if model_size < 2:
        raise ValueError(
            "train_glm_dense_2d needs a mesh with a >1 'model' axis; use "
            "train_glm (replicated params) on a data-only mesh"
        )
    dim = stack.x.shape[2]
    place, trim, dim_pad = make_feature_shard_placer(mesh, dim, model_size)
    batch = (stack.x, stack.y, stack.w)

    def factory(n_epochs):
        return make_dense_glm_train_fn_2d(
            kind, mesh, learning_rate, reg, n_epochs, tol, with_intercept
        )

    def run(n_epochs, params, dev_batch=None):
        r = _run_fused_train(
            factory(n_epochs), params,
            place_dense_2d_batch(mesh, stack, dim_pad)
            if dev_batch is None else dev_batch,
            mesh, place_params=place, batch_preplaced=True,
            n_rows=stack.n_rows,
        )
        return TrainResult(params=trim(r.params), epochs=r.epochs,
                           losses=r.losses, final_delta=r.final_delta,
                           metrics=r.metrics)

    if checkpoint is None:
        return run(max_iter, init_params, _resolve_thunk(device_batch))
    return run_chunked_checkpoint(
        run, init_params, max_iter, tol, checkpoint, mesh, batch,
        device_batch=device_batch
        if device_batch is not None
        else (lambda: place_dense_2d_batch(mesh, stack, dim_pad)),
    )


def make_feature_shard_placer(mesh, dim: int, model_size: int):
    """Placement for a ``model``-axis-sharded GLM parameter pytree.

    Returns ``(place, trim, dim_pad)``: ``place`` pads the weight vector up
    to ``dim_pad`` (the model-axis multiple) and device_puts (w sharded over
    'model', intercept replicated); ``trim`` slices the padding back off.
    The ONE copy of this logic — the in-memory 2-D driver and the
    out-of-core 2-D path both use it, so their placements cannot drift.
    Multi-process-safe: every process derives the identical full weight
    vector and materializes only its model-axis slice
    (:func:`~flink_ml_tpu.parallel.mesh.global_put`).
    """
    from jax.sharding import PartitionSpec as P

    from flink_ml_tpu.parallel.mesh import global_put

    dim_pad = -(-dim // model_size) * model_size

    def place(params):
        w0, b0 = params
        w0 = np.asarray(w0, dtype=np.float32)
        if dim_pad != int(w0.shape[0]):
            w0 = np.concatenate(
                [w0, np.zeros((dim_pad - w0.shape[0],), w0.dtype)]
            )
        return (
            global_put(mesh, w0, P("model")),
            global_put(mesh, np.asarray(b0, dtype=np.float32), P()),
        )

    def trim(params):
        return (params[0][:dim], params[1])

    return place, trim, dim_pad


def train_glm_sparse(
    init_params,
    sstack: SparseMinibatchStack,
    kind: str,
    mesh,
    learning_rate: float,
    max_iter: int,
    reg: float = 0.0,
    tol: float = 0.0,
    with_intercept: bool = True,
    checkpoint=None,
    device_batch=None,
) -> TrainResult:
    """Sparse counterpart of :func:`train_glm` (always the fused device loop).

    On a mesh with a >1-sized ``model`` axis the weight vector is sharded
    over it (:func:`make_sparse_glm_train_fn_2d`); the feature dimension is
    padded up to a multiple of the axis size.  With a
    :class:`~flink_ml_tpu.iteration.checkpoint.CheckpointConfig` the run
    executes as fused chunks of ``every_n_epochs`` epochs with a snapshot
    between chunks (and resumes from the latest snapshot).
    """
    model_size = dict(mesh.shape).get("model", 1)
    dim = sstack.dim
    if model_size > 1:
        place, trim, dim_pad = make_feature_shard_placer(mesh, dim, model_size)

        def factory(n_epochs):
            return make_sparse_glm_train_fn_2d(
                kind, mesh, sstack.mb, sstack.nnz_pad, dim_pad,
                learning_rate, reg, n_epochs, tol, with_intercept,
            )
    else:
        place = None  # replicated: the driver's own placement

        def factory(n_epochs):
            return make_sparse_glm_train_fn(
                kind, mesh, sstack,
                learning_rate, reg, n_epochs, tol, with_intercept,
            )

        def trim(params):
            return params

    batch = sstack.batch
    if sstack.hot_ids is not None:
        _start_kernels_import()  # before the placement, which hides it

    def run(n_epochs, params, dev_batch=None):
        r = _run_fused_train(
            factory(n_epochs), params,
            batch if dev_batch is None else dev_batch, mesh,
            place_params=place, batch_preplaced=dev_batch is not None,
            n_rows=sstack.n_rows,
        )
        # beside train.fused_runs: what the sparse step consumed, in
        # stored entries and in the slots it walked for them (pads too:
        # step_slots a device a step, len(ints) = n_dev * steps), and which
        # of the two layouts it walked (0 keeps the counter there from the
        # first sparse fit)
        obs.counter_add("train.sparse_fits")
        obs.counter_add("train.sparse_ell_fits", int(sstack.row_regular))
        if sstack.ell_declined:
            obs.counter_add("train.sparse_ell_declined")
        # and in how many width classes (0 on segment-CSR, 1 at one width)
        obs.counter_add("train.sparse_ell_classes", sstack.ell_classes)
        # and whether its hot features were looked up by comparison
        obs.counter_add("train.sparse_hot_fits",
                        int(sstack.hot_ids is not None))
        if sstack.hot_declined:
            obs.counter_add("train.sparse_hot_declined")
        if sstack.hot_ids is not None:
            obs.counter_add("train.sparse_hot_entries",
                            sstack.n_hot_entries * r.epochs)
            # and the cold list's slots walked, its tail of pads included,
            # and the hot kernels', beside the hot entries
            obs.counter_add("train.sparse_cold_slots",
                            sstack.cold_slots * len(sstack.ints) * r.epochs)
            obs.counter_add("train.sparse_hot_slots",
                            sstack.hot_slots * r.epochs)
        obs.counter_add("train.sparse_entries", sstack.n_entries * r.epochs)
        obs.counter_add("train.sparse_slots",
                        sstack.step_slots * len(sstack.ints) * r.epochs)
        # and the slots the rule reckoned for the row-regular layout (0
        # where the pack was not asked): over train.sparse_slots, by how
        # much the table passed or failed _ELL_MAX_SLOT_RATIO
        obs.counter_add("train.sparse_ell_slots_reckoned",
                        sstack.ell_step_slots * len(sstack.ints) * r.epochs)
        return TrainResult(params=trim(r.params), epochs=r.epochs,
                           losses=r.losses, final_delta=r.final_delta,
                           metrics=r.metrics)

    if checkpoint is None:
        return run(max_iter, init_params, _resolve_thunk(device_batch))
    return run_chunked_checkpoint(
        run, init_params, max_iter, tol, checkpoint, mesh, batch,
        device_batch=device_batch,
    )


def _resolve_thunk(x):
    """Zero-arg callables stand in for expensive values (k-means++ init,
    device placement) that must not be computed on paths that skip them
    (no-op checkpoint resume); everything else passes through unchanged."""
    return x() if callable(x) else x


def run_chunked_checkpoint(
    run, init_params, max_iter: int, tol: float, checkpoint, mesh, batch,
    device_batch=None, like=None,
) -> TrainResult:
    """Shared chunked-checkpoint driver for fused training programs.

    Executes ``run(n_epochs, params, device_batch) -> TrainResult`` in fused
    chunks of ``checkpoint.every_n_epochs`` epochs with a snapshot between
    chunks; resumes from the latest snapshot in ``checkpoint.directory``.
    ``init_params`` may be a thunk (expensive host init, e.g. k-means++):
    it is resolved only when there is no snapshot to resume from — pass
    ``like`` (a structure template; values unused) for the resume load.
    A finished run (recorded tol convergence at this-or-stricter tolerance,
    or max epochs reached) resumes to a no-op — the fused while_loop always
    executes a chunk's epoch 0, which would drift from the uninterrupted
    result.  The batch is placed on the mesh ONCE across all chunks.  Used
    by the sparse GLM and KMeans paths (one copy of the resume semantics).
    """
    from flink_ml_tpu.iteration.checkpoint import (
        agreed_latest_checkpoint,
        load_checkpoint,
        prune_checkpoints,
        save_checkpoint,
    )
    from flink_ml_tpu.parallel.mesh import shard_batch

    start_epoch = 0
    losses: list = []
    latest = agreed_latest_checkpoint(checkpoint.directory)
    if latest is None:
        params = _resolve_thunk(init_params)
    else:
        template = like if like is not None else init_params
        params, meta = load_checkpoint(latest, like=template)
        start_epoch = int(meta["epoch"]) + 1
        losses = list(meta.get("losses", []))
        if _meta_converged(meta, tol) or start_epoch >= max_iter:
            # no-op re-fit: self-describing result from the snapshot meta
            # (final_delta persisted at save time; metrics default empty)
            delta = meta.get("final_delta")
            return TrainResult(
                params=params, epochs=start_epoch, losses=losses,
                final_delta=None if delta is None else float(delta),
            )

    chunk_metrics = StepMetrics("fused_train")
    # pin the training dtype across chunk boundaries: under x64 the fetch
    # returns f64 copies of f32 device params, and re-placing those would
    # silently promote every chunk after the first to double precision —
    # a continuous checkpointed run would then drift from both the
    # unchunked fused run and a kill-and-resumed one (load_checkpoint
    # casts back to the template dtype for the same reason).  The f64
    # copies hold the f32 values exactly, so the cast is lossless.
    _chunk_dtypes = [
        getattr(x, "dtype", None)
        for x in jax.tree_util.tree_leaves(params)
    ]

    def _pin_dtypes(pytree):
        leaves, treedef = jax.tree_util.tree_flatten(pytree)
        leaves = [
            np.asarray(x, dtype=dt) if dt is not None else x
            for x, dt in zip(leaves, _chunk_dtypes)
        ]
        return jax.tree_util.tree_unflatten(treedef, leaves)

    # placement happens AFTER the no-op-resume early return above: a finished
    # run must not pay the host->device transfer just to return the snapshot.
    # ``device_batch`` may be a thunk (lazy placement) for the same reason.
    device_batch = _resolve_thunk(device_batch)
    if device_batch is None:
        from flink_ml_tpu.fault.retry import with_retry

        # place ONCE across all chunks; cold H2D is a transient surface
        device_batch = with_retry(
            lambda: shard_batch(mesh, batch), "place"
        )
    last_delta = None
    with fault.preemption_scope():
        while start_epoch < max_iter:
            chunk = min(checkpoint.every_n_epochs, max_iter - start_epoch)
            r = run(chunk, params, device_batch)
            params = _pin_dtypes(r.params)
            losses.extend(r.losses)
            start_epoch += r.epochs
            last_delta = r.final_delta
            chunk_metrics.extend(r.metrics)
            converged = r.epochs < chunk or (  # mid-chunk or at boundary
                tol > 0.0 and r.final_delta is not None
                and r.final_delta <= tol
            )
            # health precedes the snapshot: the latest checkpoint is by
            # construction the last GOOD state, so a guard rollback never
            # resumes into the divergence (the fused runner checked the
            # same values already; this guards custom `run` callables too)
            fault.check_health(
                r.losses, jax.tree_util.tree_leaves(params),
                where="chunked_train",
            )
            save_checkpoint(
                checkpoint.directory, start_epoch - 1, params,
                meta={"losses": losses, "converged": converged, "tol": tol,
                      "final_delta": r.final_delta},
            )
            prune_checkpoints(checkpoint.directory, checkpoint.keep)
            if fault.preempted() and not converged and start_epoch < max_iter:
                # the boundary snapshot just committed IS the emergency
                # checkpoint; exit cleanly for the resume path
                fault.emergency_save(lambda: None)
            if converged:
                break
    return TrainResult(params=params, epochs=start_epoch, losses=losses,
                       final_delta=last_delta, metrics=chunk_metrics)


def _meta_converged(meta: dict, tol: float) -> bool:
    """Does a checkpoint's recorded convergence satisfy the CURRENT tol?

    A run stamped converged at a looser tolerance must keep training when
    re-fit with a tighter (or zero) tol, so the early return fires only when
    the stored criterion is at least as strict as the requested one.
    """
    if not meta.get("converged") or tol <= 0.0:
        return False
    stored_tol = float(meta.get("tol") or 0.0)
    return 0.0 < stored_tol <= tol


def fit_pool_extra(stage, result) -> dict:
    """Per-fit slab-pool + latency extras for the fit RunReport.

    ``stage._fit_pool_stats0`` is the (hits, misses, t0) snapshot the
    estimator's ``fit`` took on entry; the delta is THIS fit's pool
    traffic and ``fit_wall_ms`` its TRUE end-to-end wall — pack, pooled
    placement (which happens before the fused driver runs), dispatch, and
    sync.  ``call_latency_ms`` sums the driver-recorded device-call
    windows; a broken pool shows up in ``fit_wall_ms`` (and in the
    ``slab_pool.build`` timing) even when the device-call window alone
    looks healthy."""
    import time as _time

    from flink_ml_tpu.table import slab_pool

    h, m = slab_pool.pool().counters()
    now = _time.perf_counter()
    h0, m0, t0 = getattr(stage, "_fit_pool_stats0", (h, m, now))
    hits, misses = max(h - h0, 0), max(m - m0, 0)
    extra = {"slab_pool_hits": hits, "slab_pool_misses": misses,
             "fit_wall_ms": round((now - t0) * 1e3, 3)}
    if hits + misses:
        extra["slab_pool_hit_rate"] = round(hits / (hits + misses), 4)
    steps = getattr(result.metrics, "steps", None) or []
    latency = sum(
        float(s["call_latency_ms"]) for s in steps if "call_latency_ms" in s
    )
    if latency:
        extra["call_latency_ms"] = round(latency, 3)
    return extra


def fetch_flat(*arrays):
    """Fetch device arrays in ONE transfer (concatenated flat), then split.

    Per-array device->host reads each pay a dispatch plus a sync; bundling
    them makes the readback cost one of each.  The fetch dtype follows the
    backend: f64 only when x64 is enabled (CPU test mesh) — requesting f64
    on TPU would just truncate to f32 with a warning per call.
    """
    fetch_dtype = jnp.float64 if jax.config.jax_enable_x64 else jnp.float32
    shapes = [a.shape for a in arrays]
    sizes = [int(np.prod(s)) for s in shapes]
    flat = jnp.concatenate(
        [jnp.ravel(a).astype(fetch_dtype) for a in arrays]
    )
    buf = np.asarray(flat)
    out = []
    off = 0
    for shape, size in zip(shapes, sizes):
        out.append(buf[off : off + size].reshape(shape))
        off += size
    return out


#: the dense GLM training pressure surface (ISSUE 9) — shared by the
#: estimator's pooled placement gate and the micro-batch fallback
_TRAIN_PRESSURE_SURFACE = "train.glm"


def _pressure_window_fn(grad_fn: GradFn, mesh, learning_rate: float,
                        reg: float, w: int):
    """``w`` consecutive global SGD steps as ONE compiled program over a
    window batch of shape ``(n_dev*w, mb, d+2)`` — the resident-memory
    knob of the pressure fallback.  The scanned minibatch body is
    verbatim the fused program's (same grad math, same psum, same update,
    same loss bookkeeping), so streaming a run through windows of ANY
    size replays the identical per-step floating-point computation:
    final params match the whole-batch fused run exactly."""
    key = ("pressure_win", grad_fn, mesh, float(learning_rate),
           float(reg), int(w))
    cached = _cache_get(key)
    if cached is not None:
        return cached
    sgd_update = make_sgd_update(learning_rate, reg)

    def local_window(params, batch):  # local (w, mb, d+2)
        def mb_step(p, mb):
            grads, loss_sum, w_sum = grad_fn(
                p, mb[..., :-2], mb[..., -2], mb[..., -1]
            )
            with jax.named_scope("fmt.train.grad"):
                grads, loss_sum, w_sum = _psum_step(grads, loss_sum, w_sum)
            count = jnp.maximum(w_sum, 1.0)
            return sgd_update(p, grads, count), (loss_sum / count, w_sum)

        params, (losses, counts) = jax.lax.scan(mb_step, params, batch)
        return params, losses, counts

    from jax.sharding import PartitionSpec as P

    sharded = shard_map(
        local_window, mesh=mesh,
        in_specs=(P(), P("data")),
        out_specs=(P(), P(), P()),
    )
    return _cache_put(key, jax.jit(sharded))


def _pressure_grad_fn(grad_fn: GradFn, mesh, c: int):
    """psum'd gradient SUMS over one ``c``-row micro-chunk per device (no
    update) — the accumulation half of micro-batch gradient accumulation
    for a single SGD step that exceeds device capacity on its own."""
    key = ("pressure_grad", grad_fn, mesh, int(c))
    cached = _cache_get(key)
    if cached is not None:
        return cached

    def local_grad(params, chunk):  # local (1, c, d+2)
        mb = chunk[0]
        grads, loss_sum, w_sum = grad_fn(
            params, mb[..., :-2], mb[..., -2], mb[..., -1]
        )
        with jax.named_scope("fmt.train.grad"):
            return _psum_step(grads, loss_sum, w_sum)

    from jax.sharding import PartitionSpec as P

    sharded = shard_map(
        local_grad, mesh=mesh,
        in_specs=(P(), P("data")),
        out_specs=(P(), P(), P()),
    )
    return _cache_put(key, jax.jit(sharded))


def _pressure_update_fn(learning_rate: float, reg: float):
    """One SGD update from accumulated gradient sums (+ the step's mean
    loss) — the apply half of gradient accumulation."""
    key = ("pressure_upd", float(learning_rate), float(reg))
    cached = _cache_get(key)
    if cached is not None:
        return cached
    sgd_update = make_sgd_update(learning_rate, reg)

    def upd(params, grads, loss_sum, w_sum):
        count = jnp.maximum(w_sum, 1.0)
        return sgd_update(params, grads, count), loss_sum / count

    return _cache_put(key, jax.jit(upd))


def _pressure_accum_step(params, step_rows: np.ndarray, mesh,
                         grad_fn: GradFn, learning_rate: float, reg: float):
    """One SGD step whose minibatch alone exceeds device capacity:
    sum-based gradient accumulation over contiguous row micro-chunks
    (ascending ranges — a bitwise-stable accumulation order, identical on
    every run), one psum'd grad program per resident chunk, then a single
    update.  ``step_rows`` is the step's host minibatch
    ``(n_dev, mb, d+2)``."""
    from flink_ml_tpu.fault import pressure
    from flink_ml_tpu.fault.retry import with_retry
    from flink_ml_tpu.parallel.mesh import shard_batch

    n_dev, mb = step_rows.shape[0], step_rows.shape[1]

    def chunk_call(lo: int, hi: int):
        chunk = np.ascontiguousarray(step_rows[:, lo:hi])
        fault.maybe_oom(n_dev * (hi - lo))
        win = with_retry(lambda: shard_batch(mesh, chunk), "place")
        return _pressure_grad_fn(grad_fn, mesh, hi - lo)(params, win)

    def accum(pieces):
        grads, loss_sum, w_sum = pieces[0]
        for g2, l2, w2 in pieces[1:]:
            grads = jax.tree_util.tree_map(jnp.add, grads, g2)
            loss_sum = loss_sum + l2
            w_sum = w_sum + w2
        return grads, loss_sum, w_sum

    grads, loss_sum, w_sum = pressure.run_bisected(
        chunk_call, mb, surface=_TRAIN_PRESSURE_SURFACE + ".accum",
        concat=accum, evict=False,
    )
    obs.counter_add("pressure.accum_steps")
    new_params, loss = _pressure_update_fn(learning_rate, reg)(
        params, grads, loss_sum, w_sum
    )
    return new_params, loss, w_sum


def _train_glm_pressure(init_params, stack: MinibatchStack,
                        grad_fn: GradFn, mesh, learning_rate: float,
                        reg: float, max_iter: int, tol: float) -> TrainResult:
    """Micro-batch GLM training under HBM pressure (ISSUE 9).

    The whole-run fused program needs the entire packed batch
    device-resident; when that allocation OOMs, this driver streams the
    SAME update schedule through bounded windows instead: per pass, the
    rows of ``w`` consecutive global steps are placed and scanned by
    :func:`_pressure_window_fn` (per-step math verbatim the fused
    program's — exact-parity contract), shrinking ``w`` on further OOM
    down to one step, below which :func:`_pressure_accum_step` splits the
    single minibatch into accumulated gradient micro-chunks.  The
    ``train.glm`` pressure state remembers the workable window across
    fits and AIMD-probes back toward the whole-batch fused path."""
    from flink_ml_tpu.fault import pressure
    from flink_ml_tpu.fault.retry import with_retry
    from flink_ml_tpu.parallel.mesh import replicate, shard_batch

    comb = _combined_view(stack)
    steps, mb = stack.steps, stack.mb
    n_dev = comb.shape[0] // max(steps, 1)
    group_rows = n_dev * mb
    st = pressure.state(_TRAIN_PRESSURE_SURFACE)
    metrics = StepMetrics("pressure_train")
    metrics.start_step()
    params = replicate(mesh, init_params)
    losses_dev: list = []
    delta = None
    tol_ = float(tol)
    epoch = 0

    def window_steps() -> int:
        # limit_rows converts the per-device cap back to mesh-global rows
        # (ISSUE 15): an 8-device window shrinks to what one device
        # couldn't hold, not to a 1-device budget for the whole mesh
        cap = st.limit_rows(n_dev)
        if cap is None:
            return steps
        return max(1, min(steps, cap // max(group_rows, 1)))

    while epoch < max_iter:
        if tol_ > 0.0 and epoch > 0 and float(delta) <= tol_:
            break
        # AIMD up-probe between epochs
        st.admit(comb.shape[0] * mb, n_dev=n_dev)
        start = params
        ep_losses: list = []
        ep_counts: list = []
        s = 0
        while s < steps:
            w = min(window_steps(), steps - s)
            cap = st.limit_rows(n_dev)
            if w == 1 and cap is not None and cap < group_rows:
                # the cap already says ONE step cannot fit: go straight
                # to gradient accumulation instead of paying a doomed
                # full-minibatch placement (and an OOM event) per step
                idx = np.arange(n_dev) * steps + s
                params, loss1, count1 = _pressure_accum_step(
                    params, comb[idx], mesh, grad_fn, learning_rate, reg
                )
                ep_losses.append(jnp.reshape(loss1, (1,)))
                ep_counts.append(jnp.reshape(count1, (1,)))
                s += 1
                continue
            # device-major gather: global step s' uses dim-0 rows
            # {k*steps + s'} — window rows stay device-contiguous so the
            # 'data'-axis shard sees its own steps in order
            idx = (np.arange(n_dev)[:, None] * steps
                   + (s + np.arange(w))[None, :]).reshape(-1)
            host_win = np.ascontiguousarray(comb[idx])
            rows = n_dev * w * mb
            try:
                fault.maybe_oom(rows)
                win = with_retry(
                    lambda hw=host_win: shard_batch(mesh, hw), "place"
                )
                params, losses_w, counts_w = _pressure_window_fn(
                    grad_fn, mesh, learning_rate, reg, w
                )(params, win)
            except Exception as exc:  # noqa: BLE001 - OOM-filtered
                if not fault.is_oom(exc):
                    raise
                if w > 1:
                    pressure.note_oom(_TRAIN_PRESSURE_SURFACE, rows, exc,
                                      floor=group_rows, n_dev=n_dev)
                    obs.counter_add("pressure.bisections")
                    obs.counter_add(
                        f"pressure.bisections.{_TRAIN_PRESSURE_SURFACE}"
                    )
                    continue  # same step range, smaller window
                # a single step is too big on its own: accumulate
                pressure.note_oom(_TRAIN_PRESSURE_SURFACE, rows, exc,
                                  n_dev=n_dev)
                params, loss1, count1 = _pressure_accum_step(
                    params, comb[idx], mesh, grad_fn, learning_rate, reg
                )
                ep_losses.append(jnp.reshape(loss1, (1,)))
                ep_counts.append(jnp.reshape(count1, (1,)))
                s += 1
                continue
            ep_losses.append(losses_w)
            ep_counts.append(counts_w)
            s += w
        losses_all = jnp.concatenate(ep_losses)
        counts_all = jnp.concatenate(ep_counts)
        total = jnp.maximum(jnp.sum(counts_all), 1.0)
        losses_dev.append(jnp.sum(losses_all * counts_all) / total)
        delta = jnp.sqrt(sum(
            jnp.sum((a - b) ** 2)
            for a, b in zip(jax.tree_util.tree_leaves(params),
                            jax.tree_util.tree_leaves(start))
        ))
        epoch += 1

    leaves, treedef = jax.tree_util.tree_flatten(params)
    loss_hist = (
        jnp.stack(losses_dev) if losses_dev
        else jnp.zeros((0,), dtype=jnp.float32)
    )
    fetched = fetch_flat(
        *leaves, loss_hist,
        jnp.asarray(delta if delta is not None else jnp.inf),
    )
    losses = [float(x) for x in fetched[-2]]
    host_params = jax.tree_util.tree_unflatten(
        treedef, fetched[: len(leaves)]
    )
    metrics.end_step(
        samples=stack.n_rows * epoch, epochs=epoch,
        loss=losses[-1] if losses else 0.0,
    )
    obs.counter_add("train.pressure_runs")
    obs.counter_add("train.epochs", epoch)
    obs.counter_add("train.rows", stack.n_rows * epoch)
    obs.record_hbm_gauges()
    fault.check_health(
        losses, fetched[: len(leaves)],
        float(fetched[-1]) if epoch else None,
        where="pressure_train",
    )
    return TrainResult(
        params=host_params,
        epochs=epoch,
        losses=losses,
        final_delta=float(fetched[-1]),
        metrics=metrics,
    )


def train_glm(
    init_params,
    stack: MinibatchStack,
    grad_fn: GradFn,
    mesh,
    learning_rate: float,
    max_iter: int,
    reg: float = 0.0,
    tol: float = 0.0,
    listeners: Sequence = (),
    checkpoint=None,
    device_batch=None,
) -> TrainResult:
    """Drive GLM training to termination.

    Termination mirrors the reference's two bounded modes: a max epoch count,
    and — when ``tol`` > 0 — an empty-criteria round, realized as "parameter
    update norm below tol" (SURVEY.md §3.5, IterationBodyResult.java:44-48).

    Without listeners or checkpointing the entire run is ONE device program
    (fused epoch while_loop, single transfer each way).  With listeners or a
    :class:`~flink_ml_tpu.iteration.checkpoint.CheckpointConfig`, epochs go
    through the bounded iteration runtime so per-epoch watermark callbacks
    fire and snapshots land at the configured cadence; an existing snapshot
    in ``checkpoint.directory`` resumes the run from its epoch, and the
    deterministic packing order makes resumed runs bit-match uninterrupted
    ones.
    """
    from flink_ml_tpu.parallel.mesh import replicate, shard_batch

    if not listeners and checkpoint is None:
        from flink_ml_tpu.fault import pressure
        from flink_ml_tpu.parallel.mesh import data_parallel_size

        row_slots = stack.x.shape[0] * stack.mb
        # per-device-denominated caps (ISSUE 15): an OOM shrinks what ONE
        # device could not hold, so the mesh width scales the global cap
        n_dev_mesh = data_parallel_size(mesh)
        st = pressure.state(_TRAIN_PRESSURE_SURFACE)
        if pressure.enabled() and st.capped_below(row_slots,
                                                 n_dev=n_dev_mesh):
            # known pressure from an earlier fit: go straight to the
            # micro-batch path at the remembered window (no failing
            # whole-batch probe); the AIMD up-probe inside restores the
            # fused path once the cap recovers
            return _train_glm_pressure(
                init_params, stack, grad_fn, mesh, learning_rate, reg,
                max_iter, tol,
            )
        _import_kernels_early(grad_fn, mesh)
        try:
            fault.maybe_oom(row_slots)
            # device_batch may be a thunk (lib/glm.py passes one so no
            # caller frame pins the placed slab): resolve it HERE, inside
            # the pressure scope, so a placement OOM recovers too
            device_batch = _resolve_thunk(device_batch)
            # dispatch diet (ISSUE 17): the fast path always bundles the
            # result fetch into the training program.  Built once the slab
            # is placed: how it lies on the device decides the step
            train_fn = make_glm_train_fn(
                grad_fn, mesh, learning_rate, reg, max_iter, tol,
                bundle=True,
                onepass_rows=_onepass_rows(grad_fn, mesh, device_batch),
            )
            return _run_fused_train(
                train_fn, init_params,
                device_batch if device_batch is not None
                else _combined_view(stack),
                mesh, batch_preplaced=device_batch is not None,
                n_rows=stack.n_rows,
            )
        except Exception as exc:  # noqa: BLE001 - OOM-filtered below
            if not (pressure.enabled() and fault.is_oom(exc)):
                raise
            # the whole-batch resident program exhausted the allocator:
            # DROP the placed slab (our local is the last strong
            # reference — the pool entry goes with evict_for_pressure, so
            # the runtime can actually free the HBM the windows need),
            # remember the pressure, and stream the identical update
            # schedule through bounded windows
            from flink_ml_tpu.table import slab_pool

            device_batch = None
            slab_pool.evict_for_pressure()
            pressure.note_oom(_TRAIN_PRESSURE_SURFACE, row_slots, exc,
                              n_dev=n_dev_mesh)
            return _train_glm_pressure(
                init_params, stack, grad_fn, mesh, learning_rate, reg,
                max_iter, tol,
            )

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
            start_epoch = int(meta["epoch"]) + 1
            losses = list(meta.get("losses", []))
            if _meta_converged(meta, tol) or start_epoch >= max_iter:
                # finished run (max epochs or recorded tol convergence at
                # this-or-stricter tolerance): re-fitting runs nothing more
                return TrainResult(
                    params=jax.tree_util.tree_map(np.asarray, init_params),
                    epochs=start_epoch,
                    losses=[float(x) for x in losses],
                )

    from flink_ml_tpu.fault.retry import with_retry

    epoch_step = make_glm_epoch_step(grad_fn, mesh, learning_rate, reg)
    # cold H2D placement is a transient surface on this path too (the
    # pooled and streamed paths already retry theirs)
    batch = with_retry(
        lambda: shard_batch(mesh, (stack.x, stack.y, stack.w)), "place"
    )
    params0 = replicate(mesh, init_params)
    converted: list = list(losses)  # float prefix (resumed history)
    metrics = StepMetrics("epoch_train")

    tol_converged = [False]  # last epoch's delta <= tol (for the final stamp)

    def body(params, inputs, epoch):
        # per-epoch wall time; without a sync (tol/checkpoint off) this times
        # the async dispatch, which is the honest host-side cost of the epoch
        metrics.start_step()
        new_params, (loss, delta) = epoch_step(params, inputs["batch"])
        criteria = None
        if tol > 0.0:
            # convergence needs the value on host: one readback per epoch —
            # the device-friendly "criteria stream empty" check
            tol_converged[0] = float(delta) <= tol
            criteria = [] if tol_converged[0] else [1]
        # keep the loss as a device value: converting here would sync every
        # epoch and collapse the async dispatch pipeline
        losses.append(loss)
        if checkpoint is not None:
            true_epoch = start_epoch + epoch
            at_interval = (true_epoch + 1) % checkpoint.every_n_epochs == 0
            if at_interval or fault.preempted():
                from flink_ml_tpu.iteration.checkpoint import (
                    prune_checkpoints,
                    save_checkpoint,
                )

                # convert only the not-yet-converted tail (the save itself
                # syncs anyway; re-converting the whole history each time
                # would be O(E^2) blocking float() calls)
                converted.extend(float(x) for x in losses[len(converted):])
                host = jax.tree_util.tree_map(np.asarray, new_params)
                # health precedes the snapshot (last checkpoint = last
                # good state); the guard's rollback relies on it
                fault.check_health(
                    converted, jax.tree_util.tree_leaves(host),
                    where="epoch_train",
                )

                def _snapshot():
                    save_checkpoint(
                        checkpoint.directory, true_epoch, host,
                        meta={"losses": list(converted)},
                    )
                    prune_checkpoints(checkpoint.directory, checkpoint.keep)

                # a run that just FINISHED (tol converged this epoch, or
                # this was the final epoch) returns its result instead of
                # exiting for resume — the same rule as the other drivers;
                # exiting here would also skip the converged stamp below
                if fault.preempted() and not tol_converged[0] \
                        and true_epoch + 1 < max_iter:
                    metrics.end_step(samples=stack.n_rows)
                    fault.emergency_save(_snapshot)  # raises Preempted
                _snapshot()
        metrics.end_step(samples=stack.n_rows)
        return IterationBodyResult(
            feedback=new_params,
            outputs={"loss": loss},
            termination_criteria=criteria,
        )

    import contextlib as _contextlib

    scope = (
        fault.preemption_scope() if checkpoint is not None
        else _contextlib.nullcontext()
    )
    with scope:
        result = iterate_bounded(
            params0,
            ReplayableInputs.replay(batch=batch),
            body,
            IterationConfig(max_epochs=max_iter - start_epoch),
            listeners=listeners,
        )
    final = jax.tree_util.tree_map(np.asarray, result.final_variables)
    total_epochs = start_epoch + result.epochs_run
    float_losses = [float(x) for x in losses]
    fault.check_health(
        float_losses, jax.tree_util.tree_leaves(final), where="epoch_train"
    )
    if checkpoint is not None and tol_converged[0]:
        # terminated by tol (including convergence landing exactly on the
        # final permitted epoch): stamp the final state as converged so a
        # re-fit resumes to a no-op instead of running extra epochs
        from flink_ml_tpu.iteration.checkpoint import (
            prune_checkpoints,
            save_checkpoint,
        )

        save_checkpoint(
            checkpoint.directory, total_epochs - 1, final,
            meta={"losses": float_losses, "converged": True, "tol": tol},
        )
        prune_checkpoints(checkpoint.directory, checkpoint.keep)
    return TrainResult(
        params=final,
        epochs=total_epochs,
        losses=float_losses,
        metrics=metrics,
    )


def apply_sharded(apply_factory, X: np.ndarray, *args,
                  bucket_minimum: int = 256, pool_key=None):
    """Run a mesh-sharded model apply over the default environment's mesh.

    ``apply_factory(mesh)`` returns the (memoized) row-aligned device fn for
    that mesh (built via
    :func:`~flink_ml_tpu.parallel.collectives.make_data_parallel_apply`);
    rows pad to a multiple of the data-axis size so the shard_map sees equal
    shards.  The single shared entry point for every ModelMapper hot path.
    Multi-process it runs on the process-LOCAL mesh
    (:func:`~flink_ml_tpu.parallel.mesh.inference_mesh`): each process
    scores its own rows with its own model copy, no collectives.

    ``pool_key`` opts the placement of ``X`` into the device slab pool:
    re-scoring the same rows (bench loops, repeated transforms over a
    retained table) reuses the padded device copy instead of re-padding and
    re-transferring.  The key must capture what the placement depends on
    beyond X's own identity (column name, model dim); correctness never
    depends on it (a pool miss just places).
    """
    from flink_ml_tpu.parallel.mesh import data_parallel_size, inference_mesh
    from flink_ml_tpu.utils.environment import MLEnvironmentFactory

    mesh = inference_mesh(MLEnvironmentFactory.get_default().get_mesh())
    fn = apply_factory(mesh)
    row_multiple = data_parallel_size(mesh)
    if pool_key is not None:
        from flink_ml_tpu.fault import pressure
        from flink_ml_tpu.table import slab_pool

        if not slab_pool.enabled():
            pool_key = None  # skip tokenization entirely: pooling is off
        elif pressure.state("apply").capped_below(X.shape[0],
                                                  n_dev=row_multiple):
            # active memory pressure: the pooled path would place the
            # FULL padded batch the cap says cannot fit — go straight to
            # the bisected unpooled path below
            pool_key = None
    if pool_key is not None:
        from jax.sharding import NamedSharding, PartitionSpec as P

        from flink_ml_tpu.table import slab_pool

        n = X.shape[0]
        b = _bucket_for(n, bucket_minimum, row_multiple)

        def build():
            Xp = _pad_rows_to(X, b)
            if row_multiple > 1:
                return jax.device_put(Xp, NamedSharding(mesh, P("data")))
            return jnp.asarray(Xp)

        refs: list = []
        token = slab_pool.array_token(X, refs)
        try:
            fault.maybe_oom(n)
            # agreed=False: inference is collective-free by contract (each
            # process scores its own rows on its own local mesh, with batch
            # counts no peer mirrors) — a pool-level allgather here would
            # hang
            Xd = slab_pool.pool().get_or_build(
                ("apply", mesh, pool_key, token, b), build, refs=refs,
                agreed=False,
            )
            with slab_pool.pool().pinned(Xd):
                out = fn(Xd, *args)
                return np.asarray(out)[:n]
        except Exception as exc:  # noqa: BLE001 - OOM-filtered below
            if not fault.is_oom(exc):
                raise
            # allocator exhaustion on the pooled full-batch placement:
            # the bisected path below rediscovers the workable chunk size
            # (and records the pressure telemetry as it does)
    return apply_batched(
        fn, X, *args,
        bucket_minimum=bucket_minimum,
        row_multiple=row_multiple,
    )


def bucket_rows(n: int, minimum: int = 256) -> int:
    """Next power-of-two row count >= n (bounds the jit cache for inference)."""
    b = minimum
    while b < n:
        b *= 2
    return b


def _bucket_for(n: int, bucket_minimum: int, row_multiple: int) -> int:
    """The ONE copy of the inference bucket rule — the pooled and unpooled
    apply paths must choose identical padded shapes or pool_key callers
    would compile different programs than plain callers.

    Delegates to the shared batch-shape ladder
    (:func:`~flink_ml_tpu.utils.compile_cache.bucket_batch_rows`), which
    the fused pipeline plans and the serving runtime's coalesced
    micro-batches also pad to: a 3-row serving request and a 3-row staged
    apply dispatch the same compiled program.  ``bucket_minimum`` is
    retained for signature stability but the ladder (whose bottom rungs
    sit below the old 256-row floor exactly so single-row serving requests
    stop padding to training-shaped buckets) owns the rule now."""
    del bucket_minimum  # the shared ladder owns the rung choice
    from flink_ml_tpu.utils.compile_cache import bucket_batch_rows

    return bucket_batch_rows(n, row_multiple)


def _pad_rows_to(X: np.ndarray, b: int) -> np.ndarray:
    """Zero-pad X's rows up to ``b`` (pass-through when already there)."""
    n = X.shape[0]
    if b == n:
        return X
    Xp = np.zeros((b,) + X.shape[1:], dtype=X.dtype)
    Xp[:n] = X
    return Xp


def apply_batched(
    fn, X: np.ndarray, *args, bucket_minimum: int = 256, row_multiple: int = 1
) -> np.ndarray:
    """Run a jitted row function over X padded to a power-of-two bucket.

    ``fn(x_padded, *args)`` must be row-aligned; the result is sliced back to
    the true row count.  Padding rows are zeros.  A 0-row input still runs one
    padded bucket so the output keeps fn's true rank (sliced to 0 rows).
    ``row_multiple`` rounds the bucket up so mesh-sharded applies
    (:func:`~flink_ml_tpu.parallel.collectives.make_data_parallel_apply`)
    always see a row count divisible by the data-axis size.

    Memory-pressure resilient (ISSUE 9): the dispatch runs under the
    shared ``apply`` pressure surface — an allocator OOM chunks X's rows
    (KMeans assign, the Knn reference scan, scaler applies all route
    here), each chunk padded to its own ladder bucket, and the sliced
    results concatenate host-side.  Row-aligned fns are row-independent,
    so the concatenation is bit-identical to the unsplit call.
    """
    n = X.shape[0]

    def run(lo: int, hi: int) -> np.ndarray:
        sub = X[lo:hi]
        fault.maybe_oom(hi - lo)
        Xp = _pad_rows_to(sub, _bucket_for(hi - lo, bucket_minimum,
                                           row_multiple))
        out = fn(jnp.asarray(Xp), *args)
        return np.asarray(out)[: hi - lo]

    return fault.run_bisected(run, n, surface="apply",
                              floor=max(1, row_multiple),
                              n_dev=row_multiple)
